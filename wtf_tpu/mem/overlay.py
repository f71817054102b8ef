"""Per-lane dirty-page overlay: copy-on-write guest memory.

This is the TPU-native replacement for the reference's dirty-page tracking +
restore machinery (bochs write hooks bochscpu_backend.cc:550-593, KVM dirty
bitmaps kvm_backend.cc:1568-1637, WHV R-X write-protection faults
whv_backend.cc:1163-1189, and `Ram_t::Restore` ram.h:235-280).  Instead of
mutating guest RAM and rolling dirty pages back after every testcase, each
lane owns a small copy-on-write overlay: the first write to a page claims
one of the lane's overlay rows, and every later read/write checks the
overlay first.  `Restore()` frees every row and zeroes the word-validity
plane — no page data ever moves.

Layout: page data is uint64 WORDS (little-endian), matching MemImage.  The
hot primitives operate on aligned word windows — `pte_read` (1 word),
`load_window3` (3 words cover any <=16-byte span), `store_window3` (a
two-word read-modify-write; the window's third word, which only a store
wider than 8 bytes to an address that is not 8-aligned reaches, goes
through `commit_spills`) — so a memory access costs a handful of word
gathers instead of per-byte gathers.  Byte-granular `gather_bytes` /
`scatter_span` remain for host-driven paths (testcase insertion, traces,
tests).

RESIDENT LAYOUT: the two big planes (`data`, `valid`) of the batched
`DirtyOverlay` are FLAT and lane-major: word `w` of row `r` of lane `l`
is word `(l * capacity + r) * PAGE_WORDS + w` of the planes
(`row_start`).  `valid` holds one byte per word; `data` holds each u64
word as its two little-endian u32 limbs side by side (the words' memory
image, `uint32[2 * words]`), because the chip has no 64-bit integers:
a u64 plane would be split into a low and a high u32 plane on the way into
every dispatch and combined on the way out, with both planes as
temporaries.  A flat plane is the layout the TPU compiler scatters into.
Held as `[lanes, capacity, PAGE_WORDS]` (the layout until PR 26) the v5e
compile of the chunk program served the per-lane store, inside the while
body and on every sweep, as

  %copy.1935 = u32[16384,4,8,128]{3,1,2,0:T(4,128)} copy(%bitcast...)
  %reshape.4693 = u32[1024,128,512]{2,1,0:T(8,128)} reshape(%fusion.5376)

i.e. it relayouted each whole plane (tile (8,128) -> (4,128)), bitcast it
to `u32[67108864]{0:T(1024)}` for the scatter, and reshaped it back: 4 GB
moved at 4096 lanes to store 48 KB.  A flat plane already IS
`{0:T(1024)}`, so the scatter updates the loop carry in place.

The per-lane functions below take a `LaneOverlay`: the lane's own index
state plus the WHOLE planes and the lane's `base` offset into them.
`jax.vmap` them over `lanes(overlay)` with `in_axes=LANE_AXES` (planes
unmapped: every lane gathers from the one resident buffer).  Because the
planes are unmapped, a vmapped store cannot return them: `store_window3`
and `scatter_span` return a `StoreCommit` (plane indices + words), and
the caller applies ONE flat scatter per plane for all lanes with
`commit_stores` OUTSIDE the vmap (and `commit_spills` after it, which
touches the planes only when a lane in hand spills).  The two sweeping
engines (step.make_run_chunk, the megachunk ladder) do that through
`step.step_lanes`.
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from wtf_tpu.core.gxa import PAGE_SHIFT, PAGE_SIZE
from wtf_tpu.interp.limbs import pack_u64, unpack_u64
from wtf_tpu.mem.physmem import MemImage, PAGE_WORDS, frame_slot

# pfn sentinel for "out of physical range" — never matches a stored pfn and
# frame_slot() maps it to the zero page.  Plain int: module import must not
# touch the device (jnp scalars would initialize the backend).
_PFN_OOB = 0x7FFFFFFF

_U64_MAX = (1 << 64) - 1


def _u(x: int) -> jnp.ndarray:
    return jnp.uint64(x & _U64_MAX)


def _shl(x, s):
    """x << s with s >= 64 yielding 0 (XLA leaves it undefined)."""
    return jnp.where(s >= _u(64), _u(0), x << jnp.minimum(s, _u(63)))


def _shr(x, s):
    return jnp.where(s >= _u(64), _u(0), x >> jnp.minimum(s, _u(63)))


# The per-lane translation cache (`Tlb`; lookup and fill are
# paging.translate_cached).  TLB_ENTRIES is chosen from the counters
# `device.tlb_walks` / `device.tlb_fill_steps` (PERF.md section 6, PR 38): the
# benchmark's lanes touch a handful to a dozen pages, and an instruction
# needs at most six entries at once.
TLB_ENTRIES = 8
TLB_VALID, TLB_OK, TLB_WRITABLE, TLB_USER = 1, 2, 4, 8


class Tlb(NamedTuple):
    """A lane's translation cache: what `paging.translate_vec` returned
    for TLB_ENTRIES virtual pages, a failed walk included.  It lives in
    the overlay because every path that changes what a walk reads goes
    through this module or a function that takes the overlay whole: a
    guest store drops the entries whose walk read a frame it writes
    (`tlb_drop`, in `store_window3` / `scatter_span`), the host's page
    pushes likewise (runner._apply_page_writes), the insert and the
    restore drop the lane's entries (runner.device_insert_impl,
    `overlay_reset`), and entries walked under another CR3 are never
    served (`cr3`).  So the cache only ever answers what a fresh walk
    would.  Empty is all zeros."""

    vpn: jax.Array       # uint64[lanes, E]: gva >> 12
    page: jax.Array      # uint64[lanes, E]: the 4-KiB physical page (a large
                         # page is cached by the piece); 0 for a failed walk
    flags: jax.Array     # int32[lanes, E]: TLB_* bits; 0 = empty
    frames: jax.Array    # int32[lanes, E, 4]: pfn of the table page read at
                         # each level, -1 where the walk had ended
    next: jax.Array      # int32[lanes]: the entry the next fill replaces
    cr3: jax.Array       # uint64[lanes]: the CR3 the entries were walked under


@lru_cache(maxsize=None)
def _filler(shape: tuple, value: int, dtype, sharding):
    return jax.jit(lambda: jnp.full(shape, value, dtype),
                   out_shardings=sharding)


def filled(shape: tuple, value: int, dtype, sharding=None) -> jax.Array:
    """`jnp.full` that every device of `sharding` runs for its own part
    (None: the default device).  A mesh's machine is born so, leaf by
    leaf: at 16,384 lanes the data plane alone is 8.6 GB, and an array
    made on one device and sharded afterwards would be whole on it first
    (`interp/machine.py` `machine_init`).  An empty array (a template's
    planes) has no part to allocate, and XLA would call it replicated."""
    if 0 in shape:
        return jax.device_put(np.full(shape, value, dtype), sharding)
    return _filler(tuple(shape), value, jnp.dtype(dtype), sharding)()


def tlb_init(n_lanes: int, sharding=None) -> Tlb:
    e = TLB_ENTRIES
    return Tlb(vpn=filled((n_lanes, e), 0, jnp.uint64, sharding),
               page=filled((n_lanes, e), 0, jnp.uint64, sharding),
               flags=filled((n_lanes, e), 0, jnp.int32, sharding),
               frames=filled((n_lanes, e, 4), 0, jnp.int32, sharding),
               next=filled((n_lanes,), 0, jnp.int32, sharding),
               cr3=filled((n_lanes,), 0, jnp.uint64, sharding))


def tlb_drop(tlb: Tlb, pfns: jax.Array, enabled: jax.Array) -> Tlb:
    """ONE lane's cache without the entries whose walk read a table page
    in one of the frames `pfns[k]` being written (`enabled[k]`).
    Compares over [E, 4] a frame: no gather, no scatter, no reduce."""
    hit = jnp.zeros(tlb.flags.shape, bool)
    for k in range(pfns.shape[0]):
        read = (tlb.frames == pfns[k]) & enabled[k]
        hit = hit | read[:, 0] | read[:, 1] | read[:, 2] | read[:, 3]
    return tlb._replace(flags=jnp.where(hit, 0, tlb.flags))


class DirtyOverlay(NamedTuple):
    """Every lane's dirty pages, as the machine holds them between
    dispatches (the RESIDENT layout; see the module docstring for why the
    planes are flat).

    Rows are DELTAS, not copies: the `valid` byte of a word marks that it
    has been written; reads take the overlay word when its valid byte is
    set and the base image word otherwise.  Allocating a row therefore
    never copies the 4 KiB base page.  INVARIANT: every word of a FREE
    row has valid == 0 (`overlay_init`/`overlay_reset` zero the plane),
    so claiming a row writes nothing but its pfn."""

    pfn: jax.Array       # int32[lanes, capacity]; -1 = free row
    data: jax.Array      # uint32[2 * lanes * capacity * PAGE_WORDS]: limbs
    valid: jax.Array     # uint8[lanes * capacity * PAGE_WORDS]; 1 = overlaid
    count: jax.Array     # int32[lanes]: allocated rows
    overflow: jax.Array  # bool[lanes]: lane ran out of overlay rows
    tlb: Tlb             # each lane's translation cache


class LaneOverlay(NamedTuple):
    """ONE lane's view of a `DirtyOverlay` — what the per-lane functions
    of this module take.  `data`/`valid` are the WHOLE resident planes
    (unmapped under vmap); `base` is the plane index of the lane's row 0."""

    pfn: jax.Array       # int32[capacity]
    data: jax.Array      # the whole uint32 limb plane
    valid: jax.Array     # the whole uint8 plane
    count: jax.Array     # int32 scalar
    overflow: jax.Array  # bool scalar
    base: jax.Array      # int32 scalar
    tlb: Tlb             # the lane's entries (no lane axis)


# vmap in_axes / out_axes for a `lanes(overlay)` view
LANE_AXES = LaneOverlay(pfn=0, data=None, valid=None, count=0, overflow=0,
                        base=0, tlb=0)


class StoreCommit(NamedTuple):
    """Words a lane's store writes, by plane index (distinct and in
    bounds; a disabled store rewrites what was there)."""

    idx: jax.Array       # int32[K] word index (the limbs' is twice that)
    data: jax.Array      # uint64[K]
    valid: jax.Array     # uint8[K]


class StoreSpill(NamedTuple):
    """A word of a store's window as far as it costs no index (what
    `_merged_word` reads and merges); `store_window3` hands the THIRD
    word of a lane's 16-byte window out so: only a store wider than 8
    bytes to an address that is not 8-aligned reaches it (`spill`), so
    its read, merge and commit are `commit_spills`', which every
    lane-step pays a conditional for and not eight indices (PERF.md
    section 6, PR 44)."""

    idx: jax.Array       # int32 plane index of the word
    slot: jax.Array      # int32 row of the image's pages under it
    widx: jax.Array      # int32 word index within the page
    mask: jax.Array      # uint64 bits of the word the store writes
    value: jax.Array     # uint64 the store's bits, in place
    spill: jax.Array     # bool: an enabled store reaches the word


def overlay_init(n_lanes: int, capacity: int,
                 sharding=None) -> DirtyOverlay:
    """Allocate the batched overlay store for `n_lanes` lanes; under a
    mesh's lane `sharding` each device allocates its own lanes' part of
    every leaf, and a plane's indices are local to that part (`lanes`)."""
    n_words = n_lanes * capacity * PAGE_WORDS
    part = (2 * n_words if sharding is None
            else sharding.shard_shape((2 * n_words,))[0])
    assert part < 1 << 31, "overlay plane indices are int32"
    return DirtyOverlay(
        pfn=filled((n_lanes, capacity), -1, jnp.int32, sharding),
        data=filled((2 * n_words,), 0, jnp.uint32, sharding),
        valid=filled((n_words,), 0, jnp.uint8, sharding),
        count=filled((n_lanes,), 0, jnp.int32, sharding),
        overflow=filled((n_lanes,), 0, bool, sharding),
        tlb=tlb_init(n_lanes, sharding),
    )


def overlay_reset(overlay: DirtyOverlay) -> DirtyOverlay:
    """Restore(): drop every dirty page; no page data moves.

    Replaces `Ram_t::Restore` + per-backend dirty loops (ram.h:235-280).
    The validity plane is zeroed here (one byte per overlay word, written
    in place under donation) so that no store has to clear a row when it
    claims it: a restore happens once a testcase, a claim in every sweep
    of every lane."""
    return DirtyOverlay(
        pfn=jnp.full_like(overlay.pfn, -1),
        data=overlay.data,   # stale data is unreachable once valid is 0
        valid=jnp.zeros_like(overlay.valid),
        count=jnp.zeros_like(overlay.count),
        overflow=jnp.zeros_like(overlay.overflow),
        # a restored lane walks the snapshot's tables again
        tlb=jax.tree.map(jnp.zeros_like, overlay.tlb),
    )


def lanes(overlay: DirtyOverlay) -> LaneOverlay:
    """Every lane's `LaneOverlay`, batched on the lane axis except for the
    planes: `jax.vmap(f, in_axes=LANE_AXES)(lanes(ov))`.  `base` is local
    to the planes in hand, so a mesh shard's view indexes its own slab."""
    n_lanes, capacity = overlay.pfn.shape
    base = jnp.arange(n_lanes, dtype=jnp.int32) * jnp.int32(
        capacity * PAGE_WORDS)
    return LaneOverlay(overlay.pfn, overlay.data, overlay.valid,
                       overlay.count, overlay.overflow, base, overlay.tlb)


def lane(overlay: DirtyOverlay, i) -> LaneOverlay:
    """Lane `i`'s view (unbatched callers: tests, host-side probes); an
    index ARRAY gives the batched view of those lanes."""
    v = lanes(overlay)
    return LaneOverlay(v.pfn[i], v.data, v.valid, v.count[i],
                       v.overflow[i], v.base[i],
                       jax.tree.map(lambda x: x[i], v.tlb))


def resident(view: LaneOverlay) -> DirtyOverlay:
    """Back from a (batched) `lanes()` view to the resident overlay."""
    return DirtyOverlay(view.pfn, view.data, view.valid, view.count,
                        view.overflow, view.tlb)


def row_start(overlay: DirtyOverlay, lane_idx, row):
    """Plane index of word 0 of (lane, row)."""
    return (lane_idx * overlay.pfn.shape[-1] + row) * PAGE_WORDS


def _limbs(words: jax.Array) -> jax.Array:
    """uint64[..., n] -> uint32[..., 2 * n], limbs side by side."""
    return unpack_u64(words).reshape(words.shape[:-1] + (-1,))


def _words(limbs: jax.Array) -> jax.Array:
    """uint32[..., 2 * n] -> uint64[..., n]."""
    return pack_u64(limbs.reshape(limbs.shape[:-1] + (-1, 2)))


def _limb_index(at: jax.Array) -> jax.Array:
    """Word indices -> int32[..., 2] indices of their two limbs."""
    return jnp.stack([2 * at, 2 * at + 1], axis=-1)


def read_row(overlay: DirtyOverlay, lane_idx, row
             ) -> Tuple[jax.Array, jax.Array]:
    """(data u64[PAGE_WORDS], valid u8[PAGE_WORDS]) of one overlay row."""
    start = row_start(overlay, lane_idx, row)
    return (_words(lax.dynamic_slice(overlay.data, (2 * start,),
                                     (2 * PAGE_WORDS,))),
            lax.dynamic_slice(overlay.valid, (start,), (PAGE_WORDS,)))


_read_row_jit = jax.jit(read_row)


def fetch_row(overlay: DirtyOverlay, lane_idx: int, row: int):
    """`read_row` for the host mirror: one dispatch and one transfer ->
    (np.uint64[PAGE_WORDS], np.uint8[PAGE_WORDS])."""
    return jax.device_get(_read_row_jit(overlay, lane_idx, row))


def read_lane_rows(overlay: DirtyOverlay, rows
                   ) -> Tuple[jax.Array, jax.Array]:
    """Row `rows[lane]` of every lane at once (-1: none, reads as zeros)
    -> (limbs u32[lanes, 2 * PAGE_WORDS], valid u8[lanes, PAGE_WORDS]).

    Lane-local like `fill_rows`: a lane indexes its own rows, so a mesh
    shard runs this on the planes in hand, and the shape hangs on the
    lane count alone.  The words stay limbs on the device; the host reads
    their memory image as u64 (`fetch_lane_rows`)."""
    n_lanes = overlay.pfn.shape[0]
    start = row_start(overlay, jnp.arange(n_lanes, dtype=jnp.int32), rows)
    # a window that starts past the plane is out of bounds and filled
    start = jnp.where(rows >= 0, start, overlay.valid.shape[0])[:, None]
    dnums = lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))

    def take(plane, at, size):
        return lax.gather(plane, at, dnums, (size,),
                          mode=lax.GatherScatterMode.FILL_OR_DROP,
                          fill_value=0)

    return (take(overlay.data, 2 * start, 2 * PAGE_WORDS),
            take(overlay.valid, start, PAGE_WORDS))


read_lane_rows_jit = jax.jit(read_lane_rows)


def fetch_lane_rows(overlay: DirtyOverlay, rows, read):
    """`read_lane_rows` for the host mirror: one dispatch and one transfer
    for the whole batch -> (np.uint64[lanes, PAGE_WORDS],
    np.uint8[lanes, PAGE_WORDS]).  `read` is the compiled reader:
    `read_lane_rows_jit`, or the mesh's per-shard one."""
    limbs, valid = jax.device_get(read(overlay, jnp.asarray(rows)))
    # the two little-endian limbs of a word, side by side, are its bytes
    return np.ascontiguousarray(limbs).view(np.uint64), valid


def write_row(overlay: DirtyOverlay, lane_idx, row, data, valid
              ) -> DirtyOverlay:
    """Overwrite one whole overlay row (host page pushes)."""
    start = row_start(overlay, lane_idx, row)
    return overlay._replace(
        data=lax.dynamic_update_slice(overlay.data, _limbs(data),
                                      (2 * start,)),
        valid=lax.dynamic_update_slice(overlay.valid, valid, (start,)))


def fill_rows(overlay: DirtyOverlay, first_row, words,
              enabled) -> DirtyOverlay:
    """Each enabled lane overwrites its consecutive rows from
    `first_row[lane]` with `words[lane]` (u64[lanes, n * PAGE_WORDS]:
    whole pages, every word valid) — the batch's testcase insert."""
    n_lanes = overlay.pfn.shape[0]
    start = row_start(overlay, jnp.arange(n_lanes, dtype=jnp.int32),
                      first_row)
    # a window that starts past the plane is out of bounds and dropped
    start = jnp.where(enabled, start, overlay.valid.shape[0])[:, None]
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0,))

    def put(plane, at, updates):
        return lax.scatter(plane, at, updates, dnums, unique_indices=True,
                           mode=lax.GatherScatterMode.FILL_OR_DROP)

    return overlay._replace(
        data=put(overlay.data, 2 * start, _limbs(words)),
        valid=put(overlay.valid, start,
                  jnp.ones(words.shape, jnp.uint8)))


def _scatter_words(overlay, commit: StoreCommit):
    """One flat scatter per plane; the caller names the scope."""
    idx = commit.idx.reshape(-1)
    return overlay._replace(
        data=overlay.data.at[_limb_index(idx)].set(
            unpack_u64(commit.data.reshape(-1)), unique_indices=True),
        valid=overlay.valid.at[idx].set(commit.valid.reshape(-1),
                                        unique_indices=True))


@jax.named_scope("overlay-store")
def commit_stores(overlay, commit: StoreCommit):
    """Apply a (possibly lane-batched) `StoreCommit` to the planes of a
    `DirtyOverlay` or `LaneOverlay`: one flat scatter per plane."""
    return _scatter_words(overlay, commit)


@jax.named_scope("store-spill")
def commit_spills(image: MemImage, overlay, spill: StoreSpill):
    """The third words of the stores of the lanes in hand (a lane-batched
    `StoreSpill`, or one lane's), after `commit_stores`: ONE conditional
    on whether any lane spills.  Its taken side reads, merges and
    scatters one word a lane (a lane that does not spill rewrites what
    was there, as a disabled store does); the other side hands the planes
    on, and both alias them as the sweep's loops do
    (tests/test_chip_compile.py)."""

    def taken(planes):
        view = overlay._replace(data=planes[0], valid=planes[1])
        new, vnew = _merged_word(image, view, spill)
        view = _scatter_words(view, StoreCommit(spill.idx, new, vnew))
        return view.data, view.valid

    data, valid = lax.cond(jnp.any(spill.spill), taken, lambda planes: planes,
                           (overlay.data, overlay.valid))
    return overlay._replace(data=data, valid=valid)


def split_gpa(image: MemImage, gpa: jax.Array):
    """gpa (uint64) -> (pfn int32 with OOB sentinel, offset int32)."""
    nframes = image.frame_table.shape[-1]
    pfn64 = gpa >> PAGE_SHIFT
    in_range = pfn64 < jnp.uint64(nframes)
    pfn = jnp.where(in_range, pfn64, jnp.uint64(_PFN_OOB)).astype(jnp.int32)
    off = (gpa & jnp.uint64(PAGE_SIZE - 1)).astype(jnp.int32)
    return pfn, off


def lookup(overlay: LaneOverlay, pfn: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Find `pfn` in this lane's overlay -> (slot index, hit)."""
    eq = overlay.pfn == pfn
    idx = jnp.argmax(eq).astype(jnp.int32)
    hit = eq[idx]
    return idx, hit


def lookup_vec(
    overlay: LaneOverlay, pfn_vec: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Find K pfns in this lane's overlay at once -> (idx[K], hit[K]).

    One [K, capacity] compare + one row-wise argmax instead of K scalar
    probes: the interpreter batches every overlay lookup a step needs into
    a single call, cutting the per-step count of unfusable gather kernels
    (the TPU cost is per-kernel dispatch latency, not the compares)."""
    eq = overlay.pfn[None, :] == pfn_vec[:, None]
    idx = jnp.argmax(eq, axis=1).astype(jnp.int32)
    hit = jnp.any(eq, axis=1)  # gather-free: argmax picks the first True
    return idx, hit


def read_words_vec(
    image: MemImage,
    overlay: LaneOverlay,
    slot_vec: jax.Array,    # int32[K] image page slots
    row_vec: jax.Array,     # int32[K] overlay rows
    use_ov_vec: jax.Array,  # bool[K]: the page hit an overlay slot
    widx_vec: jax.Array,    # int32[K] word index within the page
) -> jax.Array:
    """K overlay-aware aligned words in three gathers (image + overlay
    data + overlay word-validity)."""
    base = image_words(image, slot_vec, widx_vec)
    at = overlay.base + row_vec * PAGE_WORDS + widx_vec
    ov_valid = overlay.valid[at] != 0
    return jnp.where(use_ov_vec & ov_valid, read_data(overlay, at), base)


def _gathered_words(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """(low limbs, high limbs) uint32[...] -> uint64[...].  The limbs come
    from a gather each and meet elementwise: gathered as pairs
    (`u32[..., 2]`) they are packed by a reduce over the pair, which the
    TPU compiler made a kernel of its own wherever it laid the lanes out
    major (the walk's `u32[lanes, 2, 2]`, the windows' `u32[lanes, 9, 2]`:
    ten kernels that loop over the lanes one by one, PERF.md section 5,
    PR 38).  The gathered indices are the same either way."""
    return lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << jnp.uint64(32))


def image_words(image: MemImage, slot: jax.Array, widx: jax.Array
                ) -> jax.Array:
    """The u64 words `widx` of the base image's page rows `slot` (any
    shape): a gather of their low limbs and one of their high limbs."""
    return _gathered_words(image.pages[slot, 2 * widx],
                           image.pages[slot, 2 * widx + 1])


def read_data(overlay: LaneOverlay, at: jax.Array) -> jax.Array:
    """The u64 data words at word indices `at` (any shape): a gather of
    their low limbs and one of their high limbs."""
    return _gathered_words(overlay.data[2 * at], overlay.data[2 * at + 1])


def pte_read_vec(
    image: MemImage, overlay: LaneOverlay, gpa_vec: jax.Array
) -> jax.Array:
    """K 8-aligned little-endian u64 reads (one page-walk level for every
    translation a step needs) -> u64[K]."""
    pfn, off = split_gpa(image, gpa_vec)
    row, hit = lookup_vec(overlay, pfn)
    slot = frame_slot(image, pfn)
    return read_words_vec(image, overlay, slot, row, hit, off >> 3)


@jax.named_scope("overlay-load")
def load_windows3_vec(
    image: MemImage,
    overlay: LaneOverlay,
    gpa_first_vec: jax.Array,  # uint64[K] first-byte GPA per window
    gpa_last_vec: jax.Array,   # uint64[K] last-byte GPA per window
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """K 3-word windows (any <=16-byte span each) -> (w0[K], w1[K], w2[K]).

    The batched form of `load_window3`: one overlay lookup over the 2K
    page frames and one 3K-word gather pair, instead of K independent
    window loads."""
    k = gpa_first_vec.shape[0]
    pfn0, off0 = split_gpa(image, gpa_first_vec)
    pfn1, _ = split_gpa(image, gpa_last_vec)
    rows, hits = lookup_vec(overlay, jnp.concatenate([pfn0, pfn1]))
    row0, row1 = rows[:k], rows[k:]
    hit0, hit1 = hits[:k], hits[k:]
    slot0 = frame_slot(image, pfn0)
    slot1 = frame_slot(image, pfn1)

    w_start = off0 >> 3
    j = jnp.arange(3, dtype=jnp.int32)[:, None]           # [3, 1]
    on_first = (w_start[None, :] + j) < PAGE_WORDS        # [3, K]
    widx = jnp.where(on_first, w_start[None, :] + j,
                     w_start[None, :] + j - PAGE_WORDS)
    slot = jnp.where(on_first, slot0[None, :], slot1[None, :])
    row = jnp.where(on_first, row0[None, :], row1[None, :])
    use_ov = jnp.where(on_first, hit0[None, :], hit1[None, :])
    words = read_words_vec(image, overlay, slot.reshape(-1), row.reshape(-1),
                           use_ov.reshape(-1), widx.reshape(-1)).reshape(3, k)
    return words[0], words[1], words[2]


def ensure_page(
    image: MemImage, overlay: LaneOverlay, pfn: jax.Array, enabled: jax.Array
) -> Tuple[LaneOverlay, jax.Array, jax.Array]:
    """Claim an overlay slot for `pfn` when `enabled` (delta semantics: the
    row's data words are MEANINGLESS until their valid bytes are set by a
    store — always read through `read_words_vec`, never `data` directly).
    Only the lane's index state changes: a free row's valid bytes are
    already zero (the `DirtyOverlay` invariant), so the planes are not
    touched.

    Returns (overlay', slot index, ok).  ok=False when the overlay is full
    (the run loop surfaces that lane as a hard error) or pfn is out of range.
    """
    capacity = overlay.pfn.shape[0]
    idx0, hit = lookup(overlay, pfn)

    in_range = pfn != _PFN_OOB
    can_alloc = overlay.count < capacity
    do_alloc = enabled & ~hit & can_alloc & in_range
    idx = jnp.where(hit, idx0, overlay.count % capacity).astype(jnp.int32)

    pfns = overlay.pfn.at[idx].set(
        jnp.where(do_alloc, pfn, overlay.pfn[idx]).astype(jnp.int32)
    )
    count = overlay.count + do_alloc.astype(jnp.int32)
    overflow = overlay.overflow | (enabled & ~hit & ~can_alloc & in_range)

    ok = (hit | do_alloc) & in_range
    return overlay._replace(pfn=pfns, count=count, overflow=overflow), idx, ok


# ---------------------------------------------------------------------------
# hot word-window primitives (the interpreter's memory path)
# ---------------------------------------------------------------------------

def pte_read(image: MemImage, overlay: LaneOverlay, gpa: jax.Array) -> jax.Array:
    """Read an 8-aligned little-endian u64 (page-table entries).  K=1
    wrapper over `pte_read_vec` — one implementation of the overlay-aware
    word read."""
    return pte_read_vec(image, overlay,
                        jnp.asarray(gpa, jnp.uint64).reshape(1))[0]


def load_window3(
    image: MemImage,
    overlay: LaneOverlay,
    gpa_first: jax.Array,
    gpa_last: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scalar (K=1) convenience wrapper over `load_windows3_vec` — one
    3-word window covering any <=16-byte span."""
    w0, w1, w2 = load_windows3_vec(
        image, overlay, gpa_first[None], gpa_last[None])
    return w0[0], w1[0], w2[0]


def extract_pair(w0, w1, w2, gpa_first):
    """(lo, hi) u64 value pair of the 16 bytes starting at gpa_first,
    from its 3-word window."""
    sh = (gpa_first & _u(7)) * _u(8)
    inv = _u(64) - sh
    lo = _shr(w0, sh) | _shl(w1, inv)
    hi = _shr(w1, sh) | _shl(w2, inv)
    return lo, hi


def _merged_word(image: MemImage, overlay, word: StoreSpill):
    """Read-modify-write of the words `word` describes (any shape) ->
    (word, valid byte).  Delta rows: a partial write to a not-yet-valid
    word merges with the base image word; the stored word is then
    complete -> valid.  An untouched word comes back as it was."""
    was_valid = overlay.valid[word.idx] != 0
    old = jnp.where(was_valid, read_data(overlay, word.idx),
                    image_words(image, word.slot, word.widx))
    return (jnp.where(word.spill,
                      (old & ~word.mask) | (word.value & word.mask), old),
            jnp.where(word.spill, jnp.uint8(1),
                      was_valid.astype(jnp.uint8)))


@jax.named_scope("overlay-store")
def store_window3(
    image: MemImage,
    overlay: LaneOverlay,
    t_first,               # Translation-like with .gpa of first byte
    t_last,                # Translation-like with .gpa of last byte
    size,                  # traced int32, 1..16
    lo: jax.Array,
    hi: jax.Array,
    enabled: jax.Array,
) -> Tuple[LaneOverlay, jax.Array, StoreCommit, StoreSpill]:
    """Store up to 16 bytes (value (lo, hi), little-endian) through the
    lane overlay: claim the one or two touched pages' rows, then a
    read-modify-write with per-word bitmasks of the first TWO words of
    the 3-word window.  Returns (overlay', ok, commit, spill): overlay'
    carries the claimed rows, !ok = overlay full, `commit` is the two
    words for `commit_stores`, and `spill` what `commit_spills` needs of
    the third."""
    pfn0, off0 = split_gpa(image, t_first.gpa)
    pfn1, _ = split_gpa(image, t_last.gpa)
    crosses = (off0 + size) > PAGE_SIZE
    overlay, row0, ok0 = ensure_page(image, overlay, pfn0, enabled)
    overlay, row1, ok1 = ensure_page(image, overlay, pfn1, enabled & crosses)
    ok = ok0 & (ok1 | ~crosses)
    do = enabled & ok
    # a store into a page table: the translations walked through it go
    overlay = overlay._replace(tlb=tlb_drop(
        overlay.tlb, jnp.stack([pfn0, pfn1]), jnp.stack([do, do & crosses])))
    slot0 = frame_slot(image, pfn0)
    slot1 = frame_slot(image, pfn1)

    sh = (off0.astype(jnp.uint64) & _u(7)) * _u(8)
    inv = _u(64) - sh
    # value spread over the 3-word window
    v0 = _shl(lo, sh)
    v1 = _shr(lo, inv) | _shl(hi, sh)
    v2 = _shr(hi, inv)
    # bit span [sh, sh + size*8) within the 192-bit window
    end_bit = sh + size.astype(jnp.uint64) * _u(8)

    w_start = off0 >> 3
    words = []
    for j, vj in enumerate((v0, v1, v2)):
        on_first = (w_start + j) < PAGE_WORDS
        widx = jnp.where(on_first, w_start + j, w_start + j - PAGE_WORDS)
        row = jnp.where(on_first, row0, row1)
        slot = jnp.where(on_first, slot0, slot1)
        lo_bit = _u(64 * j)
        # mask of the bits of word j inside the span [sh, end_bit)
        start_in = jnp.maximum(sh, lo_bit)
        end_in = jnp.minimum(end_bit, lo_bit + _u(64))
        has = end_in > start_in
        n_bits = jnp.where(has, end_in - start_in, _u(0))
        off_in = jnp.where(has, start_in - lo_bit, _u(0))
        # n_bits == 64 wraps (1 << 64 -> 0) to the all-ones mask, correct
        mask = _shl(_shl(_u(1), n_bits) - _u(1), off_in)
        at = overlay.base + row * PAGE_WORDS + widx
        words.append(StoreSpill(at, slot, widx, mask, vj,
                                do & (mask != _u(0))))
    # words 0 and 1 are read, merged and committed in every pass; the
    # third costs no index here
    news, vnews = zip(*(_merged_word(image, overlay, w) for w in words[:2]))
    # the three plane indices are distinct by construction (word indices
    # strictly increase within a page and the straddle moves to another
    # row), and lanes own disjoint spans of the planes: the caller commits
    # every lane's words with ONE flat scatter per plane.  Scattered here,
    # per lane under vmap, into [lanes, capacity, PAGE_WORDS] planes, the
    # v5e compile relayouted both whole planes on the way in and out of
    # the scatter in every sweep (module docstring)
    return overlay, ok, StoreCommit(
        jnp.stack([w.idx for w in words[:2]]), jnp.stack(news),
        jnp.stack(vnews)), words[2]


# ---------------------------------------------------------------------------
# byte-granular compatibility paths (host-driven I/O, traces, tests)
# ---------------------------------------------------------------------------

def gather_bytes(
    image: MemImage,
    overlay: LaneOverlay,
    gpa_vec: jax.Array,   # uint64[size]: per-byte physical address
    first_mask: jax.Array # bool[size]: byte belongs to page of gpa_vec[0]
) -> jax.Array:
    """Overlay-aware read of bytes spread over at most two physical pages."""
    size = gpa_vec.shape[0]
    pfn0, _ = split_gpa(image, gpa_vec[0])
    pfn1, _ = split_gpa(image, gpa_vec[size - 1])

    idx0, hit0 = lookup(overlay, pfn0)
    idx1, hit1 = lookup(overlay, pfn1)
    slot0 = frame_slot(image, pfn0)
    slot1 = frame_slot(image, pfn1)

    byte_off = (gpa_vec & jnp.uint64(PAGE_SIZE - 1)).astype(jnp.int32)
    word_idx = byte_off >> 3
    shift = ((byte_off & 7) * 8).astype(jnp.uint64)
    slot = jnp.where(first_mask, slot0, slot1)
    row = jnp.where(first_mask, idx0, idx1)
    use_ov = jnp.where(first_mask, hit0, hit1)

    words = read_words_vec(image, overlay, slot, row, use_ov, word_idx)
    return ((words >> shift) & jnp.uint64(0xFF)).astype(jnp.uint8)


def scatter_span(
    image: MemImage,
    overlay: LaneOverlay,
    gpa_first: jax.Array,  # translated GPA of the span's first byte
    gpa_last: jax.Array,   # translated GPA of the span's last byte
    values: jax.Array,     # uint8[size], a virtually-contiguous span
    enabled: jax.Array,    # bool scalar
) -> Tuple[LaneOverlay, jax.Array, StoreCommit]:
    """Overlay-aware write of a contiguous span over at most two physical
    pages -> (overlay', ok, commit), `store_window3`'s contract: the
    bytes are packed into aligned words for ONE collision-free word
    scatter (`commit_stores`).  Every guest-visible write lands in the
    overlay and is therefore "dirty" by construction (VirtWriteDirty,
    backend.cc:91-127)."""
    size = values.shape[0]
    pfn0, off0 = split_gpa(image, gpa_first)
    pfn1, _ = split_gpa(image, gpa_last)
    two_pages = pfn1 != pfn0

    overlay, idx0, ok0 = ensure_page(image, overlay, pfn0, enabled)
    overlay, idx1, ok1 = ensure_page(image, overlay, pfn1, enabled & two_pages)
    ok = ok0 & jnp.where(two_pages, ok1, True)
    do = enabled & ok
    overlay = overlay._replace(tlb=tlb_drop(
        overlay.tlb, jnp.stack([pfn0, pfn1]),
        jnp.stack([do, do & two_pages])))

    # pack bytes into the aligned word window [w_start, w_start + W)
    head = (off0 & 7).astype(jnp.int32)
    n_words = (int(size) + 7 + 7) // 8  # worst-case unaligned span
    w_start = off0 >> 3
    vals64 = values.astype(jnp.uint64)
    slot0 = frame_slot(image, pfn0)
    slot1 = frame_slot(image, pfn1)
    ats, news, vnews = [], [], []
    for j in range(n_words):
        # byte indices of this word: i such that head + i in [8j, 8j+8)
        i0 = 8 * j - head  # may be negative (traced)
        k = jnp.arange(8, dtype=jnp.int32)
        src = i0 + k
        valid = (src >= 0) & (src < size)
        src_c = jnp.clip(src, 0, size - 1)
        word_val = jnp.sum(
            jnp.where(valid, vals64[src_c], jnp.uint64(0))
            << (k.astype(jnp.uint64) * jnp.uint64(8)))
        mask = jnp.sum(
            jnp.where(valid, jnp.uint64(0xFF), jnp.uint64(0))
            << (k.astype(jnp.uint64) * jnp.uint64(8)))
        on_first = (w_start + j) < PAGE_WORDS
        widx = jnp.where(on_first, w_start + j, w_start + j - PAGE_WORDS)
        row = jnp.where(on_first, idx0, jnp.where(two_pages, idx1, idx0))
        slot = jnp.where(on_first, slot0, jnp.where(two_pages, slot1, slot0))
        at = overlay.base + row * PAGE_WORDS + widx
        new, vnew = _merged_word(image, overlay, StoreSpill(
            at, slot, widx, mask, word_val, do & (mask != 0)))
        ats.append(at)
        news.append(new)
        vnews.append(vnew)
    # plane indices are distinct (word indices strictly increase within
    # each page; the straddle changes row)
    return overlay, ok, StoreCommit(jnp.stack(ats), jnp.stack(news),
                                    jnp.stack(vnews))


def _contiguous_vec(gpa: jax.Array, size: int):
    offs = jnp.arange(size, dtype=jnp.uint64)
    gpa_vec = gpa + offs
    page_off = (gpa & jnp.uint64(PAGE_SIZE - 1)).astype(jnp.int32)
    first_mask = (page_off + jnp.arange(size, dtype=jnp.int32)) < PAGE_SIZE
    return gpa_vec, first_mask


def phys_read(
    image: MemImage, overlay: LaneOverlay, gpa: jax.Array, size: int
) -> jax.Array:
    """Contiguous overlay-aware physical read (size <= PAGE_SIZE)."""
    gpa_vec, first_mask = _contiguous_vec(gpa, size)
    return gather_bytes(image, overlay, gpa_vec, first_mask)


def phys_write(
    image: MemImage,
    overlay: LaneOverlay,
    gpa: jax.Array,
    values: jax.Array,
    enabled: jax.Array,
) -> Tuple[LaneOverlay, jax.Array]:
    """Contiguous overlay-aware physical write (size <= PAGE_SIZE),
    committed at once: for ONE lane's view, not under vmap (there the
    planes are unmapped; take `scatter_span`'s commit out of it)."""
    last = gpa + jnp.uint64(values.shape[0] - 1)
    overlay, ok, commit = scatter_span(image, overlay, gpa, last, values,
                                       enabled)
    return commit_stores(overlay, commit), ok


def phys_read_u64(image: MemImage, overlay: LaneOverlay, gpa: jax.Array) -> jax.Array:
    """Read a little-endian u64 (used for page-table entries; PTEs are
    8-aligned so this is a single word)."""
    return pte_read(image, overlay, gpa)
