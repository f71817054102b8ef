"""Per-lane dirty-page overlay: copy-on-write guest memory.

This is the TPU-native replacement for the reference's dirty-page tracking +
restore machinery (bochs write hooks bochscpu_backend.cc:550-593, KVM dirty
bitmaps kvm_backend.cc:1568-1637, WHV R-X write-protection faults
whv_backend.cc:1163-1189, and `Ram_t::Restore` ram.h:235-280).  Instead of
mutating guest RAM and rolling dirty pages back after every testcase, each
lane owns a small copy-on-write overlay: the first write to a page copies it
from the shared HBM image into the lane's overlay slot, and every later
read/write checks the overlay first.  `Restore()` is then a counter reset —
no page data ever moves.

Layout: page data is uint64 WORDS (little-endian), matching MemImage.  The
hot primitives operate on aligned word windows — `pte_read` (1 word),
`load_window3` (3 words cover any <=16-byte span), `store_window3` (3-word
read-modify-write) — so a memory access costs a handful of word gathers
instead of per-byte gathers.  Byte-granular `gather_bytes`/`scatter_span`
remain for host-driven paths (testcase insertion, traces, tests).

All functions here operate on a SINGLE lane's overlay and are `vmap`ped over
the lane axis by the interpreter (MemImage broadcast, Overlay mapped).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from wtf_tpu.core.gxa import PAGE_SHIFT, PAGE_SIZE
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


class DirtyOverlay(NamedTuple):
    """One lane's dirty pages (batched: leading lane axis on every field).

    Rows are DELTAS, not copies: `valid[row, w]` marks the words of `data`
    that have been written; reads take the overlay word when its valid
    byte is set and the base image word otherwise.  Allocating a slot
    therefore never copies the 4 KiB base page — the former copy-on-write
    fill was the hot path's dominant memory traffic (16 KiB/lane/step on
    store-heavy code)."""

    pfn: jax.Array       # int32[capacity]; -1 = free slot
    data: jax.Array      # uint64[capacity, PAGE_WORDS]
    valid: jax.Array     # uint8[capacity, PAGE_WORDS]; 1 = word overlaid
    count: jax.Array     # int32 scalar: allocated slots
    overflow: jax.Array  # bool scalar: lane ran out of overlay slots


def overlay_init(n_lanes: int, capacity: int) -> DirtyOverlay:
    """Allocate the batched overlay store for `n_lanes` lanes."""
    return DirtyOverlay(
        pfn=jnp.full((n_lanes, capacity), -1, dtype=jnp.int32),
        data=jnp.zeros((n_lanes, capacity, PAGE_WORDS), dtype=jnp.uint64),
        valid=jnp.zeros((n_lanes, capacity, PAGE_WORDS), dtype=jnp.uint8),
        count=jnp.zeros((n_lanes,), dtype=jnp.int32),
        overflow=jnp.zeros((n_lanes,), dtype=bool),
    )


def overlay_reset(overlay: DirtyOverlay) -> DirtyOverlay:
    """Restore(): drop every dirty page, O(1) in page data.

    Replaces `Ram_t::Restore` + per-backend dirty loops (ram.h:235-280)."""
    return DirtyOverlay(
        pfn=jnp.full_like(overlay.pfn, -1),
        data=overlay.data,   # stale data is unreachable once pfn is -1
        valid=overlay.valid,  # stale too: cleared when a slot reallocates
        count=jnp.zeros_like(overlay.count),
        overflow=jnp.zeros_like(overlay.overflow),
    )


def split_gpa(image: MemImage, gpa: jax.Array):
    """gpa (uint64) -> (pfn int32 with OOB sentinel, offset int32)."""
    nframes = image.frame_table.shape[-1]
    pfn64 = gpa >> PAGE_SHIFT
    in_range = pfn64 < jnp.uint64(nframes)
    pfn = jnp.where(in_range, pfn64, jnp.uint64(_PFN_OOB)).astype(jnp.int32)
    off = (gpa & jnp.uint64(PAGE_SIZE - 1)).astype(jnp.int32)
    return pfn, off


def lookup(overlay: DirtyOverlay, pfn: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Find `pfn` in this lane's overlay -> (slot index, hit)."""
    eq = overlay.pfn == pfn
    idx = jnp.argmax(eq).astype(jnp.int32)
    hit = eq[idx]
    return idx, hit


def lookup_vec(
    overlay: DirtyOverlay, pfn_vec: jax.Array
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
    overlay: DirtyOverlay,
    slot_vec: jax.Array,    # int32[K] image page slots
    row_vec: jax.Array,     # int32[K] overlay rows
    use_ov_vec: jax.Array,  # bool[K]: the page hit an overlay slot
    widx_vec: jax.Array,    # int32[K] word index within the page
) -> jax.Array:
    """K overlay-aware aligned words in three gathers (image + overlay
    data + overlay word-validity)."""
    base = image.pages[slot_vec, widx_vec]
    ov = overlay.data[row_vec, widx_vec]
    ov_valid = overlay.valid[row_vec, widx_vec] != 0
    return jnp.where(use_ov_vec & ov_valid, ov, base)


def pte_read_vec(
    image: MemImage, overlay: DirtyOverlay, gpa_vec: jax.Array
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
    overlay: DirtyOverlay,
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
    image: MemImage, overlay: DirtyOverlay, pfn: jax.Array, enabled: jax.Array
) -> Tuple[DirtyOverlay, jax.Array, jax.Array]:
    """Claim an overlay slot for `pfn` when `enabled` (delta semantics: the
    row's data words are MEANINGLESS until their valid bytes are set by a
    store — always read through `read_words_vec`, never `data` directly).

    Returns (overlay', slot index, ok).  ok=False when the overlay is full
    (the run loop surfaces that lane as a hard error) or pfn is out of range.
    """
    capacity = overlay.pfn.shape[0]
    idx0, hit = lookup(overlay, pfn)

    in_range = pfn != _PFN_OOB
    can_alloc = overlay.count < capacity
    do_alloc = enabled & ~hit & can_alloc & in_range
    idx = jnp.where(hit, idx0, overlay.count % capacity).astype(jnp.int32)

    # delta rows: allocation just claims the slot and clears its word
    # validity (512 bytes) — no 4 KiB base-page copy
    valid = overlay.valid.at[idx].set(
        jnp.where(do_alloc, jnp.zeros(PAGE_WORDS, jnp.uint8),
                  overlay.valid[idx]))
    pfns = overlay.pfn.at[idx].set(
        jnp.where(do_alloc, pfn, overlay.pfn[idx]).astype(jnp.int32)
    )
    count = overlay.count + do_alloc.astype(jnp.int32)
    overflow = overlay.overflow | (enabled & ~hit & ~can_alloc & in_range)

    ok = (hit | do_alloc) & in_range
    return DirtyOverlay(pfns, overlay.data, valid, count, overflow), idx, ok


# ---------------------------------------------------------------------------
# hot word-window primitives (the interpreter's memory path)
# ---------------------------------------------------------------------------

def pte_read(image: MemImage, overlay: DirtyOverlay, gpa: jax.Array) -> jax.Array:
    """Read an 8-aligned little-endian u64 (page-table entries).  K=1
    wrapper over `pte_read_vec` — one implementation of the overlay-aware
    word read."""
    return pte_read_vec(image, overlay,
                        jnp.asarray(gpa, jnp.uint64).reshape(1))[0]


def load_window3(
    image: MemImage,
    overlay: DirtyOverlay,
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


@jax.named_scope("overlay-store")
def store_window3(
    image: MemImage,
    overlay: DirtyOverlay,
    t_first,               # Translation-like with .gpa of first byte
    t_last,                # Translation-like with .gpa of last byte
    size,                  # traced int32, 1..16
    lo: jax.Array,
    hi: jax.Array,
    enabled: jax.Array,
) -> Tuple[DirtyOverlay, jax.Array]:
    """Commit up to 16 bytes (value (lo, hi), little-endian) through the
    lane overlay: copy-on-write the one or two touched pages, then a
    3-word read-modify-write with per-word bitmasks.  Returns
    (overlay', ok); !ok = overlay full."""
    pfn0, off0 = split_gpa(image, t_first.gpa)
    pfn1, _ = split_gpa(image, t_last.gpa)
    crosses = (off0 + size) > PAGE_SIZE
    overlay, row0, ok0 = ensure_page(image, overlay, pfn0, enabled)
    overlay, row1, ok1 = ensure_page(image, overlay, pfn1, enabled & crosses)
    ok = ok0 & (ok1 | ~crosses)
    do = enabled & ok
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
    rows = []
    widxs = []
    news = []
    vnews = []
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
        # delta rows: a partial write to a not-yet-valid word merges with
        # the base image word; the stored word is then complete -> valid
        was_valid = overlay.valid[row, widx] != 0
        old = jnp.where(was_valid, overlay.data[row, widx],
                        image.pages[slot, widx])
        touched = do & (mask != _u(0))
        new = jnp.where(touched, (old & ~mask) | (vj & mask), old)
        rows.append(row)
        widxs.append(widx)
        news.append(new)
        vnews.append(jnp.where(touched, jnp.uint8(1),
                               was_valid.astype(jnp.uint8)))
    # ONE scatter for all three words (the (row, widx) pairs are distinct
    # by construction: word indices strictly increase within a page and
    # the straddle moves to another row) — sequential single-word
    # scatters would each materialize an overlay copy on some backends
    rows3, widxs3 = jnp.stack(rows), jnp.stack(widxs)
    data = overlay.data.at[rows3, widxs3].set(jnp.stack(news))
    valid = overlay.valid.at[rows3, widxs3].set(jnp.stack(vnews))
    return overlay._replace(data=data, valid=valid), ok


# ---------------------------------------------------------------------------
# byte-granular compatibility paths (host-driven I/O, traces, tests)
# ---------------------------------------------------------------------------

def gather_bytes(
    image: MemImage,
    overlay: DirtyOverlay,
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
    overlay: DirtyOverlay,
    gpa_first: jax.Array,  # translated GPA of the span's first byte
    gpa_last: jax.Array,   # translated GPA of the span's last byte
    values: jax.Array,     # uint8[size], a virtually-contiguous span
    enabled: jax.Array,    # bool scalar
) -> Tuple[DirtyOverlay, jax.Array]:
    """Overlay-aware write of a contiguous span over at most two physical
    pages -> (overlay', ok).  Bytes are packed into aligned words and
    committed with ONE collision-free word scatter.  Every guest-visible
    write lands in the overlay and is therefore "dirty" by construction
    (VirtWriteDirty, backend.cc:91-127)."""
    size = values.shape[0]
    pfn0, off0 = split_gpa(image, gpa_first)
    pfn1, _ = split_gpa(image, gpa_last)
    two_pages = pfn1 != pfn0

    overlay, idx0, ok0 = ensure_page(image, overlay, pfn0, enabled)
    overlay, idx1, ok1 = ensure_page(image, overlay, pfn1, enabled & two_pages)
    ok = ok0 & jnp.where(two_pages, ok1, True)
    do = enabled & ok

    # pack bytes into the aligned word window [w_start, w_start + W)
    head = (off0 & 7).astype(jnp.int32)
    n_words = (int(size) + 7 + 7) // 8  # worst-case unaligned span
    w_start = off0 >> 3
    vals64 = values.astype(jnp.uint64)
    slot0 = frame_slot(image, pfn0)
    slot1 = frame_slot(image, pfn1)
    rows, widxs, news, vnews = [], [], [], []
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
        # delta rows: merge partial words with the base image word
        was_valid = overlay.valid[row, widx] != 0
        old = jnp.where(was_valid, overlay.data[row, widx],
                        image.pages[slot, widx])
        touched = do & (mask != 0)
        rows.append(row)
        widxs.append(widx)
        news.append(jnp.where(touched,
                              (old & ~mask) | (word_val & mask), old))
        vnews.append(jnp.where(touched, jnp.uint8(1),
                               was_valid.astype(jnp.uint8)))
    # one scatter: (row, widx) pairs are distinct (word indices strictly
    # increase within each page; the straddle changes row)
    rws, wxs = jnp.stack(rows), jnp.stack(widxs)
    data = overlay.data.at[rws, wxs].set(jnp.stack(news))
    validmap = overlay.valid.at[rws, wxs].set(jnp.stack(vnews))
    return overlay._replace(data=data, valid=validmap), ok


def _contiguous_vec(gpa: jax.Array, size: int):
    offs = jnp.arange(size, dtype=jnp.uint64)
    gpa_vec = gpa + offs
    page_off = (gpa & jnp.uint64(PAGE_SIZE - 1)).astype(jnp.int32)
    first_mask = (page_off + jnp.arange(size, dtype=jnp.int32)) < PAGE_SIZE
    return gpa_vec, first_mask


def phys_read(
    image: MemImage, overlay: DirtyOverlay, gpa: jax.Array, size: int
) -> jax.Array:
    """Contiguous overlay-aware physical read (size <= PAGE_SIZE)."""
    gpa_vec, first_mask = _contiguous_vec(gpa, size)
    return gather_bytes(image, overlay, gpa_vec, first_mask)


def phys_write(
    image: MemImage,
    overlay: DirtyOverlay,
    gpa: jax.Array,
    values: jax.Array,
    enabled: jax.Array,
) -> Tuple[DirtyOverlay, jax.Array]:
    """Contiguous overlay-aware physical write (size <= PAGE_SIZE)."""
    last = gpa + jnp.uint64(values.shape[0] - 1)
    return scatter_span(image, overlay, gpa, last, values, enabled)


def phys_read_u64(image: MemImage, overlay: DirtyOverlay, gpa: jax.Array) -> jax.Array:
    """Read a little-endian u64 (used for page-table entries; PTEs are
    8-aligned so this is a single word)."""
    return pte_read(image, overlay, gpa)
