"""x86-64 long-mode address translation as a pure, traceable function.

Same 4-level walk the reference implements in software for KVM/WHV
(reference kvm_backend.cc:1937-1998 `VirtTranslate`, whv_backend.cc:650-721
`TranslateGva`), expressed as straight-line JAX with where-accumulation
instead of early returns so it vmaps over lanes.  Large pages (1GiB PDPTE.PS,
2MiB PDE.PS) are supported; accessed/dirty PTE bits are NOT set (documented
divergence — bochs sets them, which only grows the dirty-page set, and our
restore is overlay-based so nothing is lost).

Page-table reads go through the lane's dirty overlay so guest-modified page
tables are honored within a testcase.

The interpreter's step does not walk every address every pass: a lane keeps
its last `TLB_ENTRIES` translations (`overlay.Tlb`) and `translate_cached`
serves the step's six addresses from them, sending only the first `K_WALK`
pages that are needed and not cached to `translate_vec`.  The cache answers
what a fresh walk would, always: mem/overlay.py drops an entry when a frame
its walk read is written.
"""

from __future__ import annotations

import functools
import operator
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from wtf_tpu.core.gxa import PAGE_SIZE
from wtf_tpu.mem.overlay import (
    TLB_ENTRIES,
    TLB_OK,
    TLB_USER,
    TLB_VALID,
    TLB_WRITABLE,
    LaneOverlay,
    Tlb,
    commit_stores,
    gather_bytes,
    phys_read_u64,
    pte_read_vec,
    scatter_span,
    split_gpa,
)
from wtf_tpu.mem.physmem import MemImage

# Plain ints (promote against uint64 arrays): importing this module must not
# initialize the JAX backend.
PHYS_MASK = 0x000F_FFFF_FFFF_F000
PHYS_MASK_1G = 0x000F_FFFF_C000_0000
PHYS_MASK_2M = 0x000F_FFFF_FFE0_0000

PTE_PRESENT = 1
PTE_WRITE = 1 << 1
PTE_USER = 1 << 2
PTE_PS = 1 << 7

# Pages a pass of the step sends to the walk (`translate_cached`).  A lane
# that needs more of them at once takes a fill pass; chosen, with
# overlay.TLB_ENTRIES, from `device.tlb_walks` / `device.tlb_fill_steps`:
# the benchmark's lanes send 7-59 pages to the walk per thousand
# instructions and take no fill pass at two (PERF.md section 6, PR 38).
K_WALK = 2


class Translation(NamedTuple):
    gpa: jax.Array       # uint64
    ok: jax.Array        # bool: canonical and present all the way down
    writable: jax.Array  # bool: AND of W bits along the walk
    user: jax.Array      # bool: AND of U/S bits along the walk


def is_canonical(gva: jax.Array) -> jax.Array:
    """48-bit canonical check (bits 63:47 all equal)."""
    top = gva >> jnp.uint64(47)
    return (top == jnp.uint64(0)) | (top == jnp.uint64(0x1FFFF))


def translate(
    image: MemImage, overlay: LaneOverlay, cr3: jax.Array, gva: jax.Array
) -> Translation:
    """Walk PML4 -> PDPT -> PD -> PT for one GVA (single lane; vmapped).

    K=1 wrapper over `translate_vec` so the walk has exactly one
    implementation (host-side reads and the device step cannot diverge)."""
    t = translate_vec(image, overlay, cr3,
                      jnp.asarray(gva, jnp.uint64).reshape(1))
    return Translation(gpa=t.gpa[0], ok=t.ok[0],
                       writable=t.writable[0], user=t.user[0])


def translate_vec(
    image: MemImage, overlay: LaneOverlay, cr3: jax.Array, gva_vec: jax.Array
) -> Translation:
    """Walk K GVAs at once -> Translation with [K] fields.

    Bit-identical to `translate` per element; the K walks share one
    overlay lookup + PTE gather per level.  The one walk: K = 1 for
    `translate`, K = K_WALK in the step (`translate_cached`)."""
    return _walk(image, overlay, cr3, gva_vec)[0]


@jax.named_scope("page-walk")
def _walk(image: MemImage, overlay: LaneOverlay, cr3: jax.Array,
          gva_vec: jax.Array) -> Tuple[Translation, jax.Array]:
    """`translate_vec`, and the frames it read: int32[K, 4], the pfn of
    the table page whose entry decided each level (`split_gpa`'s, out of
    range included), -1 for a level the walk did not reach (a large page
    or a non-present entry above it, a non-canonical address).  A store
    to any other frame cannot change what this walk returns."""
    table = jnp.broadcast_to(cr3 & PHYS_MASK, gva_vec.shape)
    ok = is_canonical(gva_vec)
    writable = jnp.ones_like(ok)
    user = jnp.ones_like(ok)
    done = jnp.zeros_like(ok)
    gpa = jnp.zeros_like(gva_vec)
    frames = []

    levels = ((39, None), (30, PHYS_MASK_1G), (21, PHYS_MASK_2M), (12, None))
    for shift, large_mask in levels:
        index = (gva_vec >> jnp.uint64(shift)) & jnp.uint64(0x1FF)
        entry_gpa = table + index * jnp.uint64(8)
        frames.append(jnp.where(ok & ~done, split_gpa(image, entry_gpa)[0],
                                jnp.int32(-1)))
        entry = pte_read_vec(image, overlay, entry_gpa)
        present = (entry & PTE_PRESENT) != 0
        ok = ok & (done | present)
        writable = writable & (done | ((entry & PTE_WRITE) != 0))
        user = user & (done | ((entry & PTE_USER) != 0))

        if large_mask is not None:
            is_large = present & ((entry & PTE_PS) != 0) & ~done
            page_mask = (jnp.uint64(1) << jnp.uint64(shift)) - jnp.uint64(1)
            large_gpa = (entry & large_mask) | (gva_vec & page_mask)
            gpa = jnp.where(is_large, large_gpa, gpa)
            done = done | is_large
        if shift == 12:
            leaf_gpa = (entry & PHYS_MASK) | (gva_vec & jnp.uint64(0xFFF))
            gpa = jnp.where(done, gpa, leaf_gpa)

        table = entry & PHYS_MASK

    return (Translation(gpa=gpa, ok=ok, writable=writable, user=user),
            jnp.stack(frames, axis=-1))


def _fold(op, x: jax.Array, axis: int) -> jax.Array:
    """`op` over a SHORT static axis of `x`, as a chain of elementwise
    operations that fuse into their neighbours, where a reduce over an
    axis of two to eight would be a kernel of its own on the TPU."""
    return functools.reduce(op, (lax.index_in_dim(x, i, axis, keepdims=False)
                                 for i in range(x.shape[axis])))


def _any(x: jax.Array, axis: int) -> jax.Array:
    return _fold(operator.or_, x, axis)


def _pick(onehot: jax.Array, values: jax.Array, axis: int) -> jax.Array:
    """`values` where `onehot` holds, OR-ed over `axis`: a select by
    compares, no gather (rows of `onehot` hold at most once)."""
    return _fold(operator.or_, jnp.where(
        onehot, values, jnp.zeros((), values.dtype)), axis)


def translate_cached(
    image: MemImage, overlay: LaneOverlay, cr3: jax.Array,
    gva_vec: jax.Array, need: jax.Array,
) -> Tuple[Translation, LaneOverlay, jax.Array, jax.Array]:
    """The step's translations (uint64[N] addresses of ONE lane, `need[n]`
    those the instruction uses) through the lane's cache ->
    (Translation with [N] fields, overlay', pages walked, short).

    A needed address whose page is cached is served from its entry; a
    non-canonical one needs no walk; of the pages left, the first K_WALK
    distinct ones go to `translate_vec`, and their results serve this
    call and fill the cache (round robin).  `short`: a needed page was
    left over, and the caller repeats the call (the step's fill pass)
    before it uses any of the results; six pages are at most three
    calls, TLB_ENTRIES being more than N.  An address not needed reads
    not-ok.  A failed walk is served and cached like any other, `ok`
    false and the rest zero, so callers read `gpa`, `writable` and
    `user` only under `ok`.  No gather, no scatter and no reduce but the
    walk's: compares over [N, E] and selects over the E axis."""
    tlb = overlay.tlb
    n = gva_vec.shape[0]
    twelve = jnp.uint64(12)
    with jax.named_scope("tlb"):
        vpn = gva_vec >> twelve
        fresh = tlb.cr3 == cr3
        match = ((vpn[:, None] == tlb.vpn[None, :])
                 & (fresh & (tlb.flags != 0))[None, :])           # [N, E]
        want = need & is_canonical(gva_vec) & ~_any(match, 1)
        # one walk a page: the first of the addresses that share it leads
        same = vpn[:, None] == vpn[None, :]
        earlier = jnp.tril(jnp.ones((n, n), bool), k=-1)
        lead = want & ~_any(same & earlier & want[None, :], 1)
        rank = _fold(operator.add,
                     (earlier & lead[None, :]).astype(jnp.int32), 1)
        pick = lead[None, :] & (
            rank[None, :] == jnp.arange(K_WALK)[:, None])         # [K, N]
        walked = _any(pick, 1)
        walk_gva = _pick(pick, gva_vec[None, :], 1)

    t, frames = _walk(image, overlay, cr3, walk_gva)

    with jax.named_scope("tlb"):
        w_vpn = walk_gva >> twelve
        w_page = jnp.where(t.ok, t.gpa & ~jnp.uint64(0xFFF), jnp.uint64(0))
        w_flags = jnp.where(
            walked,
            TLB_VALID | jnp.where(t.ok, TLB_OK, 0)
            | jnp.where(t.ok & t.writable, TLB_WRITABLE, 0)
            | jnp.where(t.ok & t.user, TLB_USER, 0), 0).astype(jnp.int32)
        fwd = want[:, None] & walked[None, :] \
            & (vpn[:, None] == w_vpn[None, :])                    # [N, K]
        short = _any(want & ~_any(fwd, 1), 0)
        page = _pick(match, tlb.page[None, :], 1) \
            | _pick(fwd, w_page[None, :], 1)
        flags = _pick(match, tlb.flags[None, :], 1) \
            | _pick(fwd, w_flags[None, :], 1)
        out = Translation(
            gpa=page | (gva_vec & jnp.uint64(0xFFF)),
            ok=(flags & TLB_OK) != 0,
            writable=(flags & TLB_WRITABLE) != 0,
            user=(flags & TLB_USER) != 0)

        # fill: walk k takes entry (next + k) mod E; entries walked under
        # another CR3 go first
        slot = (tlb.next + jnp.arange(K_WALK, dtype=jnp.int32)) % TLB_ENTRIES
        put = walked[:, None] & (
            jnp.arange(TLB_ENTRIES, dtype=jnp.int32)[None, :]
            == slot[:, None])                                     # [K, E]
        filled = _any(put, 0)

        def fill(new, old):
            """`old` [E, ...] with row e from `new[k]` where put[k, e]."""
            tail = (1,) * (new.ndim - 1)
            return jnp.where(
                filled.reshape((-1,) + tail),
                _pick(put.reshape(put.shape + tail), new[:, None], 0), old)

        n_walked = _fold(operator.add, walked.astype(jnp.int32), 0)
        tlb = Tlb(
            vpn=fill(w_vpn, tlb.vpn), page=fill(w_page, tlb.page),
            flags=fill(w_flags, jnp.where(walked[0] & ~fresh, 0, tlb.flags)),
            frames=fill(frames, tlb.frames),
            next=(tlb.next + n_walked) % TLB_ENTRIES,
            cr3=jnp.where(walked[0], cr3, tlb.cr3))
    return out, overlay._replace(tlb=tlb), n_walked, short


def _virt_byte_addrs(gva: jax.Array, size: int, first: Translation, last: Translation):
    """Per-byte GPA vector for a virtual span touching at most two pages."""
    offs = jnp.arange(size, dtype=jnp.uint64)
    page_off = (gva & jnp.uint64(PAGE_SIZE - 1)).astype(jnp.int32)
    first_mask = (page_off + jnp.arange(size, dtype=jnp.int32)) < PAGE_SIZE
    gpa_first = first.gpa + offs
    gpa_last = last.gpa - jnp.uint64(size - 1) + offs
    gpa_vec = jnp.where(first_mask, gpa_first, gpa_last)
    return gpa_vec, first_mask


def virt_read(
    image: MemImage,
    overlay: LaneOverlay,
    cr3: jax.Array,
    gva: jax.Array,
    size: int,
) -> Tuple[jax.Array, jax.Array]:
    """Read uint8[size] at a guest-virtual address -> (bytes, fault).

    Two-translation form of the reference's page-by-page `VirtRead`
    (backend.cc:30-77): translate the first and last byte, stitch the spans.
    """
    first = translate(image, overlay, cr3, gva)
    last = translate(image, overlay, cr3, gva + jnp.uint64(size - 1))
    fault = ~(first.ok & last.ok)
    gpa_vec, first_mask = _virt_byte_addrs(gva, size, first, last)
    data = gather_bytes(image, overlay, gpa_vec, first_mask)
    return data, fault


def virt_write(
    image: MemImage,
    overlay: LaneOverlay,
    cr3: jax.Array,
    gva: jax.Array,
    values: jax.Array,
    enabled: jax.Array,
    enforce_writable: bool = False,
) -> Tuple[LaneOverlay, jax.Array]:
    """Write uint8[size] at a guest-virtual address -> (overlay', fault),
    committed at once: for ONE lane's view, not under vmap (see
    `overlay.phys_write`).

    `enforce_writable=True` is the guest-store path: writes to mappings whose
    walk lacks the W bit fault like a real CPU with CR0.WP would.  Host-side
    writes (InsertTestcase etc.) keep the reference's semantics of writing
    through protection (backend.cc VirtWrite is a raw memcpy).
    """
    size = values.shape[0]
    first = translate(image, overlay, cr3, gva)
    last = translate(image, overlay, cr3, gva + jnp.uint64(size - 1))
    fault = ~(first.ok & last.ok)
    if enforce_writable:
        fault = fault | ~(first.writable & last.writable)
    overlay, ok, commit = scatter_span(
        image, overlay, first.gpa, last.gpa, values, enabled & ~fault
    )
    return commit_stores(overlay, commit), fault | (enabled & ~fault & ~ok)


def virt_read_u64(
    image: MemImage,
    overlay: LaneOverlay,
    cr3: jax.Array,
    gva: jax.Array,
    size: int = 8,
) -> Tuple[jax.Array, jax.Array]:
    """Read a <=8-byte little-endian integer -> (uint64 value, fault)."""
    raw, fault = virt_read(image, overlay, cr3, gva, size)
    shifts = jnp.arange(size, dtype=jnp.uint64) * 8
    return jnp.sum(raw.astype(jnp.uint64) << shifts), fault
