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
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from wtf_tpu.core.gxa import PAGE_SIZE
from wtf_tpu.mem.overlay import (
    LaneOverlay,
    commit_stores,
    gather_bytes,
    phys_read_u64,
    pte_read_vec,
    scatter_span,
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


@jax.named_scope("page-walk")
def translate_vec_l(
    image: MemImage, overlay: LaneOverlay, cr3: jax.Array, gva_l: jax.Array
) -> Translation:
    """`translate_vec` over u32 limb-packed GVAs (uint32[K, 2], limb 0 low).

    This is the pack_u64 boundary adapter for the limb-packed interpreter
    hot path (interp/limbs.py): addresses are computed in u32 limbs, and
    the page walk — gather-bound, not elementwise-bound — converts at this
    seam with a free bitcast and runs in u64 as before.
    """
    from wtf_tpu.interp.limbs import pack_u64

    return translate_vec(image, overlay, cr3, pack_u64(gva_l))


def translate_vec(
    image: MemImage, overlay: LaneOverlay, cr3: jax.Array, gva_vec: jax.Array
) -> Translation:
    """Walk K GVAs at once -> Translation with [K] fields.

    Bit-identical to `translate` per element; the K walks share one
    overlay lookup + PTE gather per level (the interpreter's six
    translations per step collapse from 24 scalar PTE reads into 4
    vectorized ones)."""
    table = jnp.broadcast_to(cr3 & PHYS_MASK, gva_vec.shape)
    ok = is_canonical(gva_vec)
    writable = jnp.ones_like(ok)
    user = jnp.ones_like(ok)
    done = jnp.zeros_like(ok)
    gpa = jnp.zeros_like(gva_vec)

    levels = ((39, None), (30, PHYS_MASK_1G), (21, PHYS_MASK_2M), (12, None))
    for shift, large_mask in levels:
        index = (gva_vec >> jnp.uint64(shift)) & jnp.uint64(0x1FF)
        entry = pte_read_vec(image, overlay, table + index * jnp.uint64(8))
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

    return Translation(gpa=gpa, ok=ok, writable=writable, user=user)


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
