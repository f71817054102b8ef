"""Guest physical memory as an HBM-resident page store.

Replaces the reference's `Ram_t` (reference src/wtf/ram.h:96-152) and the
backends' demand-paging machinery (bochscpu lazy page handler
bochscpu_backend.cc:36-138, KVM userfaultfd kvm_backend.cc:2114-2304): on TPU
the whole snapshot image is uploaded once into HBM as a dense `[slots, 4096]`
uint8 array shared read-only by every lane, plus an int32 frame table mapping
guest page-frame-number -> slot.  Slot 0 is a shared zero page; pages absent
from the dump read as zeros, matching the reference's zero-fill semantics
(ram.h:249-262).

Guest writes NEVER touch this image — they go to the per-lane dirty overlay
(wtf_tpu/mem/overlay.py), which is what makes `Restore()` O(1).
"""

from __future__ import annotations

import mmap
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from wtf_tpu.core.gxa import PAGE_SHIFT, PAGE_SIZE
from wtf_tpu.telemetry.metrics import Counter


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


PAGE_WORDS = PAGE_SIZE // 8

# Both dims of the image are padded so that guests of similar size share
# XLA-compiled executables: to a power of two while that is small, and to
# the next multiple of a granule above it (a 4-GiB dump doubled would be
# 8 GiB of HBM; padded to 4096 rows it is 16 MiB over).
ROW_GRANULE = 4096        # page rows: 16 MiB
SPAN_GRANULE = 1 << 16    # frame-table entries: 256 KiB


def padded(n: int, granule: int) -> int:
    """`n` rounded up to a power of two up to `granule`, to a multiple of
    `granule` above it."""
    if n <= granule:
        return _next_pow2(n)
    return -(-n // granule) * granule


class MemImage(NamedTuple):
    """Device half of PhysMem; broadcast (unmapped) under vmap over lanes.

    Pages are stored as little-endian 64-bit WORDS, not bytes: the
    interpreter's accesses (page-table entries, operand loads/stores,
    code fetch) read aligned word windows and extract bytes with shifts,
    cutting gather counts ~5-8x vs a byte-granular layout (a 16-byte
    unaligned access is 3 word gathers instead of 16 byte gathers; a PTE
    read is 1 instead of 8).  Each word is held as its two uint32 limbs,
    low first (`overlay.image_words` gathers each): the chip has no
    64-bit integers, and a uint64 operand is split into two uint32
    planes at the head of every dispatch, which for a 4-GiB dump was a
    second copy of the image in HBM (X64SplitLow/High, PR 34); the host's
    uint64 rows upload as they lie (`rows.view(uint32)`).

    Multi-tenancy (wtf_tpu/tenancy): `frame_table` carries a leading
    TENANT axis — one pfn->slot row per base image, padded to a common
    page-span layout — and the optional `tenant` leaf is the per-lane
    row selector (int32[L] at dispatch; scalar under vmap).  A
    single-snapshot image is the degenerate [1, span] table with
    tenant=None (row 0 statically), so the pre-tenancy contract is
    unchanged and the pytree gains no leaf."""

    pages: jax.Array       # uint32[slots, 2 * PAGE_WORDS]; slot 0 = zero page
    frame_table: jax.Array # int32[tenants, span]; pfn -> slot (0 = absent)
    tenant: Optional[jax.Array] = None  # int32[L] lane -> frame-table row


def alloc_rows(n_present: int) -> np.ndarray:
    """The packed page array for `n_present` frames, for a producer that
    fills it in place (`PhysMem.from_rows`): uint64[rows, PAGE_WORDS],
    all zeros (so row 0 and the padding rows behind the frames stay as
    they are), no row touched yet."""
    n_rows = padded(n_present + 1, ROW_GRANULE)
    buf = mmap.mmap(-1, n_rows * PAGE_SIZE)     # anonymous: reads as zeros
    if hasattr(mmap, "MADV_HUGEPAGE"):
        # the first touch of fresh memory is what filling a dump costs;
        # where the kernel has huge pages a fault covers 2 MiB, not 4 KiB
        buf.madvise(mmap.MADV_HUGEPAGE)
    return np.frombuffer(buf, dtype=np.uint64).reshape(n_rows, PAGE_WORDS)


def _lies_under(array: jax.Array, sharding) -> bool:
    """Is `array` placed as `jax.device_put(..., sharding)` would place it
    (None: on one device)?"""
    if sharding is None:
        return len(array.sharding.device_set) == 1
    return array.sharding.is_equivalent_to(sharding, array.ndim)


class PhysMem:
    """Host-side container of the packed image.

    It keeps the host arrays it was built from: `rows` (page words, row 0
    the shared zero page, then the present frames by ascending pfn, then
    zeroed padding) and `table` (pfn -> row, 0 = absent).  Host readers
    (`host_read`: HostView, crash bucketing, the EmuCpu oracle) read
    those; the device image is made from them on the first use of
    `image`, so a process that never dispatches (`--backend emu`) never
    builds a device array, and nothing ever pulls the image back.  A
    PhysMem made around a device image alone (`PhysMem(image=...)`)
    fetches it on its first host read and counts that in `pull_counter`
    (`physmem.device_pulls`; the CLI puts its registry's counter there).
    """

    def __init__(self, rows: Optional[np.ndarray] = None,
                 table: Optional[np.ndarray] = None,
                 image: Optional[MemImage] = None):
        if image is None and (rows is None or table is None):
            raise ValueError("PhysMem needs host arrays or a device image")
        self._rows = rows
        self._table = table
        self._image = image
        self.pull_counter = Counter("physmem.device_pulls")

    # -- builders ---------------------------------------------------------
    @classmethod
    def from_rows(cls, pfns: np.ndarray, rows: np.ndarray,
                  min_frames: int = 16) -> "PhysMem":
        """Adopt a packed array from `alloc_rows(len(pfns))` whose rows
        1..n hold the frames `pfns` (ascending, unique): no page is
        copied, only the frame table is built."""
        pfns = np.asarray(pfns, dtype=np.int64)
        n = len(pfns)
        if rows.shape != (padded(n + 1, ROW_GRANULE), PAGE_WORDS) \
                or rows.dtype != np.uint64:
            raise ValueError(f"rows {rows.dtype}{rows.shape} are not "
                             f"alloc_rows({n})")
        if n and (pfns[0] < 0 or np.any(np.diff(pfns) <= 0)):
            raise ValueError("pfns must be ascending, unique and >= 0")
        span = padded(max(int(pfns[-1]) + 1 if n else 0, min_frames),
                      SPAN_GRANULE)
        table = np.zeros(span, dtype=np.int32)
        table[pfns] = np.arange(1, n + 1, dtype=np.int32)
        return cls(rows=rows, table=table)

    @classmethod
    def from_arrays(cls, pfns: np.ndarray, pages: np.ndarray,
                    min_frames: int = 16) -> "PhysMem":
        """Build from pfns int[n] (any order, unique) and their pages,
        uint8[n, 4096] or uint64[n, 512]: one bulk copy into the packed
        array, no per-page Python.

        Equivalent of `Ram_t::Populate` (ram.h:96-152) — but produces a dense
        packed array (only pages present in the dump occupy slots) instead of
        a flat mmap sized to the biggest GPA."""
        pfns = np.asarray(pfns, dtype=np.int64)
        pages = np.asarray(pages)
        if (pages.dtype, pages.shape) not in (
                (np.uint8, (len(pfns), PAGE_SIZE)),
                (np.uint64, (len(pfns), PAGE_WORDS))):
            raise ValueError(f"pages {pages.dtype}{pages.shape} for "
                             f"{len(pfns)} pfns")
        rows = alloc_rows(len(pfns))
        body = rows[1:len(pfns) + 1].view(pages.dtype)
        if np.all(np.diff(pfns) > 0):       # a dump's order: one memcpy
            body[...] = pages
        else:
            order = np.argsort(pfns, kind="stable")
            np.take(pages, order, axis=0, out=body)
            pfns = pfns[order]
        return cls.from_rows(pfns, rows, min_frames)

    @classmethod
    def from_pages(cls, pages: Dict[int, bytes], min_frames: int = 16) -> "PhysMem":
        """Build from {pfn: 4KiB page bytes} (tests and small synthetic
        guests; a dump goes through `from_arrays`)."""
        for pfn, data in pages.items():
            if len(data) != PAGE_SIZE:
                raise ValueError(f"page {pfn:#x} has size {len(data)}")
        return cls.from_arrays(
            np.fromiter(pages, dtype=np.int64, count=len(pages)),
            np.frombuffer(b"".join(pages.values()), dtype=np.uint8
                          ).reshape(len(pages), PAGE_SIZE),
            min_frames)

    # -- the two halves ---------------------------------------------------
    @property
    def image(self) -> MemImage:
        """The device image as it is held, uploaded on first use."""
        return self._image if self._image is not None else self.upload()

    def upload(self, sharding=None) -> MemImage:
        """The device image under `sharding` (None: the default device):
        placed from the host rows on first use, and again only when what
        is held lies elsewhere.  A mesh runner passes its replicated
        sharding, so every chip's copy comes from the host once and no
        chip holds a second one; `image` is then that array."""
        if self._image is None or not _lies_under(self._image.pages,
                                                  sharding):
            rows, table = self._host()
            self._image = MemImage(
                pages=jax.device_put(rows.view(np.uint32), sharding),
                frame_table=jax.device_put(table[None, :],   # [1, span]
                                           sharding))
        return self._image

    @property
    def replicas(self) -> int:
        """How many devices hold the image (0: not uploaded)."""
        return (0 if self._image is None
                else len(self._image.pages.sharding.device_set))

    @property
    def device_pulls(self) -> int:
        return self.pull_counter.value

    @property
    def on_device(self) -> bool:
        return self._image is not None

    def _host(self):
        if self._rows is None:
            # built around a device image alone: the one path that moves
            # image bytes device-to-host, and it is counted
            self.pull_counter.inc()
            self._rows = np.asarray(self._image.pages).view(np.uint64)
            self._table = np.asarray(self._image.frame_table)[0]
        return self._rows, self._table

    @property
    def rows(self) -> np.ndarray:
        """uint64[rows, PAGE_WORDS] on the host (read-only by contract)."""
        return self._host()[0]

    @property
    def table(self) -> np.ndarray:
        """int32[span] on the host: pfn -> row, 0 = absent."""
        return self._host()[1]

    @property
    def frames_present(self) -> int:
        return int(np.count_nonzero(self.table))

    def present_pfns(self) -> np.ndarray:
        """The present frames, ascending (so `rows[table[pfns]]` are
        their pages in order)."""
        return np.nonzero(self.table)[0]

    @property
    def nbytes(self) -> int:
        if self._rows is not None:
            return int(self._rows.nbytes + self._table.nbytes)
        return int(self._image.pages.nbytes
                   + self._image.frame_table.nbytes)

    def host_read(self, gpa: int, size: int) -> bytes:
        """Debug/host-side read of the *base* image (no overlay)."""
        rows, table = self._host()
        flat = rows.view(np.uint8)
        out = bytearray()
        pos = gpa
        end = gpa + size
        while pos < end:
            pfn = pos >> PAGE_SHIFT
            off = pos & (PAGE_SIZE - 1)
            chunk = min(end - pos, PAGE_SIZE - off)
            slot = int(table[pfn]) if pfn < len(table) else 0
            out += flat[slot, off : off + chunk].tobytes()
            pos += chunk
        return bytes(out)


# vmap in_axes for a dispatch image: pages/frame_table broadcast, the
# per-lane tenant selector mapped.  Only valid for images normalized
# through `lane_image` (tenant populated).
IMAGE_IN_AXES = MemImage(pages=None, frame_table=None, tenant=0)


def lane_image(image: MemImage, n_lanes: int) -> MemImage:
    """Normalize a dispatch image so `tenant` is always a populated
    int32[n_lanes] row selector (zeros for the single-image case) —
    executors normalize in-body so legacy callers passing a bare
    PhysMem image and tenancy runners share one vmap structure."""
    if image.tenant is None:
        return image._replace(tenant=jnp.zeros((n_lanes,), jnp.int32))
    return image


def frame_slot(image: MemImage, pfn: jax.Array) -> jax.Array:
    """pfn (int32) -> slot, with out-of-range pfns mapping to the zero page.

    The lane's frame-table row comes from `image.tenant` (the per-lane
    base-image selector, scalar under the interpreter's vmap); tenant=None
    is the single-image case and indexes row 0 statically — same program
    as the pre-tenancy 1-D table."""
    span = image.frame_table.shape[-1]
    in_range = (pfn >= 0) & (pfn < span)
    safe_pfn = jnp.clip(pfn, 0, span - 1)
    row = jnp.int32(0) if image.tenant is None else image.tenant
    return jnp.where(in_range, image.frame_table[row, safe_pfn], 0)
