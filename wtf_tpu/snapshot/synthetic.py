"""Synthetic snapshot builder: long-mode guests for tests and benches.

The reference's snapshots are Windows kernel crash-dumps taken with bdump.js
(reference README.md:168-240); none ship with the tree (targets/ is empty).
For unit tests, demo targets, and benchmarks we synthesize minimal but
architecturally real snapshots: 4-level page tables, long-mode CpuState, code
and data mapped at arbitrary GVAs.  The result loads through the same
`Snapshot` path as a parsed crash-dump, so everything downstream is exercised
identically.

Two sizes.  `SyntheticSnapshotBuilder` alone makes a guest of tens of
frames as a dict of pages.  With `RamLayout`, `fill_noise` and
`direct_map_tables` a target lays such a guest into a Windows-shaped full
dump (harness/demo_tlv_dump.py): every frame of a PC-style RAM present, the
guest's frames where a seeded scatter puts them, a supervisor-only direct
map of RAM in the kernel half, everything else seeded noise, all of it as
arrays filled in place (no page is a Python object).
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Optional

import numpy as np

from wtf_tpu.core.cpustate import (
    CR0_PE,
    CR0_PG,
    CR4_PAE,
    CpuState,
    EFER_LMA,
    EFER_LME,
    Seg,
)
from wtf_tpu.core.gxa import PAGE_SHIFT, PAGE_SIZE

_PTE_P = 1
_PTE_W = 1 << 1
_PTE_U = 1 << 2
_PTE_PS = 1 << 7

KERNEL_BASE = 0xFFFF_8000_0000_0000   # PML4 slot 256: the direct map
_LARGE = 1 << 21                      # a PDE's reach: 2 MiB, 512 frames
_MASK64 = (1 << 64) - 1


class SyntheticSnapshotBuilder:
    """Builds {pfn: page bytes} + a long-mode CpuState with real page tables.

    Guest-physical layout: page tables from `table_base` (an arena of
    0x100 frames), mapped data pages allocated by a bump allocator above
    them; or, with `place`, every frame (data and page table alike) where
    the caller says: `place()` returns the next pfn, and the tables take
    as many as the address space needs.
    """

    def __init__(self, table_base: int = 0x10000,
                 place: Optional[Callable[[], int]] = None):
        self._place = place
        self._phys: Dict[int, bytearray] = {}
        self._mappings: Dict[int, int] = {}  # gva pfn -> gpa pfn
        self._writable: Dict[int, bool] = {}
        self._supervisor = set()             # gva pfns mapped without U
        self._next_pfn = (table_base >> PAGE_SHIFT) + 0x100
        self._table_base = table_base
        self._large = []  # (gva, gpa, size_shift) large-page mappings
        self.cpu = CpuState()

    def _phys_page(self, pfn: int) -> bytearray:
        page = self._phys.get(pfn)
        if page is None:
            page = bytearray(PAGE_SIZE)
            self._phys[pfn] = page
        return page

    def alloc_phys(self) -> int:
        if self._place is not None:
            pfn = self._place()
        else:
            pfn = self._next_pfn
            self._next_pfn += 1
        self._phys_page(pfn)
        return pfn

    def map(self, gva: int, size: int, writable: bool = True,
            user: bool = True) -> None:
        """Map [gva, gva+size) to freshly allocated physical pages;
        `user=False` leaves U/S clear in their PTEs (a kernel's pages)."""
        start = gva >> PAGE_SHIFT
        end = (gva + size + PAGE_SIZE - 1) >> PAGE_SHIFT
        for vpn in range(start, end):
            if vpn not in self._mappings:
                self._mappings[vpn] = self.alloc_phys()
                self._writable[vpn] = writable
                if not user:
                    self._supervisor.add(vpn)

    def write(self, gva: int, data: bytes, map_if_needed: bool = True,
              user: bool = True) -> None:
        """Write snapshot contents at a GVA (mapping pages on demand)."""
        if map_if_needed:
            self.map(gva, len(data), user=user)
        pos = 0
        while pos < len(data):
            vpn = (gva + pos) >> PAGE_SHIFT
            off = (gva + pos) & (PAGE_SIZE - 1)
            chunk = min(len(data) - pos, PAGE_SIZE - off)
            gpa_pfn = self._mappings[vpn]
            self._phys_page(gpa_pfn)[off : off + chunk] = data[pos : pos + chunk]
            pos += chunk

    def map_discontiguous_pair(self, gva: int) -> None:
        """Map two virtually-adjacent pages to non-adjacent frames (for
        page-crossing tests)."""
        vpn = gva >> PAGE_SHIFT
        self._mappings[vpn] = self.alloc_phys()
        self._writable[vpn] = True
        self.alloc_phys()  # hole
        self._mappings[vpn + 1] = self.alloc_phys()
        self._writable[vpn + 1] = True

    def _build_tables(self) -> int:
        """Materialize 4-level page tables; returns cr3."""
        import struct

        next_table = [self._table_base >> PAGE_SHIFT]

        def alloc_table() -> int:
            if self._place is not None:
                return self.alloc_phys()
            pfn = next_table[0]
            next_table[0] += 1
            if pfn >= (self._table_base >> PAGE_SHIFT) + 0x100:
                raise RuntimeError("page-table arena exhausted")
            self._phys_page(pfn)
            return pfn

        pml4_pfn = alloc_table()
        # level maps: {table pfn: {index: child pfn}}
        tables: Dict[int, Dict[int, int]] = {pml4_pfn: {}}

        def get_child(table_pfn: int, index: int) -> int:
            children = tables.setdefault(table_pfn, {})
            if index not in children:
                child = alloc_table()
                children[index] = child
                page = self._phys_page(table_pfn)
                entry = (child << PAGE_SHIFT) | _PTE_P | _PTE_W | _PTE_U
                page[index * 8 : index * 8 + 8] = struct.pack("<Q", entry)
            return children[index]

        for vpn, gpa_pfn in self._mappings.items():
            gva = vpn << PAGE_SHIFT
            i4 = (gva >> 39) & 0x1FF
            i3 = (gva >> 30) & 0x1FF
            i2 = (gva >> 21) & 0x1FF
            i1 = (gva >> 12) & 0x1FF
            pdpt = get_child(pml4_pfn, i4)
            pd = get_child(pdpt, i3)
            pt = get_child(pd, i2)
            flags = _PTE_P | (0 if vpn in self._supervisor else _PTE_U) \
                | (_PTE_W if self._writable.get(vpn, True) else 0)
            entry = (gpa_pfn << PAGE_SHIFT) | flags
            self._phys_page(pt)[i1 * 8 : i1 * 8 + 8] = struct.pack("<Q", entry)

        return pml4_pfn << PAGE_SHIFT

    def add_large_page_mapping(self, gva: int, gpa: int, size_shift: int) -> None:
        """Map a 2MiB (size_shift=21) or 1GiB (30) large page (PS entries)."""
        assert size_shift in (21, 30)
        self._large.append((gva, gpa, size_shift))

    def build(self, rip: int = 0, rsp: int = 0):
        """Finalize -> (pages dict, CpuState in long mode)."""
        import struct

        cr3 = self._build_tables()
        # Splice in large-page mappings after regular tables exist.
        for gva, gpa, size_shift in self._large:
            i4 = (gva >> 39) & 0x1FF
            i3 = (gva >> 30) & 0x1FF
            i2 = (gva >> 21) & 0x1FF
            pml4_pfn = cr3 >> PAGE_SHIFT
            pml4 = self._phys_page(pml4_pfn)
            pdpt_entry = struct.unpack("<Q", pml4[i4 * 8 : i4 * 8 + 8])[0]
            if not pdpt_entry & _PTE_P:
                raise RuntimeError("large-page parent PML4E missing; map() a sibling first")
            pdpt_pfn = (pdpt_entry >> PAGE_SHIFT) & ((1 << 40) - 1)
            if size_shift == 30:
                entry = gpa | _PTE_P | _PTE_W | _PTE_U | (1 << 7)
                self._phys_page(pdpt_pfn)[i3 * 8 : i3 * 8 + 8] = struct.pack("<Q", entry)
            else:
                pdpt = self._phys_page(pdpt_pfn)
                pd_entry = struct.unpack("<Q", pdpt[i3 * 8 : i3 * 8 + 8])[0]
                if not pd_entry & _PTE_P:
                    raise RuntimeError("large-page parent PDPTE missing; map() a sibling first")
                pd_pfn = (pd_entry >> PAGE_SHIFT) & ((1 << 40) - 1)
                entry = gpa | _PTE_P | _PTE_W | _PTE_U | (1 << 7)
                self._phys_page(pd_pfn)[i2 * 8 : i2 * 8 + 8] = struct.pack("<Q", entry)

        cpu = self.cpu
        cpu.cr3 = cr3
        cpu.cr0 = CR0_PE | CR0_PG | 0x50030  # PE+PG plus typical NE/ET/MP bits
        cpu.cr4 = CR4_PAE | 0x668
        cpu.efer = EFER_LME | EFER_LMA | 0x1  # long mode + SCE
        cpu.rip = rip
        cpu.rsp = rsp
        cpu.rflags = 0x202
        # attr bits 8..11 mirror limit[16:19] (see core.cpustate.Seg).
        cpu.cs = Seg(present=True, selector=0x33, base=0, limit=0xFFFFFFFF, attr=0xAFFB)
        cpu.ss = Seg(present=True, selector=0x2B, base=0, limit=0xFFFFFFFF, attr=0xCFF3)
        pages = {pfn: bytes(page) for pfn, page in self._phys.items()}
        return pages, cpu


# ---------------------------------------------------------------------------
# a full dump's worth of RAM, as arrays
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RamLayout:
    """PC-style guest RAM: `frames` frames, all present, under a pfn span
    with a hole below the top of low memory (reference ram.h:41: 4 GiB of
    RAM end at 0x1'08'00'00'00).  RAM [0, lo), hole [lo, frames), RAM
    [frames, span)."""

    frames: int
    hole: int

    @classmethod
    def of_bytes(cls, ram_bytes: int) -> "RamLayout":
        """The layout of `ram_bytes` of RAM; the hole is 1/32 of it (128
        MiB of 4 GiB), kept a multiple of 2 MiB."""
        if ram_bytes <= 0 or ram_bytes % (32 * _LARGE):
            raise ValueError(f"RAM of {ram_bytes:#x} bytes is not a "
                             "multiple of 64 MiB")
        frames = ram_bytes // PAGE_SIZE
        return cls(frames=frames, hole=frames // 32)

    @property
    def lo(self) -> int:
        return self.frames - self.hole

    @property
    def span(self) -> int:
        return self.frames + self.hole

    def present_pfns(self) -> np.ndarray:
        return np.concatenate([np.arange(self.lo, dtype=np.int64),
                               np.arange(self.frames, self.span,
                                         dtype=np.int64)])

    def index_of(self, pfns):
        """Position of present frame(s) among `present_pfns()`."""
        pfns = np.asarray(pfns, dtype=np.int64)
        return np.where(pfns < self.lo, pfns, pfns - self.hole)

    def scatter(self, rng: np.random.Generator, count: int) -> np.ndarray:
        """`count` distinct present frames, none of them frame 0, in the
        order drawn."""
        picks = 1 + rng.choice(self.frames - 1, size=count, replace=False)
        return np.where(picks < self.lo, picks, picks + self.hole)


def _mix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


def fill_noise(words: np.ndarray, seed: int, block: int = 1 << 17) -> None:
    """Seeded content for the cold frames of a dump, written in place
    into `words` (uint64, contiguous): one block of mixed counters, xored
    with a key of its own for every block of 1 MiB.  No page is zero and
    no two are equal, so no packer can elide one; one pass over the
    memory.  What it costs is the first touch of that memory (a page
    fault every 4 KiB where the kernel has no huge pages: 11-16 s for 4
    GiB on the chip machine's host from one thread, PR 34), so a few
    threads share the blocks; numpy releases the interpreter lock inside
    the xor, and the result does not depend on who wrote which block."""
    flat = words.reshape(-1)
    base = np.arange(block, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    base ^= base >> np.uint64(29)
    base *= np.uint64(0xBF58476D1CE4E5B9)
    base ^= base >> np.uint64(32)
    base |= np.uint64(1)

    def fill(i: int) -> None:
        out = flat[i * block:(i + 1) * block]
        np.bitwise_xor(base[:out.size],
                       np.uint64(_mix64(seed + i) & ~1), out=out)

    n_blocks = -(-flat.size // block)
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(n_blocks), chunksize=64))


def direct_map_tables(layout: RamLayout, place: Callable[[], int],
                      large_share: int = 4):
    """The kernel half of a dump's address space: RAM mapped 1:1 at
    KERNEL_BASE + gpa, supervisor-only, the first 1/`large_share` of the
    2-MiB regions by large pages and the rest through page tables whose
    frames `place()` gives; the hole is not mapped.  Returns (the PML4
    entry for slot 256, table pfns int64[k], their pages uint64[k, 512]).
    """
    flags = np.uint64(_PTE_P | _PTE_W)
    n_regions = -(-layout.span * PAGE_SIZE // _LARGE)
    n_large = n_regions // large_share
    n_pds = -(-n_regions // 512)
    pdpt_pfn = place()
    pd_pfns = [place() for _ in range(n_pds)]
    pdpt = np.zeros(512, dtype=np.uint64)
    pdpt[:n_pds] = (np.array(pd_pfns, dtype=np.uint64) << np.uint64(12)) \
        | flags
    pds = np.zeros(n_pds * 512, dtype=np.uint64)
    regions = np.arange(n_regions, dtype=np.int64)
    first = regions * 512                       # a region's first frame
    mapped = (first + 512 <= layout.lo) | (first >= layout.frames)
    large = mapped & (regions < n_large)
    pds[:n_regions][large] = (first[large].astype(np.uint64)
                              << np.uint64(12)) | flags | np.uint64(_PTE_PS)
    small = np.nonzero(mapped & ~large)[0]
    pt_pfns = np.array([place() for _ in small], dtype=np.int64)
    pds[small] = (pt_pfns.astype(np.uint64) << np.uint64(12)) | flags
    pts = ((first[small, None] + np.arange(512)).astype(np.uint64)
           << np.uint64(12)) | flags
    pfns = np.concatenate([[pdpt_pfn], pd_pfns, pt_pfns]).astype(np.int64)
    pages = np.concatenate([pdpt[None, :], pds.reshape(n_pds, 512), pts])
    return (pdpt_pfn << PAGE_SHIFT) | _PTE_P | _PTE_W, pfns, pages
