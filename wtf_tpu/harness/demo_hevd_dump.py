"""Demo target: demo_hevd inside a Windows-shaped full memory dump.

The HEVD campaign as upstream documents it starts from a full `mem.dmp`
(reference README.md:168-240), so this is demo_hevd's guest inside
demo_tlv_dump's dump: the same `RamLayout` (4 GiB of RAM, every frame
present, the hole below 4 GiB), the same noise and direct map, a generator
seed of its own.  The driver's code, stacks, per-thread page, IDT, GDT and
TSS, the user pages and their page tables lie at seeded scattered frames
and are mapped at demo_hevd's virtual addresses through 4-KiB tables (the
driver in PML4 slot 496, supervisor-only).  The pool's pages (HEVD's
chunks; the handle table, the objects and the IRP lookaside list that the
I/O path of every request walks) are frames of the dump that the kernel
reaches through the 1:1 direct map (slot 256): the chunk that pool
overflow fills and the IRPs lie in the quarter of RAM the direct map
covers with 2-MiB pages, so every request walks a large page; the others
lie behind 4-KiB tables.

The target is demo_hevd's in everything but the snapshot.  The benchmark
runs it as configuration `hevd-fulldump`, cell `hevd-faults`
(benchmark/configs/hevd-fulldump.json).  `build_snapshot(ram_bytes)`
builds the same dump at another size; the CPU tests take 64 MiB.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from wtf_tpu.harness import demo_hevd
from wtf_tpu.harness.demo_tlv_dump import KERNEL_PML4_SLOT, RAM_BYTES
from wtf_tpu.mem.physmem import PhysMem, alloc_rows
from wtf_tpu.snapshot.loader import Snapshot
from wtf_tpu.snapshot.synthetic import (
    KERNEL_BASE, RamLayout, SyntheticSnapshotBuilder, direct_map_tables,
    fill_noise,
)

DUMP_SEED = 0x4E5D_2026_36    # the generator's: one dump, bit for bit
LARGE_SHARE = 4               # direct_map_tables': the large-page quarter
LARGE_PAGED = ("chunk", "irps")   # of demo_hevd.POOL_PAGES


def pool_frames(layout: RamLayout, rng: np.random.Generator) -> list:
    """Present frames for the pool, in demo_hevd.POOL_PAGES' order: the
    LARGE_PAGED ones inside the direct map's 2-MiB-page quarter, the
    others behind its 4-KiB tables."""
    n_regions = -(-layout.span // 512)
    large_end = (n_regions // LARGE_SHARE) * 512
    names = demo_hevd.POOL_PAGES
    large = rng.choice(np.arange(512, min(large_end, layout.lo)),
                       size=len(LARGE_PAGED), replace=False)
    small = rng.choice(np.arange(large_end, layout.lo),
                       size=len(names) - len(LARGE_PAGED), replace=False)
    pools = {True: iter(map(int, large)), False: iter(map(int, small))}
    return [next(pools[name in LARGE_PAGED]) for name in names]


def build_snapshot(ram_bytes: int = RAM_BYTES,
                   seed: int = DUMP_SEED) -> Snapshot:
    """The dump as a packed image, filled in place: noise over every
    frame, then the pool's pages, demo_hevd's frames and the
    kernel's page tables over the frames a seeded scatter gave them."""
    layout = RamLayout.of_bytes(ram_bytes)
    rng = np.random.default_rng(seed)
    pool = pool_frames(layout, rng)
    taken = set(pool)
    # one draw serves the guest and the direct map; more than they take
    scatter = (pfn for pfn in
               layout.scatter(rng, 96 + layout.span // 512).tolist()
               if pfn not in taken)
    place = scatter.__next__

    pool_at = [KERNEL_BASE + (pfn << 12) for pfn in pool]
    pages, cpu = demo_hevd.lay_out(SyntheticSnapshotBuilder(place=place),
                                   pool_at)
    pml4e, table_pfns, table_pages = direct_map_tables(
        layout, place, large_share=LARGE_SHARE)
    pml4 = np.frombuffer(pages[cpu.cr3 >> 12], dtype=np.uint64).copy()
    pml4[KERNEL_PML4_SLOT] = pml4e
    pages[cpu.cr3 >> 12] = pml4.tobytes()
    pages.update(zip(pool, demo_hevd.pool_pages(pool_at)))

    rows = alloc_rows(layout.frames)
    body = rows[1:layout.frames + 1]
    fill_noise(body, seed)
    body[layout.index_of(table_pfns)] = table_pages
    for pfn, data in pages.items():
        body[layout.index_of(pfn)] = np.frombuffer(data, dtype=np.uint64)
    return Snapshot(physmem=PhysMem.from_rows(layout.present_pfns(), rows),
                    cpu=cpu, symbols=dict(demo_hevd.SYMBOLS))


TARGET = dataclasses.replace(demo_hevd.TARGET, name="demo_hevd_dump",
                             snapshot=build_snapshot)
