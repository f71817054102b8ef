"""Demo target: demo_tlv inside a Windows-shaped full memory dump.

Every campaign upstream documents starts from a full-memory kernel crash
dump taken with bdump.js from a Windows VM (reference README.md:168-240),
loaded whole into the backend's RAM (ram.h:96-152; guest RAM up to
0x1'08'00'00'00, ram.h:41).  None ships with the tree, so this target
synthesizes one from a fixed generator seed (data takes the place of
weights): 4 GiB of guest RAM, every frame present, under a PC-style pfn
span of 0x108000 (the hole below 4 GiB); demo_tlv's 21 frames (code, input,
scratch, stack, its page tables) at seeded scattered frames across that
span and at demo_tlv's virtual addresses; a kernel half, supervisor-only,
that maps RAM 1:1 through some 1,500 scattered page-table pages and a
region of 2-MiB pages; every other frame seeded noise where a Windows dump
holds pool, cache and other processes.

The target is demo_tlv's in everything but the snapshot: init, testcase
insertion, TlvStructureMutator and the DeviceInsertSpec are shared, and at
equal campaign seed the testcases see the same virtual address space.  What
differs is the image the device gathers from: 1,052,672 page rows and a
frame table of 1,114,112 entries where demo_tlv has 32 and 512.

The benchmark runs it as configuration `tlv-fulldump`, cell `big-image`
(benchmark/configs/tlv-fulldump.json).  `build_snapshot(ram_bytes)` builds
the same dump at another size; the CPU tests take 64 MiB.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from wtf_tpu.harness import demo_tlv
from wtf_tpu.mem.physmem import PhysMem, alloc_rows
from wtf_tpu.snapshot.loader import Snapshot
from wtf_tpu.snapshot.synthetic import (
    RamLayout, SyntheticSnapshotBuilder, direct_map_tables, fill_noise,
)

RAM_BYTES = 4 << 30          # the configuration's (tlv-fulldump.json)
DUMP_SEED = 0x57F_D0_2026    # the generator's: one dump, bit for bit
KERNEL_PML4_SLOT = 256


def build_snapshot(ram_bytes: int = RAM_BYTES,
                   seed: int = DUMP_SEED) -> Snapshot:
    """The dump as a packed image, filled in place: noise over every
    frame, then demo_tlv's frames and the kernel's page tables over the
    frames a seeded scatter gave them."""
    layout = RamLayout.of_bytes(ram_bytes)
    rng = np.random.default_rng(seed)
    # one draw serves both halves; more than they take (21 + one table
    # per 2-MiB region, a PD per GiB and a PDPT)
    scatter = iter(layout.scatter(rng, 64 + layout.span // 512).tolist())
    place = scatter.__next__

    pages, cpu = demo_tlv.lay_out(SyntheticSnapshotBuilder(place=place))
    pml4e, table_pfns, table_pages = direct_map_tables(layout, place)
    pml4 = np.frombuffer(pages[cpu.cr3 >> 12], dtype=np.uint64).copy()
    pml4[KERNEL_PML4_SLOT] = pml4e
    pages[cpu.cr3 >> 12] = pml4.tobytes()

    rows = alloc_rows(layout.frames)
    body = rows[1:layout.frames + 1]
    fill_noise(body, seed)
    body[layout.index_of(table_pfns)] = table_pages
    for pfn, data in pages.items():
        body[layout.index_of(pfn)] = np.frombuffer(data, dtype=np.uint64)
    return Snapshot(physmem=PhysMem.from_rows(layout.present_pfns(), rows),
                    cpu=cpu, symbols=dict(demo_tlv.SYMBOLS))


TARGET = dataclasses.replace(demo_tlv.TARGET, name="demo_tlv_dump",
                             snapshot=build_snapshot)
