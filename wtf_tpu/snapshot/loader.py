"""Unified snapshot loading.

A snapshot is the checkpoint the whole system revolves around (reference
README.md:168-240): guest memory (`mem.dmp`) + registers (`regs.json`) +
symbols (`symbol-store.json`) living in a target's `state/` directory
(wtf.cc:127-129).  This module loads any of:

  - `mem.dmp`   — Windows kernel crash-dump, parsed by wtf_tpu.snapshot.kdmp
                  (kdmp-parser equivalent; see native/ for the C++ fast path),
  - `mem.npz`   — the raw packed format used by synthetic snapshots/tests,

into a `Snapshot{PhysMem, CpuState, symbols}` ready for device upload.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
from pathlib import Path
from typing import Dict, Optional

import numpy as np

from wtf_tpu.core.cpustate import CpuState, load_cpu_state_json, sanitize_cpu_state
from wtf_tpu.core.gxa import PAGE_SIZE
from wtf_tpu.mem.physmem import PhysMem


@dataclasses.dataclass
class Snapshot:
    physmem: PhysMem
    cpu: CpuState
    symbols: Dict[str, int] = dataclasses.field(default_factory=dict)

    @classmethod
    def from_pages(
        cls, pages: Dict[int, bytes], cpu: CpuState, symbols: Optional[Dict[str, int]] = None
    ) -> "Snapshot":
        return cls(physmem=PhysMem.from_pages(pages), cpu=cpu, symbols=symbols or {})

    def pages_dict(self) -> Dict[int, bytes]:
        """{pfn: 4KiB page bytes} of the present frames: for writers and
        tests of small images (a page a Python object)."""
        flat = self.physmem.rows.view(np.uint8)
        table = self.physmem.table
        return {int(pfn): flat[table[pfn]].tobytes()
                for pfn in self.physmem.present_pfns()}

    def save_raw(self, state_dir) -> None:
        """Persist in the raw format (mem.npz + regs.json + symbol-store.json)."""
        state_dir = Path(state_dir)
        state_dir.mkdir(parents=True, exist_ok=True)
        pfns = self.physmem.present_pfns()
        np.savez_compressed(
            state_dir / "mem.npz",
            pfns=pfns.astype(np.int64),
            pages=self.physmem.rows[self.physmem.table[pfns]],
        )
        (state_dir / "regs.json").write_text(dump_cpu_state_json(self.cpu))
        (state_dir / "symbol-store.json").write_text(
            json.dumps({k: hex(v) for k, v in self.symbols.items()}, indent=1)
        )


def load_snapshot(state_dir, sanitize: bool = True, spans=None) -> Snapshot:
    """Load a snapshot directory (reference startup path wtf.cc:378-465:
    LoadCpuStateFromJSON -> backend init -> SanitizeCpuState).  The pages
    travel as arrays from the file to the packed image; `spans` (a
    telemetry `Spans`) times reading them (`parse`) and packing them
    (`pack`) as children of the caller's open span."""
    span = spans.span if spans is not None else (
        lambda name: contextlib.nullcontext())
    state_dir = Path(state_dir)
    cpu = load_cpu_state_json(state_dir / "regs.json")
    if sanitize and not sanitize_cpu_state(cpu):
        raise ValueError(f"unusable CPU state in {state_dir}")

    symbols: Dict[str, int] = {}
    symbol_path = state_dir / "symbol-store.json"
    if symbol_path.exists():
        raw = json.loads(symbol_path.read_text())
        symbols = {k: (int(v, 0) if isinstance(v, str) else int(v)) for k, v in raw.items()}

    npz_path = state_dir / "mem.npz"
    dmp_path = state_dir / "mem.dmp"
    with span("parse"):
        if npz_path.exists():
            data = np.load(npz_path)
            pfns, pages = data["pfns"], data["pages"]
        elif dmp_path.exists():
            from wtf_tpu.snapshot.kdmp import parse_kdmp_arrays

            # the pages: one view over the mapped file
            pfns, pages = parse_kdmp_arrays(dmp_path)
        else:
            raise FileNotFoundError(
                f"no mem.npz or mem.dmp under {state_dir}")
    with span("pack"):
        physmem = PhysMem.from_arrays(pfns, pages)
    return Snapshot(physmem=physmem, cpu=cpu, symbols=symbols)


def dump_cpu_state_json(cpu: CpuState) -> str:
    """Serialize a CpuState back to the bdump.js regs.json format, so
    synthetic snapshots round-trip through the same loader as real ones."""
    from wtf_tpu.core.cpustate import _REG_KEYS, _SEG_KEYS  # noqa: SLF001

    data = {}
    for key in _REG_KEYS:
        data[key] = hex(getattr(cpu, key))
    for key in _SEG_KEYS:
        seg = getattr(cpu, key)
        data[key] = {
            "present": seg.present,
            "selector": hex(seg.selector),
            "base": hex(seg.base),
            "limit": hex(seg.limit),
            "attr": hex(seg.attr),
        }
    data["gdtr"] = {"base": hex(cpu.gdtr.base), "limit": hex(cpu.gdtr.limit)}
    data["idtr"] = {"base": hex(cpu.idtr.base), "limit": hex(cpu.idtr.limit)}
    data["fpst"] = [hex(v) for v in cpu.fpst]
    data["zmm"] = [{"q": [hex(limb) for limb in reg]} for reg in cpu.zmm]
    return json.dumps(data, indent=1)
