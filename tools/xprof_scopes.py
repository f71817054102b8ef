#!/usr/bin/env python3
"""Device time of a `--xprof-dir` window summed per `jax.named_scope`.

    python tools/xprof_scopes.py <xprof dir> [--json OUT]

Reads the `.xplane.pb` the window wrote (ROADMAP S16 item 1, PERF.md
section 5).  Busy time is the leaf operations of the line `XLA Ops` of
plane `/device:TPU:0`, as the benchmark counts it
(benchmark/harness/trace.py).  An operation's `op_name` comes from the HLO
the profiler keeps on its `/host:metadata` plane (instruction name ->
`metadata.op_name`), so nothing hangs on what the trace calls its stats.
A fusion is one operation on the device: its time goes

  by root      to the scope in the fusion's own `op_name`, and
  by gathers   to the scopes of the gathers and scatters fused into it, in
               proportion to their number (a sweep is priced by the
               gathered index: PERF.md section 5); a fusion without any
               goes by its root.

Scopes are the program's (PERF.md section 3); an operation of the chunk
program inside no scope is `step_lane inline`, one outside the chunk
program `other programs`.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from collections import Counter, defaultdict
from pathlib import Path
from typing import NamedTuple

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

SCOPES = ("tlb", "page-walk", "overlay-load", "overlay-store",
          "decode-probe", "coverage", "ea", "alu", "unary", "shift", "mul",
          "sse-fp", "x87", "iret", "device-insert", "restore",
          "run-quiesce", "decode-service", "cov-merge", "devmut-generate")
_SCOPE_RE = re.compile(
    r"(?<![\w-])(" + "|".join(map(re.escape, SCOPES)) + r")(?![\w-])")
INLINE, OTHER = "step_lane inline", "other programs"
_INDEXED = ("gather", "scatter")


class Op(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    module: str


def scope_of(op_name: str, module: str) -> str:
    found = _SCOPE_RE.findall(op_name or "")
    if found:
        return found[-1]                      # the innermost
    return INLINE if "run_chunk" in module else OTHER


def _protos():
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from tensorflow.compiler.xla.service import hlo_pb2
    return xplane_pb2, hlo_pb2


def load(xplane: Path):
    """(ops of TPU:0's `XLA Ops` line, {module: {instruction: (op_name,
    Counter of the scopes of its fused gathers)}})."""
    from benchmark.harness.trace import leaf_rows

    xplane_pb2, hlo_pb2 = _protos()
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(xplane).read_bytes())
    hlo = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        for md in plane.event_metadata.values():
            for stat in md.stats:
                if not stat.bytes_value:
                    continue
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(stat.bytes_value)
                hlo[re.sub(r"\(\d+\)$", "", md.name)] = _instructions(
                    proto.hlo_module)
    ops = []
    for plane in space.planes:
        if plane.name != "/device:TPU:0":
            continue
        lines = {ln.name: ln for ln in plane.lines}
        modules = sorted(
            (ln_t(lines["XLA Modules"], ev) + (re.sub(
                r"\(\d+\)$", "", plane.event_metadata[ev.metadata_id].name),)
             for ev in lines["XLA Modules"].events)
        ) if "XLA Modules" in lines else []
        line = lines.get("XLA Ops")
        for ev in (line.events if line is not None else ()):
            start, dur = ln_t(line, ev)
            if dur <= 0:
                continue
            module = next((m for s, d, m in modules
                           if s <= start and start + dur <= s + d + 1), "")
            md = plane.event_metadata[ev.metadata_id]
            ops.append(Op(md.name or md.display_name, start, dur, module))
    return leaf_rows(ops), hlo


def ln_t(line, ev):
    return (line.timestamp_ns + ev.offset_ps / 1e3, ev.duration_ps / 1e3)


def _instructions(module) -> dict:
    comps = {c.id: c for c in module.computations}
    out = {}

    def indexed(comp, module_name, seen=()):
        c = Counter()
        for i in comp.instructions:
            if i.opcode in _INDEXED:
                c[scope_of(i.metadata.op_name, module_name)] += 1
            for cid in i.called_computation_ids:
                if i.opcode == "fusion" and cid not in seen:
                    c += indexed(comps[cid], module_name, seen + (cid,))
        return c

    for comp in module.computations:
        for i in comp.instructions:
            inner = Counter()
            if i.opcode == "fusion":
                for cid in i.called_computation_ids:
                    inner += indexed(comps[cid], module.name)
            elif i.opcode in _INDEXED:
                inner[scope_of(i.metadata.op_name, module.name)] += 1
            out[i.name] = (i.metadata.op_name, inner)
    return out


def reduce(ops, hlo) -> dict:
    by_root, by_gather = defaultdict(float), defaultdict(float)
    unscoped = defaultdict(float)
    busy = 0.0
    for op in ops:
        name = op.name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
        table = hlo.get(op.module) or next(
            (t for t in hlo.values() if name in t), {})
        op_name, inner = table.get(name, ("", Counter()))
        module = op.module or ("run_chunk" if "run_chunk" in op_name else "")
        root = scope_of(op_name, module)
        s = op.duration_ns / 1e9
        busy += s
        by_root[root] += s
        if root in (INLINE, OTHER):
            unscoped[f"{name} [{op_name[-70:]}]"] += s
        total = sum(inner.values())
        if total:
            for scope, n in inner.items():
                by_gather[scope] += s * n / total
        else:
            by_gather[root] += s

    def shares(d):
        return {k: [round(v, 6), round(100 * v / busy, 2)]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

    return {"busy_s": busy, "ops": len(ops),
            "by_root": shares(by_root), "by_gathers": shares(by_gather),
            "unscoped_top": sorted(unscoped.items(),
                                   key=lambda kv: -kv[1])[:12]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xprof_dir", type=Path)
    ap.add_argument("--json", type=Path, default=None)
    args = ap.parse_args(argv)
    from benchmark.harness.trace import find_xplane

    ops, hlo = load(find_xplane(args.xprof_dir))
    if not ops:
        print("no operation on /device:TPU:0 in this window",
              file=sys.stderr)
        return 1
    out = reduce(ops, hlo)
    out["modules"] = sorted(hlo)
    if args.json:
        args.json.write_text(json.dumps(out, indent=1))
    print(f"busy {out['busy_s']:.4f} s over {out['ops']} leaf operations")
    for view in ("by_root", "by_gathers"):
        print(f"-- {view}")
        for scope, (s, pct) in out[view].items():
            print(f"  {scope:18s} {s:9.5f} s  {pct:6.2f}%")
    print("-- largest operations inside no scope")
    for name, s in out["unscoped_top"]:
        print(f"  {s:9.5f} s  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
