#!/usr/bin/env python3
"""Device time of a `--xprof-dir` window summed per `jax.named_scope`, and
the chip's long gaps laid against the host's spans.

    python tools/xprof_scopes.py <xprof dir> [--json OUT]

Reads the `.xplane.pb` the window wrote (ROADMAP S16 item 1, PERF.md
section 5).  Busy time is the leaf operations of the line `XLA Ops` of
plane `/device:TPU:0`, as the benchmark counts it
(benchmark/harness/trace.py).  An operation's `op_name` comes from the HLO
the profiler keeps on its `/host:metadata` plane (instruction name ->
`metadata.op_name`), so nothing hangs on what the trace calls its stats.
A fusion is one operation on the device: its time goes

  by root      to the scope in the fusion's own `op_name`, and
  by gathers   to the scopes of the indexed operations fused into it, in
               proportion to their number (a sweep is priced by the
               gathered index: PERF.md section 5); a fusion without any
               goes by its root.

Scopes are the program's, from ONE list (`wtf_tpu.telemetry.xprof.SCOPES`,
PERF.md section 3); an operation of the chunk program inside no scope is
`step_lane inline`, one the compiler made itself and gave no `op_name`
(copies, reduce combiners, the slices it makes of a constant index) is
`no op_name`, one outside the chunk program `other programs`.  The chunk
program holds no indexed operation outside a scope
(tests/test_analysis.py), so `unscoped indexed ops` reads 0 of the
program's; the compiler's own are counted beside it.

`per pass` divides a scope's time by the times its most frequent operation
ran in the window: a sweep of one group for the step's scopes, a level for
`level-sort` and `level-account` (sixteen a chunk), a chunk for
`lane-writeback`.

The gaps: stretches of `GAP_MS` (5 ms) and more in which no operation ran
on the chip, each with the span path of the host plane's annotation that
covers it (every span of the program is a `TraceAnnotation`:
telemetry/spans.py; the window's own `/host:` planes, on the device
plane's clock).
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

from wtf_tpu.telemetry.xprof import (  # noqa: E402
    CHUNK_SCOPES, INDEXED_OPS, SCOPES, scope_of as listed_scope,
)

INLINE, OTHER = "step_lane inline", "other programs"
NO_NAME = "no op_name"      # the compiler's own: copies, reduce combiners
GAP_MS = 5.0                # an idle stretch of the chip worth a row
# what one pass of a chunk-level scope is; every other scope of the chunk
# program passes once a sweep of one group
PASS_OF = {"level-sort": "level", "level-account": "level",
           "lane-writeback": "chunk"}


class Op(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    module: str


def scope_of(op_name: str, module: str) -> str:
    found = listed_scope(op_name)
    if found:
        return found                          # the innermost
    if "run_chunk" not in module:
        return OTHER
    return INLINE if op_name else NO_NAME


def _protos():
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from tensorflow.compiler.xla.service import hlo_pb2
    return xplane_pb2, hlo_pb2


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def load(xplane: Path):
    """(ops of TPU:0's `XLA Ops` line, {module: {instruction: (op_name,
    Counter of the scopes of the indexed operations it is or has fused
    into it, whether it is one itself)}}, the host planes' annotations as
    (name, start_ns, duration_ns))."""
    from benchmark.harness.trace import leaf_rows

    xplane_pb2, hlo_pb2 = _protos()
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(xplane).read_bytes())
    hlo, ops, host = {}, [], []
    for plane in space.planes:
        if plane.name == "/host:metadata":
            for md in plane.event_metadata.values():
                for stat in md.stats:
                    if not stat.bytes_value:
                        continue
                    proto = hlo_pb2.HloProto()
                    proto.ParseFromString(stat.bytes_value)
                    hlo[_module_name(md.name)] = _instructions(
                        proto.hlo_module)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((plane.event_metadata[ev.metadata_id].name,
                                 *ln_t(line, ev)))
        elif plane.name == "/device:TPU:0":
            lines = {ln.name: ln for ln in plane.lines}
            modules = sorted(
                (ln_t(lines["XLA Modules"], ev) + (_module_name(
                    plane.event_metadata[ev.metadata_id].name),)
                 for ev in lines["XLA Modules"].events)
            ) if "XLA Modules" in lines else []
            line = lines.get("XLA Ops")
            for ev in (line.events if line is not None else ()):
                start, dur = ln_t(line, ev)
                if dur <= 0:
                    continue
                module = next((m for s, d, m in modules
                               if s <= start and start + dur <= s + d + 1),
                              "")
                md = plane.event_metadata[ev.metadata_id]
                ops.append(Op(md.name or md.display_name, start, dur,
                              module))
    return leaf_rows(ops), hlo, host


def ln_t(line, ev):
    return (line.timestamp_ns + ev.offset_ps / 1e3, ev.duration_ps / 1e3)


def _instructions(module) -> dict:
    comps = {c.id: c for c in module.computations}
    out = {}

    def indexed(comp, module_name, seen=()):
        c = Counter()
        for i in comp.instructions:
            if i.opcode in INDEXED_OPS:
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
            elif i.opcode in INDEXED_OPS:
                inner[scope_of(i.metadata.op_name, module.name)] += 1
            out[i.name] = (i.metadata.op_name, inner,
                           i.opcode in INDEXED_OPS)
    return out


def _bare(name: str) -> str:
    return name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]


def reduce(ops, hlo) -> dict:
    by_root, by_gather = defaultdict(float), defaultdict(float)
    unscoped = defaultdict(float)
    runs = defaultdict(Counter)       # scope -> operation -> times it ran
    busy = unscoped_indexed_s = 0.0
    for op in ops:
        name = _bare(op.name)
        table = hlo.get(op.module) or next(
            (t for t in hlo.values() if name in t), {})
        op_name, inner, _ = table.get(name, ("", Counter(), False))
        module = op.module or ("run_chunk" if "run_chunk" in op_name else "")
        root = scope_of(op_name, module)
        s = op.duration_ns / 1e9
        busy += s
        by_root[root] += s
        runs[root][name] += 1
        if root in (INLINE, OTHER, NO_NAME):
            unscoped[f"{name} [{op_name[-70:]}]"] += s
        total = sum(inner.values())
        if total:
            for scope, n in inner.items():
                by_gather[scope] += s * n / total
            unscoped_indexed_s += s * inner.get(INLINE, 0) / total
        else:
            by_gather[root] += s

    def shares(d):
        return {k: [round(v, 6), round(100 * v / busy, 2)]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

    per_pass = {}
    for scope, s in by_root.items():
        if scope in (INLINE, OTHER, NO_NAME):
            continue
        n = max(runs[scope].values())
        per_pass[scope] = {"passes": n, "of": PASS_OF.get(scope, "group sweep"),
                           "us_per_pass": round(1e6 * s / n, 2)}
    # the chunk program's indexed operations by scope, each counted once
    # (a fused one has a row of its own beside its fusion's)
    census = sum((inner for m, t in hlo.items() if "run_chunk" in m
                  for _, inner, indexed in t.values() if indexed), Counter())
    return {"busy_s": busy, "ops": len(ops),
            "by_root": shares(by_root), "by_gathers": shares(by_gather),
            "per_pass": per_pass,
            "unscoped_indexed_ops": census[INLINE],
            "unnamed_indexed_ops": census[NO_NAME],
            "unscoped_indexed_s": round(unscoped_indexed_s, 6),
            "unscoped_top": sorted(unscoped.items(),
                                   key=lambda kv: -kv[1])[:12]}


def gaps(ops, host, min_ms: float = GAP_MS) -> list:
    """The stretches of `min_ms` and more between two operations of the
    chip, each with the host annotation that covers it: of those that
    hold the gap's middle the shortest (the innermost span), else the one
    that overlaps it most; the tracer thread's `open at ...` rows serve
    only where the main thread began no span inside the window."""
    ordered = sorted(ops, key=lambda o: o.start_ns)
    if not ordered:
        return []
    t0 = ordered[0].start_ns
    out, end, before = [], ordered[0].start_ns, None
    for op in ordered:
        if op.start_ns - end >= min_ms * 1e6:
            out.append({"at_ms": round((end - t0) / 1e6, 3),
                        "ms": round((op.start_ns - end) / 1e6, 3),
                        "after": _bare(before.name),
                        "before": _bare(op.name),
                        "host": _covering(host, end, op.start_ns)})
        if op.start_ns + op.duration_ns > end:
            end, before = op.start_ns + op.duration_ns, op
    return out


def _covering(host, a: float, b: float) -> list:
    """[(annotation, share of the gap it overlaps)], the best first."""
    mid, found = (a + b) / 2, []
    for name, start, dur in host:
        overlap = min(b, start + dur) - max(a, start)
        if overlap <= 0:
            continue
        found.append((name.startswith("open at "),
                      not (start <= mid <= start + dur), dur, name,
                      round(overlap / (b - a), 3)))
    found.sort()
    return [(name, share) for _, _, _, name, share in found[:3]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xprof_dir", type=Path)
    ap.add_argument("--json", type=Path, default=None)
    args = ap.parse_args(argv)
    from benchmark.harness.trace import find_xplane

    ops, hlo, host = load(find_xplane(args.xprof_dir))
    if not ops:
        print("no operation on /device:TPU:0 in this window",
              file=sys.stderr)
        return 1
    out = reduce(ops, hlo)
    out["modules"] = sorted(hlo)
    out["gaps"] = gaps(ops, host)
    out["host_annotations"] = len(host)
    if args.json:
        args.json.write_text(json.dumps(out, indent=1))
    print(f"busy {out['busy_s']:.4f} s over {out['ops']} leaf operations")
    for view in ("by_root", "by_gathers"):
        print(f"-- {view}")
        for scope, (s, pct) in out[view].items():
            print(f"  {scope:18s} {s:9.5f} s  {pct:6.2f}%")
    print("-- per pass (by root): the chunk's own scopes, then the step's")
    for scope in sorted(out["per_pass"], key=lambda s: (
            s not in CHUNK_SCOPES, SCOPES.index(s))):
        row = out["per_pass"][scope]
        print(f"  {scope:18s} {row['us_per_pass']:11.2f} us a {row['of']}"
              f"  ({row['passes']} in the window)")
    print(f"unscoped indexed ops: {out['unscoped_indexed_ops']} in the chunk "
          f"program ({out['unscoped_indexed_s']:.5f} s of the window); "
          f"{out['unnamed_indexed_ops']} more are the compiler's own, with "
          "no op_name")
    print("-- largest operations inside no scope")
    for name, s in out["unscoped_top"]:
        print(f"  {s:9.5f} s  {name}")
    print(f"-- the chip's gaps of {GAP_MS:g} ms and more "
          f"({out['host_annotations']} host annotations in the window)")
    for g in out["gaps"]:
        covered = "; ".join(f"{name} ({share:.0%})"
                            for name, share in g["host"]) or "no annotation"
        print(f"  {g['ms']:8.3f} ms at {g['at_ms']:9.3f} ms, after "
              f"{g['after']} before {g['before']}: {covered}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
