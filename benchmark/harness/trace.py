"""From the device traces the benchmark's child takes of itself to numbers.

Two parts.  The loader turns an `.xplane.pb` into rows and knows which
planes are devices.  The arithmetic works on rows alone, so it is tested
on rows written by hand.  The child traces short slices spread over the
window; a slice in which the chip only waited has no operation on it, and
counts as idle, but a run none of whose slices shows an operation on a
/device:TPU:N plane was not traced on a chip: an error, never "100% idle".

Operations nest on the trace's line (a `%while` holds its body's
operations, a call its callee's): the chip is busy only while a leaf runs,
so parents are left out of every sum.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Iterable, NamedTuple

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
# the line of a TPU plane that holds one event per executed HLO operation
# (seen in the first chip trace, PERF.md section 5); "XLA Modules" and
# "Steps" cover the same time again as whole programs
OPS_LINE = "XLA Ops"


class TraceError(Exception):
    pass


class Row(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    duration_ns: float


def find_xplane(xprof_dir: Path) -> Path:
    found = sorted(Path(xprof_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise TraceError(f"no .xplane.pb under {xprof_dir}")
    return found[-1]


def load_rows(path: Path, planes: "re.Pattern | None" = None) -> list:
    """Every event of the planes whose name matches `planes` (all planes
    when None) as a Row."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(str(path)).planes:
        if planes is not None and not planes.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                rows.append(Row(plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return rows


def load_device_rows(path: Path) -> list:
    """The rows of the device planes only (the host planes hold the
    host tracer's events by the hundred thousand)."""
    return load_rows(path, DEVICE_PLANE_RE)


def device_op_rows(rows: Iterable[Row]) -> dict:
    """The leaf operation rows of each device plane, by chip number;
    empty where no operation ran."""
    chips: dict = {}
    for r in rows:
        m = DEVICE_PLANE_RE.match(r.plane)
        if m and r.line == OPS_LINE and r.duration_ns > 0:
            chips.setdefault(int(m.group(1)), []).append(r)
    return {chip: leaf_rows(ops) for chip, ops in chips.items()}


def leaf_rows(rows: Iterable[Row]) -> list:
    """The rows that hold no other row.  Sorted by start, longest first,
    a parent is followed at once by its first child."""
    ordered = sorted(rows, key=lambda r: (r.start_ns, -r.duration_ns))
    leaves = []
    for r, nxt in zip(ordered, ordered[1:] + [None]):
        end = r.start_ns + r.duration_ns
        if (nxt is not None and nxt.start_ns < end
                and nxt.start_ns + nxt.duration_ns <= end):
            continue
        leaves.append(r)
    return leaves


def union_ns(intervals: Iterable) -> float:
    """Length covered by (start, end) intervals that may overlap or nest."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def short_name(name: str) -> str:
    """`%fusion.12 = u32[...] fusion(...)`, as the TPU's trace names an
    operation, cut to `%fusion.12`: the result line holds ten of them."""
    return name.split(" = ", 1)[0][:64]


def _spans(rows: Iterable[Row]) -> list:
    return [(r.start_ns, r.start_ns + r.duration_ns) for r in rows]


def busy_and_extent(chips: dict) -> tuple:
    """(busy_s, extent_s) of one slice: the seconds in which a leaf
    operation ran, averaged over the chips, and the time from the first
    operation's start on any chip to the last one's end.  (0, 0) for a
    slice with no operation."""
    spans = {chip: _spans(rows) for chip, rows in chips.items() if rows}
    if not spans:
        return 0.0, 0.0
    start = min(s for sp in spans.values() for s, _ in sp)
    end = max(e for sp in spans.values() for _, e in sp)
    busy = sum(union_ns(sp) for sp in spans.values()) / len(chips)
    return busy / 1e9, (end - start) / 1e9


def reduce_slices(slices: list, names: "list | None" = None) -> dict:
    """`slices` is a list of (chips, host_s): the leaf operation rows of a
    slice by chip, and the seconds the host slept between starting and
    stopping that trace; `names` says what the gaps call each slice (its
    place in the list when None).  The profiler records from somewhere inside
    start_trace to somewhere inside stop_trace, so both the sleep and the
    extent of the operations seen are lower bounds of a slice's length: it
    is the longer of the two.  busy_s and window_s are sums over the
    slices."""
    if not any(rows for chips, _ in slices for rows in chips.values()):
        raise TraceError("no slice of the trace has an operation on a "
                         "/device:TPU:N plane")
    busy_s = window_s = 0.0
    ops: dict = {}
    gaps = []
    n_chips = max(len(chips) for chips, _ in slices)
    for i, (chips, host_s) in zip(names or range(len(slices)), slices):
        busy, extent = busy_and_extent(chips)
        length = max(host_s, extent)
        busy_s += busy
        window_s += length
        for rows in chips.values():
            for r in rows:
                key = short_name(r.name)
                ops[key] = ops.get(key, 0.0) + r.duration_ns / 1e9 / n_chips
        if busy == 0.0:
            gaps.append([f"unattributed: slice {i}, no operation", length])
        else:
            gaps.extend([f"unattributed: slice {i}, {what}", s]
                        for what, s in longest_gaps(chips))
    ranked = sorted(ops.items(), key=lambda kv: (-kv[1], kv[0]))
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": busy_s, "window_s": window_s,
            "breakdown": {"device_ops": [list(kv) for kv in ranked[:10]],
                          "idle_gaps": gaps[:10]}}


def reduce_traced(traced: dict, load, min_taken: int) -> dict:
    """The child's `.traced` document reduced: `reduce_slices` over the
    slices it took (those its write-out budget cut are due and not taken),
    with how many were due and taken, what starting and stopping them cost,
    and what each held.  `load(dir)` gives a taken slice's device rows.
    Fewer than `min_taken` slices are no reading of a window."""
    due = traced["slices"]
    taken = [sl for sl in due if sl["taken"]]
    if len(taken) < min_taken:
        raise TraceError(
            f"{len(taken)} of {len(due)} due slices were taken before the "
            f"write-out budget of {traced.get('budget_s')} s was spent; "
            f"fewer than {min_taken} are no reading of the window")
    slices, lines, per_slice = [], set(), []
    for sl in taken:
        rows = load(sl["dir"])
        lines |= {(r.plane, r.line) for r in rows}
        chips = device_op_rows(rows)
        slices.append((chips, sl["stop_ts"] - sl["start_ts"]))
        per_slice.append({
            "dir": sl["dir"], "busy_s": busy_and_extent(chips)[0],
            "device_rows": len(rows),
            "cost_s": sl["start_took_s"] + sl["stop_took_s"]})
    return dict(reduce_slices(slices, [sl["dir"] for sl in taken]),
                slices_due=len(due), slices_taken=len(taken),
                writeout_s=sum(s["cost_s"] for s in per_slice),
                per_slice=per_slice, lines=sorted(lines))


def longest_gaps(chips: dict, n: int = 10) -> list:
    """The longest stretches in which no leaf operation ran on the first
    chip's plane, `[what, seconds]`.  The trace carries no host span to
    attribute them to (no TraceAnnotation in the program), so `what` names
    the operations on either side."""
    rows = sorted(chips[min(chips)], key=lambda r: r.start_ns)
    gaps = []
    cur_end, cur_name = None, None
    for r in rows:
        if cur_end is not None and r.start_ns > cur_end:
            gaps.append((r.start_ns - cur_end, cur_name, r.name))
        if cur_end is None or r.start_ns + r.duration_ns > cur_end:
            cur_end, cur_name = r.start_ns + r.duration_ns, r.name
    gaps.sort(key=lambda g: -g[0])
    return [[f"after {short_name(a)} before {short_name(b)}", ns / 1e9]
            for ns, a, b in gaps[:n]]
