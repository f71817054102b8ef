"""Phase spans: where the wall-clock goes, fenced against async dispatch.

JAX dispatch is asynchronous — `machine = run_chunk(...)` returns before
the TPU finishes, so a naive timer around it measures Python dispatch,
not device execution, and the "missing" time surfaces in whichever later
span happens to synchronize first (the classic async-profiling lie; the
Concordia/Cudagrind phase-accounting papers in PAPERS.md exist because
of it).  A Span therefore exposes `fence(value)` — an explicit
`jax.block_until_ready` barrier the caller drops on the device values it
just produced, so the span's end time is taken AFTER the device work is
actually done:

    with spans.span("device-step"):
        with spans.span("dispatch"):
            machine = run_chunk(tab, image, machine, limit)
        with spans.span("wait") as sp:
            sp.fence(machine.status)

Spans nest: a span opened inside another records under the joined path
("execute/device-step"), so a report can both account top-level phases
against wall-clock (paths without "/") and break a phase down.  Totals
land in the owning registry as `phase.seconds{path}` / `phase.calls{path}`
labeled counters — one metric namespace shared with everything else, one
heartbeat dump carries it all.

Every span also enters a `jax.profiler.TraceAnnotation(path)`: inert
without a profiler session, and inside one (`--xprof-dir`, or any
`jax.profiler` trace of the process) the span paths land on the host
plane of the profiler's own trace, on the clock its device plane uses —
which is what lets an idle stretch of the device be laid against the
host span that covered it.
"""

from __future__ import annotations

import time
from typing import List, Optional

from wtf_tpu.telemetry.metrics import Registry

SECONDS = "phase.seconds"
CALLS = "phase.calls"

# Span leaves that measure DEVICE work (each is fenced with
# jax.block_until_ready before its span closes): the device-step
# executor, the devmut generation / insert / megachunk-window waits
# ("device" under mutate/insert/execute), the overlay restore, and the
# coverage readback.  Everything else inside a top-level phase is host
# time.  The ONE list the host/device wall breakdown rides on —
# tools/telemetry_report.py and `wtf-tpu status` both consume it, so
# the split cannot drift between them.
DEVICE_SPAN_LEAVES = frozenset((
    "device", "device-step", "overlay-restore", "cov-readback",
))
# A device leaf that calls a chunk or a window program has two children
# that sum to it (interp/runner.py `Runner.run`, backend/tpu.py):
# `dispatch`, the jit call, and `wait`, the fence.  The split below does
# not read them: under --dispatch-timeout the supervisor's bounded wait
# runs inside `dispatch`, so the leaf alone is the device's time in both
# modes (the children are `chunk_dispatch_ms`'s and `telemetry_report`'s
# "chunk:" line's to read).


def device_seconds_by_phase(phase_seconds: dict) -> dict:
    """{top-level phase: seconds in the fenced device spans nested under
    it}."""
    out: dict = {}
    for path, secs in phase_seconds.items():
        parts = path.split("/")
        if len(parts) > 1 and parts[-1] in DEVICE_SPAN_LEAVES:
            out[parts[0]] = out.get(parts[0], 0.0) + secs
    return out


def block_until_ready(value) -> None:
    """Fence: wait until every device array in `value` has materialized.
    No-op for host values and when jax isn't importable (telemetry stays
    usable from pure-host tools)."""
    if value is None:
        return
    try:
        import jax
    except Exception:  # pragma: no cover - jax is baked into this image
        return
    try:
        jax.block_until_ready(value)
    except Exception:
        pass  # non-pytree host object: already materialized


class Span:
    """One open phase measurement (context-managed via Spans.span)."""

    __slots__ = ("path", "_spans", "_t0")

    def __init__(self, spans: "Spans", path: str):
        self.path = path
        self._spans = spans
        self._t0 = spans._clock()

    def fence(self, value) -> None:
        """Block until `value`'s device buffers are ready — call on the
        chunk's outputs before the span closes so async dispatch can't
        shift its time into a later span."""
        block_until_ready(value)

    @property
    def elapsed(self) -> float:
        return self._spans._clock() - self._t0


class TraceCollector:
    """Chrome-trace-event sink for spans: every closed span becomes one
    "ph":"X" complete event (ts/dur in microseconds, category "device"
    for the fenced DEVICE_SPAN_LEAVES, "host" otherwise), and point
    events (compile, checkpoint, prelaunch drops, supervisor recoveries)
    become "ph":"i" instants — so chrome://tracing / Perfetto renders
    the host-vs-device overlap per batch directly.

    Collection is O(1) appends on close; nothing is serialized until
    write() (keeping the emit path off the dispatch seams — the
    telemetry lint family pins this).  `max_events` bounds memory on
    long campaigns by dropping the oldest half once full (the steady
    state is what a timeline capture is for)."""

    def __init__(self, clock=time.perf_counter, max_events: int = 200_000):
        self._clock = clock
        self._events: List[tuple] = []  # ("X", path, t0, dur) | ("i", ...)
        self._max = max_events
        self.dropped = 0
        # one reading of both clocks: turns the rebased timeline's zero
        # back into Unix time (otherData.epoch_unix_s)
        self._unix_at = (time.time(), clock())

    def complete(self, path: str, t0: float, dur: float) -> None:
        self._append(("X", path, t0, dur))

    def instant(self, name: str, args=None) -> None:
        self._append(("i", name, self._clock(), args))

    def _append(self, event: tuple) -> None:
        if len(self._events) >= self._max:
            keep = self._max // 2
            self.dropped += len(self._events) - keep
            self._events = self._events[-keep:]
        self._events.append(event)

    def trace_events(self) -> List[dict]:
        """The Chrome trace-event list (ts rebased to the first event)."""
        if not self._events:
            return []
        epoch = min(ev[2] for ev in self._events)
        out = []
        for ev in self._events:
            ts = round((ev[2] - epoch) * 1e6, 3)
            if ev[0] == "X":
                path = ev[1]
                leaf = path.rsplit("/", 1)[-1]
                cat = "device" if leaf in DEVICE_SPAN_LEAVES else "host"
                out.append({"name": leaf, "cat": cat, "ph": "X",
                            "ts": ts, "dur": round(ev[3] * 1e6, 3),
                            "pid": 1, "tid": 1, "args": {"path": path}})
            else:
                record = {"name": ev[1], "cat": "event", "ph": "i",
                          "ts": ts, "pid": 1, "tid": 1, "s": "t"}
                if ev[3]:
                    record["args"] = ev[3]
                out.append(record)
        out.sort(key=lambda e: e["ts"])
        return out

    def write(self, path) -> int:
        """Write the JSON object form ({"traceEvents": [...]}) — the
        schema both chrome://tracing and Perfetto load.  Returns the
        event count."""
        import json
        from pathlib import Path

        events = self.trace_events()
        other = {"producer": "wtf-tpu", "dropped_events": self.dropped}
        if self._events:
            # Unix time of ts 0 (the first event), so the timeline can be
            # laid against heartbeat `ts` and a profiler slice's start
            unix0, clock0 = self._unix_at
            other["epoch_unix_s"] = unix0 + (
                min(ev[2] for ev in self._events) - clock0)
        payload = {"traceEvents": events, "displayTimeUnit": "ms",
                   "otherData": other}
        Path(path).parent.mkdir(parents=True, exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(payload, fh)
        return len(events)


class Spans:
    """Registry-owned span timer.  Single-threaded by design (the run
    loop is); the nesting stack is just a list.  `collector` (normally
    None) mirrors every closed span into a TraceCollector for --trace-out
    timeline export."""

    def __init__(self, registry: Registry, clock=time.perf_counter):
        self._registry = registry
        self._clock = clock
        self._stack: List[str] = []
        self.collector: Optional[TraceCollector] = None
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # pragma: no cover - jax is baked into this image
            TraceAnnotation = None  # pure-host tools: spans still time
        self._annotation = TraceAnnotation

    def span(self, name: str) -> "_SpanCtx":
        """Open a phase span (context manager; call sp.fence(value) inside
        the with-block on the device values the phase produced)."""
        return _SpanCtx(self, name)

    def open_path(self) -> str:
        """The path of the innermost open span ("" between spans).  Safe
        to call from another thread: it copies the stack in one step."""
        return "/".join(list(self._stack))

    def seconds(self, path: str) -> float:
        """Accumulated seconds recorded under `path` (0.0 if never hit)."""
        children = self._registry.counter(SECONDS).children
        child = children.get(path)
        return child.value if child is not None else 0.0

    def trace_mark(self, name: str) -> "_TraceMarkCtx":
        """A trace-timeline-only span: emits an "X" event to the
        collector (if attached) but does NOT enter the nesting stack or
        the phase.seconds counters — for visual grouping boxes whose
        extra path level would skew path-keyed accounting (e.g. the
        megachunk window drawn around execute/device)."""
        return _TraceMarkCtx(self, name)

    def _record(self, path: str, dt: float) -> None:
        self._registry.counter(SECONDS).labels(path).inc(dt)
        self._registry.counter(CALLS).labels(path).inc()


class _SpanCtx:
    __slots__ = ("_spans", "_name", "_span", "_anno")

    def __init__(self, spans: Spans, name: str):
        self._spans = spans
        self._name = name
        self._span: Optional[Span] = None
        self._anno = None

    def __enter__(self) -> Span:
        spans = self._spans
        path = "/".join(spans._stack + [self._name])
        spans._stack.append(self._name)
        if spans._annotation is not None:
            self._anno = spans._annotation(path)
            self._anno.__enter__()
        self._span = Span(spans, path)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        # record even on an in-span exception: a crashed phase's time
        # is exactly what a post-mortem wants attributed
        spans = self._spans
        dt = self._span.elapsed
        if self._anno is not None:
            self._anno.__exit__(exc_type, exc, tb)
        if spans._stack and spans._stack[-1] == self._name:
            spans._stack.pop()
        spans._record(self._span.path, dt)
        if spans.collector is not None:
            spans.collector.complete(self._span.path, self._span._t0, dt)
        return None


class _TraceMarkCtx:
    __slots__ = ("_spans", "_name", "_t0", "_anno")

    def __init__(self, spans: Spans, name: str):
        self._spans = spans
        self._name = name
        self._t0 = 0.0
        self._anno = None

    def __enter__(self) -> "_TraceMarkCtx":
        spans = self._spans
        if spans._annotation is not None:
            self._anno = spans._annotation(
                "/".join(spans._stack + [self._name]))
            self._anno.__enter__()
        self._t0 = spans._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        spans = self._spans
        if self._anno is not None:
            self._anno.__exit__(exc_type, exc, tb)
        if spans.collector is not None:
            path = "/".join(spans._stack + [self._name])
            spans.collector.complete(path, self._t0,
                                     spans._clock() - self._t0)
        return None
