"""`--xprof-dir`: one bounded `jax.profiler` trace of the running campaign.

Writing a device trace out costs tens of seconds for each second in
which the chip ran, and the Python tracer multiplies that, so a trace of
whole batches does not come back (PERF.md section 6).  The window here
is a fraction of a second, Python tracer off, opened by a thread at an
offset INSIDE a steady-state batch — the main thread is in its spans at
that moment, and every span is a `TraceAnnotation` (telemetry/spans.py),
so the xplane carries the span paths on its host plane beside the
device's operations, on one clock.

A TraceAnnotation is recorded only if it BEGINS inside the session, so a
span that encloses the whole window (a 4-second `execute/results`, an
8-second `harvest` seen between two of its children) would leave no row.
The window therefore reads the open span path when it opens and when it
closes (`open_path`, normally `Spans.open_path`) and writes both into
the trace (`open at start: <path>` spans the window on the tracer
thread's line) and into its record.

The thread touches nothing of the campaign's: it sleeps, starts and
stops the profiler, reads that one path, and leaves a small dict for the
loop to pick up at a batch boundary (`at_batch_boundary`) or at the
campaign's end (`finish`).
"""

from __future__ import annotations

import re
import threading
import time
from pathlib import Path
from typing import Callable, Optional

# Every `jax.named_scope` of the device programs, by what one pass of it
# covers: THE list (PERF.md section 3).  tools/xprof_scopes.py sums a
# window's device time by it, and the census in tests/test_analysis.py
# holds the chunk program to it: no indexed operation outside a scope.
CHUNK_SCOPES = (              # make_run_chunk: once a level or a chunk
    "level-sort", "group-slice", "level-account", "lane-writeback")
VECTOR_SCOPES = (             # the vector unit: XMM file, SSE classes, x87
    "vector-file", "sse-bytes", "sse-fp", "x87")
STEP_SCOPES = (               # step_lane and below: once a lane-step
                              # (`store-spill`: once a sweep, over its lanes)
    "decode-probe", "uop-fetch", "regfile-read", "regfile-write",
    "tlb", "page-walk", "overlay-load", "overlay-store", "store-spill",
    "ea", "alu", "unary", "shift", "mul", "cond", "string") + VECTOR_SCOPES + (
    "iret", "flags-status", "counters", "coverage")
OTHER_SCOPES = (              # the other device programs
    "device-insert", "restore", "run-quiesce", "decode-service",
    "cov-merge", "cov-allreduce", "devmut-generate")
SCOPES = CHUNK_SCOPES + STEP_SCOPES + OTHER_SCOPES
# the operations a sweep is priced by (PERF.md section 5), and the sort
INDEXED_OPS = ("gather", "scatter", "dynamic-slice",
               "dynamic-update-slice", "sort")
_SCOPE_RE = re.compile(
    r"(?<![\w-])(" + "|".join(map(re.escape, SCOPES)) + r")(?![\w-])")
# `lax.cond` names its own operation `cond` and its sides
# `cond/branch_N_fun`: the primitive, not the step's scope of that name
_LAX_COND = re.compile(r"(?<=/)cond(?=/branch_\d+_fun|$)")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost listed scope in an HLO `op_name`, or None."""
    found = _SCOPE_RE.findall(_LAX_COND.sub("", op_name or ""))
    return found[-1] if found else None


class ProfilerWindow:
    """One trace of `seconds` seconds into `out_dir`, at most once, opened
    `share` of a batch's length into the first batch after WARM_BATCHES
    (compile and warm-up come first)."""

    WARM_BATCHES = 2

    def __init__(self, out_dir, seconds: float, share: float,
                 open_path: Optional[Callable[[], str]] = None):
        self.out_dir = Path(out_dir)
        self.seconds = float(seconds)
        self.share = float(share)
        self._open_path = open_path or (lambda: "")
        self._cancel = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._result: Optional[dict] = None
        self._reported = False
        self._boundary_ts = time.time()

    def at_batch_boundary(self, batches_done: int) -> Optional[dict]:
        """Call between batches: arms the window at the first steady-state
        boundary (to open `share` of the last batch's length into the
        next one); afterwards hands out the finished window's record,
        exactly once."""
        now = time.time()
        last_batch_s, self._boundary_ts = now - self._boundary_ts, now
        if self._thread is None:
            if batches_done >= self.WARM_BATCHES:
                self._thread = threading.Thread(
                    target=self._run, args=(self.share * last_batch_s,),
                    name="xprof-window", daemon=True)
                self._thread.start()
            return None
        return self._take()

    def _run(self, offset_s: float) -> None:
        if self._cancel.wait(offset_s):
            return
        try:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            t0 = time.time()
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=options)
            t1 = time.time()
            at_start = at_stop = self._open_path() or "(no span)"
            try:
                with jax.profiler.TraceAnnotation(
                        f"open at start: {at_start}"):
                    self._cancel.wait(self.seconds)
                at_stop = self._open_path() or "(no span)"
                with jax.profiler.TraceAnnotation(
                        f"open at stop: {at_stop}"):
                    pass
            finally:
                t2 = time.time()
                jax.profiler.stop_trace()
            self._result = {"dir": str(self.out_dir), "start_ts": t1,
                            "stop_ts": t2, "start_took_s": t1 - t0,
                            "stop_took_s": time.time() - t2,
                            "open_at_start": at_start,
                            "open_at_stop": at_stop}
        except Exception as e:  # noqa: BLE001 - the profiler is optional
            self._result = {"error": f"{type(e).__name__}: {e}"}

    def _take(self) -> Optional[dict]:
        """The finished window's record, once; None while the thread runs,
        after the record was handed out, or for a cancelled window."""
        if self._reported or self._thread is None \
                or self._thread.is_alive():
            return None
        self._reported = True
        return self._result

    def finish(self, timeout: float = 120.0) -> Optional[dict]:
        """Campaign end: a window not yet open is cancelled, an open one
        is cut short; wait for the trace to be written out."""
        if self._thread is None or self._reported:
            return None
        self._cancel.set()
        self._thread.join(timeout)
        if self._thread.is_alive():
            self._reported = True
            return {"error": f"trace not written within {timeout:.0f} s"}
        return self._take()
