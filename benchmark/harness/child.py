"""`python -m wtf_tpu <argv>` in a process that also says which device it
held and how full it got.

    python child.py <report.json> <platform|any> <chips> <trace> <wtf_tpu argv...>

The campaign is the program's own: `wtf_tpu.cli.main(argv)`, nothing
built here.  The harness's parent process must stay off the chip (a chip
belongs to one process), so the device's identity and its peak memory can
only be read here.  They are written to <report.json> at start (identity)
and again when the campaign ends or SIGINT / SIGTERM arrives (peak bytes).
The campaign has no "stop now" of its own and its main thread may sit in
a device call for a whole loop iteration, so the signal is taken on a
wake-up pipe by a thread, which writes the report and ends the process:
nothing is needed from the campaign after the window's last heartbeat
(records are flushed one by one, finds are written atomically).

<trace> is `0`, or `<slice s>:<budget s>:<least slices>:<offset>,<offset>,...`:
a thread waits for the file <report.json>.trace to appear (the parent writes
the moment into it when the measured window opens), then takes one short
device trace with JAX's profiler, the Python tracer off, at each offset
(seconds after that moment) into <report.json>.xprof/<NN>, and writes
<report.json>.traced.  The slices are spread over the whole window, so that
they meet the chip running and the chip waiting in the shares the window
has them; one trace of a whole loop iteration cannot be had: writing a
slice out costs seconds for each tenth of a second in which the chip ran
in it, and more for every further chip the process holds (PERF.md section
3).  So the write-out has a budget: before each slice the thread sums what
the slices so far cost (`start_took_s` + `stop_took_s`) and, once it has
<least slices>, takes no further slice when the sum has reached <budget s>;
`.traced` lists every due slice, taken or not.  The first slice that meets
the chunk program running costs some 15 s more than a later one, and no
session taken ahead pays that for it (an empty one at the child's start,
or one around a small program of its own, reads 0.3 s and changes nothing:
PERF.md section 6, PR 42); it is part of the budget.  Only the process
that holds the chip can trace it.

Exit 3, before the campaign starts, when JAX finds another platform or
fewer chips than asked for.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time


def _report(path: str, devices, final: bool) -> None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    doc = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "final": final,
           "memory_peak_bytes": max((p for p in peaks if p is not None),
                                    default=None),
           "peak_bytes_per_device": peaks}
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh)
    os.replace(tmp, path)


def take_slices(profiler, xprof: str, slice_s: float, budget_s: float,
                least: int, offsets: list, opened: float, options=None,
                clock=time.time, sleep=time.sleep) -> list:
    """One entry per due slice, in order.  A slice whose turn has passed
    (the one before took long to write) starts at once; once `least`
    slices are taken and they have cost `budget_s` of starting and
    stopping, the rest are listed as not taken."""
    slices, spent, taken = [], 0.0, 0
    for i, offset in enumerate(offsets):
        if taken >= least and spent >= budget_s:
            slices.append({"dir": None, "due_s": offset, "taken": False})
            continue
        taken += 1
        sleep(max(0.0, opened + offset - clock()))
        t0 = clock()
        profiler.start_trace(f"{xprof}/{i:02d}", profiler_options=options)
        t1 = clock()
        sleep(slice_s)
        t2 = clock()
        profiler.stop_trace()
        t3 = clock()
        spent += (t1 - t0) + (t3 - t2)
        slices.append({"dir": f"{i:02d}", "due_s": offset, "taken": True,
                       "start_ts": t1, "stop_ts": t2,
                       "start_took_s": t1 - t0, "stop_took_s": t3 - t2})
    return slices


def _trace_when_asked(report: str, slice_s: float, budget_s: float,
                      least: int, offsets: list) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    while not os.path.exists(report + ".trace"):
        time.sleep(0.1)
    with open(report + ".trace") as fh:
        opened = float(fh.read())   # the parent's clock, which is this one
    slices = take_slices(jax.profiler, report + ".xprof", slice_s, budget_s,
                         least, offsets, opened, options)
    with open(report + ".traced.tmp", "w") as fh:
        json.dump({"opened_ts": opened, "budget_s": budget_s,
                   "slices": slices}, fh)
    os.replace(report + ".traced.tmp", report + ".traced")


def main(argv: list) -> int:
    report, platform, chips = argv[0], argv[1], int(argv[2])
    trace, cli_argv = argv[3], argv[4:]
    import wtf_tpu  # noqa: F401  (x64 and the compile cache, before JAX starts)
    import jax

    devices = jax.devices()
    if platform != "any" and (devices[0].platform != platform
                              or len(devices) < chips):
        print(f"benchmark child: need {chips} {platform} device(s), JAX "
              f"found {devices}", file=sys.stderr)
        return 3
    _report(report, devices, final=False)

    read_fd, write_fd = os.pipe()
    os.set_blocking(write_fd, False)
    signal.set_wakeup_fd(write_fd, warn_on_full_buffer=False)
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: None)   # the pipe's reader acts

    def on_signal() -> None:
        os.read(read_fd, 1)
        try:
            _report(report, devices, final=True)
        finally:
            sys.stdout.flush()
            os._exit(0)

    threading.Thread(target=on_signal, daemon=True).start()
    if trace != "0":
        slice_s, budget_s, least, offsets = trace.split(":")
        threading.Thread(
            target=_trace_when_asked, daemon=True,
            args=(report, float(slice_s), float(budget_s), int(least),
                  [float(o) for o in offsets.split(",")])).start()

    from wtf_tpu.cli import main as cli_main

    try:
        return cli_main(cli_argv)
    finally:
        _report(report, devices, final=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
