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

<trace> is `0`, or `<slice seconds>:<offset>,<offset>,...`: a thread waits
for the file <report.json>.trace to appear (the parent makes it when the
measured window opens), then takes one short device trace with JAX's
profiler, the Python tracer off, at each offset (seconds after that
moment) into <report.json>.xprof/<NN>, and writes <report.json>.traced.
The slices are spread over the whole window, so that they meet the chip
running and the chip waiting in the shares the window has them; one trace
of a whole loop iteration cannot be had (writing a trace out takes some
30 s for each second in which the chip ran, PERF.md section 3).  Only the
process that holds the chip can trace it.

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


def _trace_when_asked(report: str, slice_s: float, offsets: list) -> None:
    import jax

    while not os.path.exists(report + ".trace"):
        time.sleep(0.1)
    opened = time.time()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    slices = []
    for i, offset in enumerate(offsets):
        # a slice whose turn has passed (the one before took long to
        # write) starts at once
        time.sleep(max(0.0, opened + offset - time.time()))
        t0 = time.time()
        jax.profiler.start_trace(f"{report}.xprof/{i:02d}",
                                 profiler_options=options)
        t1 = time.time()
        time.sleep(slice_s)
        t2 = time.time()
        jax.profiler.stop_trace()
        slices.append({"dir": f"{i:02d}", "due_s": offset,
                       "start_ts": t1, "stop_ts": t2,
                       "start_took_s": t1 - t0,
                       "stop_took_s": time.time() - t2})
    with open(report + ".traced.tmp", "w") as fh:
        json.dump({"opened_ts": opened, "slices": slices}, fh)
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
        slice_s, offsets = trace.split(":")
        threading.Thread(
            target=_trace_when_asked, daemon=True,
            args=(report, float(slice_s),
                  [float(o) for o in offsets.split(",")])).start()

    from wtf_tpu.cli import main as cli_main

    try:
        return cli_main(cli_argv)
    finally:
        _report(report, devices, final=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
