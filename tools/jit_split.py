#!/usr/bin/env python3
"""What the first `jit` call of a process spent, term by term: trace,
lower, backend compile, the compile cache's get and put.

    python tools/jit_split.py run --out DIR -- <command> [arguments]
    python tools/jit_split.py report DIR

`run` starts <command> with a `sitecustomize` on PYTHONPATH that calls
`install` in every Python process under it (the benchmark's child, a
campaign, a test), so the program is run as it is, with no flag and no
edit.  Each process appends to DIR/<pid>.jsonl one line an event of JAX's
own (`jax.monitoring`), at the moment it fires and with the wall clock
beside it: the process may be ended by `os._exit` (benchmark/harness/
child.py), so nothing waits for an exit hook.

`report` sums a process's events by program (`fun_name`) and prints, for
each program that took `MIN_S` seconds or more (`read_process` returns
the same rows as a dict, for a script that wants them):

  trace      /jax/core/compile/jaxpr_trace_duration
  lower      /jax/core/compile/jaxpr_to_mlir_module_duration
  compile    /jax/core/compile/backend_compile_duration: on a hit of the
             persistent cache this is the read and the client's load of
             the executable, on a miss XLA's compile (and the put)
  get        /jax/compilation_cache/cache_retrieval_time_sec (hits only)
  saved      /jax/compilation_cache/compile_time_saved_sec
  hit/miss   /jax/compilation_cache/cache_hits, cache_misses

and the span from the first term's start to the last one's end.  What the
campaign's heartbeat reads for the same call
(`execute/device-step/dispatch` of batch 1) less that span is the
client's: argument handling, the executable's load onto the device, the
enqueue.  A term that grows between two trees on the WARM run is paid by
every later run of the checkout: pair a cold and a warm set-up of each
tree before a step PR goes in (RUNBOOK.md, "Pairing set-ups"; PERF.md
section 6, PR 41).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

ENV = "JIT_SPLIT_DIR"
MIN_S = 0.5    # a program's trace + lower + compile below this is not a row
TERMS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "get",
    "/jax/compilation_cache/compile_time_saved_sec": "saved",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


def install(out_dir: str) -> None:
    """Register the listeners in this process: every event, duration and
    time span JAX records goes to <out_dir>/<pid>.jsonl as it happens."""
    from jax import monitoring

    os.makedirs(out_dir, exist_ok=True)
    fh = open(os.path.join(out_dir, f"{os.getpid()}.jsonl"), "a", buffering=1)
    fh.write(json.dumps({"argv": sys.argv, "ts": time.time()}) + "\n")

    def write(event, **fields):
        fh.write(json.dumps({"event": event, "ts": time.time(), **fields},
                            default=str) + "\n")

    monitoring.register_event_listener(
        lambda event, **kw: write(event, **kw))
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: write(event, secs=secs, **kw))
    monitoring.register_event_time_span_listener(
        lambda event, start, end, **kw: write(event, start=start, end=end, **kw))


def run(out_dir: str, command: list) -> int:
    out_dir = os.path.abspath(out_dir)
    with tempfile.TemporaryDirectory() as hook:
        Path(hook, "sitecustomize.py").write_text(
            "import os, sys\n"
            f"if os.environ.get({ENV!r}):\n"
            f"    sys.path.insert(0, {str(Path(__file__).parent)!r})\n"
            "    import jit_split\n"
            f"    jit_split.install(os.environ[{ENV!r}])\n"
            "    sys.path.pop(0)\n")
        env = dict(os.environ)
        env[ENV] = out_dir
        env["PYTHONPATH"] = os.pathsep.join(
            [hook] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        return subprocess.call(command, env=env)


def read_process(path: Path) -> dict:
    """One process's file -> {"argv", "programs": [...]}: its programs in
    the order they were first traced, each with its terms summed."""
    argv, order, programs, last = None, [], {}, None
    for line in path.read_text().splitlines():
        rec = json.loads(line)
        if "argv" in rec:
            argv = rec["argv"]
            continue
        term = TERMS.get(rec["event"])
        if term is None:
            continue
        # the cache's own events carry no fun_name: they fire inside the
        # compile of the program whose trace and lowering came last
        name = rec.get("fun_name", last)
        if name is None:
            continue
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]    # lowering and compile say jit(f), tracing f
        last = name
        if name not in programs:
            order.append(name)
            programs[name] = defaultdict(float)
        p = programs[name]
        if "start" in rec:       # the time span repeats the duration
            p["first_start"] = min(p.get("first_start", rec["start"]), rec["start"])
            p["last_end"] = max(p.get("last_end", rec["end"]), rec["end"])
        elif "secs" in rec:
            p[term] += rec["secs"]
        else:
            p[term] += 1
    rows = []
    for name in order:
        p = programs[name]
        row = {"program": name, **{t: round(p[t], 3) for t in TERMS.values() if t in p}}
        if "first_start" in p:
            row["span"] = round(p["last_end"] - p["first_start"], 3)
        rows.append(row)
    return {"argv": argv, "programs": rows}


def report(out_dir: str) -> int:
    for path in sorted(Path(out_dir).glob("*.jsonl"), key=lambda p: p.stat().st_mtime):
        doc = read_process(path)
        # jnp's own little jits are traced inside every program: a row is a
        # program that itself cost something
        doc["programs"] = [
            r for r in doc["programs"]
            if sum(r.get(t, 0.0) for t in ("trace", "lower", "compile")) >= MIN_S]
        if not doc["programs"]:
            continue
        print(" ".join(doc["argv"] or [path.name])[:160])
        for r in doc["programs"]:
            terms = "  ".join(f"{t} {r[t]:g}" for t in TERMS.values() if t in r)
            print(f"  {r['program'][:40]:40s} span {r.get('span', 0.0):8.3f} s   {terms}")
    return 0


def main(argv: "list | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("run")
    r.add_argument("--out", required=True)
    r.add_argument("command", nargs=argparse.REMAINDER)
    p = sub.add_parser("report")
    p.add_argument("dir")
    args = ap.parse_args(argv)
    if args.what == "run":
        command = args.command[1:] if args.command[:1] == ["--"] else args.command
        return run(args.out, command)
    return report(args.dir)


if __name__ == "__main__":
    sys.exit(main())
