#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It starts the served path itself, `wtf_tpu campaign ... --telemetry-dir D`,
as a child process, follows D/events.jsonl, and takes every number from
the campaign's own heartbeat records (benchmark/harness/window.py) or from
short device traces that the child takes of itself at seeded moments
spread over the window (harness/child.py, harness/trace.py).
This process never touches JAX: the chip belongs to the child.

--seed is the campaign's seed (passed on as seed + 1: the campaign takes
0 for "unseeded"), so it makes the whole mutation stream and every find,
unless the cell's file fixes a `campaign_seed`: then every run is that one
campaign, bit for bit, and --seed only draws the moments of the trace
slices (PERF.md section 4 says why a cell does that).

The measured window starts at the first heartbeat at which the cell's
`warm_batches` are done and ends at the first heartbeat at least
--seconds later; then the child is stopped.  Outside the window every
find the campaign wrote, crashes/ and outputs/, is replayed on the EmuCpu
oracle (outputs/ by one process, crashes/ in as many parts at once as the
host has cores and memory for), and `correct` is whether the oracle
reproduces each crash under its name and counts the coverage the campaign
counted (harness/oracle.py).

The last stdout line is the result object; the lines before it say what
was compared against which limit, how long each part took against the
run's limit of RUN_LIMIT_S, and what the campaign last printed; the
numbers compared are also the last lines of standard error.  Without a
TPU, with a child that dies, or with a window that keeps compiling, the
exit code is not 0 and there is no result line.  `--rehearse-cpu` is for
the sandbox: 16 lanes, whatever platform JAX finds, no result line.
"""

from __future__ import annotations

import time

T0 = time.time()        # set-up counts from here

import argparse         # noqa: E402
import json             # noqa: E402
import os               # noqa: E402
import random           # noqa: E402
import shutil           # noqa: E402
import signal           # noqa: E402
import subprocess       # noqa: E402
import sys              # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cells, oracle, trace, window  # noqa: E402

HEARTBEAT_TIMEOUT_S = 600   # a cold megachunk window compiles for ~4 min
REPLAY_TIMEOUT_S = 900
RUNS_NO_WINDOW_REACHES = 10 ** 12
RC_RUN_OK = (0, 2)          # `run` exits 2 when a replayed testcase crashed
# a traced run: 16 slices of a twentieth of a second, one in each
# sixteenth of the window, at a moment of its first half drawn from the
# seed; once it has TRACE_MIN_SLICES the child takes no further slice when
# starting and stopping the ones it took has cost TRACE_BUDGET_S, and a
# run with fewer is no reading (PERF.md section 3 has the costs)
TRACE_SLICES = 16
TRACE_SLICE_S = 0.05
TRACE_BUDGET_S = 45
TRACE_MIN_SLICES = 4
RUN_LIMIT_S = 360           # what the driver gives a run that compiles nothing


class RunFailed(Exception):
    pass


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def child_env(root: Path, platform: "str | None" = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    if platform:
        env["JAX_PLATFORMS"] = platform
    return env


def tail(path: Path, n: int = 40) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError:
        return ""


def stop(proc: subprocess.Popen, grace: float = 20.0) -> None:
    """SIGINT, then SIGTERM, then SIGKILL to the child's whole group; the
    child is reaped before this returns."""
    for sig, wait in ((signal.SIGINT, grace), (signal.SIGTERM, 10.0),
                      (signal.SIGKILL, 10.0)):
        if proc.poll() is not None:
            break
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(timeout=wait)
        except subprocess.TimeoutExpired:
            continue
    proc.wait()


def out_dir(root: Path) -> Path:
    """Work directories: benchmark/out/, which benchmark/.gitignore lists."""
    return cells.bench_dir(root, cells.load_benchmark(root)) / "out"


def trace_spec(seed: int, seconds: float) -> str:
    """`<slice s>:<budget s>:<least slices>:<offsets>` for the child: the
    moments, in seconds after the window opens, at which its slices are
    due."""
    rng = random.Random(seed)
    stratum = seconds / TRACE_SLICES
    return f"{TRACE_SLICE_S}:{TRACE_BUDGET_S}:{TRACE_MIN_SLICES}:" + ",".join(
        f"{(i + rng.random() / 2) * stratum:.3f}"
        for i in range(TRACE_SLICES))


def campaign_seed(cell, seed: int) -> int:
    """The cell's own, or --seed + 1: 0 would leave the campaign unseeded."""
    return int(cell.window.get("campaign_seed", seed + 1))


def campaign_argv(cell, root: Path, work: Path, trace: str, platform: str,
                  seed: int) -> list:
    return [sys.executable, str(root / "benchmark/harness/child.py"),
            str(work / "device.json"), platform, str(cell.chips), trace,
            "campaign", *cell.campaign_flags(),
            "--seed", str(campaign_seed(cell, seed)),
            "--runs", str(RUNS_NO_WINDOW_REACHES),
            "--target", str(work), "--telemetry-dir", str(work / "telem")]


def replay_argv(cell, finds_dir: Path, limit: int) -> list:
    return [sys.executable, "-m", "wtf_tpu", "run", "--name",
            cell.config["target"], "--backend", "emu",
            "--input", str(finds_dir), "--limit", str(limit)]


def measure(cell, argv: list, env: dict, work: Path, seconds: float,
            traced: bool) -> dict:
    """Runs the campaign until its window has closed (and, traced, its
    trace is written), stops it, and returns the two heartbeats with what
    else the stream said."""
    picker = window.WindowPicker(seconds, cell.window["warm_batches"])
    log = work / "child.log"
    trigger = work / "device.json.trace"
    traced_doc = work / "device.json.traced"
    with open(log, "w") as out:
        proc = subprocess.Popen(argv, stdout=out, stderr=subprocess.STDOUT,
                                env=env, cwd=str(cells.ROOT),
                                start_new_session=True)
    try:
        last_sign = time.time()
        for record in window.follow(work / "telem" / "events.jsonl",
                                    alive=lambda: proc.poll() is None):
            if record is not None:
                if record.get("type") == "heartbeat":
                    last_sign = time.time()
                picker.feed(record)
                if (traced and picker.first is not None
                        and not trigger.exists()):
                    # the child traces inside the window: its slices are
                    # due so long after this moment
                    fresh = work / "device.json.trace.tmp"
                    fresh.write_text(repr(time.time()))
                    fresh.replace(trigger)
            elif time.time() - last_sign > HEARTBEAT_TIMEOUT_S:
                raise RunFailed(
                    f"no heartbeat for {HEARTBEAT_TIMEOUT_S} s; the "
                    f"child's last output:\n{tail(log)}")
            if picker.done and (not traced or traced_doc.is_file()):
                break
        else:
            raise RunFailed(
                f"the campaign exited with code {proc.poll()} before its "
                f"window closed; its last output:\n{tail(log)}")
    finally:
        t_stop = time.time()
        stop(proc)
    return {"stop_s": time.time() - t_stop,
            "first": picker.first, "last": picker.last,
            "heartbeats_in_window": picker.heartbeats,
            "restarts": picker.restarts, "log": log,
            "traced": cells.load_json(traced_doc) if traced else None}


class Replay:
    """The oracle's listing of the finds: one `wtf_tpu run --backend emu`
    process for each directory of `part_dirs`, off the chip
    (JAX_PLATFORMS=cpu), all started at once; leaving the `with` stops and
    reaps them.  `listing()` waits for them and merges what they printed by
    file; a part that exits with anything but RC_RUN_OK, or outlasts
    REPLAY_TIMEOUT_S, fails the run."""

    def __init__(self, cell, part_dirs: list, limit: int, root: Path):
        self.started = time.time()
        self.parts = []
        try:
            for part in part_dirs:
                out = open(f"{part}.listing", "w+")
                err = open(f"{part}.err", "w+")
                self.parts.append((subprocess.Popen(
                    replay_argv(cell, part, limit), stdout=out, stderr=err,
                    env=child_env(root, "cpu"), cwd=str(root),
                    start_new_session=True), part, out, err))
        except BaseException:
            self.__exit__()
            raise

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        for proc, _, out, err in self.parts:
            stop(proc, grace=2.0)
            out.close()
            err.close()

    def listing(self) -> dict:
        merged = {}
        for proc, part, out, err in self.parts:
            left = self.started + REPLAY_TIMEOUT_S - time.time()
            try:
                proc.wait(timeout=max(left, 0.0))
            except subprocess.TimeoutExpired:
                raise RunFailed(
                    f"the oracle's replay of {part.name}/ did not end "
                    f"within {REPLAY_TIMEOUT_S} s")
            if proc.returncode not in RC_RUN_OK:
                err.seek(0)
                raise RunFailed(
                    f"the oracle's replay of {part.name}/ exited "
                    f"{proc.returncode}:\n"
                    + "\n".join(err.read().splitlines()[-30:]))
            out.seek(0)
            merged.update(oracle.parse_listing(out.read()))
        return merged


def last_heartbeat(events: Path) -> dict:
    """The last heartbeat the campaign wrote before it was stopped."""
    last = None
    for line in events.read_text(errors="replace").splitlines():
        try:
            record = json.loads(line)
        except ValueError:
            continue                 # the line the stop tore
        if record.get("type") == "heartbeat":
            last = record
    return last


def reduce_trace(run: dict, work: Path, first: dict, last: dict) -> dict:
    """busy_s and window_s summed over the slices the child took, the
    breakdown, and the window's own length and guest instructions, which
    the slices sample.  The traces themselves are deleted (tens of MB)."""
    xdir = work / "device.json.xprof"
    try:
        return dict(
            trace.reduce_traced(
                run["traced"], lambda d: trace.load_device_rows(
                    trace.find_xplane(xdir / d)), TRACE_MIN_SLICES),
            span_s=last["ts"] - first["ts"],
            instructions=window.delta(first, last, "backend.instructions"),
            profiler=run["traced"])
    finally:
        shutil.rmtree(xdir, ignore_errors=True)


def end_to_end(first: dict, last: dict) -> dict:
    rate = {"denominator": ["window_s"]}
    return {
        "execs_per_s": {"unit": "execs/s", "value": window.evaluate(
            dict(rate, numerator=["campaign.testcases"]), first, last)},
        "guest_instr_per_s": {"unit": "instr/s", "value": window.evaluate(
            dict(rate, numerator=["backend.instructions"]), first, last)},
        "setup_s": {"unit": "s", "value": first["ts"] - T0},
    }


def per_layer(cell_name: str, first: dict, last: dict, traced: dict,
              root: Path) -> dict:
    out = {}
    for entry, spec in cells.layer_metrics(cell_name, root):
        source = spec["source"]
        if source["kind"] == "heartbeat_delta":
            value = window.evaluate(source, first, last)
        else:
            value = cells.load_reducer(
                entry["name"], source["reducer"], root)(traced)
        if value is not None:     # a reader that found nothing: left out
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="sandbox rehearsal: 16 lanes, any platform, no "
                         "result line")
    ap.add_argument("--control", action="store_true",
                    help="also hold the finds to an oracle whose "
                         "instruction limit is cut to "
                         f"{oracle.CONTROL_LIMIT}: has to come out not "
                         "correct (PERF.md section 2)")
    args = ap.parse_args(argv)
    root = cells.ROOT
    if not 0 <= args.seed < 2 ** 32 - 1:
        raise SystemExit(f"--seed {args.seed} is outside 0 .. 2**32 - 2")

    try:
        cell = cells.load_cell(args.workload, root)
    except cells.BenchmarkDataError as e:
        print(f"benchmark run failed: {e}", file=sys.stderr)
        return 1
    traced = bool(args.trace)
    platform = "tpu"
    if args.rehearse_cpu:
        platform = "any"
        cell.config.update(lanes=16)
    work = out_dir(root) / f"{cell.name}-s{args.seed}-t{args.trace}"
    shutil.rmtree(work, ignore_errors=True)
    # <target>/inputs/ is the campaign's seed corpus: none
    (work / "inputs").mkdir(parents=True)
    spec = trace_spec(args.seed, args.seconds) if traced else "0"
    say(workload=cell.name, seed=args.seed,
        campaign_seed=campaign_seed(cell, args.seed),
        seconds=args.seconds, traced=traced, trace=spec, work=str(work),
        campaign=cell.campaign_flags(), rehearsal=args.rehearse_cpu)

    try:
        run = measure(cell, campaign_argv(cell, root, work, spec, platform,
                                          args.seed),
                      child_env(root), work, args.seconds, traced)
        first, last = run["first"], run["last"]
        device_path = work / "device.json"
        if not device_path.is_file():
            raise RunFailed("the child left no device report:\n"
                            + tail(run["log"]))
        device = cells.load_json(device_path)

        config = cell.config
        t_replay = time.time()
        final = last_heartbeat(work / "telem" / "events.jsonl")
        finds_dir = work / "replay"
        manifest = oracle.gather(work, finds_dir, final["ts"],
                                 lambda n: oracle.parts(config, n))
        part_dirs = oracle.part_dirs(manifest, finds_dir)
        n_parts = len(part_dirs) - 1          # beside the outputs' process
        gather_s = time.time() - t_replay
        held_to = (oracle.campaign_cov(final), window.read_key(
            final, "devdec.crosscheck_mismatches") or 0)
        traced_numbers = {}
        with Replay(cell, part_dirs, config["limit"], root) as replaying:
            # the trace is read while the oracle's processes run
            t_reduce = time.time()
            if traced:
                try:
                    traced_numbers = reduce_trace(run, work, first, last)
                except trace.TraceError as e:
                    if not args.rehearse_cpu:
                        raise RunFailed(f"trace: {e}")
                    say(trace_error=str(e), profiler=run["traced"])
            reduce_s = time.time() - t_reduce
            verdict = oracle.compare(manifest, replaying.listing(),
                                     *held_to)
        correct = verdict["correct"]
        replay_s = time.time() - t_replay
        say(compared=verdict["compared"], replay_parts=n_parts,
            differences=verdict["differences"][:20])
        if args.control:
            with Replay(cell, part_dirs, oracle.CONTROL_LIMIT,
                        root) as cut_short:
                control = oracle.compare(manifest, cut_short.listing(),
                                         *held_to)
            say(control_limit=oracle.CONTROL_LIMIT,
                control_correct=control["correct"],
                control_compared=control["compared"])
    except (RunFailed, window.WindowError, cells.BenchmarkDataError) as e:
        print(f"benchmark run failed: {e}", file=sys.stderr)
        return 1

    e2e = end_to_end(first, last)
    if any(m["value"] is None for m in e2e.values()):
        print(f"benchmark run failed: the heartbeats lack a counter: {e2e}",
              file=sys.stderr)
        return 1
    layers = per_layer(cell.name, first, last, traced_numbers, root)
    attempted = int(window.delta(first, last, "campaign.testcases") or 0)
    # testcases the system did not finish on its own terms: lanes that ran
    # out of overlay slots, and the lanes of batches the supervisor re-ran
    failed = int((window.delta(first, last, "campaign.overlay_fulls") or 0)
                 + config["lanes"] * (window.delta(
                     first, last, "supervise.batch_retries") or 0))
    finals = [ln for ln in tail(run["log"], 400).splitlines()
              if ln.startswith("#")]
    total_s = time.time() - T0
    say(window_s=last["ts"] - first["ts"],
        heartbeats_in_window=run["heartbeats_in_window"],
        batches_in_window=window.delta(first, last, "backend.batches"),
        compile_restarts=run["restarts"],
        warm_s=first["ts"] - T0, replay_s=replay_s, gather_s=gather_s,
        reduce_s=reduce_s,
        replay_parts=n_parts, trace_writeout_s=traced_numbers.get(
            "writeout_s"), stop_s=run["stop_s"],
        total_s=total_s, limit_s=RUN_LIMIT_S,
        headroom_s=RUN_LIMIT_S - total_s,
        native_mutator_built=(root / "wtf_tpu/native/_build"
                              / "libwtfmangle.so").is_file(),
        campaign_last_line=finals[-1] if finals else "",
        traced_end_to_end=e2e if traced else None,
        trace={k: v for k, v in traced_numbers.items() if k != "breakdown"},
        phase_seconds={k: (window.delta(first, last, "phase.seconds." + k))
                       for k in last["metrics"].get("phase.seconds", {})})
    if args.rehearse_cpu:
        say(rehearsal_only=True, correct=correct, attempted=attempted,
            failed=failed, metrics=layers if traced else e2e, device=device)
        print("rehearsal only: no TPU was asserted, no result line")
        return 0

    if not device.get("final"):
        print("benchmark run failed: the child was killed before it could "
              "report its peak memory", file=sys.stderr)
        return 1
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": layers if traced else e2e,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": device["memory_peak_bytes"]},
    }
    if traced:
        result["device"].update(
            busy_s=traced_numbers["busy_s"],
            window_s=traced_numbers["window_s"],
            slices_due=traced_numbers["slices_due"],
            slices_taken=traced_numbers["slices_taken"])
        result["breakdown"] = traced_numbers["breakdown"]
    # each number compared beside its limit: the last lines of standard
    # error, and the result's last key
    result["compared"] = verdict["compared"]
    for name, number in verdict["compared"].items():
        print(f"compared {name}: {json.dumps(number)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
