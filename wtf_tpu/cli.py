"""CLI entry point: `python -m wtf_tpu {master|fuzz|run|campaign}`.

Mirror of the reference's wtf.cc:33-371 (CLI11 subcommands + path
defaulting) and subcommands.cc:16-101 (drivers):

  run       replay input file/dir on a backend, optional rip/cov trace
            (RunSubcommand, subcommands.cc:16-92)
  fuzz      node loop: dial the master, execute, report
            (FuzzSubcommand -> Client_t::Run, subcommands.cc:94-97)
  master    testcase server: corpus, mutation, coverage aggregation
            (MasterSubcommand -> Server_t::Run, subcommands.cc:99-101)
  campaign  single-process fused master+node over one device batch
            (this framework's native mode; no reference equivalent)
  triage    batched crash triage on the device batch (wtf_tpu/triage):
            minimize (crash bisection), distill (exact-attribution
            corpus minset), vbreak (virtual-breakpoint replay) — the
            reference's host-serial `run`-mode workflows as mesh
            dispatches
  lint      graph-invariant static analysis of the hot-path contracts
            (wtf_tpu/analysis; CPU-only, no reference equivalent)

Target selection is by --name over the self-registering target registry;
--target-module imports additional harness modules first (the reference
compiles fuzzer_*.cc in; here any importable module registering a Target
works, wtf.cc:378-383).
"""

from __future__ import annotations

import argparse
import importlib
import logging
import random
import sys
import time
from contextlib import contextmanager
from pathlib import Path
from typing import List, Optional

from wtf_tpu.config import (
    BACKENDS, CampaignOptions, DEFAULT_ADDRESS, FuzzOptions, MasterOptions,
    RunOptions, TargetPaths, TRACE_TYPES, TriageOptions,
)
from wtf_tpu.core.results import Crash
from wtf_tpu.harness.targets import Targets, load_builtin_targets
from wtf_tpu.telemetry import Registry, open_event_log


def _add_paths(p: argparse.ArgumentParser) -> None:
    p.add_argument("--target", type=Path, default=None,
                   help="target root dir (defaults inputs/outputs/crashes/"
                        "state underneath, wtf.cc:48-68)")
    p.add_argument("--inputs", type=Path, default=None)
    p.add_argument("--outputs", type=Path, default=None)
    p.add_argument("--crashes", type=Path, default=None)
    p.add_argument("--state", type=Path, default=None)
    p.add_argument("--telemetry-dir", type=Path, default=None,
                   help="write machine-readable telemetry (events.jsonl: "
                        "run-start/heartbeat/new-coverage/crash/timeout/"
                        "compile/run-end records with a full metrics dump; "
                        "summarize with tools/telemetry_report.py)")
    p.add_argument("--trace-out", type=Path, default=None,
                   help="write a Chrome-trace-event timeline "
                        "(chrome://tracing / Perfetto JSON) of every "
                        "span — fenced device dispatches, compiles, "
                        "megachunk windows — plus instant marks for "
                        "point events (crash/new-coverage/checkpoint/"
                        "recovery)")


def _add_target_selection(p: argparse.ArgumentParser) -> None:
    p.add_argument("--name", required=True, help="registered target name")
    p.add_argument("--target-module", action="append", default=[],
                   help="extra python module(s) to import for target "
                        "registration")


def _add_backend_tuning(p: argparse.ArgumentParser, mesh: bool = False
                        ) -> None:
    """Execution-engine knobs of the tpu backend (ignored by emu)."""
    if mesh:
        p.add_argument("--mesh-devices", type=int, default=None,
                       metavar="N",
                       help="shard the lane batch over a device mesh "
                            "(wtf_tpu/meshrun): N devices, 0 = every "
                            "local device.  --lanes is the TOTAL lane "
                            "count (lanes/N per chip) and must divide "
                            "by N; coverage OR-reduces on-chip, so the "
                            "fuzz loop sees one logical backend")
    p.add_argument("--burst-any-tier", choices=("auto", "on", "off"),
                   default="auto",
                   help="the oracle burst's any-instruction tier for "
                        "chronically diverting lanes.  auto = platform "
                        "default (on off-CPU); on/off force it, e.g. to "
                        "run or bench the tier on the CPU platform")
    p.add_argument("--device-decode", action="store_true",
                   help="device-resident x86 decode (interp/devdec.py): "
                        "megachunk windows service decode-cache misses "
                        "in-graph (page-walked fetch + batched decode + "
                        "publish-order slot reservation), parking only "
                        "unsupported encodings for the host; the host "
                        "decoder cross-checks every device-published "
                        "entry at harvest")
    p.add_argument("--supervise", action="store_true",
                   help="self-healing device runtime (wtf_tpu/supervise): "
                        "watchdogged dispatches, rebuild-and-replay "
                        "recovery, the degradation ladder, per-batch "
                        "integrity checks + lane quarantine")
    p.add_argument("--dispatch-timeout", type=float, default=0.0,
                   metavar="SECS",
                   help="watchdog bound for ONE base-chunk dispatch "
                        "(scaled by chunk steps and megachunk window); "
                        "0 = no watchdog.  Implies --supervise")


def _backend_tuning_kwargs(args) -> dict:
    kwargs = {}
    tier = getattr(args, "burst_any_tier", "auto")
    if tier != "auto":
        kwargs["burst_any_tier"] = tier == "on"
    mesh = getattr(args, "mesh_devices", None)
    if mesh is not None:
        kwargs["mesh_devices"] = mesh
    timeout = getattr(args, "dispatch_timeout", 0.0) or 0.0
    if getattr(args, "supervise", False) or timeout:
        kwargs["supervise"] = True
        kwargs["dispatch_timeout"] = timeout
    if getattr(args, "device_decode", False):
        kwargs["device_decode"] = True
    return kwargs


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="wtf_tpu",
        description="TPU-native distributed snapshot fuzzer")
    sub = parser.add_subparsers(dest="subcommand", required=True)

    run = sub.add_parser("run", help="replay testcases / write traces")
    _add_target_selection(run)
    _add_paths(run)
    run.add_argument("--backend", choices=BACKENDS, default="emu")
    run.add_argument("--input", type=Path, required=True,
                     help="testcase file or directory")
    run.add_argument("--limit", type=int, default=0,
                     help="instruction budget per testcase (0 = none)")
    run.add_argument("--runs", type=int, default=1,
                     help="times to run each testcase")
    run.add_argument("--trace-path", type=Path, default=None,
                     help="file (single input) or dir to write traces")
    run.add_argument("--trace-type", choices=TRACE_TYPES, default="rip")
    run.add_argument("--coverage", type=Path, default=None,
                     help="dir of .cov files (IDA/Binja/Ghidra exports); "
                          "prints covered/total per run set")
    run.add_argument("--lanes", type=int, default=4)
    _add_backend_tuning(run)

    fuzz = sub.add_parser("fuzz", help="fuzz node (dials the master)")
    _add_target_selection(fuzz)
    _add_paths(fuzz)
    fuzz.add_argument("--backend", choices=BACKENDS, default="tpu")
    fuzz.add_argument("--limit", type=int, default=0)
    fuzz.add_argument("--address", default=DEFAULT_ADDRESS)
    fuzz.add_argument("--seed", type=int, default=0)
    fuzz.add_argument("--lanes", type=int, default=64)
    fuzz.add_argument("--mux", action="store_true",
                      help="one multiplexed master connection for the whole"
                           " lane batch instead of one per lane (scales a"
                           " wide node past the master's fd budget)")
    fuzz.add_argument("--max-retry-secs", type=float, default=60.0,
                      help="survive mid-campaign socket loss: reconnect "
                           "with jittered exponential backoff for this "
                           "long before giving up (0 = reference "
                           "behavior: first loss ends the node)")
    fuzz.add_argument("--wire-v1", action="store_true",
                      help="speak the legacy (pre-WTF2) hello to a "
                           "not-yet-upgraded master: raw downstream "
                           "frames, no BYE, and therefore no reconnect "
                           "(rolling-upgrade escape hatch)")
    fuzz.add_argument("--no-cov-delta", action="store_true",
                      help="ship whole coverage sets per result (the "
                           "pre-fleet WTF2 wire) instead of streaming "
                           "coverage deltas against the master's ack "
                           "cursor — the escape hatch for masters that "
                           "predate WTF3 (--wire-v1 implies it)")
    _add_backend_tuning(fuzz, mesh=True)

    master = sub.add_parser("master", help="master node (serves testcases)")
    _add_target_selection(master)
    _add_paths(master)
    master.add_argument("--address", default=DEFAULT_ADDRESS)
    master.add_argument("--runs", type=int, default=0,
                        help="mutation budget; 0 = minset over inputs/")
    master.add_argument("--max_len", type=int, default=1024 * 1024)
    master.add_argument("--seed", type=int, default=0)
    master.add_argument("--reclaim-timeout", type=float, default=0.0,
                        help="reclaim in-flight testcases from a node "
                             "silent this long (presumed dead); 0 = off. "
                             "Reclaim-on-disconnect is always on; SIGTERM "
                             "drains gracefully either way")
    master.add_argument("--store", type=Path, default=None, metavar="DIR",
                        help="content-addressed corpus/crash store root "
                             "(wtf_tpu/fleet/store): digest-named blobs "
                             "in sharded fanout dirs with a manifest "
                             "journal; outputs//crashes/ become flat "
                             "views of it")

    snap = sub.add_parser(
        "snapshot", help="convert snapshots between formats")
    snap.add_argument("--state", type=Path, required=True,
                      help="input state dir (mem.npz or mem.dmp + regs.json)")
    snap.add_argument("--out", type=Path, required=True,
                      help="output state dir")
    snap.add_argument("--format", choices=("npz", "dmp-bmp", "dmp-full"),
                      default="npz")

    camp = sub.add_parser(
        "campaign", help="single-process fused master+node fuzz loop")
    _add_target_selection(camp)
    _add_paths(camp)
    camp.add_argument("--backend", choices=BACKENDS, default="tpu")
    camp.add_argument("--limit", type=int, default=0)
    camp.add_argument("--runs", type=int, default=0,
                      help="testcase budget; 0 = minset: replay inputs/ "
                           "once and write the coverage-minimal subset to "
                           "outputs/ (reference --runs=0, server.h:552-556)")
    camp.add_argument("--max_len", type=int, default=1024 * 1024)
    camp.add_argument("--seed", type=int, default=0)
    camp.add_argument("--lanes", type=int, default=64)
    camp.add_argument("--mutator",
                      choices=("auto", "byte", "mangle", "tlv", "devmangle"),
                      default="auto",
                      help="mutation engine: auto = the target's custom "
                           "mutator, else the best host mangle engine. "
                           "devmangle = the device-resident engine "
                           "(wtf_tpu/devmut): the whole batch is "
                           "generated in-graph from the HBM corpus slab "
                           "(tpu backend + a target with a "
                           "DeviceInsertSpec only)")
    camp.add_argument("--stop-on-crash", action="store_true")
    camp.add_argument("--megachunk", type=int, default=0, metavar="N",
                      help="one-dispatch multi-batch windows (wtf_tpu/"
                           "fuzz/megachunk): fold up to N whole batches "
                           "— restore, devmut generation, insert, the "
                           "run ladder, the coverage merge — into ONE "
                           "compiled program per dispatch, so per-batch "
                           "host work collapses to the status pull and "
                           "find harvest.  Needs --mutator devmangle "
                           "and a nonzero --limit; 0 = off")
    camp.add_argument("--checkpoint-every", type=int, default=0,
                      metavar="N",
                      help="crash-safe checkpointing (wtf_tpu/resume): "
                           "persist the resumable campaign state every N "
                           "batches (atomic tmp+fsync+rename; previous "
                           "generation kept as .prev).  A kill at any "
                           "point costs at most one interval")
    camp.add_argument("--checkpoint-dir", type=Path, default=None,
                      help="checkpoint directory (default: "
                           "<target>/checkpoint when --target is given; "
                           "a --resume dir is reused)")
    camp.add_argument("--resume", type=Path, default=None, metavar="DIR",
                      help="resume from a checkpoint dir: coverage, crash "
                           "set, corpus, RNG and devmut streams restore "
                           "bit-identically to the uninterrupted run")
    camp.add_argument("--store", type=Path, default=None, metavar="DIR",
                      help="content-addressed corpus/crash store root "
                           "(wtf_tpu/fleet/store); outputs//crashes/ "
                           "become flat views of it")
    camp.add_argument("--xprof-dir", type=Path, default=None,
                      help="capture ONE short jax.profiler trace (Python "
                           "tracer off) inside the first steady-state "
                           "batch, at an offset drawn from --seed; its "
                           "host plane carries the span paths beside the "
                           "device's operations (open with xprof/"
                           "tensorboard/Perfetto)")
    camp.add_argument("--xprof-seconds", type=float, default=0.25,
                      metavar="S",
                      help="length of the --xprof-dir trace; writing one "
                           "out costs tens of seconds per traced second")
    camp.add_argument("--coordinator", default=None,
                      help="jax.distributed coordinator address for a"
                           " multi-host launch (host:port)")
    camp.add_argument("--num-processes", type=int, default=None)
    camp.add_argument("--process-id", type=int, default=None)
    _add_backend_tuning(camp, mesh=True)

    sched = sub.add_parser(
        "sched", help="multi-tenant campaign scheduler (wtf_tpu/tenancy):"
                      " a jobs table placed onto ONE batched device "
                      "program by priority and lane quota, preempted "
                      "via per-tenant checkpoints")
    sched.add_argument("--jobs", type=Path, required=True,
                       help='jobs table: {"jobs": [{"name", "target", '
                            '"lanes", "runs", "priority", "seed", '
                            '"mutator", "max_len", "inputs", '
                            '"checkpoint_every"}, ...]}')
    sched.add_argument("--workdir", type=Path, required=True,
                       help="per-job state root: <workdir>/<job>/"
                            "{checkpoint,crashes}.  Checkpoints carry "
                            "every bit a job needs across placements, "
                            "so a killed sched run resumes from here")
    sched.add_argument("--lanes", type=int, default=64,
                       help="total lane budget of the shared batch")
    sched.add_argument("--limit", type=int, default=0,
                       help="instruction budget per testcase (applies "
                            "to every job: the limit is one operand of "
                            "the shared compiled program)")
    sched.add_argument("--quantum", type=int, default=4,
                       help="batches per scheduling round; at each "
                            "quantum boundary unfinished placed jobs "
                            "checkpoint, and jobs left waiting preempt "
                            "them in the next placement")
    sched.add_argument("--max-rounds", type=int, default=1 << 12)
    sched.add_argument("--target-module", action="append", default=[],
                       help="extra python module(s) to import for "
                            "target registration")
    sched.add_argument("--telemetry-dir", type=Path, default=None,
                       help="events.jsonl with tenant-tagged records + "
                            "sched-round/sched-preempt/sched-complete; "
                            "summarize with tools/telemetry_report.py")
    sched.add_argument("--store", type=Path, default=None, metavar="DIR",
                       help="content-addressed store root; each job "
                            "gets its own tenant-<name> namespace "
                            "(wtf_tpu/fleet/store)")
    _add_backend_tuning(sched, mesh=True)

    triage = sub.add_parser(
        "triage", help="batched crash triage on the device batch "
                       "(wtf_tpu/triage): minimize / distill / vbreak")
    tsub = triage.add_subparsers(dest="triage_cmd", required=True)

    tmin = tsub.add_parser(
        "minimize", help="bisect a crasher to a minimal reproducer of "
                         "the SAME crash bucket — thousands of in-graph "
                         "candidate reductions per dispatch")
    _add_target_selection(tmin)
    _add_paths(tmin)
    tmin.add_argument("--backend", choices=("tpu",), default="tpu")
    tmin.add_argument("--input", type=Path, required=True,
                      help="the crashing testcase")
    tmin.add_argument("--output", type=Path, default=None,
                      help="where the minimized reproducer lands "
                           "(default: <input>.min)")
    tmin.add_argument("--limit", type=int, default=0)
    tmin.add_argument("--lanes", type=int, default=64,
                      help="candidates per dispatch")
    tmin.add_argument("--max-rounds", type=int, default=64)
    _add_backend_tuning(tmin, mesh=True)

    tdis = tsub.add_parser(
        "distill", help="re-execute the corpus in one batched sweep, "
                        "compute exact per-testcase edge attribution "
                        "from the coverage bit-planes, and keep a "
                        "set-cover subset with identical aggregate "
                        "coverage (the exact-attribution minset)")
    _add_target_selection(tdis)
    _add_paths(tdis)
    tdis.add_argument("--backend", choices=("tpu",), default="tpu")
    tdis.add_argument("--from-checkpoint", type=Path, default=None,
                      metavar="DIR",
                      help="distill a campaign checkpoint's corpus "
                           "(wtf_tpu/resume dir) instead of inputs/")
    tdis.add_argument("--limit", type=int, default=0)
    tdis.add_argument("--lanes", type=int, default=64)
    _add_backend_tuning(tdis, mesh=True)

    tvb = tsub.add_parser(
        "vbreak", help="virtual-breakpoint replay: arm a breakpoint at "
                       "a RIP/icount and capture a register+memory "
                       "window per lane across (perturbed) replays")
    _add_target_selection(tvb)
    _add_paths(tvb)
    tvb.add_argument("--backend", choices=BACKENDS, default="tpu",
                     help="emu = the single-step oracle (debugging "
                          "convenience; one replay at a time)")
    tvb.add_argument("--input", type=Path, required=True,
                     help="testcase file or directory")
    tvb.add_argument("--break-at", required=True,
                     help="capture point: hex address, symbol, or "
                          "symbol+0xOFF")
    tvb.add_argument("--hit", type=int, default=1,
                     help="capture on the Nth arrival at the RIP")
    tvb.add_argument("--min-icount", type=int, default=0,
                     help="only capture once this many instructions "
                          "retired (arrivals before resume past the bp)")
    tvb.add_argument("--mem", default="",
                     help="memory window GVA:LEN (hex ok; default: 64 "
                          "bytes at rsp)")
    tvb.add_argument("--variants", type=int, default=0,
                     help="add N deterministic single-byte perturbations "
                          "per input to the sweep")
    tvb.add_argument("--out", type=Path, default=None,
                     help="write captures as JSON")
    tvb.add_argument("--limit", type=int, default=0)
    tvb.add_argument("--lanes", type=int, default=64)
    _add_backend_tuning(tvb, mesh=True)

    fleet = sub.add_parser(
        "fleet", help="fleet tier (wtf_tpu/fleet): elastic resharding, "
                      "the corpus/crash store, the thousand-client soak")
    fsub = fleet.add_subparsers(dest="fleet_cmd", required=True)

    fre = fsub.add_parser(
        "reshard", help="resume a checkpointed campaign under a "
                        "DIFFERENT --mesh-devices placement: coverage, "
                        "crash buckets, corpus and devmut streams are "
                        "placement-free, so the resumed run is "
                        "bit-identical to never having moved")
    _add_target_selection(fre)
    _add_paths(fre)
    fre.add_argument("--checkpoint", type=Path, required=True,
                     metavar="DIR",
                     help="the campaign checkpoint dir (PR-8 format) to "
                          "re-place; a running campaign writes one at "
                          "every batch boundary under --checkpoint-every")
    fre.add_argument("--runs", type=int, required=True,
                     help="total testcase budget to finish (the budget "
                          "is not part of the checkpoint)")
    fre.add_argument("--limit", type=int, default=0)
    fre.add_argument("--lanes", type=int, default=64,
                     help="must equal the checkpoint's lane count (the "
                          "lane count is the stream identity; "
                          "lanes-per-chip is what resharding changes)")
    fre.add_argument("--mutator",
                     choices=("auto", "byte", "mangle", "tlv",
                              "devmangle"), default="auto")
    fre.add_argument("--max_len", type=int, default=1024 * 1024)
    fre.add_argument("--seed", type=int, default=0)
    _add_backend_tuning(fre, mesh=True)

    fso = fsub.add_parser(
        "soak", help="the chaos soak (wtf_tpu/fleet/soak): N simulated "
                     "clients over the real wire with injected "
                     "resets/reclaims/frame drops; zero-lost + "
                     "serial-replay-parity + delta-ratio assertions")
    fso.add_argument("--clients", type=int, default=256)
    fso.add_argument("--runs-per-client", type=int, default=60)
    fso.add_argument("--seed", type=int, default=0xF1EE7)
    fso.add_argument("--threads", type=int, default=16)
    fso.add_argument("--min-ratio", type=float, default=10.0)

    ffs = fsub.add_parser(
        "fsck", help="verify (and with --repair, recover) a fleet "
                     "store: quarantine torn blobs, drop journal "
                     "entries whose blob vanished, journal orphans")
    ffs.add_argument("--store", type=Path, required=True, metavar="DIR")
    ffs.add_argument("--namespace", default="default")
    ffs.add_argument("--repair", action="store_true")

    status = sub.add_parser(
        "status", help="live campaign/fleet status: render the "
                       "atomically-refreshed status.json a running "
                       "campaign (--telemetry-dir) or master "
                       "(--telemetry-dir exports) maintains")
    status.add_argument("dir", type=Path,
                        help="the telemetry/export dir holding "
                             "status.json (a campaign's --telemetry-dir "
                             "or a master's)")
    status.add_argument("--json", action="store_true",
                        help="print the raw status document")
    status.add_argument("--watch", type=float, default=0.0, metavar="SECS",
                        help="re-render every SECS seconds until ^C "
                             "(0 = render once)")

    lint = sub.add_parser(
        "lint", help="graph-invariant static analysis of the hot-path "
                     "contracts (wtf_tpu/analysis; CPU-only, no chip)")
    lint.add_argument("--json", action="store_true",
                      help="machine-readable output (one JSON object)")
    lint.add_argument("--families", default=None,
                      help="comma list: dtype,budget,recompile,"
                           "mesh,supervise,telemetry,state,transfer,"
                           "thread,contracts (default: all)")
    lint.add_argument("--budgets", type=Path, default=None,
                      help="alternate budgets.json")
    lint.add_argument("--rebaseline", action="store_true",
                      help="rewrite the kernel-count budget file from the "
                           "current tree (record why in PERF.md).  "
                           "Ratcheted: refuses a total increase without "
                           "--allow-regression")
    lint.add_argument("--allow-regression", action="store_true",
                      help="let --rebaseline record a budget INCREASE "
                           "(conscious perf giveback; name it in PERF.md)")
    lint.add_argument("--telemetry-dir", type=Path, default=None,
                      help="write lint findings into events.jsonl")
    lint.add_argument("--deep", action="store_true",
                      help="run the transfer family's jaxpr host-transfer"
                           " census even without the budget family")
    lint.add_argument("--sarif", type=Path, default=None,
                      metavar="OUT.json",
                      help="also write findings as SARIF 2.1.0 (file:line"
                           " provenance as physical locations)")
    return parser


def _paths_from(args) -> TargetPaths:
    return TargetPaths(target=args.target, inputs=args.inputs,
                       outputs=args.outputs, crashes=args.crashes,
                       state=args.state).resolve()


def _lookup_target(args):
    load_builtin_targets()
    for module in args.target_module:
        importlib.import_module(module)
    return Targets.instance().get(args.name)


@contextmanager
def _telemetry_for(args):
    """One registry + one event sink per CLI invocation, wired into the
    backend, the campaign driver, and the heartbeat — the 'unified'
    in unified telemetry.  A fresh Registry (not the process-global one)
    so repeated in-process invocations don't bleed counters.  Context
    manager so the `JSONL always ends with run-end` invariant is
    structural: run-start on entry, run-end + close on ANY exit —
    including a failed backend build."""
    registry = Registry()
    events = open_event_log(getattr(args, "telemetry_dir", None))
    trace_out = getattr(args, "trace_out", None)
    collector = None
    if trace_out is not None:
        # --trace-out: every span becomes a Chrome-trace complete event
        # via the registry's span collector, and every point event (the
        # JSONL records minus the bulky heartbeat/run-start/run-end)
        # becomes an instant mark on the same timeline
        from wtf_tpu.telemetry import TapEventLog, TraceCollector

        collector = TraceCollector()
        registry.spans.collector = collector

        def _instant(type_, fields):
            if type_ in ("heartbeat", "run-start", "run-end"):
                return
            collector.instant(type_, {
                k: v for k, v in fields.items()
                if isinstance(v, (str, int, float, bool))})

        events = TapEventLog(events, _instant)
    events.emit("run-start", subcommand=args.subcommand,
                name=getattr(args, "name", None),
                backend=getattr(args, "backend", None),
                argv=getattr(args, "_argv", None))
    try:
        yield registry, events
    finally:
        events.emit("run-end", metrics=registry.dump())
        if collector is not None:
            try:
                n = collector.write(trace_out)
                print(f"trace: {n} events -> {trace_out}")
            except OSError as e:
                logging.getLogger("wtf_tpu").warning(
                    "trace write failed: %s", e)
        events.close()


def _image_sharding(mesh_devices: Optional[int]):
    """Where a campaign's image lies: on the default device, or replicated
    over the mesh that `--mesh-devices` makes (0: every device)."""
    if mesh_devices is None:
        return None
    from wtf_tpu.meshrun.mesh import make_mesh, replicated_sharding

    return replicated_sharding(make_mesh(mesh_devices))


def _build_backend(target, backend_name: str, paths: TargetPaths,
                   limit: int, lanes: int, registry=None, events=None,
                   tuning: Optional[dict] = None):
    from wtf_tpu.backend import create_backend
    from wtf_tpu.snapshot.loader import load_snapshot

    registry = registry if registry is not None else Registry()
    spans = registry.spans
    with spans.span("snapshot-load"):
        if paths.state and Path(paths.state).exists():
            snapshot = load_snapshot(paths.state, spans=spans)
        elif target.snapshot is not None:
            # a target's own factory fills the packed image in place:
            # there is no `pack` beside it
            with spans.span("synthesize"):
                snapshot = target.snapshot()
        else:
            raise SystemExit(
                f"target {target.name!r} has no snapshot factory and no "
                f"--state dir was given")
        physmem = snapshot.physmem
        physmem.pull_counter = registry.counter("physmem.device_pulls")
        if backend_name == "tpu":
            # the oracle backend reads the host arrays and never builds
            # the device image; under --mesh-devices every chip's copy
            # goes up here, from the host rows, as the one replicated
            # array the runner takes
            mesh = (tuning or {}).get("mesh_devices")
            with spans.span("upload") as sp:
                sp.fence(physmem.upload(_image_sharding(mesh)))
            registry.gauge("image.replicas").set(physmem.replicas)
        for name, value in (("bytes", physmem.nbytes),
                            ("frames_present", physmem.frames_present),
                            ("span_frames", len(physmem.table)),
                            ("rows", len(physmem.rows))):
            registry.gauge(f"image.{name}").set(value)
    # engine tuning (--burst-any-tier etc.) applies to the batched
    # tpu backend only; the oracle backend has no runner underneath
    kwargs = ({"n_lanes": lanes, **(tuning or {})}
              if backend_name == "tpu" else {})
    backend = create_backend(backend_name, snapshot, limit=limit,
                             registry=registry, events=events, **kwargs)
    with registry.spans.span("init"):
        backend.initialize()
    return backend


def _minset_seed_walk(paths: TargetPaths, corpus):
    """The ONE minset measurement walk shared by `campaign --runs 0`
    and `triage distill`: a single scan over inputs/ AND any prior
    outputs/ feeds `corpus` (shared size-sorted replay ordering;
    add_digested dedups) and snapshots outputs/ (pre-dedup census) so
    it can end as exactly the kept subset of what was measured.
    Returns the [(path, digest)] outputs snapshot."""
    from wtf_tpu.fuzz.corpus import seed_paths

    out_dir = Path(paths.outputs) if paths.outputs else None
    outputs_snapshot = []
    for p, digest, data in seed_paths([paths.inputs, paths.outputs],
                                      with_data=True, keep_dups=True):
        corpus.add_digested(data, digest)
        if out_dir and p.parent == out_dir:
            outputs_snapshot.append((p, digest))
    return outputs_snapshot


def _prune_outputs(outputs_snapshot, kept) -> None:
    """outputs/ ends as exactly the kept subset of what was measured:
    every snapshot file's content was replayed (directly or via a
    content-identical twin), so prune by content digest.  Files that
    appeared after the walk were never measured and stay untouched."""
    for p, digest in outputs_snapshot:
        if not (digest in kept.digests and p.name == digest):
            p.unlink(missing_ok=True)


def _mutator_for(target, rng: random.Random, max_len: int):
    from wtf_tpu.fuzz.native_mutator import best_mangle_mutator

    if target.create_mutator is not None:
        return target.create_mutator(rng, max_len)
    return best_mangle_mutator(rng, max_len)


# ---------------------------------------------------------------------------
# subcommand drivers (subcommands.cc:16-101)
# ---------------------------------------------------------------------------

def cmd_run(args) -> int:
    from wtf_tpu.dist.client import run_testcase_and_restore

    opts = RunOptions(name=args.name, backend=args.backend,
                      input=args.input, limit=args.limit, runs=args.runs,
                      trace_path=args.trace_path,
                      trace_type=args.trace_type, lanes=args.lanes,
                      paths=_paths_from(args))
    target = _lookup_target(args)
    crashes = 0
    with _telemetry_for(args) as (registry, events):
        backend = _build_backend(target, opts.backend, opts.paths,
                                 opts.limit, opts.lanes,
                                 registry=registry, events=events,
                                 tuning=_backend_tuning_kwargs(args))
        target.init(backend)

        inputs: List[Path] = (
            sorted(p for p in opts.input.iterdir() if p.is_file())
            if opts.input.is_dir() else [opts.input])
        trace_dir = (opts.trace_path
                     if opts.trace_path and len(inputs) > 1 else None)
        if trace_dir:
            trace_dir.mkdir(parents=True, exist_ok=True)

        for path in inputs:
            data = path.read_bytes()
            for _ in range(max(opts.runs, 1)):
                if opts.trace_path:
                    trace_file = (trace_dir / f"{path.name}.trace"
                                  if trace_dir else opts.trace_path)
                    backend.set_trace_file(trace_file, opts.trace_type)
                result, coverage = run_testcase_and_restore(
                    backend, target, data)
                if isinstance(result, Crash):
                    crashes += 1
                    events.emit("crash", name=result.name,
                                input=path.name)
                print(f"{path.name}: {result} (|cov| = {len(coverage)})")
        backend.print_run_stats()
        if args.coverage is not None:
            from wtf_tpu.utils.covfiles import parse_cov_files

            wanted = parse_cov_files(args.coverage)
            covered = backend.aggregate_coverage() & wanted
            print(f"coverage: {len(covered)}/{len(wanted)} "
                  f"listed basic blocks hit")
    return 0 if crashes == 0 else 2


def cmd_fuzz(args) -> int:
    from wtf_tpu.dist.client import BatchClient, Client

    opts = FuzzOptions(name=args.name, backend=args.backend,
                       limit=args.limit, address=args.address,
                       seed=args.seed, lanes=args.lanes,
                       mesh_devices=args.mesh_devices,
                       max_retry_secs=args.max_retry_secs,
                       cov_delta=not args.no_cov_delta,
                       paths=_paths_from(args))
    target = _lookup_target(args)
    with _telemetry_for(args) as (registry, events):
        backend = _build_backend(target, opts.backend, opts.paths,
                                 opts.limit, opts.lanes,
                                 registry=registry, events=events,
                                 tuning=_backend_tuning_kwargs(args))
        if opts.backend == "tpu":
            node = BatchClient(backend, target, opts.address, mux=args.mux,
                               registry=registry, events=events,
                               print_stats=True,
                               max_retry_secs=opts.max_retry_secs,
                               wire_v1=args.wire_v1,
                               cov_delta=opts.cov_delta)
        else:
            node = Client(backend, target, opts.address,
                          registry=registry, events=events,
                          print_stats=True,
                          max_retry_secs=opts.max_retry_secs,
                          wire_v1=args.wire_v1,
                          cov_delta=opts.cov_delta)
        served = node.run()
    print(f"node served {served} testcases")
    return 0


def cmd_master(args) -> int:
    from wtf_tpu.dist.server import Server
    from wtf_tpu.fuzz.corpus import Corpus

    opts = MasterOptions(name=args.name, address=args.address,
                         runs=args.runs, max_len=args.max_len,
                         seed=args.seed,
                         reclaim_timeout=args.reclaim_timeout,
                         store=args.store, paths=_paths_from(args))
    target = _lookup_target(args)
    with _telemetry_for(args) as (registry, events):
        rng = random.Random(opts.seed or None)
        store = None
        if opts.store:
            from wtf_tpu.fleet.store import FleetStore

            store = FleetStore(opts.store, registry=registry,
                               events=events)
        corpus = Corpus(outputs_dir=opts.paths.outputs, rng=rng,
                        store=store)
        coverage_path = (Path(opts.paths.target) / "coverage.cov"
                         if opts.paths.target else None)
        server = Server(opts.address,
                        _mutator_for(target, rng, opts.max_len),
                        corpus, inputs_dir=opts.paths.inputs,
                        crashes_dir=opts.paths.crashes, runs=opts.runs,
                        max_len=opts.max_len, print_stats=True,
                        coverage_path=coverage_path,
                        registry=registry, events=events,
                        reclaim_timeout=opts.reclaim_timeout,
                        store=store, telemetry_dir=args.telemetry_dir)
        stats = server.run()
    print(server.stats.line(len(server.coverage), len(corpus), 0))
    if server.drained:
        # SIGTERM drain: state persisted, nodes notified — a supervisor
        # restarting the master must read this as a clean stop
        print("master drained (state persisted)")
        return 0
    return 0 if stats.crashes == 0 else 2


def cmd_campaign(args) -> int:
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.fuzz.loop import FuzzLoop

    opts = CampaignOptions(name=args.name, backend=args.backend,
                           limit=args.limit, runs=args.runs,
                           max_len=args.max_len, seed=args.seed,
                           lanes=args.lanes, mutator=args.mutator,
                           mesh_devices=args.mesh_devices,
                           stop_on_crash=args.stop_on_crash,
                           checkpoint_every=args.checkpoint_every,
                           checkpoint_dir=args.checkpoint_dir,
                           resume=args.resume, store=args.store,
                           megachunk=args.megachunk,
                           paths=_paths_from(args))
    # checkpoint dir defaulting: explicit flag > the resume dir (a
    # resumed campaign keeps checkpointing in place) > <target>/checkpoint
    ckpt_dir = opts.checkpoint_dir or opts.resume
    if ckpt_dir is None and opts.checkpoint_every and opts.paths.target:
        ckpt_dir = Path(opts.paths.target) / "checkpoint"
    if opts.checkpoint_every and ckpt_dir is None:
        raise SystemExit("--checkpoint-every needs --checkpoint-dir "
                         "(or --target to default one under)")
    if opts.resume and opts.runs == 0:
        raise SystemExit("--resume applies to fuzz campaigns "
                         "(--runs > 0); minset replays are stateless")
    if args.coordinator or args.num_processes:
        # multi-host launch: join the jax distributed runtime first (DCN
        # coordination; tests/test_parallel.py exercises the same path on
        # 2 CPU processes).  Each host then drives its local chips; the
        # global mesh is available to sharded execution paths
        # (wtf_tpu/meshrun), and cross-host work distribution rides the
        # TCP master plane exactly like separate pods.
        from wtf_tpu.meshrun.mesh import init_multihost

        init_multihost(coordinator=args.coordinator,
                       num_processes=args.num_processes,
                       process_id=args.process_id)
    target = _lookup_target(args)
    with _telemetry_for(args) as (registry, events):
        backend = _build_backend(target, opts.backend, opts.paths,
                                 opts.limit, opts.lanes,
                                 registry=registry, events=events,
                                 tuning=_backend_tuning_kwargs(args))
        target.init(backend)
        rng = random.Random(opts.seed or None)
        store = None
        if opts.store:
            from wtf_tpu.fleet.store import FleetStore

            store = FleetStore(opts.store, registry=registry,
                               events=events)
        # minset (--runs=0) fills its corpus from ONE merged scan below
        # (no double read of inputs/); fuzz mode loads inputs and
        # persists coverage-increasing finds into outputs/
        if opts.runs == 0:
            corpus = Corpus(rng=rng)
        elif opts.paths.inputs and Path(opts.paths.inputs).is_dir():
            corpus = Corpus.load_dir(opts.paths.inputs, rng=rng,
                                     outputs_dir=opts.paths.outputs)
            corpus.store = store
        else:
            corpus = Corpus(outputs_dir=opts.paths.outputs, rng=rng,
                            store=store)
        if opts.runs and not len(corpus) and target.seeds is not None:
            # a self-contained target brings its seed corpus as it brings
            # its snapshot; inputs/ wins wherever it holds a file
            for data in target.seeds():
                corpus.add(data)
        from wtf_tpu.fuzz.mutator import create_mutator

        mutator = (_mutator_for(target, rng, opts.max_len)
                   if opts.mutator == "auto"
                   else create_mutator(opts.mutator, rng, opts.max_len))
        loop = FuzzLoop(backend, target, mutator,
                        corpus, crashes_dir=opts.paths.crashes,
                        registry=registry, events=events,
                        checkpoint_dir=ckpt_dir,
                        checkpoint_every=opts.checkpoint_every,
                        store=store, megachunk=opts.megachunk,
                        xprof_dir=args.xprof_dir,
                        xprof_seconds=args.xprof_seconds,
                        xprof_seed=opts.seed)
        loop.run_first(corpus)      # a resume restores the queue it saved
        if opts.resume:
            from wtf_tpu.resume import load_campaign, restore_campaign

            state, fell_back = load_campaign(opts.resume)
            batch = restore_campaign(loop, state, opts.resume)
            note = " (newest torn; resumed from .prev)" if fell_back else ""
            print(f"resumed at batch {batch}: "
                  f"{loop.stats.testcases} testcases, "
                  f"{len(corpus)} corpus entries{note}")
        if opts.runs == 0:
            # reference semantics (server.h:552-556): replay the seeds —
            # plus any prior campaign's outputs/, so a corpus can minimize
            # itself — and leave outputs/ holding exactly the
            # coverage-minimal subset (walk + prune shared with
            # `triage distill`)
            outputs_snapshot = _minset_seed_walk(opts.paths, corpus)
            kept = loop.minset(opts.paths.outputs, print_stats=True)
            _prune_outputs(outputs_snapshot, kept)
            print(loop.stats.line(len(corpus), loop._coverage()))
            print(f"minset: kept {len(kept)}/{len(corpus)} seeds")
            return 0 if loop.stats.crashes == 0 else 2
        stats = loop.fuzz(runs=opts.runs, print_stats=True,
                          stop_on_crash=opts.stop_on_crash)
        print(stats.line(len(corpus), loop._coverage()))
        return 0 if stats.crashes == 0 else 2


def cmd_sched(args) -> int:
    from wtf_tpu.tenancy.sched import Scheduler, load_jobs

    load_builtin_targets()
    for module in args.target_module:
        importlib.import_module(module)
    jobs = load_jobs(args.jobs)
    tuning = _backend_tuning_kwargs(args)
    mesh_devices = tuning.pop("mesh_devices", None)
    with _telemetry_for(args) as (registry, events):
        store = None
        if args.store:
            from wtf_tpu.fleet.store import FleetStore

            store = FleetStore(args.store, registry=registry,
                               events=events)
        sched = Scheduler(jobs, n_lanes=args.lanes, workdir=args.workdir,
                          limit=args.limit, quantum=args.quantum,
                          mesh_devices=mesh_devices,
                          registry=registry, events=events,
                          backend_tuning=tuning, store=store)
        summary = sched.run(max_rounds=args.max_rounds)
    crashes = 0
    for name, s in summary.items():
        crashes += s["crashes"]
        state = ("done" if s["done"]
                 else f"stopped at batch {s['batches']}")
        print(f"[sched] {name}: {state}, {s['testcases']} testcases, "
              f"{s['crashes']} crashes, {s['preemptions']} preemptions")
    print(f"[sched] {sched.rounds} rounds over {args.lanes} lanes")
    return 0 if crashes == 0 else 2


def _parse_break_at(spec: str, symbols: dict) -> int:
    """hex address, symbol, or symbol+0xOFF over the snapshot's symbol
    store (the reference resolves bp sites the same way, backend.cc:
    214-239)."""
    base, _, off = spec.partition("+")
    offset = int(off, 0) if off else 0
    try:
        return int(base, 0) + offset
    except ValueError:
        pass
    if base in symbols:
        return int(symbols[base]) + offset
    raise SystemExit(
        f"--break-at {spec!r}: not an address and not in the symbol "
        f"store ({len(symbols)} symbols; e.g. {sorted(symbols)[:4]})")


def _triage_inputs(path: Path) -> List[tuple]:
    """[(name, bytes)] for a testcase file or directory."""
    if path.is_dir():
        return [(p.name, p.read_bytes())
                for p in sorted(p for p in path.iterdir() if p.is_file())]
    return [(path.name, path.read_bytes())]


def cmd_triage(args) -> int:
    """`wtf-tpu triage {minimize,distill,vbreak}` — the batched triage
    engine (wtf_tpu/triage): replay variants at campaign throughput on
    the same hardware, mesh-sharded under --mesh-devices."""
    opts = TriageOptions(
        name=args.name, cmd=args.triage_cmd, backend=args.backend,
        input=getattr(args, "input", None),
        output=getattr(args, "output", None),
        limit=args.limit, lanes=args.lanes,
        mesh_devices=getattr(args, "mesh_devices", None),
        max_rounds=getattr(args, "max_rounds", 64),
        from_checkpoint=getattr(args, "from_checkpoint", None),
        break_at=getattr(args, "break_at", ""),
        hit=getattr(args, "hit", 1),
        min_icount=getattr(args, "min_icount", 0),
        mem=getattr(args, "mem", ""), variants=getattr(args, "variants", 0),
        out=getattr(args, "out", None), paths=_paths_from(args))
    target = _lookup_target(args)
    with _telemetry_for(args) as (registry, events):
        backend = _build_backend(target, opts.backend, opts.paths,
                                 opts.limit, opts.lanes,
                                 registry=registry, events=events,
                                 tuning=_backend_tuning_kwargs(args))
        target.init(backend)
        driver = {"minimize": _triage_minimize, "distill": _triage_distill,
                  "vbreak": _triage_vbreak}[opts.cmd]
        return driver(opts, backend, target, registry, events)


def _triage_minimize(opts, backend, target, registry, events) -> int:
    from wtf_tpu.triage import minimize

    crasher = opts.input.read_bytes()
    try:
        result = minimize(backend, target, crasher,
                          registry=registry, events=events,
                          max_rounds=opts.max_rounds)
    except ValueError as e:
        print(f"minimize: {e}")
        return 1
    out = opts.output or opts.input.with_name(opts.input.name + ".min")
    from wtf_tpu.utils.atomicio import atomic_write_bytes

    atomic_write_bytes(out, result.data)
    print(f"minimize: {result.from_len} -> {len(result.data)} bytes "
          f"(bucket {result.bucket}; {result.rounds} rounds, "
          f"{result.dispatches} dispatches, {result.candidates} "
          f"candidates, {result.simplified} bytes zeroed)")
    print(f"wrote {out}")
    return 0


def _triage_distill(opts, backend, target, registry, events) -> int:
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.triage import distill

    out_dir = Path(opts.paths.outputs) if opts.paths.outputs else None
    outputs_snapshot: List[tuple] = []
    if opts.from_checkpoint:
        # checkpoint-aware input: the campaign checkpoint's corpus, in
        # manifest order with digests verified (wtf_tpu/resume).  The
        # checkpoint is the measurement domain — pre-existing outputs/
        # files were not measured and stay untouched.
        from wtf_tpu.resume import load_campaign
        from wtf_tpu.resume.checkpoint import restore_corpus

        state, _ = load_campaign(opts.from_checkpoint)
        source = Corpus()
        restore_corpus(source, state, opts.from_checkpoint)
    else:
        # the campaign --runs 0 measurement walk + prune, shared with
        # cmd_campaign so minset and distill can never drift on which
        # outputs/ files they delete
        source = Corpus()
        outputs_snapshot = _minset_seed_walk(opts.paths, source)
    if not len(source):
        raise SystemExit("distill found no seeds (--inputs/--target "
                         "dirs, or --from-checkpoint)")
    testcases = list(source)
    result = distill(backend, target, testcases,
                     registry=registry, events=events)
    kept = Corpus(outputs_dir=out_dir)
    for idx in result.keep:
        kept.add(testcases[idx])
    _prune_outputs(outputs_snapshot, kept)
    crashes = registry.counter("triage.crashes").value
    print(f"distill: kept {len(result.keep)}/{len(testcases)} seeds "
          f"(exact cover, {result.kept_bits}/{result.total_bits} bits; "
          f"prefix minset would keep {len(result.prefix_keep)}; "
          f"{registry.counter('triage.dispatches').value} dispatches, "
          f"{crashes} crashes)")
    if out_dir:
        print(f"wrote minset to {out_dir}")
    return 0


def _triage_vbreak(opts, backend, target, registry, events) -> int:
    import json

    from wtf_tpu.triage import oracle_capture, perturbations, vbreak
    from wtf_tpu.triage.bucket import TOS_BYTES

    rip = _parse_break_at(opts.break_at, getattr(backend, "symbols", {}))
    mem_gva, mem_len = None, TOS_BYTES
    if opts.mem:
        try:
            gva_s, _, len_s = opts.mem.partition(":")
            mem_gva = int(gva_s, 0)
            mem_len = int(len_s, 0) if len_s else TOS_BYTES
        except ValueError:
            raise SystemExit(f"--mem {opts.mem!r}: expected GVA[:LEN] "
                             "(hex ok, e.g. 0x7fffe000:128)")
    named = _triage_inputs(opts.input)
    testcases = []
    for _, data in named:
        testcases.extend(perturbations(data, opts.variants + 1))
    try:
        if opts.backend == "emu":
            captures = [
                oracle_capture(backend, target, data, rip, index=i,
                               hit=opts.hit, min_icount=opts.min_icount,
                               mem_gva=mem_gva, mem_len=mem_len)
                for i, data in enumerate(testcases)]
        else:
            captures, _results = vbreak(
                backend, target, testcases, rip, hit=opts.hit,
                min_icount=opts.min_icount, mem_gva=mem_gva,
                mem_len=mem_len, registry=registry, events=events)
    except ValueError as e:
        # e.g. the target's init already owns the breakpoint — the
        # same clean one-liner the minimize subcommand gives
        print(f"vbreak: {e}")
        return 1
    got = [c for c in captures if c is not None]
    print(f"vbreak: {len(got)}/{len(testcases)} replays captured at "
          f"{rip:#x} (hit {opts.hit})")
    for c in got:
        print(f"  #{c.index} icount={c.icount} rip={c.rip:#x} "
              f"rsp={c.gpr[4]:#x} rax={c.gpr[0]:#x} "
              f"mem[{len(c.mem)}]@{c.mem_gva:#x}={c.mem[:16].hex()}")
    if opts.out:
        opts.out.write_text(json.dumps(
            [c.as_dict() if c else None for c in captures], indent=1))
        print(f"wrote {opts.out}")
    return 0


def cmd_fleet(args) -> int:
    """`wtf-tpu fleet {reshard,soak,fsck}` — the fleet tier
    (wtf_tpu/fleet)."""
    if args.fleet_cmd == "soak":
        import tempfile

        from wtf_tpu.fleet.soak import run_soak

        logging.getLogger("wtf_tpu").setLevel(logging.ERROR)
        with tempfile.TemporaryDirectory() as tmp:
            report = run_soak(tmp, clients=args.clients,
                              runs_per_client=args.runs_per_client,
                              seed=args.seed, threads=args.threads,
                              min_ratio=args.min_ratio)
        import json

        print(json.dumps(report, indent=1))
        print(f"fleet soak PASS ({report['clients']} clients, zero "
              f"lost, delta {report['delta_ratio']}x smaller)")
        return 0
    if args.fleet_cmd == "fsck":
        from wtf_tpu.fleet.store import FleetStore

        store = FleetStore(args.store, namespace=args.namespace)
        report = store.verify(repair=args.repair)
        print(f"fsck {args.store}/{args.namespace}: "
              f"{report['ok']}/{report['blobs']} blobs ok, "
              f"{len(report['torn'])} torn, "
              f"{len(report['missing'])} missing, "
              f"{len(report['orphans'])} orphan(s)"
              + (" — repaired" if args.repair else ""))
        broken = report["torn"] or report["missing"] or report["orphans"]
        return 0 if (args.repair or not broken) else 1
    return _fleet_reshard(args)


def _fleet_reshard(args) -> int:
    """Resume a checkpointed campaign under a different --mesh-devices
    placement (wtf_tpu/fleet/elastic).  Checkpoints are placement-free
    and devmut streams are shard-count invariant, so the resumed run is
    bit-identical to one that never moved."""
    import random as _random

    from wtf_tpu.config import FleetOptions
    from wtf_tpu.fleet.elastic import describe_checkpoint, run_elastic, \
        validate_placement
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.fuzz.loop import FuzzLoop
    from wtf_tpu.fuzz.mutator import create_mutator
    from wtf_tpu.resume import CheckpointError, load_campaign

    opts = FleetOptions(name=args.name, checkpoint=args.checkpoint,
                        mesh_devices=args.mesh_devices, runs=args.runs,
                        limit=args.limit, lanes=args.lanes,
                        mutator=args.mutator, max_len=args.max_len,
                        seed=args.seed, paths=_paths_from(args))
    try:
        info = describe_checkpoint(opts.checkpoint)
        state, _ = load_campaign(opts.checkpoint)
        validate_placement(state, opts.mesh_devices)
    except (CheckpointError, ValueError) as e:
        print(f"reshard: {e}")
        return 1
    cfg = info["config"]
    print(f"reshard: checkpoint at batch {info['batches']} "
          f"({cfg.get('lanes')} lanes on "
          f"{cfg.get('mesh_devices') or 1} device(s), "
          f"{info['corpus']} corpus entries) -> "
          f"{opts.mesh_devices or 1} device(s)")
    target = _lookup_target(args)
    with _telemetry_for(args) as (registry, events):
        tuning = _backend_tuning_kwargs(args)
        tuning.pop("mesh_devices", None)

        def build_loop(mesh_devices):
            build = dict(tuning)
            if mesh_devices is not None:
                build["mesh_devices"] = mesh_devices
            backend = _build_backend(target, "tpu", opts.paths,
                                     opts.limit, opts.lanes,
                                     registry=registry, events=events,
                                     tuning=build)
            target.init(backend)
            rng = _random.Random(opts.seed or None)
            corpus = Corpus(outputs_dir=opts.paths.outputs, rng=rng)
            mutator = (_mutator_for(target, rng, opts.max_len)
                       if opts.mutator == "auto"
                       else create_mutator(opts.mutator, rng,
                                           opts.max_len))
            return FuzzLoop(backend, target, mutator, corpus,
                            crashes_dir=opts.paths.crashes,
                            registry=registry, events=events,
                            checkpoint_dir=opts.checkpoint,
                            checkpoint_every=1)
        loop = run_elastic(build_loop, opts.runs, opts.checkpoint,
                           start_devices=opts.mesh_devices, resume=True,
                           print_stats=True)
        print(loop.stats.line(len(loop.corpus), loop._coverage()))
        return 0 if loop.stats.crashes == 0 else 2


def cmd_lint(args) -> int:
    """`wtf-tpu lint`: the graph-invariant linter (wtf_tpu/analysis),
    telemetry-wired like every other subcommand — findings land in the
    registry (`analysis.*`) and the JSONL stream."""
    from wtf_tpu.analysis import lint_main

    families = args.families.split(",") if args.families else None
    with _telemetry_for(args) as (registry, events):
        return lint_main(families=families, budgets=args.budgets,
                         rebaseline=args.rebaseline,
                         allow_regression=args.allow_regression,
                         as_json=args.json, deep=args.deep,
                         sarif=str(args.sarif) if args.sarif else None,
                         registry=registry, events=events)


def _derived_status_rows(metrics: dict) -> List[str]:
    """The operator-facing derived lines shared by campaign and fleet
    status: each row appears only when its subsystem actually ran, so a
    plain campaign renders just the heartbeat line."""
    rows: List[str] = []

    def val(name, default=0):
        v = metrics.get(name, default)
        return v if isinstance(v, (int, float)) else default

    windows = val("megachunk.windows")
    if windows:
        zh = val("devdec.zero_host_windows")
        rows.append(f"zero-host windows: {zh}/{windows} "
                    f"({zh / windows:.0%})")
        prelaunched = val("megachunk.prelaunched")
        if prelaunched:
            rows.append(f"prelaunch: "
                        f"{val('megachunk.prelaunch_hits')}/{prelaunched}"
                        f" adopted, {val('megachunk.prelaunch_dropped')}"
                        f" dropped")
    phase = metrics.get("phase.seconds") or {}
    if isinstance(phase, dict) and phase:
        from wtf_tpu.telemetry.spans import device_seconds_by_phase

        top = sum(s for p, s in phase.items() if "/" not in p)
        dev = sum(device_seconds_by_phase(phase).values())
        if top:
            rows.append(f"host share: "
                        f"{max(top - dev, 0.0) / top:.1%} of "
                        f"accounted wall")
    if val("supervise.dispatches"):
        rows.append(f"supervisor: rung {val('supervise.rung')}, "
                    f"{val('supervise.rebuilds')} rebuilds, "
                    f"{val('supervise.quarantined_lanes')} lanes "
                    f"quarantined")
    delta = val("dist.cov_bytes_delta")
    bitmap = val("dist.cov_bytes_bitmap")
    if delta and bitmap:
        rows.append(f"delta frames: {bitmap - delta} cov bytes saved "
                    f"({bitmap / delta:.1f}x smaller)")
    tenants = sorted({name.split(".")[1] for name in metrics
                      if name.startswith("tenant.")
                      and len(name.split(".")) >= 3})
    for t in tenants:
        rows.append(f"tenant {t}: "
                    f"execs={metrics.get(f'tenant.{t}.testcases', 0) or 0}"
                    f" newcov="
                    f"{metrics.get(f'tenant.{t}.new_coverage', 0) or 0}"
                    f" crashes="
                    f"{metrics.get(f'tenant.{t}.crashes', 0) or 0}")
    return rows


def _render_status(doc: dict) -> None:
    age = max(time.time() - float(doc.get("ts", 0) or 0), 0.0)
    if doc.get("kind") == "fleet":
        print(f"fleet: {doc.get('nodes', 0)} node(s), "
              f"{doc.get('frames', 0)} telem frames "
              f"({doc.get('duplicates_dropped', 0)} duplicates dropped), "
              f"as of {age:.0f}s ago")
        for row in doc.get("per_node", []):
            print(f"  {row.get('node', '?')[:12]:<12} "
                  f"seq={row.get('seq')}/e{row.get('epoch')} "
                  f"execs={row.get('testcases')} "
                  f"({row.get('execs_per_s')}/s) "
                  f"crash={row.get('crashes')} "
                  f"newcov={row.get('new_coverage')}")
    else:
        print(f"campaign: batch {doc.get('batches', 0)}, "
              f"as of {age:.0f}s ago")
        if doc.get("line"):
            print(f"  {doc['line']}")
    for row in _derived_status_rows(doc.get("metrics") or {}):
        print(f"  {row}")


def cmd_status(args) -> int:
    """`wtf-tpu status <dir>`: render the status.json a running campaign
    (FuzzLoop._write_status, every heartbeat) or fleet master
    (FleetTelemetry.write_exports, every persistence interval) refreshes
    atomically — readers always see a complete document."""
    import json

    path = args.dir / "status.json"
    while True:
        try:
            doc = json.loads(path.read_text())
        except FileNotFoundError:
            print(f"status: no status.json under {args.dir} — is the "
                  f"campaign/master running with --telemetry-dir?")
            return 1
        except ValueError:
            doc = None  # mid-rotation torn read: keep the last render
        if doc is not None:
            if args.json:
                print(json.dumps(doc))
            else:
                if args.watch:
                    print("\x1b[2J\x1b[H", end="")
                _render_status(doc)
        if not args.watch:
            return 0
        try:
            time.sleep(args.watch)
        except KeyboardInterrupt:
            return 0


def cmd_snapshot(args) -> int:
    """Format conversion: the bdump-side tooling the reference leaves to
    external scripts.  npz <-> Windows crash dump both ways."""
    import json

    from wtf_tpu.snapshot.kdmp import write_kdmp
    from wtf_tpu.snapshot.loader import dump_cpu_state_json, load_snapshot

    snap = load_snapshot(args.state)
    args.out.mkdir(parents=True, exist_ok=True)
    if args.format == "npz":
        snap.save_raw(args.out)
    else:
        write_kdmp(args.out / "mem.dmp", snap.pages_dict(),
                   dump_type="bmp" if args.format == "dmp-bmp" else "full",
                   dtb=snap.cpu.cr3, cpu=snap.cpu)
        (args.out / "regs.json").write_text(dump_cpu_state_json(snap.cpu))
        (args.out / "symbol-store.json").write_text(json.dumps(
            {k: hex(v) for k, v in snap.symbols.items()}, indent=1))
    n_pages = snap.physmem.frames_present
    print(f"wrote {args.format} snapshot ({n_pages} pages) to {args.out}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    # Operational failures (crash-save, coverage-write, malformed-frame —
    # the bare-print replacements) go through `logging`; a message-only
    # handler on stdout keeps them stream-stable with the prints they
    # replaced.  Scoped to the wtf_tpu logger, NOT the root logger:
    # third-party WARNINGs (jax/absl) must not leak bare into the
    # parseable stdout stream.  Heartbeat lines themselves stay print()
    # (CampaignStats.maybe_heartbeat) so they reach stdout even without
    # this config.  Handlers are rebound to the CURRENT stdout on every
    # invocation (pytest capture swaps streams between in-process calls).
    handler = logging.StreamHandler(sys.stdout)
    handler.setFormatter(logging.Formatter("%(message)s"))
    wlog = logging.getLogger("wtf_tpu")
    wlog.handlers[:] = [handler]
    wlog.setLevel(logging.INFO)
    wlog.propagate = False
    args = build_parser().parse_args(argv)
    # the argv actually parsed (programmatic main(argv) included) — the
    # provenance recorded in the run-start telemetry event
    args._argv = list(argv) if argv is not None else sys.argv[1:]
    driver = {
        "run": cmd_run,
        "fuzz": cmd_fuzz,
        "master": cmd_master,
        "campaign": cmd_campaign,
        "sched": cmd_sched,
        "snapshot": cmd_snapshot,
        "triage": cmd_triage,
        "fleet": cmd_fleet,
        "lint": cmd_lint,
        "status": cmd_status,
    }[args.subcommand]
    return driver(args)


def console_main() -> None:
    """setuptools console-script entry (`wtf-tpu ...`)."""
    try:
        sys.exit(main())
    except BrokenPipeError:
        # `wtf-tpu status --json | head` closed the pipe: normal
        # operator usage, not an error
        sys.exit(0)


if __name__ == "__main__":
    console_main()
