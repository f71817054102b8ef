"""Unified telemetry subsystem tests (wtf_tpu/telemetry/ + the device
counter block): registry counter/label semantics, span fencing, JSONL
schema round-trip, device-counter vs oracle differentials, campaign
wall-clock accounting, and the report tool."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from wtf_tpu.backend import create_backend
from wtf_tpu.backend.emu import EmuBackend
from wtf_tpu.core.results import Crash, Ok
from wtf_tpu.dist.client import run_testcase_and_restore
from wtf_tpu.harness import demo_tlv
from wtf_tpu.interp.machine import (
    CTR_DECODE_MISS, CTR_INSTR, CTR_MEM_FAULT, CTR_SWEPT, N_CTRS,
)
from wtf_tpu.telemetry import (
    EventLog, NULL, Registry, StatsDict, get_registry, open_event_log,
    read_events,
)

from test_harness import BENIGN, OVERFLOW

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    reg = Registry()
    c = reg.counter("x.count")
    c.inc()
    c.inc(5)
    assert c.value == 6
    assert reg.counter("x.count") is c  # idempotent registration
    g = reg.gauge("x.depth")
    g.set(3)
    g.set(2)
    assert g.value == 2
    h = reg.histogram("x.lat")
    for v in (0.5, 1.5, 1.0):
        h.observe(v)
    d = h.dump()
    assert d == {"count": 3, "sum": 3.0, "min": 0.5, "max": 1.5}
    with pytest.raises(TypeError):
        reg.gauge("x.count")  # type mismatch on re-registration


def test_labeled_children_semantics():
    reg = Registry()
    c = reg.counter("fallbacks")
    c.labels("ssefp").inc(3)
    c.labels("x87").inc()
    assert c.labels("ssefp").value == 3
    assert reg.dump()["fallbacks"] == {"ssefp": 3, "x87": 1}


def test_registry_dump_is_json_able():
    reg = Registry()
    reg.counter("a").inc()
    reg.counter("b").labels("k").inc()
    reg.histogram("h").observe(1)
    reg.gauge("g").set(7)
    parsed = json.loads(json.dumps(reg.dump()))
    assert parsed["a"] == 1 and parsed["g"] == 7
    assert parsed["b"] == {"k": 1}


def test_stats_dict_facade_preserves_dict_api():
    reg = Registry()
    stats = StatsDict(reg, "runner", fields=("chunks", "fallbacks"),
                      gauges=("max_chunk_steps",),
                      labeled=("fallbacks_by_opclass",))
    stats["chunks"] += 1
    stats["chunks"] += 1
    stats["max_chunk_steps"] = max(stats["max_chunk_steps"], 512)
    by_class = stats["fallbacks_by_opclass"]
    by_class["ssefp"] = by_class.get("ssefp", 0) + 1
    assert stats["chunks"] == 2
    assert stats["max_chunk_steps"] == 512
    assert dict(stats["fallbacks_by_opclass"]) == {"ssefp": 1}
    assert set(stats) >= {"chunks", "fallbacks", "max_chunk_steps"}
    # the same numbers are visible registry-side (the whole point)
    dump = reg.dump()
    assert dump["runner.chunks"] == 2
    assert dump["runner.fallbacks_by_opclass"] == {"ssefp": 1}
    # a declared-labeled key with no children dumps as {} (not 0)
    stats2 = StatsDict(Registry(), "r", labeled=("by_x",))
    assert stats2._registry.dump()["r.by_x"] == {}


def test_registry_isolation_between_instances():
    a, b = Registry(), Registry()
    a.counter("n").inc()
    assert b.counter("n").value == 0
    assert get_registry() is get_registry()  # global singleton exists


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_records_monotonic_and_nested_paths():
    reg = Registry()
    clock = [0.0]

    def fake_clock():
        return clock[0]

    from wtf_tpu.telemetry.spans import Spans

    spans = Spans(reg, clock=fake_clock)
    with spans.span("execute"):
        clock[0] += 1.0
        with spans.span("device-step"):
            clock[0] += 2.0
        clock[0] += 0.5
    with spans.span("restore"):
        clock[0] += 0.25
    secs = reg.counter("phase.seconds").children
    assert secs["execute"].value == pytest.approx(3.5)
    assert secs["execute/device-step"].value == pytest.approx(2.0)
    assert secs["restore"].value == pytest.approx(0.25)
    calls = reg.counter("phase.calls").children
    assert calls["execute"].value == 1
    assert spans.seconds("execute") == pytest.approx(3.5)
    # re-entry accumulates and stays monotonic
    with spans.span("execute"):
        clock[0] += 1.0
    assert secs["execute"].value == pytest.approx(4.5)


def test_span_records_on_exception_and_rebalances_stack():
    reg = Registry()
    spans = reg.spans
    with pytest.raises(ValueError):
        with spans.span("boom"):
            raise ValueError("x")
    assert reg.counter("phase.calls").children["boom"].value == 1
    # the stack recovered: a new span is top-level, not nested under boom
    with spans.span("after"):
        pass
    assert "after" in reg.counter("phase.seconds").children


def test_span_fence_blocks_device_values():
    import jax.numpy as jnp

    reg = Registry()
    with reg.spans.span("device") as sp:
        value = jnp.arange(8).sum()
        sp.fence(value)  # must not raise; host values fine too
        sp.fence(None)
        sp.fence({"nested": [value]})
    assert reg.spans.seconds("device") >= 0.0


# ---------------------------------------------------------------------------
# JSONL event log
# ---------------------------------------------------------------------------

def test_event_log_schema_round_trip(tmp_path):
    reg = Registry()
    reg.counter("campaign.testcases").inc(7)
    path = tmp_path / "telem"
    with open_event_log(path) as log:
        log.emit("run-start", subcommand="test", argv=["--x"])
        log.heartbeat(reg, line="#7 exec/s: 1.0", nodes=2)
        log.emit("crash", name="crash-read-0xdead", size=9)
        log.emit("run-end", metrics=reg.dump())
    records = list(read_events(path / "events.jsonl"))
    assert [r["type"] for r in records] == [
        "run-start", "heartbeat", "crash", "run-end"]
    # schema: every record has ts + monotonically increasing seq
    assert all("ts" in r for r in records)
    assert [r["seq"] for r in records] == [0, 1, 2, 3]
    hb = records[1]
    assert hb["line"] == "#7 exec/s: 1.0" and hb["nodes"] == 2
    assert hb["metrics"]["campaign.testcases"] == 7
    assert records[3]["metrics"]["campaign.testcases"] == 7
    # append mode: a second log continues the file
    with EventLog(path / "events.jsonl") as log:
        log.emit("run-start")
    assert len(list(read_events(path / "events.jsonl"))) == 5


def test_event_log_skips_torn_tail(tmp_path):
    path = tmp_path / "events.jsonl"
    with EventLog(path) as log:
        log.emit("run-start")
    with open(path, "a") as fh:
        fh.write('{"ts": 1.0, "seq": 1, "type": "hea')  # killed mid-write
    records = list(read_events(path))
    assert len(records) == 1


def test_event_log_rotates_at_size_cap(tmp_path):
    """Size-based rotation: events.jsonl -> events.jsonl.1, exactly one
    generation of history, and read_events(rotated=True) replays both
    generations oldest-first."""
    path = tmp_path / "events.jsonl"
    with EventLog(path, max_bytes=400) as log:
        for i in range(40):
            log.emit("tick", n=i)
    rotated = tmp_path / "events.jsonl.1"
    assert rotated.exists()
    assert not (tmp_path / "events.jsonl.2").exists()  # one gen only
    assert rotated.stat().st_size >= 400  # rotation fired AT the cap
    current = [r["n"] for r in read_events(path)]
    merged = [r["n"] for r in read_events(path, rotated=True)]
    assert len(current) < 40  # the cap actually bounded the live file
    # both generations parse, in order, ending at the newest record;
    # older rotated-away generations are the deliberate loss
    assert merged == list(range(merged[0], 40))
    assert merged[:len(merged) - len(current)] + current == merged


def test_event_log_torn_tail_survives_rotation(tmp_path):
    """A killed run can freeze a torn line into the generation that then
    rotates to .1 — readers must skip it in EVERY generation (the
    test_event_log_skips_torn_tail contract, extended to rotation)."""
    path = tmp_path / "events.jsonl"
    with EventLog(path, max_bytes=150) as log:
        log.emit("run-start")
        log._fh.write('{"ts": 1.0, "seq": 99, "type": "hea')  # torn
        log._fh.flush()
        # this record glues onto the torn tail (one unparseable line)
        # and its size pushes the file past the cap -> rotation
        log.emit("casualty", fill="x" * 200)
        log.emit("after-rotation")
    assert (tmp_path / "events.jsonl.1").exists()
    types = [r["type"] for r in read_events(path, rotated=True)]
    assert types == ["run-start", "after-rotation"]


def test_null_event_log_swallows_everything(tmp_path):
    assert open_event_log(None) is NULL
    NULL.emit("crash", name="x")
    NULL.heartbeat(Registry(), line="y")
    NULL.flush()
    NULL.close()


def test_event_log_degrades_to_noop_on_write_failure(tmp_path):
    """Telemetry is a side-channel: a full disk must not abort the
    campaign it narrates — emit degrades to a no-op after one OSError."""
    path = tmp_path / "events.jsonl"
    log = EventLog(path)
    log.emit("run-start")

    class _BrokenFH:
        closed = False

        def write(self, s):
            raise OSError(28, "No space left on device")

        def flush(self):
            pass

        def close(self):
            pass

    log._fh = _BrokenFH()
    log.emit("heartbeat")  # must not raise
    assert log._broken
    log.emit("crash", name="x")  # silent no-op now
    log.flush()
    log.close()
    assert [r["type"] for r in read_events(path)] == ["run-start"]


def test_maybe_heartbeat_skips_line_fn_when_unobserved():
    """line_fn can cost a device coverage readback — it must not run when
    neither a human (print_stats) nor a real event sink consumes it."""
    from wtf_tpu.fuzz.loop import CampaignStats

    stats = CampaignStats(Registry())
    calls = []

    def line_fn():
        calls.append(1)
        return "#0 line"

    assert stats.maybe_heartbeat(NULL, None, line_fn, every=0.0) is None
    assert not calls
    assert stats.maybe_heartbeat(NULL, None, line_fn, every=0.0,
                                 print_stats=True) == "#0 line"
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# trace timeline (--trace-out)
# ---------------------------------------------------------------------------

def test_trace_collector_round_trip(tmp_path):
    """Spans mirrored into a TraceCollector -> Chrome-trace JSON: exact
    event count, µs durations, device/host categorization by fenced
    leaf, child spans nested inside their parents, instants carried."""
    from wtf_tpu.telemetry.spans import Spans, TraceCollector

    reg = Registry()
    clock = [100.0]  # non-zero epoch: ts must rebase to the first event
    collector = TraceCollector(clock=lambda: clock[0])
    spans = Spans(reg, clock=lambda: clock[0])
    spans.collector = collector
    with spans.span("execute"):
        clock[0] += 1.0
        with spans.span("device-step"):
            clock[0] += 2.0
    collector.instant("compile", {"chunk_steps": 64})
    with spans.span("harvest"):
        clock[0] += 0.5

    n = collector.write(tmp_path / "trace.json")
    doc = json.loads((tmp_path / "trace.json").read_text())
    events = doc["traceEvents"]
    assert n == len(events) == 4
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["dropped_events"] == 0
    by_name = {e["name"]: e for e in events}
    dev = by_name["device-step"]
    assert dev["ph"] == "X" and dev["cat"] == "device"
    assert dev["dur"] == pytest.approx(2e6)  # µs
    assert dev["args"]["path"] == "execute/device-step"
    exe = by_name["execute"]
    assert exe["cat"] == "host" and exe["dur"] == pytest.approx(3e6)
    assert exe["ts"] == 0.0  # rebased epoch
    # nesting: the child interval lies inside the parent interval
    assert exe["ts"] <= dev["ts"]
    assert dev["ts"] + dev["dur"] <= exe["ts"] + exe["dur"]
    inst = by_name["compile"]
    assert inst["ph"] == "i" and inst["cat"] == "event"
    assert inst["args"]["chunk_steps"] == 64
    # the registry totals are untouched by mirroring
    assert reg.counter("phase.seconds").children["execute"].value == \
        pytest.approx(3.0)


def test_trace_collector_bounds_memory_by_dropping_oldest():
    from wtf_tpu.telemetry.spans import TraceCollector

    clock = [0.0]
    collector = TraceCollector(clock=lambda: clock[0], max_events=10)
    for i in range(25):
        clock[0] += 1.0
        collector.complete(f"p{i}", clock[0], 0.1)
    events = collector.trace_events()
    assert len(events) <= 10
    assert collector.dropped == 25 - len(events)
    # the survivors are the NEWEST events (steady state, not startup)
    assert {e["name"] for e in events} <= {f"p{i}" for i in range(15, 25)}


def test_trace_collector_records_the_unix_time_of_its_zero(tmp_path):
    """--trace-out rebases ts to its first event; otherData.epoch_unix_s
    says when that was, so the timeline can be laid against heartbeat
    `ts` and a profiler slice's start."""
    import time

    from wtf_tpu.telemetry import TraceCollector

    reg = Registry()
    reg.spans.collector = TraceCollector()
    before = time.time()
    with reg.spans.span("execute"):
        pass
    after = time.time()
    reg.spans.collector.write(tmp_path / "trace.json")
    doc = json.loads((tmp_path / "trace.json").read_text())
    assert doc["traceEvents"][0]["ts"] == 0
    assert before - 0.01 <= doc["otherData"]["epoch_unix_s"] <= after + 0.01


# ---------------------------------------------------------------------------
# device-side counters
# ---------------------------------------------------------------------------

def _tpu_backend(n_lanes=2):
    backend = create_backend("tpu", demo_tlv.build_snapshot(),
                             n_lanes=n_lanes, limit=100_000,
                             chunk_steps=128)
    backend.initialize()
    demo_tlv.TARGET.init(backend)
    return backend


@pytest.fixture(scope="module")
def tpu_backend():
    return _tpu_backend()


def test_device_instr_counter_matches_oracle_differential(tpu_backend):
    """The instructions-retired counter must equal the oracle
    interpreter's icount for the same testcase on both backends — the
    anchor that makes every derived rate trustworthy."""
    emu = EmuBackend(demo_tlv.build_snapshot(), limit=100_000)
    emu.initialize()
    demo_tlv.TARGET.init(emu)
    result, _ = run_testcase_and_restore(emu, demo_tlv.TARGET, BENIGN)
    assert isinstance(result, Ok)
    oracle_instr = emu.stats["instructions"]
    assert oracle_instr > 0

    backend = tpu_backend
    backend.restore()
    results = backend.run_batch([BENIGN, BENIGN], demo_tlv.TARGET)
    assert all(isinstance(r, Ok) for r in results)
    ctr = backend.runner.device_counters()
    assert ctr.shape == (2, N_CTRS)
    icount = np.asarray(backend.runner.machine.icount)
    for lane in range(2):
        assert int(ctr[lane, CTR_INSTR]) == int(icount[lane]) == oracle_instr
    # folded host metrics carry the batch totals
    assert (backend.registry.counter("device.instructions").value
            >= 2 * oracle_instr)
    backend.restore()


def test_compile_event_fires_for_base_chunk_size(tmp_path):
    """The coldest XLA compile of a campaign (the base chunk size's first
    dispatch) must be reported — make_run_chunk pre-builds the callable
    at init, but jit compiles on the first CALL.  Uses an executor shape
    (chunk size) no other test dispatches: compile tracking is
    process-global like the jit cache, so a warm shape rightly stays
    silent."""
    with EventLog(tmp_path / "events.jsonl") as events:
        backend = create_backend("tpu", demo_tlv.build_snapshot(),
                                 n_lanes=2, limit=100_000,
                                 chunk_steps=96, events=events)
        backend.initialize()
        demo_tlv.TARGET.init(backend)
        backend.run_batch([BENIGN], demo_tlv.TARGET)
        backend.restore()
        backend.run_batch([BENIGN], demo_tlv.TARGET)
    compiles = [r for r in read_events(tmp_path / "events.jsonl")
                if r["type"] == "compile"]
    # exactly one event for the base size: fired on the FIRST dispatch
    # (cold compile), silent on the warm second batch
    assert len([r for r in compiles if r["chunk_steps"] == 96]) == 1, compiles


def test_device_decode_miss_counter_and_restore_reset():
    backend = _tpu_backend()  # fresh: cold decode cache
    backend.run_batch([BENIGN], demo_tlv.TARGET)
    ctr = backend.runner.device_counters()
    assert int(ctr[0, CTR_DECODE_MISS]) > 0  # cold cache missed at least once
    assert backend.registry.counter("device.decode_misses").value > 0
    backend.restore()
    assert int(backend.runner.device_counters().sum()) == 0  # reset wipes
    # warm cache: a re-run misses nothing
    backend.run_batch([BENIGN], demo_tlv.TARGET)
    assert int(backend.runner.device_counters()[0, CTR_DECODE_MISS]) == 0


def test_device_mem_fault_counter_on_memory_crash(tpu_backend):
    backend = tpu_backend
    backend.restore()
    results = backend.run_batch([OVERFLOW], demo_tlv.TARGET)
    assert isinstance(results[0], Crash)
    ctr = backend.runner.device_counters()
    if any(kind in (results[0].name or "")
           for kind in ("read", "write", "execute")):
        assert int(ctr[0, CTR_MEM_FAULT]) >= 1
    assert backend.registry.counter("device.mem_faults").value >= int(
        ctr[0, CTR_MEM_FAULT])
    backend.restore()


# ---------------------------------------------------------------------------
# campaign integration: spans account for wall-clock, events flow
# ---------------------------------------------------------------------------

def test_campaign_telemetry_accounts_wall_clock(tmp_path):
    """Acceptance criterion: a fuzz run with --telemetry-dir produces a
    JSONL whose top-level per-phase span totals account for >= 90% of the
    run's wall-clock (run-start -> run-end)."""
    from wtf_tpu.cli import main

    telem = tmp_path / "telem"
    rc = main(["campaign", "--name", "demo_tlv", "--backend", "emu",
               "--runs", "200", "--seed", "7", "--max_len", "64",
               "--crashes", str(tmp_path / "crashes"),
               "--telemetry-dir", str(telem)])
    assert rc in (0, 2)
    records = list(read_events(telem / "events.jsonl"))
    assert records[0]["type"] == "run-start"
    end = [r for r in records if r["type"] == "run-end"]
    assert end, [r["type"] for r in records]
    metrics = end[-1]["metrics"]
    wall = end[-1]["ts"] - records[0]["ts"]
    top = {name: secs
           for name, secs in metrics["phase.seconds"].items()
           if "/" not in name}
    assert wall > 0
    assert sum(top.values()) >= 0.9 * wall, (top, wall)
    # phases tile the batch loop
    assert {"mutate", "execute", "harvest", "restore"} <= set(top)
    assert metrics["campaign.testcases"] >= 200


def test_campaign_crash_and_heartbeat_events(tmp_path):
    from wtf_tpu.cli import main

    telem = tmp_path / "telem"
    rc = main(["campaign", "--name", "demo_tlv", "--backend", "emu",
               "--runs", "600", "--seed", "5", "--max_len", "128",
               "--crashes", str(tmp_path / "crashes"),
               "--stop-on-crash", "--telemetry-dir", str(telem)])
    assert rc == 2
    types = [r["type"] for r in read_events(telem / "events.jsonl")]
    assert "crash" in types
    assert "heartbeat" in types  # last_print starts at 0 -> first batch
    assert types[-1] == "run-end"


def test_run_end_written_when_setup_fails(tmp_path):
    """A failed backend build must still close the JSONL with a run-end
    record — a telemetry file that just stops is indistinguishable from a
    killed run."""
    from wtf_tpu.cli import main

    telem = tmp_path / "telem"
    (tmp_path / "state").mkdir()  # exists but holds no snapshot
    with pytest.raises((Exception, SystemExit)):
        main(["campaign", "--name", "demo_tlv", "--backend", "emu",
              "--runs", "1", "--state", str(tmp_path / "state"),
              "--telemetry-dir", str(telem)])
    records = list(read_events(telem / "events.jsonl"))
    assert records[0]["type"] == "run-start"
    assert records[-1]["type"] == "run-end"


def test_fuzz_loop_stats_attribute_api_still_works():
    """CampaignStats keeps the reference-shaped attribute API while the
    values live in the registry."""
    from wtf_tpu.fuzz.loop import CampaignStats

    reg = Registry()
    stats = CampaignStats(reg)
    stats.testcases += 3
    stats.crashes += 1
    assert stats.testcases == 3
    assert reg.dump()["campaign.testcases"] == 3
    line = stats.line(5, 17)
    assert line.startswith("#3 cov: 17 corp: 5 exec/s: ")
    assert "crash: 1" in line
    # the node-shaped line omits cov/corp but keeps the rest
    assert stats.line().startswith("#3 exec/s: ")


# ---------------------------------------------------------------------------
# trace writers: context-manager + flush (satellite)
# ---------------------------------------------------------------------------

def test_trace_writers_context_manager_and_flush(tmp_path):
    from wtf_tpu.trace import (
        CovTraceWriter, RipTraceWriter, TenetTraceWriter,
    )

    rip_path = tmp_path / "rip.txt"
    with RipTraceWriter(rip_path) as w:
        w.on_step(0x1000)
        w.flush()  # buffered lines reach disk BEFORE close
        assert rip_path.read_text() == "0x1000\n"
        w.on_step(0x1001)
    assert rip_path.read_text() == "0x1000\n0x1001\n"
    w.close()  # double-close is safe
    with CovTraceWriter(tmp_path / "cov.txt") as w:
        w.on_step(0x2000)
        w.on_step(0x2000)
    assert (tmp_path / "cov.txt").read_text() == "0x2000\n"
    regs = {name: 0 for name in
            ("rax", "rbx", "rcx", "rdx", "rbp", "rsp", "rsi", "rdi", "r8",
             "r9", "r10", "r11", "r12", "r13", "r14", "r15", "rip")}
    try:
        with TenetTraceWriter(tmp_path / "tenet.txt") as w:
            w.on_step(regs)
            raise RuntimeError("crash mid-trace")
    except RuntimeError:
        pass
    # the crashed run's buffered lines were not lost
    assert "rax=0x0" in (tmp_path / "tenet.txt").read_text()


# ---------------------------------------------------------------------------
# report tool smoke test
# ---------------------------------------------------------------------------

def test_telemetry_report_on_device_campaign(tlv_campaign):
    """telemetry_report over the stream of a campaign on the batched
    device backend: testcases, per-phase shares and the in-graph device
    counters all come from the registry dump the run-end record carries."""
    import telemetry_report

    metrics = tlv_campaign["metrics"]
    summary = telemetry_report.summarize(tlv_campaign["target"] / "telem")
    assert summary["testcases"] == metrics["campaign.testcases"] >= 96
    assert "execute" in summary["phases"]
    assert summary["device"]["instructions"] \
        == metrics["device.instructions"] > 0
    assert 0 < summary["device"]["lane_occupancy"] <= 1
    # the campaign section: a crash's file work is done once a name
    assert summary["crash_files_written"] == 6
    assert summary["crash_saves_skipped"] == summary["crashes"] - 6 == 16


def test_heartbeats_carry_no_fused_engine_counters(tlv_campaign):
    """One device interpreter: no heartbeat (and not the run-end dump) of
    a campaign carries a `device.fused_*` counter any more."""
    dumps = [r["metrics"] for r in tlv_campaign["records"]
             if r["type"] in ("heartbeat", "run-end") and "metrics" in r]
    assert len(dumps) >= 2
    for dump in dumps:
        assert "device.sweeps" in dump
        assert not [k for k in dump if k.startswith("device.fused_")]


def test_telemetry_report_segments_appended_runs(tmp_path):
    """EventLog appends, so one events.jsonl can hold several runs; the
    report must summarize the LATEST run, not stretch wall-clock across
    the gap between runs (which would crater every rate and share)."""
    import telemetry_report

    path = tmp_path / "events.jsonl"
    reg = Registry()
    reg.counter("campaign.testcases").inc(100)
    reg.counter("phase.seconds").labels("execute").inc(9.0)
    clock = iter([0.0, 1.0,            # run 1: start, end
                  3600.0, 3610.0])     # run 2, an hour later: 10s long
    with EventLog(path, clock=lambda: next(clock)) as log:
        log.emit("run-start")
        log.emit("run-end", metrics={})
        log.emit("run-start")
        log.emit("run-end", metrics=reg.dump())
    summary = telemetry_report.summarize(path)
    assert summary["runs_in_file"] == 2
    assert summary["wall_seconds"] == 10.0  # NOT 3610
    assert summary["testcases_per_s"] == 10.0
    assert summary["phase_accounted_frac"] == 0.9


def test_telemetry_report_summarizes_campaign(tmp_path, capsys):
    from wtf_tpu.cli import main

    import telemetry_report

    telem = tmp_path / "telem"
    rc = main(["campaign", "--name", "demo_tlv", "--backend", "emu",
               "--runs", "150", "--seed", "9", "--max_len", "64",
               "--telemetry-dir", str(telem)])
    assert rc in (0, 2)
    summary = telemetry_report.summarize(telem)
    assert summary["testcases"] >= 150
    assert summary["phase_accounted_frac"] >= 0.9
    assert summary["wall_seconds"] > 0
    assert "execute" in summary["phases"]
    assert summary["events_by_type"]["run-start"] == 1
    # CLI entry: --json emits one parseable object, human mode prints
    assert telemetry_report.main([str(telem), "--json"]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(out)["testcases"] == summary["testcases"]
    assert telemetry_report.main([str(telem)]) == 0
    assert "phases" in capsys.readouterr().out
    assert telemetry_report.main([]) == 1


def test_telemetry_report_host_device_wall_breakdown(tmp_path):
    """ISSUE 6 satellite: the report splits each top-level phase into
    host-busy vs device-busy wall-clock from the fenced nested spans —
    the artifact that makes the devmut double-buffer overlap claim
    directly measurable.  With mutate-on-device, mutate's host share is
    its total minus the nested mutate/device fence."""
    import telemetry_report

    path = tmp_path / "events.jsonl"
    reg = Registry()
    sec = reg.counter("phase.seconds")
    sec.labels("mutate").inc(2.0)
    sec.labels("mutate/device").inc(1.9)          # fenced generation wait
    sec.labels("execute").inc(10.0)
    sec.labels("execute/device-step").inc(7.0)
    sec.labels("execute/insert/device").inc(1.0)  # fused insert wait
    sec.labels("execute/service-pull").inc(2.0)   # host servicing
    sec.labels("harvest").inc(0.5)
    with EventLog(path) as log:
        log.emit("run-start")
        log.emit("run-end", metrics=reg.dump())
    wb = telemetry_report.summarize(path)["wall_breakdown"]
    assert wb["by_phase"]["mutate"]["device_seconds"] == 1.9
    assert round(wb["by_phase"]["mutate"]["host_seconds"], 4) == 0.1
    assert wb["by_phase"]["execute"]["device_seconds"] == 8.0
    assert wb["by_phase"]["execute"]["host_seconds"] == 2.0
    assert wb["by_phase"]["harvest"]["device_seconds"] == 0.0
    assert round(wb["host_busy_seconds"], 4) == 2.6
    assert round(wb["device_busy_seconds"], 4) == 9.9


def test_wall_breakdown_counts_a_fenced_leaf_whole_however_it_splits():
    """PR 39: a device leaf has a `dispatch` child (the jit call) and a
    `wait` child (the fence).  Under --dispatch-timeout the supervisor's
    bounded wait runs inside `dispatch`, so the host / device split reads
    the leaf alone: one stream reads the same however its children split,
    and the report and `wtf-tpu status` take it from ONE function."""
    import telemetry_report

    from wtf_tpu.telemetry.spans import device_seconds_by_phase

    plain = {"execute": 10.0, "execute/device-step": 7.0,
             "execute/device-step/dispatch": 0.5,
             "execute/device-step/wait": 6.5,
             "execute/device": 1.0, "execute/device/dispatch": 0.25,
             "execute/device/wait": 0.75,
             "execute/service-pull": 2.0, "harvest/wait": 3.0,
             "harvest": 4.0}
    supervised = dict(plain, **{"execute/device-step/dispatch": 6.9,
                                "execute/device-step/wait": 0.1,
                                "execute/device/dispatch": 1.0,
                                "execute/device/wait": 0.0})
    for seconds in (plain, supervised):
        assert device_seconds_by_phase(seconds) == {"execute": 8.0}
        wb = telemetry_report.wall_breakdown(seconds)
        assert wb["by_phase"]["execute"] == {
            "seconds": 10.0, "device_seconds": 8.0, "host_seconds": 2.0}
        assert wb["by_phase"]["harvest"]["device_seconds"] == 0.0


@pytest.fixture(scope="module")
def supervised_campaign(tmp_path_factory):
    """`kernel_campaign`'s first two batches in the RUNBOOK's supervised
    mode: every chunk's bounded wait runs inside `supervisor.dispatch`."""
    target = tmp_path_factory.mktemp("supervised-campaign")
    _kernel_inputs(target / "inputs")
    return _campaign(target, "demo_kernel", "--runs", "16", "--seed", "21",
                     "--mutator", "mangle", "--supervise",
                     "--dispatch-timeout", "30")


@pytest.mark.parametrize("reader", ["report", "status"])
@pytest.mark.parametrize("campaign", ["kernel_campaign",
                                      "supervised_campaign"])
def test_host_share_reads_the_parents_rule_with_and_without_a_timeout(
        request, campaign, reader):
    """'host share' is the accounted wall less the fenced leaves, in a
    supervised campaign as in a plain one: there `dispatch` holds the
    device's time (the bounded wait), and is not the host's."""
    import telemetry_report

    from wtf_tpu.cli import _derived_status_rows
    from wtf_tpu.telemetry.spans import DEVICE_SPAN_LEAVES

    metrics = request.getfixturevalue(campaign)["metrics"]
    phase = metrics["phase.seconds"]
    top = sum(s for p, s in phase.items() if "/" not in p)
    leaves = sum(s for p, s in phase.items()
                 if "/" in p and p.rsplit("/", 1)[-1] in DEVICE_SPAN_LEAVES)
    assert 0 < phase["execute/device-step"] <= leaves < top
    step = "execute/device-step"
    if campaign == "supervised_campaign":
        assert metrics["supervise.dispatches"] >= metrics["runner.chunks"]
        assert phase[f"{step}/dispatch"] > phase[f"{step}/wait"]
    if reader == "report":
        wb = telemetry_report.wall_breakdown(phase)
        assert wb["device_busy_seconds"] == pytest.approx(leaves, abs=1e-3)
        assert wb["host_busy_seconds"] == pytest.approx(top - leaves,
                                                        abs=1e-3)
    else:
        assert (f"host share: {(top - leaves) / top:.1%} of accounted wall"
                in _derived_status_rows(metrics))


def test_telemetry_report_prints_the_chunk_and_the_mix_lines(
        kernel_campaign, capsys):
    """`chunk:` (dispatch and wait a chunk, levels used of 16, the narrow
    share) and `mix:` (stores, vector, two-page passes) from the stream's
    own spans and counters; a stream from before them prints neither."""
    import telemetry_report

    telem = kernel_campaign["target"] / "telem"
    summary = telemetry_report.summarize(telem / "events.jsonl")
    metrics = kernel_campaign["metrics"]
    seconds, chunks = metrics["phase.seconds"], metrics["runner.chunks"]
    assert summary["chunk"]["dispatch_ms"] == round(
        1e3 * seconds["execute/device-step/dispatch"] / chunks, 3)
    assert summary["chunk"]["wait_ms"] == round(
        1e3 * seconds["execute/device-step/wait"] / chunks, 3)
    assert summary["chunk"]["levels_used"] == round(
        metrics["device.levels_used"] / chunks, 2)
    assert summary["mix"]["store_share"] == round(
        metrics["device.store_steps"] / metrics["device.instructions"], 4)
    telemetry_report._print_human(summary)
    out = capsys.readouterr().out
    assert "\nchunk: " in out and "\nmix: stores in " in out
    summary["chunk"] = summary["mix"] = None
    telemetry_report._print_human(summary)
    out = capsys.readouterr().out
    assert "\nchunk: " not in out and "\nmix: " not in out


@pytest.mark.parametrize("campaign,said", [("kernel_campaign", False),
                                           ("supervised_campaign", True)])
def test_chunk_line_says_when_dispatch_holds_the_bounded_wait(
        request, capsys, campaign, said):
    import telemetry_report

    telem = request.getfixturevalue(campaign)["target"] / "telem"
    summary = telemetry_report.summarize(telem / "events.jsonl")
    assert summary["chunk"]["supervised"] is said
    telemetry_report._print_human(summary)
    assert ("the bounded wait is inside dispatch"
            in capsys.readouterr().out) is said


# ---------------------------------------------------------------------------
# PR 25: the closed span tree, the in-graph sweep counter, the profiler's
# clock, named programs
# ---------------------------------------------------------------------------

def _kernel_inputs(inputs: Path) -> None:
    import struct

    inputs.mkdir(parents=True)
    (inputs / "a").write_bytes(b"\x03" + b"A" * 200)
    (inputs / "b").write_bytes(
        b"\x02" + struct.pack("<IQ", 0xDEADBEEF, 0x41) + b"pad")
    (inputs / "c").write_bytes(b"\x04" + struct.pack("<Q", 0xDEAD0000) + b"x")
    (inputs / "d").write_bytes(b"\x01\x10\x20")


def _campaign(target: Path, name: str, *args: str) -> dict:
    """One seeded campaign on the batch loop through the CLI: its
    records, its last metrics dump and its finds."""
    import contextlib
    import io

    from wtf_tpu.cli import main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(["campaign", "--name", name, "--target", str(target),
                   "--lanes", "8", "--limit", "100000", "--max_len", "256",
                   "--telemetry-dir", str(target / "telem"), *args])
    assert rc in (0, 2)
    records = list(read_events(target / "telem" / "events.jsonl"))
    end = [r for r in records if r["type"] == "run-end"][-1]
    return {"target": target, "records": records,
            "metrics": end["metrics"], "stdout": out.getvalue()}


@pytest.fixture(scope="module")
def kernel_campaign(tmp_path_factory):
    """demo_kernel, 8 lanes, 8 batches, the pure-Python mangle engine so
    no native build matters."""
    target = tmp_path_factory.mktemp("kernel-campaign")
    _kernel_inputs(target / "inputs")
    return _campaign(target, "demo_kernel", "--runs", "64", "--seed", "21",
                     "--mutator", "mangle")


@pytest.fixture(scope="module")
def tlv_campaign(tmp_path_factory):
    """demo_tlv with its own structure mutator, 8 lanes, 12 batches: its
    crashers smash the return address, so each one's stack page is in
    its lane's overlay when it is bucketed."""
    target = tmp_path_factory.mktemp("tlv-campaign")
    (target / "inputs").mkdir()
    return _campaign(target, "demo_tlv", "--backend", "tpu", "--runs", "96",
                     "--seed", "29")


def _calls(metrics, path):
    return metrics["phase.calls"].get(path, 0)


@pytest.mark.parametrize("path,counter", [
    ("execute/device-step", "runner.chunks"),
    ("execute/device-step/dispatch", "runner.chunks"),
    ("execute/device-step/wait", "runner.chunks"),
    ("execute/status-pull", "runner.chunks"),
    ("execute/results", "backend.batches"),
    ("harvest/crash-save", "backend.batches"),
    ("harvest/account", "backend.batches"),
    ("harvest/corpus-add", "backend.batches"),
    ("harvest", "backend.batches"),
    ("heartbeat", "backend.batches"),
])
def test_new_span_is_present_with_the_expected_calls(kernel_campaign,
                                                     path, counter):
    """One span per chunk or per batch, never one per lane (8 lanes a
    batch here) nor one per crash: the crashes saved are
    `campaign.crashes`, not `phase.calls`."""
    metrics = kernel_campaign["metrics"]
    assert metrics[counter] > 0
    assert _calls(metrics, path) == metrics[counter], (
        path, metrics["phase.calls"])
    assert metrics["campaign.crashes"] > metrics["backend.batches"]


def test_breakpoint_loop_gets_one_span_a_round_not_one_a_lane(
        kernel_campaign):
    metrics = kernel_campaign["metrics"]
    calls = _calls(metrics, "execute/service-bp")
    assert 0 < calls <= metrics["runner.chunks"]
    assert metrics["runner.bp_dispatches"] > calls   # lanes share a span


def test_exception_loop_gets_one_span_a_round_not_one_a_lane():
    """demo_usermode has an IDT: #PF and #DE go back into the guest, lane
    by lane, under ONE `service-exception` span per servicing round."""
    from wtf_tpu.harness import demo_usermode as du

    backend = create_backend("tpu", du.build_snapshot(), n_lanes=8,
                             limit=100_000)
    backend.initialize()
    du.TARGET.init(backend)
    backend.run_batch([b"\x02", b"\x03"] * 4, du.TARGET)  # wild read, #DE
    metrics = backend.registry.dump()
    calls = metrics["phase.calls"]["service-exception"]
    assert 0 < calls <= metrics["runner.chunks"]
    assert metrics["runner.exceptions_delivered"] > calls
    assert metrics["phase.calls"]["status-pull"] == metrics["runner.chunks"]


def _kernel_backend():
    from wtf_tpu.harness import demo_kernel as dk

    backend = create_backend("tpu", dk.build_snapshot(), n_lanes=8,
                             limit=100_000)
    backend.initialize()
    dk.TARGET.init(backend)
    return backend, dk


def _jump_to(dk, gva: int, tail: bytes = b"") -> bytes:
    import struct

    return b"\x04" + struct.pack("<Q", gva) + tail


def _result_pulls_at_most_one_a_batch(kernel_campaign):
    """The mapping's one transfer is the batch's view, and only in a
    batch with a lane whose name needs machine fields."""
    metrics = kernel_campaign["metrics"]
    assert 0 < metrics["backend.result_pulls"] <= metrics["backend.batches"]
    assert metrics["campaign.crashes"] > metrics["backend.batches"]


def _result_pulls_none_without_a_crashing_lane(kernel_campaign):
    """OK and TIMEDOUT lanes are named from their status alone: the
    batch pulls nothing while mapping, and builds no view."""
    backend, dk = _kernel_backend()
    backend.limit = 40           # the byte-sum loop of lane 1 times out
    results = backend.run_batch([b"\x01\x10\x20", b"\x01" + b"B" * 200],
                                dk.TARGET)
    assert [type(r).__name__ for r in results] == ["Ok", "Timedout"]
    assert backend.stats["result_pulls"] == 0
    assert backend._view is None


def _result_mapping_holds_no_per_lane_device_read(kernel_campaign):
    import inspect

    from wtf_tpu.backend.tpu import TpuBackend
    source = (inspect.getsource(TpuBackend._map_results)
              + inspect.getsource(TpuBackend._map_result))
    for read in ("np.asarray", "device_get", ".machine", "jnp."):
        assert read not in source, read
    assert not hasattr(TpuBackend, "_pull_field")


def _result_names_equal_a_per_lane_reference(kernel_campaign):
    """Against the mapping as it was: three whole-plane pulls per
    crashing lane, straight off the machine."""
    from wtf_tpu.core.results import StatusCode

    backend, dk = _kernel_backend()
    cases = [
        b"\x03" + b"A" * 200,                                # wild write
        _jump_to(dk, 0xDEAD0000),                            # wild execute
        _jump_to(dk, dk.USER_BUF + 9,
                 bytes.fromhex("488b042500703301")),         # wild read
        _jump_to(dk, dk.USER_BUF + 9, bytes.fromhex("31c9f7f1")),   # #DE
        # hard error: a REX prefix alone before the unmapped page
        _jump_to(dk, dk.USER_BUF + 0xFFF, b"\x48" * (0x1000 - 9)),
        b"\x02" + b"\xef\xbe\xad\xde" + b"\x41" + b"\0" * 10,  # stop()
        b"\x01\x10\x20",                                     # ok
    ]
    results = backend.run_batch(cases, dk.TARGET)
    m = backend.runner.machine
    status = np.asarray(m.status)
    reference = []
    for lane in range(len(cases)):
        if lane in backend._lane_results:
            reference.append(backend._lane_results[lane])
            continue
        reference.append(backend._result_from_fields(
            StatusCode(int(status[lane])),
            int(np.asarray(m.fault_gva)[lane]),
            int(np.asarray(m.fault_write)[lane]),
            int(np.asarray(m.rip)[lane]),
            backend.runner.lane_errors.get(lane, "hard-error")))
    assert [str(r) for r in results] == [str(r) for r in reference]
    assert [str(r) for r in results] == [
        "crash(crash-write-0xffff800000203000)",
        "crash(crash-execute-0xdead0000)",
        "crash(crash-read-0x1337000)",
        "crash(crash-de-0x2000000b)",
        "crash(crash-unsupported-0x20000fff)",
        "crash(crash-bugcheck-0xdeadbeef-0x41)",
        "ok"]
    assert backend.stats["result_pulls"] == 1
    # the harvest's bucket reads the view the mapping built
    view = backend._view
    assert view is not None and backend._ensure_view() is view


@pytest.mark.parametrize("claim", [
    _result_pulls_at_most_one_a_batch,
    _result_pulls_none_without_a_crashing_lane,
    _result_mapping_holds_no_per_lane_device_read,
    _result_names_equal_a_per_lane_reference,
], ids=lambda f: f.__name__.strip("_"))
def test_result_pulls_are_one_view_pull_a_batch(kernel_campaign, claim):
    """`backend.result_pulls` counts device-to-host transfers made while
    mapping results: the batch's view, once, where it was three planes
    per crashing lane."""
    claim(kernel_campaign)


@pytest.mark.parametrize("parent", ["execute", "harvest",
                                    "execute/device-step"])
def test_direct_children_cover_most_of_the_parent_span(kernel_campaign,
                                                       parent):
    """`execute/device-step` (PR 39): the jit call and the fence, its two
    children, are all it does."""
    seconds = kernel_campaign["metrics"]["phase.seconds"]
    depth = parent.count("/") + 1
    children = sum(v for k, v in seconds.items()
                   if k.startswith(parent + "/") and k.count("/") == depth)
    assert 0.8 * seconds[parent] <= children <= seconds[parent], seconds


def test_dispatch_and_wait_close_the_device_step_span(kernel_campaign):
    """What `device_step_spanned_share` read (99.99 in every line of the
    ledger) before PR 42 retired it: the span's two children sum to it.
    (Held by `benchmark/tests/test_split_replay.py` until PR 43.)"""
    seconds = kernel_campaign["metrics"]["phase.seconds"]
    closure = 100 * (seconds["execute/device-step/dispatch"]
                     + seconds["execute/device-step/wait"]) \
        / seconds["execute/device-step"]
    assert 99.0 <= closure <= 100.0 + 1e-9


def test_top_level_spans_cover_the_wall_clock_between_heartbeats(
        kernel_campaign):
    """mutate + execute + harvest + restore + heartbeat (+ checkpoint)
    tile the loop: between the first heartbeat and the run's end they
    account for the host's clock."""
    records = kernel_campaign["records"]
    first = [r for r in records if r["type"] == "heartbeat"][0]
    last = [r for r in records if r["type"] == "run-end"][-1]
    top = ("mutate", "execute", "harvest", "restore", "heartbeat",
           "checkpoint")
    spanned = sum(last["metrics"]["phase.seconds"].get(k, 0.0)
                  - first["metrics"]["phase.seconds"].get(k, 0.0)
                  for k in top)
    wall = last["ts"] - first["ts"]
    assert wall > 0 and spanned >= 0.9 * wall, (spanned, wall)


def test_sweeps_are_whole_and_bound_the_instructions_in_every_record(
        kernel_campaign):
    seen = 0
    for record in kernel_campaign["records"]:
        metrics = record.get("metrics")
        if not metrics or "device.lane_steps" not in metrics:
            continue
        seen += 1
        assert metrics["device.sweeps"] == int(metrics["device.sweeps"])
        assert metrics["device.sweeps"] * 8 == metrics["device.lane_steps"]
        assert metrics["device.instructions"] <= metrics["device.lane_steps"]
        assert metrics["runner.fallbacks"] == 0   # no host-retired steps
    assert seen >= 2      # the first heartbeat and the run's end


# the same campaign on the parent commit (PR 24's tree), by hand: the
# final line without its clock fields, and a digest over the sorted
# (path, sha256) listing of outputs/ and crashes/
# re-taken on purpose in PR 36: a campaign RUNS what its corpus starts
# with (this one's three inputs/ files) before it mutates, where the files
# were added to the corpus and never run; with that one call taken out the
# line, the finds and the names below read as PR 35 left them (17 / 30,
# 2ed2245a..., f96e3e62..., 19 files where 25 are now).  The TLV campaign starts from an
# empty inputs/ and its pins stand.
_PARENT_LINE = "#64 cov: 51 corp: 21 crash: 38 timeout: 0 cr3: 0"
# re-taken on purpose in PR 35: crashes/<name> now holds the FIRST
# testcase seen under the name where the last writer won (2 of the 19
# names differ in bytes here, 3 of 6 in the TLV campaign).  What did
# not move is pinned beside it, taken on PR 35's parent: the names under
# crashes/ and every (name, sha256) under outputs/.
_PARENT_FINDS = ("27609eba9e2665f0d08410214fb027b5158a5df56af3a5a6f37d31f6a"
                 "c9beeb4")
_PARENT_NAMES = ("8373ca693ba4ad0aadc6f7400fd7b54064bc2b0b4fdf1f04be403ea13"
                 "9b43a00")


def _final_line(campaign) -> str:
    import re

    line = campaign["stdout"].strip().splitlines()[-1]
    line = re.sub(r"exec/s: [0-9.]+ ", "", line)
    return re.sub(r" uptime.*", "", line)


def _finds_digest(campaign, crash_bytes: bool = True) -> str:
    """Over the sorted listing of outputs/ and crashes/: (path, sha256)
    lines, or with `crash_bytes` off the bare path for crashes/."""
    import hashlib

    listing = hashlib.sha256()
    for sub in ("outputs", "crashes"):
        for path in sorted((campaign["target"] / sub).iterdir()):
            line = f"{sub}/{path.name}"
            if crash_bytes or sub == "outputs":
                digest = hashlib.sha256(path.read_bytes()).hexdigest()[:16]
                line += f" {digest}"
            listing.update(f"{line}\n".encode())
    return listing.hexdigest()


def test_equal_seed_campaign_writes_the_parents_line_and_finds(
        kernel_campaign):
    assert _final_line(kernel_campaign) == _PARENT_LINE
    assert _finds_digest(kernel_campaign) == _PARENT_FINDS


# the same for demo_tlv, taken on the tree before the crashing lanes'
# stack pages came down in one transfer a batch (PR 29's parent), with the
# `crash` events in their order: name, new, bucket
_TLV_PARENT_LINE = "#96 cov: 53 corp: 11 crash: 22 timeout: 0 cr3: 0"
_TLV_PARENT_FINDS = ("0087f605995d190a736f108cedfcce48fefb76c843f761f5508c37"
                     "320c4c904d")     # re-taken in PR 35, as _PARENT_FINDS
_TLV_PARENT_NAMES = ("bd47d34feec9021ca444e47d96d137ae8ae3270cfd0bbcee24abb2"
                     "5622dfc7c3")
_TLV_PARENT_CRASH_EVENTS = ("b83c813bbf028284db97e6dc775cfa880694ea0b3b0975"
                            "b7fed46502e6024529")


def test_equal_seed_tlv_campaign_writes_the_parents_line_finds_and_buckets(
        tlv_campaign):
    import hashlib

    assert _final_line(tlv_campaign) == _TLV_PARENT_LINE
    assert _finds_digest(tlv_campaign) == _TLV_PARENT_FINDS
    crashes = [r for r in tlv_campaign["records"] if r["type"] == "crash"]
    assert len(crashes) == 22
    # every bucket carries a stack hash: the page was read, not given up
    assert all(r["bucket"].count(".") == 2
               and not r["bucket"].endswith("nostack") for r in crashes)
    events = hashlib.sha256("".join(
        f"{r['name']} {r['new']} {r['bucket']}\n"
        for r in crashes).encode()).hexdigest()
    assert events == _TLV_PARENT_CRASH_EVENTS


@pytest.mark.parametrize("campaign,names,files", [
    ("kernel_campaign", _PARENT_NAMES, 25),
    ("tlv_campaign", _TLV_PARENT_NAMES, 6),
])
def test_a_crash_names_file_is_written_once_and_nothing_else_moved(
        campaign, names, files, request):
    """Writing a crash name's file once changed bytes under repeated
    names only: the listing of names and all of outputs/ are as they
    were before it, and every crash wrote its name's file or found it
    held."""
    campaign = request.getfixturevalue(campaign)
    assert _finds_digest(campaign, crash_bytes=False) == names
    metrics = campaign["metrics"]
    assert metrics["campaign.crash_files_written"] == files == len(
        list((campaign["target"] / "crashes").iterdir()))
    assert (metrics["campaign.crash_files_written"]
            + metrics["campaign.crash_saves_skipped"]
            == metrics["campaign.crashes"])
    # the pair is in every heartbeat, from the first on
    beats = [r for r in campaign["records"] if r["type"] == "heartbeat"]
    assert beats and all(
        "campaign.crash_saves_skipped" in r["metrics"] for r in beats)


def test_tlv_campaign_fetches_stack_rows_once_a_batch(tlv_campaign):
    """Every crashing lane's stack page is dirty: one row each, and one
    round trip for all of a batch's."""
    metrics = tlv_campaign["metrics"]
    assert metrics["runner.view_rows"] == metrics["campaign.crashes"] == 22
    assert 0 < metrics["runner.view_row_pulls"] <= metrics["backend.batches"]
    assert metrics["runner.view_row_pulls"] == metrics["backend.result_pulls"]


def test_kernel_campaign_fetches_no_stack_row(kernel_campaign):
    """demo_kernel's crashers fault before they write their stack: no
    row is dirty, and no row program is dispatched."""
    metrics = kernel_campaign["metrics"]
    assert metrics["campaign.crashes"] > 0
    assert metrics["runner.view_row_pulls"] == 0
    assert metrics["runner.view_rows"] == 0


def test_every_lane_carries_the_same_sweep_count_on_one_device():
    from wtf_tpu.harness import demo_kernel as dk

    backend = create_backend("tpu", dk.build_snapshot(), n_lanes=8,
                             limit=100_000)
    backend.initialize()
    dk.TARGET.init(backend)
    cases = [b"\x01\x01\x02\x03\x04", b"\x03" + b"A" * 200, b"",
             b"\x02short", b"\x01" + bytes(range(250))]
    backend.run_batch(cases, dk.TARGET)
    ctr = backend.runner.device_counters()
    swept = ctr[:, CTR_SWEPT]
    assert swept[0] > 0 and (swept == swept[0]).all(), swept
    assert (ctr[:, CTR_INSTR] <= swept).all()
    reg = backend.registry
    assert reg.counter("device.sweeps").value == int(swept[0])
    assert reg.counter("device.lane_steps").value == 8 * int(swept[0])
    dk.TARGET.restore()
    backend.restore()
    assert int(backend.runner.device_counters()[:, CTR_SWEPT].sum()) == 0


def test_straight_line_testcase_sweeps_its_instructions_plus_one():
    """Warm decode cache, one live lane: one sweep per retired
    instruction, plus the ONE sweep that meets the finish breakpoint
    (pre-execution: it retires nothing and leaves RUNNING, which ends
    the loop).  A cold cache adds one sweep per decode miss."""
    backend = _tpu_backend()
    backend.run_batch([BENIGN], demo_tlv.TARGET)
    cold = backend.runner.device_counters()
    assert (int(cold[0, CTR_SWEPT]) == int(cold[0, CTR_INSTR]) + 1
            + int(cold[0, CTR_DECODE_MISS]))
    backend.restore()
    assert int(backend.runner.device_counters()[:, CTR_SWEPT].sum()) == 0
    backend.run_batch([BENIGN], demo_tlv.TARGET)
    warm = backend.runner.device_counters()
    assert int(warm[0, CTR_DECODE_MISS]) == 0
    assert int(warm[0, CTR_SWEPT]) == int(warm[0, CTR_INSTR]) + 1
    # the idle lane is swept all the same
    assert int(warm[1, CTR_INSTR]) == 0
    assert int(warm[1, CTR_SWEPT]) == int(warm[0, CTR_SWEPT])
    backend.restore()


# test_megachunk / test_meshrun shapes: compile-cache-shared
_TLV_BUILD = dict(n_lanes=8, limit=20_000, chunk_steps=128, overlay_slots=16)
_ENGINES = {"batch": {}, "window": {"megachunk": 3},
            "mesh2": {"mesh_devices": 2}}


@pytest.fixture(scope="module")
def tlv_engines():
    """One seed through the batch loop, the megachunk window and a
    2-device mesh (devmangle, 3 batches each)."""
    from wtf_tpu.analysis.trace import build_tlv_campaign

    loops = {}
    for name, kw in _ENGINES.items():
        loop = build_tlv_campaign(mutator="devmangle", seed=0x5EED,
                                  **_TLV_BUILD, **kw)
        loop.fuzz(8 * 3)
        loops[name] = loop
    return loops


@pytest.mark.parametrize("engine", sorted(_ENGINES))
def test_sweep_invariants_hold_in_every_engine(tlv_engines, engine):
    reg = tlv_engines[engine].registry
    instr = reg.counter("device.instructions").value
    lane_steps = reg.counter("device.lane_steps").value
    sweeps = reg.counter("device.sweeps").value
    assert 0 < instr <= lane_steps
    assert sweeps * 8 == lane_steps
    if engine != "mesh2":
        assert sweeps == int(sweeps)     # one device: whole iterations


def test_engines_retire_the_same_instructions_for_one_seed(tlv_engines):
    dumps = {name: loop.registry.counter("device.instructions").value
             for name, loop in tlv_engines.items()}
    assert dumps["batch"] == dumps["window"] == dumps["mesh2"], dumps
    steps = {name: loop.registry.counter("device.lane_steps").value
             for name, loop in tlv_engines.items()}
    # shards leave their chunk loops on their own: never more lane-steps
    # than one device sweeping all lanes together
    assert steps["mesh2"] <= steps["batch"], steps


def _host_event_names(trace_dir: Path) -> set:
    import jax

    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert found, list(trace_dir.rglob("*"))
    names = set()
    for plane in jax.profiler.ProfileData.from_file(str(found[-1])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def test_profiler_trace_carries_the_span_paths_on_its_host_plane(
        tlv_engines, tmp_path):
    """Every span is a TraceAnnotation: a jax.profiler trace of the
    process holds the span paths beside whatever else it records."""
    import jax

    loop = tlv_engines["batch"]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        loop.fuzz(loop.stats.testcases + 8)
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(tmp_path)
    assert {"execute/device-step", "harvest", "execute",
            "execute/device-step/dispatch", "execute/device-step/wait",
            "execute/status-pull", "harvest/account"} <= names


def test_xprof_dir_takes_one_bounded_trace_inside_a_batch(tmp_path):
    """--xprof-dir: one short window opened by a thread inside the first
    steady-state batch; the loop reports it as one `xprof` event."""
    from wtf_tpu.analysis.trace import build_tlv_campaign

    with EventLog(tmp_path / "events.jsonl") as events:
        loop = build_tlv_campaign(mutator="devmangle", seed=0x5EED,
                                  **_TLV_BUILD)
        loop.events = events
        from wtf_tpu.telemetry.xprof import ProfilerWindow

        loop.xprof = ProfilerWindow(
            tmp_path / "xprof", seconds=0.05, share=0.5,
            open_path=loop.registry.spans.open_path)
        loop.fuzz(8 * 5)
    records = [r for r in read_events(tmp_path / "events.jsonl")
               if r["type"] == "xprof"]
    assert len(records) == 1, records
    rec = records[0]
    assert 0.04 <= rec["stop_ts"] - rec["start_ts"] < 5.0
    assert rec["batch"] >= 3          # two warm batches come first
    # a span that encloses the whole window begins no row inside it: the
    # window names the path open at its two ends, in the record and in
    # the trace
    tops = ("mutate", "execute", "harvest", "restore", "heartbeat",
            "(no span)")
    assert rec["open_at_start"].split("/")[0] in tops
    assert rec["open_at_stop"].split("/")[0] in tops
    names = _host_event_names(tmp_path / "xprof")
    assert f"open at start: {rec['open_at_start']}" in names
    assert f"open at stop: {rec['open_at_stop']}" in names


def test_lowered_chunk_names_every_stage_of_the_step(tpu_backend):
    """Every step scope of THE list (telemetry/xprof.py) is in the lowered
    chunk program: a name the list keeps for a scope the step lost would
    be a row of the tool's table that can never read."""
    from wtf_tpu.analysis.trace import step_executor_lowering
    from wtf_tpu.telemetry.xprof import STEP_SCOPES

    lowered = step_executor_lowering(tpu_backend.runner, n_steps=16,
                                     donate=False)
    text = lowered.as_text(debug_info=True)
    # under the lanes' vmap, or over the lanes in hand (`store-spill`)
    missing = [s for s in STEP_SCOPES
               if f"({s})" not in text and f"/{s}/" not in text]
    assert not missing, missing


@pytest.mark.parametrize("entry,total", [
    ("xla_step", 91), ("tenant_chunk", 91),
    ("megachunk_window", 133), ("decode_service", 308),
    ("mesh_chunk", 1), ("tail_chunk", 527),
])
def test_pinned_kernel_counts_did_not_move(entry, total):
    """analysis/budgets.json as PR 26 re-pinned it with the overlay
    store, and the ladder window's entry as PR 30 pinned it
    (test_analysis holds the measured programs to the file; this holds
    the file).  `tail_chunk` (PR 31) is `xla_step` with groups of 2
    lanes asked for: one body of the step, the slices that take a group's
    rows out and put them back, the sort of the rows a level and their
    scatter back; 478 -> 507 with the six leaves of the per-lane
    translation cache in those slices and sorts (PR 38: the cache did
    not move the step's own census, its walk is the same gathers at
    K_WALK = 2 addresses where it took six), and every pin up by its
    word reads once `overlay.image_words` / `read_data` took the two
    limbs of a word from a gather each (PR 38: 104 -> 120 a step body).
    PR 39 named every indexed operation of the chunk program and moved
    three counts on the way: rax's limbs for `mul` come by a static slice
    (the compiler made three dynamic-slices without an `op_name` of a read
    at a constant index: 120 -> 117 a step body), the source register's
    two limbs are read once in `vector-file` where twelve sites read them
    (the window's census is of the jaxpr: 171 -> 160), and the level's
    account is one elementwise add over the counter block where
    `CTR_TAIL` took a scatter (`tail_chunk` 556 -> 555).  PR 41 took the
    vector unit's indexed operations out (rows and bytes by selects): 117
    -> 89 a step body, the window 160 -> 131, `tail_chunk` 555 -> 525.
    PR 44 gave the store's third word a conditional of its own: the
    census counts OPERATIONS, and the taken side's two scatters are new
    ones (89 -> 91, 131 -> 133, 525 -> 527), while the indices every
    pass pays fell (the entry lines say so; tests/test_chip_compile.py
    counts them on the chip's compile)."""
    import wtf_tpu.analysis as analysis

    budgets = json.loads(
        (Path(analysis.__file__).parent / "budgets.json").read_text())
    assert budgets[entry]["total"] == total


def test_telemetry_report_prints_occupancy_and_sweeps_per_chunk(
        kernel_campaign, capsys):
    import telemetry_report

    telem = kernel_campaign["target"] / "telem"
    summary = telemetry_report.summarize(telem / "events.jsonl")
    metrics = kernel_campaign["metrics"]
    assert summary["device"]["lane_occupancy"] == round(
        metrics["device.instructions"] / metrics["device.lane_steps"], 4)
    assert summary["device"]["sweeps_per_chunk"] == round(
        metrics["device.sweeps"] / metrics["runner.chunks"], 2)
    telemetry_report._print_human(summary)
    out = capsys.readouterr().out
    assert "lane occupancy: " in out and "sweeps/chunk: " in out
    # a stream from before the counter reads null, not 0
    summary["device"]["lane_occupancy"] = None
    summary["device"]["sweeps_per_chunk"] = None
    telemetry_report._print_human(summary)
    out = capsys.readouterr().out
    assert "lane occupancy: null" in out and "sweeps/chunk: null" in out


def test_telemetry_report_prints_lockstep_floor_share_beside_occupancy(
        kernel_campaign, capsys):
    import telemetry_report

    telem = kernel_campaign["target"] / "telem"
    summary = telemetry_report.summarize(telem / "events.jsonl")
    metrics = kernel_campaign["metrics"]
    floor = metrics["device.lockstep_floor_steps"]
    assert metrics["device.instructions"] <= floor
    assert summary["device"]["lockstep_floor_share"] == round(
        floor / metrics["device.lane_steps"], 4)
    telemetry_report._print_human(summary)
    out = capsys.readouterr().out
    assert out.index("lane occupancy: ") < out.index("lockstep floor: ")
    # a stream without the counter (the megachunk window) prints no line
    summary["device"]["lockstep_floor_share"] = None
    telemetry_report._print_human(summary)
    assert "lockstep floor" not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# PR 39: tools/xprof_scopes.py reads a window by the one scope list
# ---------------------------------------------------------------------------

def _scope_ops():
    import xprof_scopes

    ms = 1e6
    op = xprof_scopes.Op
    # two levels' sorts, a sweep of two groups, an 8-ms gap, the write-back
    return [
        op("%sort.1 = ...", 0.0, 1 * ms, "jit_run_chunk"),
        op("%fusion.7 = ...", 1 * ms, 2 * ms, "jit_run_chunk"),
        op("%fusion.9 = ...", 3 * ms, 0.5 * ms, "jit_run_chunk"),
        op("%fusion.7 = ...", 3.5 * ms, 2 * ms, "jit_run_chunk"),
        op("%sort.1 = ...", 5.5 * ms, 1 * ms, "jit_run_chunk"),
        op("%scatter.3 = ...", 14.5 * ms, 3 * ms, "jit_run_chunk"),
    ]


_SCOPE_HLO = {"jit_run_chunk": {
    "sort.1": ("jit(run_chunk)/while/body/level-sort/sort",
               {"level-sort": 1}),
    "fusion.7": ("jit(run_chunk)/while/body/vmap(page-walk)/gather",
                 {"page-walk": 3, "step_lane inline": 1}),
    "fusion.9": ("jit(run_chunk)/while/body/vmap()/add", {}),
    "scatter.3": ("jit(run_chunk)/lane-writeback/scatter",
                  {"lane-writeback": 1}),
}}


def test_xprof_scopes_takes_its_list_from_the_program():
    import xprof_scopes

    from wtf_tpu.telemetry import xprof

    assert xprof_scopes.SCOPES is xprof.SCOPES
    assert set(xprof.CHUNK_SCOPES) | set(xprof.STEP_SCOPES) <= set(
        xprof.SCOPES)
    assert len(set(xprof.SCOPES)) == len(xprof.SCOPES)
    assert xprof.scope_of("jit(f)/vmap(sse-fp)/vmap(vector-file)/gather") \
        == "vector-file"                    # the innermost
    assert xprof.scope_of("jit(f)/vmap()/gather") is None
    assert xprof.scope_of("jit(f)/not-a-tlb-scope/add") is None
    assert set(xprof_scopes.PASS_OF) <= set(xprof.CHUNK_SCOPES)


def test_xprof_scopes_sums_per_pass_and_counts_unscoped_indexed_ops():
    import collections

    import xprof_scopes

    hlo = {m: {k: (name, collections.Counter(inner), not k.startswith("f"))
               for k, (name, inner) in table.items()}
           for m, table in _SCOPE_HLO.items()}
    # the gather of fusion.7 that stands in no scope has a row of its own
    hlo["jit_run_chunk"]["gather.5"] = (
        "jit(run_chunk)/while/body/vmap()/gather",
        collections.Counter({"step_lane inline": 1}), True)
    out = xprof_scopes.reduce(_scope_ops(), hlo)
    assert out["by_root"]["page-walk"][0] == pytest.approx(0.004)
    assert out["by_root"]["step_lane inline"][0] == pytest.approx(0.0005)
    assert out["per_pass"]["level-sort"] == {
        "passes": 2, "of": "level", "us_per_pass": 1000.0}
    assert out["per_pass"]["page-walk"] == {
        "passes": 2, "of": "group sweep", "us_per_pass": 2000.0}
    assert out["per_pass"]["lane-writeback"]["of"] == "chunk"
    # one indexed operation of fusion.7's four stands in no scope, and is
    # counted once; none is the compiler's own
    assert (out["unscoped_indexed_ops"], out["unnamed_indexed_ops"]) == (1, 0)
    assert out["unscoped_indexed_s"] == pytest.approx(0.001)
    assert out["by_gathers"]["step_lane inline"][0] == pytest.approx(0.0015)


def test_xprof_scopes_lays_a_gap_against_the_innermost_host_span():
    import xprof_scopes

    ms = 1e6
    host = [("execute", 0.0, 20 * ms),
            ("execute/status-pull", 6.6 * ms, 1.0 * ms),
            ("execute/device-step", 7.8 * ms, 9 * ms),
            ("execute/device-step/dispatch", 7.9 * ms, 6.4 * ms),
            ("open at start: execute/device-step/wait", 0.0, 20 * ms)]
    found = xprof_scopes.gaps(_scope_ops(), host, min_ms=5.0)
    assert len(found) == 1
    gap = found[0]
    assert (gap["at_ms"], gap["ms"]) == (6.5, 8.0)
    assert (gap["after"], gap["before"]) == ("sort.1", "scatter.3")
    # the middle of the gap lies in `dispatch`, the shortest span there;
    # the tracer thread's own row comes last
    assert gap["host"][0] == ("execute/device-step/dispatch", 0.8)
    assert [name for name, _ in gap["host"]][1] == "execute/device-step"
    assert xprof_scopes.gaps(_scope_ops(), [], min_ms=5.0)[0]["host"] == []
    assert xprof_scopes.gaps(_scope_ops(), host, min_ms=9.0) == []
