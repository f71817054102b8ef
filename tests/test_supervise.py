"""Self-healing device runtime tests (wtf_tpu/supervise).

The acceptance contract (ISSUE 16): every device dispatch routes through the
supervisor (lint-pinned seam enumeration); a hung dispatch is abandoned by
the watchdog and the batch replays BIT-IDENTICALLY after a backend rebuild
from live host-side state; repeated failures walk the degradation ladder
(megachunk -> batch-at-a-time -> fixed-chunk) and hysteresis re-promotes
after clean batches, every rung bit-identical at equal seeds; lanes failing
the on-device integrity check are quarantined (masked idle, never harvested)
while the campaign completes; the max_chunks satellite revokes stuck lanes
as per-lane TIMEDOUT instead of aborting the batch; and the scripted
device-fault chaos (hang/error/poison on the Nth dispatch) is
operation-indexed, never wall-clock.
"""

import sys
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from wtf_tpu.analysis.rules import (
    check_seam_enumeration, check_supervised_seams,
)
from wtf_tpu.analysis.trace import build_tlv_campaign
from wtf_tpu.harness import demo_tlv
from wtf_tpu.interp.runner import Runner, warm_decode_cache
from wtf_tpu.core.results import StatusCode
from wtf_tpu.resume import load_campaign, restore_campaign
from wtf_tpu.supervise import (
    DEVICE_ERROR, DEVICE_HANG, DEVICE_POISON, SEAM_SITES, DegradationLadder,
    DispatchError, DispatchHang, Supervisor,
)
from wtf_tpu.supervise import integrity
from wtf_tpu.telemetry import EventLog, Registry
from wtf_tpu.testing.faultinject import (
    FaultPlan, chaos_device, fuzz_until_killed,
)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

# the chaos/device-chaos smoke shapes: compile-cache-shared across suite
LANES, BATCHES = 8, 4
RUNS = LANES * BATCHES
SEED = 0xC4A05 & 0xFFFF
BUILD = dict(n_lanes=LANES, mutator="devmangle", limit=20_000, seed=SEED,
             chunk_steps=128, overlay_slots=16)


def _state_of(loop) -> tuple:
    return (loop._coverage(), sorted(loop.corpus.digests),
            sorted(loop.crash_names), loop.stats.testcases,
            int(np.asarray(loop.backend.coverage_state()[1]).sum()))


@pytest.fixture(scope="module")
def ref_state():
    """The unsupervised fault-free reference: the bit-identity bar for
    every recovery leg (megachunk and mesh baselines are pinned equal to
    the plain path by their own parity tiers)."""
    loop = build_tlv_campaign(**BUILD)
    loop.fuzz(RUNS)
    return _state_of(loop)


# ---------------------------------------------------------------------------
# supervisor unit: dispatch guard, watchdog, timeout scaling
# ---------------------------------------------------------------------------

def test_dispatch_passthrough_when_inactive():
    """Supervision off and no chaos armed: the guard is a plain call —
    no op-index advance, no counters, nothing wrapped."""
    sup = Supervisor()
    sentinel = object()
    assert sup.dispatch("chunk", lambda: sentinel) is sentinel
    assert sup.registry.counter("supervise.dispatches").value == 0


def test_watchdog_abandons_hung_dispatch(monkeypatch):
    """A dispatch that never completes within the deadline raises
    DispatchHang from the host timer thread — the waiter is abandoned,
    not joined, so the guard returns promptly."""
    from wtf_tpu.supervise import supervisor as sup_mod

    monkeypatch.setattr(sup_mod, "_wait_ready",
                        lambda value: time.sleep(5.0))
    sup = Supervisor(enabled=True, dispatch_timeout=0.05)
    t0 = time.monotonic()
    with pytest.raises(DispatchHang) as ei:
        sup.dispatch("chunk", lambda: object())
    assert time.monotonic() - t0 < 2.0, "watchdog waited on the dead wait"
    assert ei.value.seam == "chunk"
    assert sup.registry.counter("supervise.watchdog_fires").value == 1


@pytest.mark.parametrize("boom", [
    ValueError("The Pallas TPU lowering currently requires that rank 1 "
               "block shapes ..."),
    jax.errors.JaxRuntimeError(
        "UNIMPLEMENTED: While rewriting computation to not contain X64 "
        "element types ..."),
    jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: ..."),
], ids=["lowering", "x64-rewrite", "mosaic"])
def test_dispatch_reraises_compile_time_refusal(boom):
    """A refusal raised while lowering or compiling is deterministic: it
    re-raises as it is, so no ladder rung can turn it into exit 0."""
    sup = Supervisor(enabled=True)

    def fn():
        raise boom

    with pytest.raises(type(boom)) as ei:
        sup.dispatch("chunk", fn)
    assert ei.value is boom
    assert sup.registry.counter("supervise.device_errors").value == 0


def test_dispatch_error_wraps_backend_exception():
    sup = Supervisor(enabled=True)
    boom = jax.errors.JaxRuntimeError("INTERNAL: device halted")

    def fn():
        raise boom

    with pytest.raises(DispatchError) as ei:
        sup.dispatch("chunk", fn)
    assert ei.value.__cause__ is boom
    assert ei.value.index == 0
    assert sup.registry.counter("supervise.device_errors").value == 1


def test_timeout_scales_with_steps_and_window():
    """--dispatch-timeout is calibrated to one base chunk; adaptive
    rungs and megachunk windows get proportionally longer."""
    sup = Supervisor(enabled=True, dispatch_timeout=2.0)
    assert sup.timeout_for(0, 1) == 2.0
    assert sup.timeout_for(128, 1) == 2.0          # below base: no shrink
    assert sup.timeout_for(512, 1) == 4.0          # 2x the 256 base
    assert sup.timeout_for(0, 3) == 6.0            # 3-batch window
    assert sup.timeout_for(512, 2) == 8.0


def test_scripted_faults_are_operation_indexed(monkeypatch):
    """The chaos schedule keys on the global dispatch index — the same
    plan fires on the same dispatch every run, no wall-clock anywhere."""
    plan = FaultPlan([], device_faults={2: DEVICE_HANG, 4: DEVICE_ERROR})
    sup = Supervisor(enabled=True)
    seen = []
    with chaos_device(plan):
        for i in range(6):
            try:
                sup.dispatch("chunk", lambda: i)
            except DispatchHang:
                seen.append(("hang", i))
            except DispatchError:
                seen.append(("error", i))
    assert seen == [("hang", 2), ("error", 4)]
    assert [f[:2] for f in plan.fired] == [("device-hang", "chunk"),
                                           ("device-error", "chunk")]


# ---------------------------------------------------------------------------
# degradation ladder unit
# ---------------------------------------------------------------------------

def _stub_loop(megachunk=2, adaptive=True):
    runner = types.SimpleNamespace(adaptive_chunks=adaptive)
    return types.SimpleNamespace(
        backend=types.SimpleNamespace(runner=runner), megachunk=megachunk)


def test_ladder_rungs_skip_inapplicable_features():
    full = DegradationLadder(_stub_loop())
    assert full.rungs == ["full", "no-megachunk", "fixed-chunk"]
    bare = DegradationLadder(_stub_loop(megachunk=0, adaptive=False))
    assert bare.rungs == ["full"]
    assert not bare.on_failure()       # nothing left to turn off
    assert bare.wants_reshard


def test_ladder_degrade_apply_and_hysteresis_promotion():
    loop = _stub_loop()
    ladder = DegradationLadder(loop, promote_after=2)
    assert ladder.rung_name == "full" and not ladder.megachunk_off

    assert ladder.on_failure() and ladder.rung_name == "no-megachunk"
    assert ladder.megachunk_off
    ladder.apply(loop)
    assert loop.backend.runner.adaptive_chunks is True
    assert ladder.on_failure() and ladder.rung_name == "fixed-chunk"
    ladder.apply(loop)
    assert loop.backend.runner.adaptive_chunks is False

    # hysteresis: promote_after CONSECUTIVE cleans win one rung back
    assert not ladder.on_clean()
    assert ladder.on_clean() and ladder.rung_name == "no-megachunk"
    assert not ladder.on_clean()       # streak reset by the promotion
    ladder.on_failure()                # a failure resets the streak too
    assert ladder.rung_name == "fixed-chunk"
    assert not ladder.on_clean()
    assert ladder.on_clean()

    ladder.apply(loop)                 # back at no-megachunk: growth back on
    assert loop.backend.runner.adaptive_chunks is True


def test_ladder_bottom_requests_reshard():
    ladder = DegradationLadder(_stub_loop())
    for _ in range(len(ladder.rungs) - 1):
        assert ladder.on_failure()
    assert ladder.rung_name == "fixed-chunk"
    assert not ladder.wants_reshard
    assert not ladder.on_failure()     # bottom: no rung change
    assert ladder.wants_reshard


def test_heartbeat_fields():
    sup = Supervisor(enabled=True)
    assert sup.heartbeat_fields() == {"supervise_rung": "full",
                                     "supervise_quarantined": 0}
    sup.ladder = DegradationLadder(_stub_loop())
    sup.ladder.on_failure()
    sup.quarantined.add(3)
    fields = sup.heartbeat_fields()
    assert fields["supervise_rung"] == "no-megachunk"
    assert fields["supervise_quarantined"] == 1


# ---------------------------------------------------------------------------
# integrity check unit (real machine pytree)
# ---------------------------------------------------------------------------

def test_integrity_flags_only_the_poisoned_lane():
    loop = build_tlv_campaign(**BUILD)
    machine = loop.backend.runner.machine
    bad, digest = integrity.check_machine(machine)
    assert not np.asarray(bad).any(), "clean snapshot machine flagged"

    poisoned = integrity.poison_machine(machine, 2)
    bad2, digest2 = integrity.check_machine(poisoned)
    assert np.asarray(bad2).tolist() == [lane == 2 for lane in range(LANES)]
    assert int(np.asarray(digest2)) != int(np.asarray(digest))

    # the write-side mask parks lanes the way untasked lanes idle
    masked = integrity.mask_idle(poisoned, np.arange(LANES) == 2)
    assert int(np.asarray(masked.status)[2]) == int(StatusCode.OK)


# ---------------------------------------------------------------------------
# recovery parity: every leg bit-identical to the fault-free reference
# ---------------------------------------------------------------------------

def test_supervised_fault_free_is_bit_identical(ref_state):
    sup = build_tlv_campaign(supervise=True, dispatch_timeout=30.0, **BUILD)
    sup.fuzz(RUNS)
    assert _state_of(sup) == ref_state
    reg = sup.backend.supervisor.registry
    assert reg.counter("supervise.dispatches").value > 0
    assert reg.counter("supervise.integrity_checks").value >= BATCHES
    assert reg.counter("supervise.rebuilds").value == 0


def test_error_recovery_replays_bit_identical(ref_state):
    """A scripted device error mid-campaign: abandon, rebuild from host
    state, replay the batch — and the ladder cycles down then back up."""
    plan = FaultPlan([], device_faults={10: DEVICE_ERROR})
    loop = build_tlv_campaign(supervise=True, dispatch_timeout=30.0,
                              promote_after=2, **BUILD)
    with chaos_device(plan):
        loop.fuzz(RUNS)
    assert _state_of(loop) == ref_state
    reg = loop.backend.supervisor.registry
    assert reg.counter("supervise.batch_retries").value >= 1
    assert reg.counter("supervise.rebuilds").value >= 1
    assert reg.counter("supervise.degradations").value >= 1
    assert reg.counter("supervise.promotions").value >= 1
    assert len(plan.fired) == 1


def test_hang_recovery_replays_bit_identical(ref_state):
    plan = FaultPlan([], device_faults={6: DEVICE_HANG})
    loop = build_tlv_campaign(supervise=True, dispatch_timeout=30.0,
                              **BUILD)
    with chaos_device(plan):
        loop.fuzz(RUNS)
    assert _state_of(loop) == ref_state
    reg = loop.backend.supervisor.registry
    assert reg.counter("supervise.watchdog_fires").value == 1
    assert reg.counter("supervise.rebuilds").value >= 1


def test_transient_poison_replays_bit_identical(ref_state):
    """Below the quarantine threshold a poisoned lane is a replay, not a
    quarantine: the batch re-runs clean and nothing is masked."""
    plan = FaultPlan([], device_faults={13: (DEVICE_POISON, 3)})
    loop = build_tlv_campaign(supervise=True, dispatch_timeout=30.0,
                              **BUILD)
    with chaos_device(plan):
        loop.fuzz(RUNS)
    assert _state_of(loop) == ref_state
    sup = loop.backend.supervisor
    assert sup.registry.counter("supervise.poisoned_lanes").value >= 1
    assert sup.quarantined == set()


def test_persistent_quarantine_masks_lane_and_completes():
    """quarantine_threshold=1: the violating lane is quarantined on
    first sight, masked idle (never harvested), and the campaign still
    completes every testcase on the surviving lanes."""
    plan = FaultPlan([], device_faults={6: (DEVICE_POISON, 3)})
    loop = build_tlv_campaign(supervise=True, dispatch_timeout=30.0,
                              quarantine_threshold=1, **BUILD)
    with chaos_device(plan):
        loop.fuzz(RUNS)
    sup = loop.backend.supervisor
    assert sup.quarantined == {3}
    assert loop.stats.testcases == RUNS
    assert sup.registry.counter("device.quarantined").value == 1
    assert sup.heartbeat_fields()["supervise_quarantined"] == 1
    # quarantine forces the batch-at-a-time path (windows can't mask)
    assert sup.megachunk_disabled


def test_megachunk_hang_degrades_and_repromotes(ref_state):
    """A hang mid-window: the watchdog abandons the in-flight window,
    the ladder drops to batch-at-a-time, replays bit-identically, and
    promote_after=1 re-promotes to megachunk within the campaign."""
    plan = FaultPlan([], device_faults={3: DEVICE_HANG})
    loop = build_tlv_campaign(megachunk=2, supervise=True,
                              dispatch_timeout=30.0, promote_after=1,
                              **BUILD)
    with chaos_device(plan):
        loop.fuzz(RUNS)
    assert _state_of(loop) == ref_state
    reg = loop.backend.supervisor.registry
    assert reg.counter("supervise.watchdog_fires").value == 1
    assert reg.counter("supervise.degradations").value >= 1
    assert reg.counter("supervise.promotions").value >= 1


def test_steady_window_hang_recovery_replays_bit_identical(ref_state):
    """DEVICE_HANG on a STEADY-STATE window: the watchdog abandons the
    in-flight window — whose machine/overlay planes are donated into the
    dispatch on real hardware — the rebuild reconstructs them from live
    host-side state, and the replayed campaign is bit-identical to the
    fault-free reference.  Index 5 is the first steady-state window in
    the supervised dispatch schedule (0 = cold window, 1-4 = cold-decode
    chunk servicing)."""
    plan = FaultPlan([], device_faults={5: DEVICE_HANG})
    loop = build_tlv_campaign(megachunk=2, supervise=True,
                              dispatch_timeout=30.0, promote_after=1,
                              **BUILD)
    with chaos_device(plan):
        loop.fuzz(RUNS)
    assert _state_of(loop) == ref_state
    assert plan.fired[0][:3] == ("device-hang", "megachunk", 5)
    assert len(plan.fired) == 1
    reg = loop.backend.supervisor.registry
    assert reg.counter("supervise.watchdog_fires").value == 1
    assert reg.counter("supervise.rebuilds").value >= 1


def test_cold_window_error_recovery_replays_bit_identical(ref_state):
    """DEVICE_ERROR on the COLD window (dispatch 0): the very first
    window's donated operands are rebuilt from the pristine host snapshot
    and the campaign replays bit-identically."""
    plan = FaultPlan([], device_faults={0: DEVICE_ERROR})
    loop = build_tlv_campaign(megachunk=2, supervise=True,
                              dispatch_timeout=30.0, promote_after=1,
                              **BUILD)
    with chaos_device(plan):
        loop.fuzz(RUNS)
    assert _state_of(loop) == ref_state
    assert [f[:2] for f in plan.fired] == [("device-error", "megachunk")]
    reg = loop.backend.supervisor.registry
    assert reg.counter("supervise.rebuilds").value >= 1


@pytest.mark.slow
def test_megachunk_hang_parity_at_every_dispatch_index(ref_state):
    """The window->legacy->window transition soak: a hang at EVERY index
    of the supervised megachunk dispatch schedule (window, cold-decode
    chunk servicing, resumed windows) recovers bit-identically."""
    probe = build_tlv_campaign(megachunk=2, supervise=True,
                               dispatch_timeout=30.0, **BUILD)
    probe.fuzz(RUNS)
    n_disp = probe.backend.supervisor.registry.counter(
        "supervise.dispatches").value
    assert _state_of(probe) == ref_state
    for idx in range(n_disp):
        plan = FaultPlan([], device_faults={idx: DEVICE_HANG})
        loop = build_tlv_campaign(megachunk=2, supervise=True,
                                  dispatch_timeout=30.0, promote_after=1,
                                  **BUILD)
        with chaos_device(plan):
            loop.fuzz(RUNS)
        assert _state_of(loop) == ref_state, \
            f"megachunk hang at dispatch {idx} broke parity ({plan.fired})"


def test_mesh_error_recovery_replays_bit_identical(ref_state):
    """On the conftest's forced 8-device mesh: a device error abandons
    the batch, the rebuilt sharded runner replays bit-identically."""
    plan = FaultPlan([], device_faults={8: DEVICE_ERROR})
    loop = build_tlv_campaign(mesh_devices=8, supervise=True,
                              dispatch_timeout=30.0, **BUILD)
    with chaos_device(plan):
        loop.fuzz(RUNS)
    assert _state_of(loop) == ref_state
    assert loop.backend.supervisor.registry.counter(
        "supervise.rebuilds").value >= 1


def test_device_chaos_with_kill_and_resume_parity(ref_state, tmp_path):
    """The combined soak: a supervised campaign takes a scripted device
    error, checkpoints every batch, is killed at a batch boundary, and
    the resumed campaign ends bit-identical to the fault-free run."""
    ckpt = tmp_path / "checkpoint"
    victim = build_tlv_campaign(supervise=True, dispatch_timeout=30.0,
                                **BUILD)
    victim.checkpoint_dir = ckpt
    victim.checkpoint_every = 1
    plan = FaultPlan([], device_faults={4: DEVICE_ERROR})
    with chaos_device(plan):
        fuzz_until_killed(victim, RUNS, kill_at_batch=2)
    assert len(plan.fired) == 1, "scripted error never fired before kill"

    state, fell_back = load_campaign(ckpt)
    assert not fell_back
    resumed = build_tlv_campaign(supervise=True, dispatch_timeout=30.0,
                                 **BUILD)
    resumed.checkpoint_dir = ckpt
    resumed.checkpoint_every = 1
    batch = restore_campaign(resumed, state, ckpt)
    assert batch == 2
    resumed.fuzz(RUNS)
    assert _state_of(resumed) == ref_state


# ---------------------------------------------------------------------------
# max_chunks satellite: per-lane TIMEDOUT revocation, not a batch abort
# ---------------------------------------------------------------------------

def test_max_chunks_revokes_stuck_lanes_as_timedout():
    snapshot = demo_tlv.build_snapshot()
    runner = Runner(snapshot, n_lanes=4, uop_capacity=1 << 10,
                    overlay_slots=16, edge_bits=12, chunk_steps=8)
    payload = b"\x01\x02AB\x03\x08CCCCCCCC"
    warm_decode_cache(runner, demo_tlv.TARGET, payload, limit=4096)
    view = runner.view()
    for lane in range(runner.n_lanes):
        view.virt_write(lane, demo_tlv.INPUT_GVA, payload)
        view.r["gpr"][lane, 2] = np.uint64(len(payload))
    runner.push(view)
    # 8 steps is nowhere near enough to parse the TLV stream: with the
    # chunk budget exhausted the lanes are revoked per-lane, not raised
    statuses = runner.run(max_chunks=1)
    assert (statuses == int(StatusCode.TIMEDOUT)).all()
    assert runner.registry.counter(
        "runner.max_chunks_timeouts").value == runner.n_lanes
    for lane in range(runner.n_lanes):
        assert "max_chunks" in runner.lane_errors[lane]


# ---------------------------------------------------------------------------
# lint: the supervise rule family
# ---------------------------------------------------------------------------

def test_lint_supervise_family_clean_on_real_tree():
    assert check_supervised_seams() == []
    assert check_seam_enumeration() == []
    # the enumeration covers every dispatch entry point the runtime has
    assert set(SEAM_SITES) == {"chunk", "device-insert", "devmut-generate",
                               "megachunk", "device-decode"}


def test_lint_supervise_flags_unrouted_seam():
    """A seam whose source never calls supervisor.dispatch with its own
    name is a finding — the rule reads the LIVE source, so a refactor
    that bypasses the guard fails lint immediately."""
    findings = check_supervised_seams(sites={
        "chunk": "wtf_tpu.supervise.ladder:DegradationLadder.apply"})
    assert len(findings) == 1
    assert findings[0].rule == "supervise.seam-routing"
    assert "chunk" in findings[0].message


def test_lint_supervise_flags_unresolvable_site():
    findings = check_supervised_seams(sites={
        "chunk": "wtf_tpu.supervise.no_such_module:Missing.fn"})
    assert len(findings) == 1
    assert findings[0].rule == "supervise.seam-routing"


# ---------------------------------------------------------------------------
# telemetry report: the device-resilience section
# ---------------------------------------------------------------------------

def test_telemetry_report_device_resilience_section(tmp_path, capsys):
    import telemetry_report

    reg = Registry()
    reg.counter("supervise.dispatches").inc(40)
    reg.counter("supervise.watchdog_fires").inc(1)
    reg.counter("supervise.device_errors").inc(2)
    reg.counter("supervise.rebuilds").inc(3)
    reg.counter("supervise.batch_retries").inc(3)
    reg.counter("supervise.degradations").inc(2)
    reg.counter("supervise.promotions").inc(1)
    reg.counter("supervise.integrity_checks").inc(12)
    reg.counter("device.quarantined").inc(1)
    reg.gauge("supervise.rung").set(1)
    reg.gauge("supervise.quarantined_lanes").set(1)
    sec = reg.counter("phase.seconds")
    sec.labels("execute").inc(9.0)
    sec.labels("execute/integrity").inc(0.06)
    sec.labels("execute/supervise-snapshot").inc(0.04)
    sec.labels("supervise-recover").inc(0.5)

    path = tmp_path / "events.jsonl"
    clock = iter([0.0, 10.0])
    with EventLog(path, clock=lambda: next(clock)) as log:
        log.emit("run-start")
        log.emit("run-end", metrics=reg.dump())
    summary = telemetry_report.summarize(path)
    dres = summary["device_resilience"]
    assert dres["watchdog_fires"] == 1
    assert dres["rebuilds"] == 3
    assert dres["quarantined_total"] == 1
    assert dres["quarantined_now"] == 1
    assert dres["final_rung"] == 1
    assert dres["integrity_seconds"] == 0.06
    assert dres["recover_seconds"] == 0.5
    # steady-state overhead = (integrity + snapshot) / wall, recovery out
    assert dres["overhead_share"] == round(0.1 / 10.0, 4)
    assert telemetry_report.main([str(path)]) == 0
    assert "device resilience" in capsys.readouterr().out

    # unsupervised stream: the section stays None (quiet runs stay quiet)
    path2 = tmp_path / "plain.jsonl"
    clock2 = iter([0.0, 1.0])
    with EventLog(path2, clock=lambda: next(clock2)) as log:
        log.emit("run-start")
        log.emit("run-end", metrics=Registry().dump())
    assert telemetry_report.summarize(path2)["device_resilience"] is None
