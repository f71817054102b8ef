"""Observability-plane tests (ISSUE 18): fleet telemetry aggregation
exactness (merge semantics, sequence-number dedup, reconnect epochs,
per-node rates), the TAG_TELEM wire codec, the `wtf-tpu status` surface, and
the telemetry lint family.  The socket-level end-to-end (a real master +
faulted clients) lives in wtf_tpu/testing/obs_smoke.py; these tests pin the
EXACT counts that chaos makes racy there."""

import json
import sys
from pathlib import Path

import pytest

from wtf_tpu.dist import wire
from wtf_tpu.fleet.telemetry import (
    FleetTelemetry, NodeTelemetry, render_prometheus,
)
from wtf_tpu.telemetry import Registry
from wtf_tpu.telemetry.metrics import merge_snapshots

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))


def _node_registry(testcases, crashes=0, lat=()):
    reg = Registry()
    reg.counter("campaign.testcases").inc(testcases)
    if crashes:
        reg.counter("campaign.crashes").inc(crashes)
    reg.gauge("supervise.rung").set(2)
    reg.counter("fallbacks").labels("ssefp").inc(testcases % 5)
    for v in lat:
        reg.histogram("chunk.lat").observe(v)
    return reg


# ---------------------------------------------------------------------------
# snapshot merge semantics
# ---------------------------------------------------------------------------

def test_merge_snapshots_equals_serial_sum():
    regs = [_node_registry(10, 1, lat=(0.5, 1.5)),
            _node_registry(20, 0, lat=(1.0,)),
            _node_registry(3, 2, lat=(0.1, 9.0))]
    merged = merge_snapshots(r.snapshot() for r in regs)
    # counters sum per label
    assert merged["campaign.testcases"]["value"] == 33
    assert merged["campaign.crashes"]["value"] == 3
    assert merged["fallbacks"]["labels"]["ssefp"] == sum(
        n % 5 for n in (10, 20, 3))
    # gauges sum (a fleet gauge is capacity-like: total across nodes)
    assert merged["supervise.rung"]["value"] == 6
    # histograms: count/sum add, min/max extremize
    h = merged["chunk.lat"]
    assert h["count"] == 5 and h["sum"] == pytest.approx(12.1)
    assert h["min"] == 0.1 and h["max"] == 9.0


def test_snapshot_restore_round_trip():
    reg = _node_registry(7, 1, lat=(2.0, 3.0))
    clone = Registry()
    clone.restore_snapshot(reg.snapshot())
    assert json.dumps(clone.snapshot(), sort_keys=True) == \
        json.dumps(reg.snapshot(), sort_keys=True)
    assert clone.dump() == reg.dump()


def test_telem_wire_round_trip():
    snapshot = _node_registry(5).snapshot()
    events = [{"type": "crash", "name": "crash-read-0x1"}]
    body = wire.encode_telem(42, snapshot, events)
    seq, snap2, ev2 = wire.decode_telem(body)
    assert seq == 42 and ev2 == events
    assert json.dumps(snap2, sort_keys=True) == \
        json.dumps(snapshot, sort_keys=True)


# ---------------------------------------------------------------------------
# aggregator: exact no-double-count accounting (fault-free)
# ---------------------------------------------------------------------------

def test_node_telemetry_drops_stale_and_duplicate_frames():
    node = NodeTelemetry("aa")
    s1 = {"campaign.testcases": {"kind": "c", "value": 10}}
    s2 = {"campaign.testcases": {"kind": "c", "value": 20}}
    assert node.apply(1, s1, now=1.0)
    assert not node.apply(1, s1, now=2.0)   # verbatim re-send
    assert node.apply(2, s2, now=3.0)
    assert not node.apply(1, s1, now=4.0)   # stale replay
    assert node.seq == 2 and node.snapshot == s2
    # rate between the two applied frames: 10 execs over 2s
    assert node.execs_per_s == pytest.approx(5.0)


def test_node_telemetry_reconnect_epoch_resets_sequence():
    node = NodeTelemetry("bb")
    assert node.apply(5, {"campaign.testcases": {"kind": "c", "value": 9}},
                      now=1.0)
    # reconnect: the client's cursor restarts at seq 0 (well, 1 after
    # its first frame) — seq 0 explicitly reopens the window
    assert node.apply(0, {"campaign.testcases": {"kind": "c", "value": 9}},
                      now=2.0)
    assert node.epoch == 1
    assert node.apply(1, {"campaign.testcases": {"kind": "c", "value": 12}},
                      now=3.0)
    assert node.seq == 1 and node.epoch == 1


def test_fleet_telemetry_exact_counts_under_resends(tmp_path):
    """The obs_smoke invariant, fault-free so the counts are EXACT: N
    applied frames, every scripted duplicate dropped, aggregate equal to
    the serial sum of the latest per-node snapshots."""
    clock = iter(float(t) for t in range(1, 100))
    fleet = FleetTelemetry(export_dir=tmp_path / "export",
                           clock=lambda: next(clock))
    last = {}
    dup_sends = 0
    for step in (1, 2, 3):
        for i, cid in enumerate((b"\x01" * 8, b"\x02" * 8, b"\x03" * 8)):
            snapshot = _node_registry(step * 10 + i).snapshot()
            assert fleet.apply(cid, step, snapshot)
            last[cid] = snapshot
            if step == 2:  # re-send every node's frame once
                assert not fleet.apply(cid, step, snapshot)
                dup_sends += 1
    assert fleet.frames == 9
    assert fleet.duplicates == dup_sends == 3
    assert json.dumps(fleet.fleet_snapshot(), sort_keys=True) == \
        json.dumps(merge_snapshots(last.values()), sort_keys=True)

    # reconnect replay: node 1 comes back at seq 0 with its running
    # totals — supersedes, never adds
    replay = last[b"\x01" * 8]
    assert fleet.apply(b"\x01" * 8, 0, replay)
    assert json.dumps(fleet.fleet_snapshot(), sort_keys=True) == \
        json.dumps(merge_snapshots(last.values()), sort_keys=True)
    assert fleet.nodes[(b"\x01" * 8).hex()].epoch == 1

    # exports: status doc + prom text + one stream record per applied
    assert fleet.write_exports()
    status = json.loads((tmp_path / "export" / "status.json").read_text())
    assert status["kind"] == "fleet" and status["nodes"] == 3
    assert status["frames"] == 10 and status["duplicates_dropped"] == 3
    rows = {r["node"]: r for r in status["per_node"]}
    assert rows[(b"\x02" * 8).hex()]["testcases"] == 31
    assert status["metrics"]["campaign.testcases"] == 31 + 30 + 32
    prom = (tmp_path / "export" / "telemetry.prom").read_text()
    assert "# TYPE wtf_campaign_testcases counter" in prom
    assert f"wtf_campaign_testcases {31 + 30 + 32}" in prom
    stream = [json.loads(ln) for ln in
              (tmp_path / "export" / "fleet-telem.jsonl")
              .read_text().splitlines()]
    assert len(stream) == fleet.frames == 10
    fleet.close()


def test_render_prometheus_shapes():
    reg = Registry()
    reg.counter("a.b").inc(2)
    reg.gauge("g").set(7)
    reg.counter("lab").labels('x"y\\z').inc(3)
    reg.histogram("h").observe(1.5)
    text = render_prometheus(reg.snapshot())
    assert "# TYPE wtf_a_b counter\nwtf_a_b 2" in text
    assert "# TYPE wtf_g gauge\nwtf_g 7" in text
    assert 'wtf_lab{label="x\\"y\\\\z"} 3' in text
    assert "wtf_h_count 1" in text and "wtf_h_sum 1.5" in text
    assert "wtf_h_min 1.5" in text and "wtf_h_max 1.5" in text


# ---------------------------------------------------------------------------
# `wtf-tpu status`
# ---------------------------------------------------------------------------

CAMPAIGN_DOC = {
    "kind": "campaign", "ts": 0.0, "batches": 12,
    "line": "#768 cov: 41 corp: 9 exec/s: 504.9 zh: 100% pre: 4/5(-1)",
    "metrics": {
        "campaign.testcases": 768,
        "device.instructions": 1000,
        "megachunk.windows": 5,
        "devdec.zero_host_windows": 5,
        "megachunk.prelaunched": 5,
        "megachunk.prelaunch_hits": 4,
        "megachunk.prelaunch_dropped": 1,
        "supervise.dispatches": 40,
        "supervise.rung": 1,
        "supervise.rebuilds": 2,
        "supervise.quarantined_lanes": 1,
        "dist.cov_bytes_delta": 100,
        "dist.cov_bytes_bitmap": 1700,
        "tenant.demo_tlv.testcases": 700,
        "tenant.demo_tlv.new_coverage": 41,
        "tenant.demo_tlv.crashes": 2,
        "phase.seconds": {"execute": 10.0, "execute/device-step": 9.0,
                          "harvest": 1.0},
    },
}


def test_status_json_golden(tmp_path, capsys):
    """--json emits the status.json document verbatim — the machine
    surface dashboards scrape."""
    from wtf_tpu.cli import main

    (tmp_path / "status.json").write_text(json.dumps(CAMPAIGN_DOC))
    assert main(["status", str(tmp_path), "--json"]) == 0
    out = capsys.readouterr().out.strip()
    assert json.loads(out) == CAMPAIGN_DOC


def test_status_renders_derived_rows(tmp_path, capsys):
    from wtf_tpu.cli import main

    (tmp_path / "status.json").write_text(json.dumps(CAMPAIGN_DOC))
    assert main(["status", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "campaign: batch 12" in out
    assert CAMPAIGN_DOC["line"] in out
    assert "zero-host windows: 5/5 (100%)" in out
    assert "prelaunch: 4/5 adopted, 1 dropped" in out
    # top-level 11s, device-fenced 9s -> host share 2/11
    assert "host share: 18.2% of accounted wall" in out
    assert "supervisor: rung 1, 2 rebuilds, 1 lanes quarantined" in out
    assert "delta frames: 1600 cov bytes saved (17.0x smaller)" in out
    assert "tenant demo_tlv: execs=700 newcov=41 crashes=2" in out


def test_status_minimal_campaign_has_no_phantom_rows(tmp_path, capsys):
    """Subsystem rows appear only when the subsystem ran: a plain emu
    campaign shows the heartbeat line and nothing else."""
    from wtf_tpu.cli import main

    doc = {"kind": "campaign", "ts": 0.0, "batches": 1,
           "line": "#10 exec/s: 5.0",
           "metrics": {"campaign.testcases": 10}}
    (tmp_path / "status.json").write_text(json.dumps(doc))
    assert main(["status", str(tmp_path)]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(lines) == 2  # header + heartbeat line


def test_status_missing_dir_fails_cleanly(tmp_path, capsys):
    from wtf_tpu.cli import main

    assert main(["status", str(tmp_path)]) == 1
    assert "no status.json" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# telemetry lint family
# ---------------------------------------------------------------------------

def test_telemetry_lint_flags_inline_serialization():
    """The family's teeth: a seam whose source serializes the registry
    (here: write_exports, which legitimately calls json.dumps — standing
    in for a dispatch seam that shouldn't) is a finding; a serialization-
    free seam is clean."""
    from wtf_tpu.analysis.rules import check_telemetry_seams

    dirty = check_telemetry_seams(sites={
        "exports": "wtf_tpu.fleet.telemetry:FleetTelemetry.write_exports"})
    assert len(dirty) == 1
    f = dirty[0]
    assert f.rule == "telemetry.seam-serialization"
    assert "json.dumps(" in f.primitive
    clean = check_telemetry_seams(sites={
        "apply": "wtf_tpu.fleet.telemetry:NodeTelemetry.apply"})
    assert clean == []
    # unresolvable sites are the supervise family's finding, not ours
    assert check_telemetry_seams(sites={"x": "no.such.module:Nope"}) == []


def test_telemetry_lint_real_seams_are_clean():
    """The live SEAM_SITES enumeration must hold the pin today — the
    dispatch hot path serializes nothing."""
    from wtf_tpu.analysis.rules import check_telemetry_seams

    assert check_telemetry_seams() == []
