"""PR 25's nine per-layer metrics: data files read by the harness's own
`heartbeat_delta` evaluator, held to values worked out by hand.  (PR 42
retired `device_ms_per_sweep`, which changed meaning with PR 31; its place
here is taken by `device_us_per_lane_step`, which reads the same span.)"""

import json

import pytest

from harness import cells, window

ROOT = cells.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["lane_occupancy", "sweeps_per_chunk", "device_us_per_lane_step",
       "lane_service_s_per_batch", "execute_spanned_share",
       "harvest_save_share", "loop_spanned_share",
       "result_pulls_per_batch", "harvest_spanned_share"]

# two heartbeats 50 s and 2 batches apart; every delta below is
# last - first
FIRST = {"ts": 1000.0, "metrics": {
    "backend.batches": 2, "runner.chunks": 7, "backend.result_pulls": 6000,
    "device.instructions": 100_000, "device.lane_steps": 4_000_000,
    "device.sweeps": 1000,
    "phase.seconds": {
        "mutate": 0.5, "execute": 40.0, "harvest": 16.0, "restore": 1.0,
        "heartbeat": 0.5, "checkpoint": 0.0,
        "execute/insert": 0.5, "execute/device-step": 30.0,
        "execute/status-pull": 0.5, "execute/service-pull": 0.25,
        "execute/service-push": 0.25, "execute/service-bp": 0.5,
        "execute/service-exception": 1.0, "execute/cov-readback": 0.5,
        "execute/results": 5.0,
        "harvest/account": 1.0, "harvest/crash-save": 12.0,
        "harvest/corpus-add": 2.0}}}
LAST = {"ts": 1050.0, "metrics": {
    "backend.batches": 4, "runner.chunks": 14,
    "backend.result_pulls": 27_000,
    "device.instructions": 275_000, "device.lane_steps": 11_000_000,
    "device.sweeps": 2750,
    "phase.seconds": {
        "mutate": 1.0, "execute": 80.0, "harvest": 24.0, "restore": 2.0,
        "heartbeat": 1.0, "checkpoint": 0.0,
        "execute/insert": 1.0, "execute/device-step": 65.0,
        "execute/status-pull": 1.0, "execute/service-pull": 0.5,
        "execute/service-push": 0.5, "execute/service-bp": 1.0,
        "execute/service-exception": 2.0, "execute/cov-readback": 1.0,
        "execute/results": 6.0,
        "harvest/account": 1.5, "harvest/crash-save": 18.0,
        "harvest/corpus-add": 2.5}}}

BY_HAND = {
    # 100 x 175,000 instructions / 7,000,000 lane-steps
    "lane_occupancy": 2.5,
    # 1750 sweeps / 7 chunks
    "sweeps_per_chunk": 250.0,
    # 1e6 x 35 s / 7,000,000 lane-steps
    "device_us_per_lane_step": 5.0,
    # (0.5 + 1.0 + 0.5 + 1.0) s / 2 batches
    "lane_service_s_per_batch": 1.5,
    # 100 x (0.5 + 35 + 0.5 + 0.25 + 0.25 + 0.5 + 1 + 0.5 + 1) / 40
    "execute_spanned_share": 98.75,
    # 100 x 6 / 8
    "harvest_save_share": 75.0,
    # 100 x (0.5 + 40 + 8 + 1 + 0.5 + 0) / 50
    "loop_spanned_share": 100.0,
    # 21,000 pulls / 2 batches
    "result_pulls_per_batch": 10500.0,
    # 100 x (0.5 + 6 + 0.5) / 8
    "harvest_spanned_share": 87.5,
}

# a key of the denominator that neither heartbeat has: nothing to read
DENOMINATOR = {
    "lane_occupancy": "device.lane_steps",
    "sweeps_per_chunk": "runner.chunks",
    "device_us_per_lane_step": "device.lane_steps",
    "lane_service_s_per_batch": "backend.batches",
    "execute_spanned_share": "phase.seconds.execute",
    "harvest_save_share": "phase.seconds.harvest",
    "result_pulls_per_batch": "backend.batches",
    "harvest_spanned_share": "phase.seconds.harvest",
}


def spec(name):
    return json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())


def without(record, key):
    metrics = json.loads(json.dumps(record["metrics"]))
    if key.startswith("phase.seconds."):
        del metrics["phase.seconds"][key[len("phase.seconds."):]]
    else:
        del metrics[key]
    return {"ts": record["ts"], "metrics": metrics}


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_the_value_worked_out_by_hand(name):
    source = spec(name)["source"]
    assert source["kind"] == "heartbeat_delta"
    assert window.evaluate(source, FIRST, LAST) == pytest.approx(
        BY_HAND[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(DENOMINATOR))
def test_metric_is_absent_where_the_program_has_no_such_key(name):
    key = DENOMINATOR[name]
    assert window.evaluate(spec(name)["source"], without(FIRST, key),
                           without(LAST, key)) is None


def test_a_numerator_key_the_parent_lacks_reads_zero_and_does_not_raise():
    source = spec("harvest_save_share")["source"]
    key = "phase.seconds.harvest/crash-save"
    assert window.evaluate(source, without(FIRST, key),
                           without(LAST, key)) == 0.0


def test_the_nine_are_there_and_the_benchmark_has_no_problem():
    """The named entries are in the index, wherever later PRs put theirs."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(NEW) <= set(by_name)
    assert cells.problems(ROOT) == []
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = by_name[name]
        assert "kernel-default" in m["workloads"]
        assert m["layer"] in layers
        assert (ROOT / "benchmark" / "layer_metrics"
                / f"{name}.json").is_file()
        assert not (ROOT / "benchmark" / "layer_metrics"
                    / f"{name}.py").exists()
