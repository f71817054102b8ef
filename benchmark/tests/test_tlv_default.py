"""PR 28's cell `tlv-default` (configuration `tlv-user`) and its three
per-layer metrics: files and index entries only, read by the harness as
it stands.  (PR 42 retired `lockstep_floor_share`, which reads over 100
since PR 31; its place here is taken by `narrow_lane_step_share`, the
counter that says how much of the stepping ran below full width.)"""

import json

import pytest

from harness import cells, window

ROOT = cells.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["mutate_s_per_batch", "bp_service_s_per_batch",
       "narrow_lane_step_share"]

# two heartbeats 80 s and 2 batches of 4096 lanes apart
FIRST = {"ts": 2000.0, "metrics": {
    "backend.batches": 2, "device.instructions": 2_600_000,
    "device.lane_steps": 16_384_000, "device.tail_lane_steps": 14_336_000,
    "phase.seconds": {"mutate": 1.0, "execute/service-bp": 3.0}}}
LAST = {"ts": 2080.0, "metrics": {
    "backend.batches": 4, "device.instructions": 5_200_000,
    "device.lane_steps": 32_768_000, "device.tail_lane_steps": 28_672_000,
    "phase.seconds": {"mutate": 2.5, "execute/service-bp": 7.0}}}
BY_HAND = {
    "mutate_s_per_batch": 0.75,          # 1.5 s / 2 batches
    "bp_service_s_per_batch": 2.0,       # 4 s / 2 batches
    "narrow_lane_step_share": 87.5,      # 100 x 14,336,000 / 16,384,000
}


def spec(name):
    return json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())


def without(record, key):
    metrics = {k: v for k, v in record["metrics"].items() if k != key}
    return {"ts": record["ts"], "metrics": metrics}


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_the_value_worked_out_by_hand(name):
    source = spec(name)["source"]
    assert source["kind"] == "heartbeat_delta"
    assert window.evaluate(source, FIRST, LAST) == pytest.approx(
        BY_HAND[name], rel=1e-12)


def test_the_narrow_share_reads_zero_on_a_program_without_the_counter():
    """A tree before PR 31: lane-steps counted, none narrow.  The reader
    does not raise; a program with neither counter leaves the metric out."""
    source = spec("narrow_lane_step_share")["source"]
    key = "device.tail_lane_steps"
    assert window.evaluate(source, without(FIRST, key),
                           without(LAST, key)) == 0.0
    key = "device.lane_steps"
    assert window.evaluate(source, without(FIRST, key),
                           without(LAST, key)) is None


def test_the_cell_is_the_issues_and_the_benchmark_has_no_problem():
    assert cells.problems(ROOT) == []
    cell = cells.load_cell("tlv-default")
    config = cell.config
    assert cell.chips == 1 and config["max_len"] in (1024, 512, 256)
    # the README's quick-start command on demo_tlv: no engine flag
    assert cell.campaign_flags() == [
        "--name", "demo_tlv", "--backend", "tpu", "--lanes", "4096",
        "--limit", "100000", "--max_len", str(config["max_len"])]
    assert cell.window["warm_batches"] in (1, 2)
    assert cell.window["campaign_seed"] in (3000000012, 3000000013,
                                            3000000014)
    assert "tlv-user" in [c["name"] for c in BENCH["configs"]]
    entry = next(w for w in BENCH["workloads"] if w["name"] == "tlv-default")
    assert entry == {
        "name": "tlv-default", "config": "tlv-user", "traffic": "default",
        "chips": 1, "why": entry["why"]}
    assert [w["name"] for w in BENCH["workloads"]][:2] == [
        "kernel-default", "tlv-default"]


def test_the_three_metrics_are_there_as_data_files():
    """The named entries are in the index, wherever later PRs put theirs."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    entries = [by_name[name] for name in NEW]
    assert all("tlv-default" in m["workloads"] for m in entries)
    assert [m["moves"] for m in entries] == [
        "execs_per_s", "execs_per_s", "guest_instr_per_s"]
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    for m in entries:
        assert m["layer"] in layers
        assert not (ROOT / "benchmark" / "layer_metrics"
                    / f"{m['name']}.py").exists()
    due = [e["name"] for e, _ in cells.layer_metrics("tlv-default")]
    assert set(NEW) <= set(due)
    # the metrics without a list are read in the cell as they are
    assert {m["name"] for m in BENCH["per_layer"]
            if "workloads" not in m} <= set(due)
    assert "mutate_s_per_batch" not in [
        e["name"] for e, _ in cells.layer_metrics("kernel-default")]
