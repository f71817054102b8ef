"""PR 43's cell `hevd-mesh4` (configuration `hevd-fulldump-mesh4`) and its
two per-layer metrics: files and index entries only, read by the harness
as it stands.  The cell is `hevd-faults`' campaign over the four chips of
one host, so the two cells' files may differ by the mesh alone."""

import json

import pytest

from harness import cells, window

ROOT = cells.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["shard_wait_share", "mesh_merge_ms_per_batch"]

# two heartbeats 60 s and 8 batches of 16,384 lanes apart
FIRST = {"ts": 1000.0, "metrics": {
    "backend.batches": 1, "device.lane_steps": 2_700_000,
    "mesh.wait_lane_steps": 100_000,
    "phase.seconds": {"execute/cov-readback": 0.2,
                      "execute/cov-readback/mesh-merge": 0.05}}}
LAST = {"ts": 1060.0, "metrics": {
    "backend.batches": 9, "device.lane_steps": 24_300_000,
    "mesh.wait_lane_steps": 2_500_000,
    "phase.seconds": {"execute/cov-readback": 1.8,
                      "execute/cov-readback/mesh-merge": 0.45}}}
BY_HAND = {
    "shard_wait_share": 10.0,          # 100 x 2.4 M / (2.4 M + 21.6 M)
    "mesh_merge_ms_per_batch": 50.0,   # 1000 x 0.4 s / 8 batches
}


def spec(name):
    return json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())


def test_the_benchmark_with_the_cell_has_no_problem():
    assert cells.problems(ROOT) == []


def test_the_cell_is_hevd_faults_campaign_over_four_chips():
    cell = cells.load_cell("hevd-mesh4")
    one = cells.load_cell("hevd-faults")
    flags = cell.campaign_flags()
    assert cell.chips == cell.config["chips"] == 4
    assert flags[flags.index("--mesh-devices") + 1] == "4"
    assert flags[flags.index("--lanes") + 1] == "16384"
    assert "--mesh-devices" not in one.campaign_flags()
    assert cell.window == one.window       # one campaign seed, one warm-up
    assert cell.traffic == one.traffic
    differs = {k for k in set(cell.config) | set(one.config)
               if cell.config.get(k) != one.config.get(k)}
    assert differs == {"lanes", "chips", "source", "assumed", "guarantees"}
    assert {k for k in cell.config["assumed"]
            if cell.config["assumed"][k] != one.config["assumed"].get(k)} \
        == {"lanes", "image_placement"}
    assert cell.config["reduced"] == one.config["reduced"]
    assert cell.config["guarantees"][:len(one.config["guarantees"])] \
        == one.config["guarantees"]
    assert len(cell.config["guarantees"]) == len(
        one.config["guarantees"]) + 2


def test_the_index_gained_one_configuration_one_cell_and_two_metrics():
    entry = next(w for w in BENCH["workloads"] if w["name"] == "hevd-mesh4")
    assert entry == dict(entry, config="hevd-fulldump-mesh4",
                         traffic="default", chips=4)
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] \
        == ["hevd-mesh4"]
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "hevd-fulldump-mesh4")
    assert config["reduced"] == ["request_path_instructions"]
    assert len(config["source"]) <= 200
    # wherever hevd-faults is listed, the mesh cell follows it
    for m in BENCH["per_layer"]:
        listed = m.get("workloads", [])
        if m["name"] not in NEW:
            assert ("hevd-mesh4" in listed) == ("hevd-faults" in listed), m


@pytest.mark.parametrize("name", NEW)
def test_the_metric_resolves_for_this_cell_and_for_no_other(name):
    for w in BENCH["workloads"]:
        due = [e["name"] for e, _ in cells.layer_metrics(w["name"])]
        assert (name in due) == (w["name"] == "hevd-mesh4"), w["name"]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["better"] == "lower"


@pytest.mark.parametrize("name", NEW)
def test_the_metric_reads_the_value_worked_out_by_hand(name):
    source = spec(name)["source"]
    assert source["kind"] == "heartbeat_delta"
    assert window.evaluate(source, FIRST, LAST) == pytest.approx(
        BY_HAND[name], rel=1e-12)


def test_a_program_without_the_counter_leaves_the_wait_share_out():
    """The parent, and every one-chip campaign: no `mesh.wait_lane_steps`.
    The reader returns nothing and does not raise."""
    source = spec("shard_wait_share")["source"]
    bare = [{"ts": r["ts"], "metrics": {
        k: v for k, v in r["metrics"].items()
        if k != "mesh.wait_lane_steps"}} for r in (FIRST, LAST)]
    assert window.evaluate(source, *bare) is None
