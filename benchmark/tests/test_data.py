"""Every file the benchmark is driven by, and BENCHMARK.json itself."""

import json
import shutil
from pathlib import Path

import pytest

from harness import cells

ROOT = cells.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_benchmark_as_committed_has_no_problem():
    assert cells.problems(ROOT) == []


def test_benchmark_json_has_the_contracts_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"][-1].startswith("benchmark/")
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert c["file"].startswith("benchmark/configs/")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_every_config_is_used_and_states_its_source_and_guarantees():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        doc = json.loads((ROOT / c["file"]).read_text())
        assert doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert doc["guarantees"] and doc["assumed"]


@pytest.mark.parametrize("sub", ["configs", "traffic", "workloads",
                                 "layer_metrics", "harness", "tests"])
def test_files_are_named_from_a_names_characters(sub):
    for p in (ROOT / "benchmark" / sub).iterdir():
        if p.name != "__pycache__":
            assert cells.NAME_RE.match(p.name), p


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_loads_and_reports_what_the_contract_asks(cell):
    loaded = cells.load_cell(cell)
    flags = loaded.campaign_flags()
    assert flags[:4] == ["--name", loaded.config["target"],
                         "--backend", "tpu"]
    assert "--seed" not in flags and "--runs" not in flags
    assert set(loaded.window) <= {"warm_batches", "campaign_seed"}
    assert loaded.window["warm_batches"] >= 1
    assert loaded.window.get("campaign_seed", 1) >= 1   # 0 is "unseeded"
    metrics = cells.layer_metrics(cell)
    assert metrics, "every cell reports at least one per-layer metric"
    for entry, spec in metrics:
        assert spec["source"]["kind"] in ("heartbeat_delta", "trace")


def test_layer_metric_files_and_index_entries_match():
    on_disk = {p.stem for p in (ROOT / "benchmark/layer_metrics").glob(
        "*.json")}
    assert on_disk == {m["name"] for m in BENCH["per_layer"]}


@pytest.mark.parametrize("edit, complaint", [
    (lambda b: b["per_layer"][0].update(moves="tokens_per_s"),
     "no end-to-end metric"),
    (lambda b: b["per_layer"][0].update(unit="execs per second"), "unit"),
    (lambda b: b["workloads"][0].update(name="tlv devres"), "not a name"),
    (lambda b: b["workloads"][0].update(traffic="nosuch"), "nosuch.json"),
    (lambda b: b["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda b: b["end_to_end"][0].update(workloads=["nocell"]),
     "no cell 'nocell'"),
    (lambda b: b["per_layer"].append(dict(b["per_layer"][0], name="nofile")),
     "nofile"),
])
def test_problems_are_found(tmp_path, edit, complaint):
    root = copy_benchmark(tmp_path)
    bench = json.loads(json.dumps(BENCH))
    edit(bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    found = cells.problems(root)
    assert any(complaint in line for line in found), found


def copy_benchmark(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_the_readmes_worked_example_is_added_by_new_files_only(tmp_path):
    """tlv-devres-mesh4, as benchmark/README.md writes it out: a
    configuration, a traffic mix, a cell and a counter-based metric as new
    files and new index entries; no file that was there is edited."""
    root = copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    readme = (ROOT / "benchmark/README.md").read_text()
    blocks = {}
    for chunk in readme.split("<!-- file: ")[1:]:
        name, rest = chunk.split(" -->", 1)
        blocks[name] = rest.split("```json\n", 1)[1].split("```", 1)[0]
    assert sorted(blocks) == [
        "BENCHMARK.json configs entry", "BENCHMARK.json per_layer entry",
        "BENCHMARK.json workloads entry",
        "benchmark/configs/tlv-user-mesh4.json",
        "benchmark/layer_metrics/zero_host_window_rate.json",
        "benchmark/traffic/devres.json",
        "benchmark/workloads/tlv-devres-mesh4.json"]
    bench = json.loads(json.dumps(BENCH))
    for name, text in blocks.items():
        if name.startswith("BENCHMARK.json"):
            bench[name.split()[1]].append(json.loads(text))
        else:
            (root / name).write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert cells.problems(root) == []
    cell = cells.load_cell("tlv-devres-mesh4", root)
    flags = cell.campaign_flags()
    assert cell.chips == 4 and cell.config["lanes"] == 4096
    assert flags[flags.index("--mesh-devices") + 1] == "4"
    assert flags[flags.index("--megachunk") + 1] == "8"
    names = [e["name"] for e, _ in cells.layer_metrics("tlv-devres-mesh4",
                                                       root)]
    assert "device_idle_share" in names and "zero_host_window_rate" in names
    assert "zero_host_window_rate" not in [
        e["name"] for e, _ in cells.layer_metrics("kernel-default", root)]
    assert all(p.read_bytes() == data for p, data in before.items())
    assert "tlv-devres-mesh4" not in [w["name"] for w in BENCH["workloads"]]
