"""Finds the benchmark's data by name: BENCHMARK.json is the index, and
every configuration, traffic mix, cell and per-layer metric is a file of
its own under the benchmark's directory.  A later PR adds files and index
entries and edits none.

    configs/<config>.json        target, lanes, limit, max_len, chips, ...
    traffic/<mix>.json           campaign flags, who sends such traffic
    workloads/<cell>.json        warm_batches, campaign_seed (optional)
    layer_metrics/<name>.json    how the metric is read (see window.evaluate)
    layer_metrics/<name>.py      only where a reader needs code (trace)
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
CONFIG_KEYS = ("target", "lanes", "limit", "max_len", "chips", "source",
               "assumed", "reduced", "guarantees")
WINDOW_KEYS = ("warm_batches",)


class BenchmarkDataError(Exception):
    """A file of the benchmark is missing or says something the harness
    cannot run."""


def load_json(path: Path):
    try:
        return json.loads(Path(path).read_text())
    except (OSError, ValueError) as e:
        raise BenchmarkDataError(f"{path}: {e}") from e


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def bench_dir(root: Path, bench: dict) -> Path:
    return Path(root) / bench["paths"][0]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict      # configs/<config>.json
    traffic: dict     # traffic/<mix>.json
    window: dict      # workloads/<cell>.json

    def campaign_flags(self) -> list:
        """The campaign's arguments that the configuration and the traffic
        mix fix; seed, budget and directories are the run's."""
        c = self.config
        return ["--name", c["target"], "--backend", "tpu",
                "--lanes", str(c["lanes"]), "--limit", str(c["limit"]),
                "--max_len", str(c["max_len"]),
                *(["--mesh-devices", str(self.chips)]
                  if self.chips > 1 else []),
                *self.traffic["flags"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkDataError(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkDataError(
            f"workload {name!r} names config {entry['config']!r}, which "
            "BENCHMARK.json does not list")
    base = bench_dir(root, bench)
    cell = Cell(name=name, chips=int(entry["chips"]),
                config=load_json(Path(root) / cfg_entry["file"]),
                traffic=load_json(base / "traffic"
                                  / f"{entry['traffic']}.json"),
                window=load_json(base / "workloads" / f"{name}.json"))
    for key in CONFIG_KEYS:
        if key not in cell.config:
            raise BenchmarkDataError(f"{cfg_entry['file']} lacks {key!r}")
    for key in WINDOW_KEYS:
        if key not in cell.window:
            raise BenchmarkDataError(f"workloads/{name}.json lacks {key!r}")
    if not isinstance(cell.traffic.get("flags"), list):
        raise BenchmarkDataError(
            f"traffic/{entry['traffic']}.json needs a list of \"flags\"")
    return cell


def metric_cells(bench: dict, metric: dict) -> list:
    """The cells a metric is due in: its `workloads`, or every cell."""
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def layer_metrics(cell_name: str, root: Path = ROOT) -> list:
    """(index entry, reader spec) for each per-layer metric due in a cell."""
    bench = load_benchmark(root)
    base = bench_dir(root, bench) / "layer_metrics"
    return [(m, load_json(base / f"{m['name']}.json"))
            for m in bench["per_layer"]
            if cell_name in metric_cells(bench, m)]


def load_reducer(name: str, function: str, root: Path = ROOT):
    """The function `function` of layer_metrics/<name>.py."""
    path = bench_dir(root, load_benchmark(root)) / "layer_metrics" \
        / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"layer_metric_{name.replace('-', '_').replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise BenchmarkDataError(f"{path}: no reader beside the metric")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, function)


def problems(root: Path = ROOT) -> list:
    """Everything wrong with BENCHMARK.json and the files it names, as
    lines; empty when a run could start on every cell."""
    out = []
    bench = load_benchmark(root)

    def check_name(what, value):
        if not isinstance(value, str) or not NAME_RE.match(value):
            out.append(f"{what}: {value!r} is not a name")

    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_name("metric", m["name"])
        if not UNIT_RE.match(m.get("unit", "")):
            out.append(f"metric {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            out.append(f"metric {m['name']}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            out.append(f"metric {m['name']}: source {m.get('source')!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                out.append(f"metric {m['name']}: no cell {w!r}")
    if "setup_s" not in e2e:
        out.append("end_to_end lacks setup_s")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"end-to-end {m['name']}: source {m['source']!r}")
        if not 0 < m.get("bound", 0) <= 0.25:
            out.append(f"end-to-end {m['name']}: bound {m.get('bound')!r}")
    for c in bench["configs"]:
        check_name("config", c["name"])
        for key in c["reduced"]:
            check_name(f"config {c['name']} reduced", key)
    for name in cells:
        check_name("workload", name)
        try:
            cell = load_cell(name, root)
        except BenchmarkDataError as e:
            out.append(str(e))
            continue
        if cell.chips not in (1, 4) or cell.chips != cell.config["chips"]:
            out.append(f"workload {name}: chips {cell.chips} against the "
                       f"configuration's {cell.config['chips']}")
    for m in bench["per_layer"]:
        try:
            spec = load_json(bench_dir(root, bench) / "layer_metrics"
                             / f"{m['name']}.json")
            kind = spec["source"]["kind"]
            if kind == "trace":
                load_reducer(m["name"], spec["source"]["reducer"], root)
            elif kind != "heartbeat_delta":
                out.append(f"metric {m['name']}: source.kind {kind!r}")
        except (BenchmarkDataError, KeyError, AttributeError) as e:
            out.append(f"metric {m['name']}: {e!r}")
        moved = e2e.get(m.get("moves"))
        if moved is None:
            out.append(f"metric {m['name']}: moves {m.get('moves')!r}, "
                       "which is no end-to-end metric")
            continue
        missing = set(metric_cells(bench, m)) - set(
            metric_cells(bench, moved))
        if missing:
            out.append(f"metric {m['name']}: moves {moved['name']}, which "
                       f"{sorted(missing)} do not report")
    return out
