"""The HEVD campaign over a mesh (cell `hevd-mesh4`'s path at a small size,
on the conftest's virtual CPU devices): `demo_hevd` (a real IDT, guest
fault handlers, fourteen seeds run first) through `campaign --mesh-devices
N`, which the tier-1 mesh tests (`tests/test_meshrun.py`, all `demo_tlv`)
never took: `Runner._service_exception`, `HostView.round`, the per-shard
row reads and page pushes, `run_first`.

What holds (ISSUE 43): the mesh campaign is the one-device campaign at
equal total lanes, bit for bit; the EmuCpu oracle reproduces every find of
it; the machine is born sharded and the image placed once, replicated;
`mesh.wait_lane_steps` counts what the arithmetic says; the span tree
stays closed with `mesh-merge` inside `cov-readback`.
"""

import contextlib
import io
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wtf_tpu.core.results import StatusCode
from wtf_tpu.harness import demo_hevd as dh
from wtf_tpu.harness import demo_tlv
from wtf_tpu.interp.machine import CTR_SWEPT
from wtf_tpu.interp.runner import Runner, warm_decode_cache
from wtf_tpu.meshrun import MeshRunner, make_mesh
from wtf_tpu.telemetry.events import read_events

ROOT = Path(__file__).resolve().parents[1]
LANES = 256
BATCHES = 3
LIMIT = 100_000


def _campaign(tmp: Path, mesh_devices) -> dict:
    """Three batches of 256 lanes from the target's seeds; the final line
    (less the clock's part), the finds and the last heartbeat."""
    from wtf_tpu import cli

    (tmp / "inputs").mkdir(parents=True)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        cli.main(["campaign", "--name", "demo_hevd", "--backend", "tpu",
                  "--lanes", str(LANES), "--limit", str(LIMIT),
                  "--max_len", "1028", "--seed", "7",
                  "--runs", str(LANES * BATCHES),
                  *(["--mesh-devices", str(mesh_devices)]
                    if mesh_devices else []),
                  "--target", str(tmp), "--telemetry-dir",
                  str(tmp / "telem")])
    line = out.getvalue().strip().splitlines()[-1]
    beats = [e for e in read_events(tmp / "telem" / "events.jsonl")
             if e.get("type") == "heartbeat"]
    return {
        "line": re.sub(r" (exec/s|uptime): \S+", "", line),
        "files": {sub: {p.name: p.read_bytes()
                        for p in sorted((tmp / sub).iterdir())}
                  for sub in ("outputs", "crashes")},
        "final": beats[-1], "dir": tmp}


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    return _campaign(tmp_path_factory.mktemp("one"), None)


@pytest.fixture(scope="module")
def mesh4(tmp_path_factory):
    return _campaign(tmp_path_factory.mktemp("mesh4"), 4)


def _same_campaign(mesh: dict, one: dict, devices: int) -> None:
    assert mesh["line"] == one["line"]
    assert mesh["line"].startswith(f"#{LANES * BATCHES} cov: ")
    assert mesh["files"] == one["files"]
    assert len(one["files"]["outputs"]) > 14 and one["files"]["crashes"]
    a, b = mesh["final"]["metrics"], one["final"]["metrics"]
    assert a["runner.exceptions_by_vector"] \
        == b["runner.exceptions_by_vector"]
    assert a["runner.exceptions_delivered"] > 0
    assert a["runner.fallbacks"] == b["runner.fallbacks"] == 0
    totals = [k for k in b if k.startswith("device.")]
    assert "device.instructions" in totals and "device.lane_steps" in totals
    for key in totals:
        # the shards sweep on their own: what counts sweeps may differ
        if key not in ("device.lane_steps", "device.sweeps",
                       "device.tail_lane_steps", "device.levels_used",
                       "device.tlb_fill_steps", "device.walk2_steps",
                       "device.tlb_walks"):
            assert a[key] == b[key], key
    for key in ("device.shard_instructions", "device.shard_lane_steps"):
        assert key not in b
        assert sorted(a[key]) == [str(s) for s in range(devices)]
    assert sum(a["device.shard_instructions"].values()) \
        == a["device.instructions"]
    assert sum(a["device.shard_lane_steps"].values()) \
        == a["device.lane_steps"]
    assert a["mesh.devices"] == devices
    assert a["image.replicas"] == devices and b["image.replicas"] == 1
    assert a["image.bytes"] == b["image.bytes"]
    assert "mesh.wait_lane_steps" in a and "mesh.wait_lane_steps" not in b


def test_four_devices_run_the_one_device_campaign(mesh4, one_device):
    """(a) Final line, outputs/, crashes/, the deliveries by vector and
    every `device.*` total that does not count sweeps."""
    _same_campaign(mesh4, one_device, 4)


def test_two_devices_run_the_one_device_campaign(tmp_path, one_device):
    _same_campaign(_campaign(tmp_path / "mesh2", 2), one_device, 2)


def test_the_oracle_reproduces_every_find_of_the_mesh_campaign(mesh4):
    """(b) What `benchmark/run.py` does with a run's finds, here in one
    part: gather, replay on `--backend emu`, compare; all five at 0."""
    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        from harness import oracle
    finally:
        sys.path.pop(0)
    from wtf_tpu import cli

    work = mesh4["dir"]
    final = mesh4["final"]
    manifest = oracle.gather(work, work / "replay", final["ts"],
                             lambda n: 1)
    listing = {}
    for part in oracle.part_dirs(manifest, work / "replay"):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.main(["run", "--name", "demo_hevd", "--backend", "emu",
                           "--input", str(part), "--limit", str(LIMIT)])
        assert rc in (0, 2)
        listing.update(oracle.parse_listing(out.getvalue()))
    verdict = oracle.compare(manifest, listing, oracle.campaign_cov(final),
                             0)
    assert verdict["correct"], verdict["differences"][:5]
    assert {k: v["value"] for k, v in verdict["compared"].items()
            if isinstance(v, dict)} == {
        "crash_names_not_reproduced": 0, "oracle_crashes_not_saved": 0,
        "coverage_gap": 0, "listing_lines_missing": 0,
        "devdec_crosscheck_mismatches": 0}
    assert verdict["compared"]["testcases"] == len(manifest) > 14


SMALL = dict(uop_capacity=1 << 10, overlay_slots=16, edge_bits=12,
             chunk_steps=8)


def test_the_machine_is_born_sharded_and_the_image_placed_once():
    """(c) After `MeshRunner` is built no live array with the whole lane
    axis lies on one device, and `physmem.image` is the runner's
    replicated array: no second copy on the default device."""
    mesh = make_mesh(4)
    snapshot = dh.build_snapshot()
    before = {id(a) for a in jax.live_arrays()}
    runner = MeshRunner(snapshot, 64, mesh=mesh, **SMALL)
    assert runner.image is snapshot.physmem.image
    assert snapshot.physmem.replicas == 4
    assert runner.image.pages.sharding.is_fully_replicated
    for leaf in jax.tree.leaves((runner.machine, runner.template)):
        assert len(leaf.sharding.device_set) == 4
        assert leaf.sharding.shard_shape(leaf.shape)[0] * 4 == leaf.shape[0]
    pages = runner.image.pages.shape
    for a in jax.live_arrays():
        if id(a) in before or len(a.sharding.device_set) > 1:
            continue
        assert a.shape[:1] != (64,) and a.shape != pages, (a.shape, a.dtype)
    # a one-device runner of the same snapshot afterwards gets an image
    # of its own placement, and the mesh runner keeps the one it took
    single = Runner(snapshot, 8, **SMALL)
    assert len(single.image.pages.sharding.device_set) == 1
    assert len(runner.image.pages.sharding.device_set) == 4


def _deep_and_parked(cls, **extra):
    """16 lanes of demo_tlv; lanes 0-3 (a mesh of four's shard 0) run the
    payload, the others are parked before the first chunk."""
    payload = b"\x01\x02AB\x03\x08CCCCCCCC"
    runner = cls(demo_tlv.build_snapshot(), 16, **SMALL, **extra)
    warm_decode_cache(runner, demo_tlv.TARGET, payload, limit=4096)
    view = runner.view()
    for lane in range(16):
        if lane < 4:
            view.virt_write(lane, demo_tlv.INPUT_GVA, payload)
            view.r["gpr"][lane, 2] = np.uint64(len(payload))
        else:
            view.set_status(lane, StatusCode.OK)
    runner.push(view)
    runner.limit = 4096
    runner.run()
    return runner


def test_wait_lane_steps_count_what_the_shards_waited():
    """(d) One shard's lanes run deep and the others' park at once: every
    chunk, the three idle shards wait for all the lane-steps the busy one
    takes less the one sweep that told them they were done."""
    runner = _deep_and_parked(MeshRunner, mesh=make_mesh(4))
    ctr = runner.fold_device_counters()
    reg = runner.registry
    by_shard = reg.counter("device.shard_lane_steps").dump()
    assert sum(by_shard.values()) == reg.counter("device.lane_steps").value \
        == int(ctr[:, CTR_SWEPT].sum())
    busy = by_shard["0"]
    assert busy == max(by_shard.values()) > 0
    assert by_shard["1"] == by_shard["2"] == by_shard["3"] < busy
    assert reg.counter("mesh.wait_lane_steps").value \
        == 3 * busy - by_shard["1"] - by_shard["2"] - by_shard["3"]
    # the chunks' vectors were read and dropped: the next batch starts anew
    assert runner._shard_steps == []

    single = _deep_and_parked(Runner)
    single.fold_device_counters()
    dump = single.registry.dump()
    assert "mesh.wait_lane_steps" not in dump
    assert "device.shard_lane_steps" not in dump
    assert dump["device.instructions"] \
        == reg.counter("device.instructions").value


def test_the_span_tree_stays_closed_with_the_merge_inside(mesh4, one_device):
    """(e) `mesh-merge` is a child of `cov-readback`, fenced, and takes
    nothing out of `execute`: what `execute_spanned_share` sums is the
    same list on a mesh."""
    spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                       / "execute_spanned_share.json").read_text())
    phases = mesh4["final"]["metrics"]["phase.seconds"]
    merge = phases["execute/cov-readback/mesh-merge"]
    assert 0 < merge <= phases["execute/cov-readback"]
    children = sum(phases.get(k[len("phase.seconds."):], 0.0)
                   for k in spec["source"]["numerator"])
    assert children == pytest.approx(phases["execute"], rel=0.01)
    assert not any(k.endswith("mesh-merge") for k in
                   one_device["final"]["metrics"]["phase.seconds"])


def test_a_row_outside_a_round_comes_from_its_lanes_shard():
    """`HostView.page` outside a service round reads one row of one lane:
    on a mesh from the shard that holds the lane, whatever the lane."""
    payload = bytes(range(1, 33))
    runners = [cls(demo_tlv.build_snapshot(), 16, **SMALL, **extra)
               for cls, extra in ((Runner, {}),
                                  (MeshRunner, {"mesh": make_mesh(4)}))]
    for runner in runners:
        view = runner.view()
        for lane in range(16):
            view.virt_write(lane, demo_tlv.INPUT_GVA,
                            payload + bytes([lane]))
        runner.push(view)
    for lane in (0, 5, 15):
        reads = [r.view().virt_read(lane, demo_tlv.INPUT_GVA, 33)
                 for r in runners]
        assert reads[0] == reads[1] == payload + bytes([lane])
    rows = [np.asarray(jax.device_get(r.machine.overlay.pfn))
            for r in runners]
    np.testing.assert_array_equal(rows[0], rows[1])
    assert jnp.all(runners[1].machine.overlay.count == 1)
