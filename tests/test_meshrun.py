"""Mesh campaign driver (wtf_tpu/meshrun) on the conftest's 8 virtual
CPU devices.

The acceptance contract (ISSUE 7): a mesh is ONE logical backend —
identical seeds produce bit-identical merged coverage, crash sets and
devmut byte streams against the single-device run at equal execs; the
compiled chunk's only cross-device collective is the coverage
all-reduce; per-shard device counters sum to the merged view.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wtf_tpu.core.results import Crash, StatusCode
from wtf_tpu.harness import demo_tlv
from wtf_tpu.interp.runner import Runner, warm_decode_cache
from wtf_tpu.interp.step import make_run_chunk
from wtf_tpu.meshrun import (
    MeshRunner, make_mesh, make_mesh_chunk, make_mesh_merge, merge_coverage,
    replicate, shard_machine,
)

PAYLOAD = b"\x01\x02AB\x03\x08CCCCCCCC"
N_DEVICES = 8
N_LANES = 16

SMALL = dict(uop_capacity=1 << 10, overlay_slots=16, edge_bits=12,
             chunk_steps=8)


def _seed_lanes(runner) -> None:
    view = runner.view()
    for lane in range(runner.n_lanes):
        data = PAYLOAD[:4 + (lane % 3) * 5]
        view.virt_write(lane, demo_tlv.INPUT_GVA, data)
        view.r["gpr"][lane, 2] = np.uint64(len(data))
    runner.push(view)


def _runner(cls=Runner, **extra) -> Runner:
    snapshot = demo_tlv.build_snapshot()
    runner = cls(snapshot, n_lanes=N_LANES, **SMALL, **extra)
    warm_decode_cache(runner, demo_tlv.TARGET, PAYLOAD, limit=4096)
    _seed_lanes(runner)
    return runner


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N_DEVICES, "conftest should provision 8"
    return make_mesh(N_DEVICES)


def test_mesh_chunk_bit_parity_and_merged_bitmaps(mesh):
    """The shard_map chunk executor == the plain chunk executor on every
    machine leaf, and its on-chip merged cov/edge == the host union."""
    r1 = _runner()
    m_single = make_run_chunk(8, donate=False)(
        r1.cache.device(), r1.physmem.image, r1.machine, jnp.uint64(500))

    r2 = _runner()
    machine = shard_machine(r2.machine, mesh)
    tab = replicate(r2.cache.device(), mesh)
    image = replicate(r2.physmem.image, mesh)
    m_mesh, cov, edge, _ = make_mesh_chunk(8, mesh, donate=False)(
        tab, image, machine, jnp.uint64(500))

    for name in m_single._fields:
        for la, lb in zip(jax.tree.leaves(getattr(m_single, name)),
                          jax.tree.leaves(getattr(m_mesh, name))):
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb),
                err_msg=f"machine leaf {name} diverges on the mesh")
    cov_host = np.bitwise_or.reduce(np.asarray(m_single.cov), axis=0)
    edge_host = np.bitwise_or.reduce(np.asarray(m_single.edge), axis=0)
    np.testing.assert_array_equal(np.asarray(cov), cov_host)
    np.testing.assert_array_equal(np.asarray(edge), edge_host)
    assert cov_host.sum() > 0  # something actually executed


def test_mesh_chunk_through_the_tail_equals_head_only(monkeypatch):
    """Each shard narrows its sweeps at the width of ITS lanes (4 shards
    x 4 lanes, groups of 1; the floor lowered for the test): every
    machine leaf but the three sweep counters equals the single-device
    full-width chunk, and a lane carries its SHARD's levels that swept."""
    from wtf_tpu.interp import step
    from wtf_tpu.interp.machine import CTR_LEVELS, CTR_SWEPT, CTR_TAIL

    r1 = _runner()
    m_single = make_run_chunk(160, donate=False, tail=0)(
        r1.cache.device(), r1.physmem.image, r1.machine, jnp.uint64(500))

    monkeypatch.setattr(step, "TAIL_MIN_LANES", 4)
    monkeypatch.setattr(step, "TAIL_SHARE", 4)
    mesh4 = make_mesh(4)
    r2 = _runner()
    m_mesh, cov, edge, _ = jax.jit(
        make_mesh_chunk(160, mesh4, donate=False, jit=False))(
        replicate(r2.cache.device(), mesh4),
        replicate(r2.physmem.image, mesh4),
        shard_machine(r2.machine, mesh4), jnp.uint64(500))

    for (path, la), lb in zip(jax.tree_util.tree_leaves_with_path(m_single),
                              jax.tree.leaves(m_mesh)):
        la, lb = np.asarray(la), np.asarray(lb)
        if jax.tree_util.keystr(path) == ".ctr":
            keep = [c for c in range(la.shape[1])
                    if c not in (CTR_SWEPT, CTR_TAIL, CTR_LEVELS)]
            la, lb = la[:, keep], lb[:, keep]
        np.testing.assert_array_equal(
            la, lb, err_msg=f"machine leaf {path} diverges in the tail")
    np.testing.assert_array_equal(
        np.asarray(cov),
        np.bitwise_or.reduce(np.asarray(m_single.cov), axis=0))
    ctr = np.asarray(m_mesh.ctr).reshape(4, 4, -1).astype(np.int64)
    swept, tail = ctr[:, :, CTR_SWEPT], ctr[:, :, CTR_TAIL]
    assert tail.sum() > 0, "no shard narrowed"
    # a shard's lanes all take its full-width sweeps, and the narrow ones
    # are counted lane by lane; the shards narrow and exit on their own
    np.testing.assert_array_equal(
        tail, swept - swept.min(axis=1, keepdims=True))
    assert len({tuple(sorted(row)) for row in swept.tolist()}) > 1
    assert swept.sum() < np.asarray(m_single.ctr)[:, CTR_SWEPT].sum()
    # CTR_LEVELS: one count a shard, on every lane of it; a shard that
    # narrowed used more than one of its four levels
    levels = ctr[:, :, CTR_LEVELS]
    assert (levels == levels[:, :1]).all() and (levels >= 1).all()
    assert ((levels[:, 0] > 1) | (tail.sum(axis=1) == 0)
            | (swept.min(axis=1) == 0)).all()
    assert levels.max() <= 4
    assert not np.asarray(m_single.ctr)[:, CTR_LEVELS].any()


def test_mesh_runner_full_run_parity(mesh):
    """MeshRunner.run() (host servicing, decode misses, breakpoints
    included) matches Runner.run() bit-for-bit, per-shard counters sum
    to the batch total, and the merged-coverage view needs no
    [lanes, words] gather."""
    r1 = _runner()
    r1.cache.set_breakpoint(demo_tlv.FINISH_GVA)
    statuses1 = r1.run(bp_handler=_stop_handler)

    r2 = _runner(cls=MeshRunner, mesh=mesh)
    r2.cache.set_breakpoint(demo_tlv.FINISH_GVA)
    statuses2 = r2.run(bp_handler=_stop_handler)
    np.testing.assert_array_equal(statuses1, statuses2)
    np.testing.assert_array_equal(np.asarray(r1.machine.icount),
                                  np.asarray(r2.machine.icount))

    # per-shard device counters sum to the merged device.* view
    ctr = r2.fold_device_counters()
    dump = r2.registry.counter("device.shard_instructions").dump()
    assert len(dump) == N_DEVICES
    assert sum(dump.values()) == int(
        ctr[:, 0].sum(dtype=np.uint64))
    assert r2.registry.counter("device.instructions").value == sum(
        dump.values())

    # the on-chip merged bitmap equals the host union of the lane planes
    merged = r2.merged_coverage()
    assert merged is not None
    np.testing.assert_array_equal(
        merged[0], np.bitwise_or.reduce(np.asarray(r2.machine.cov), axis=0))
    np.testing.assert_array_equal(
        merged[1], np.bitwise_or.reduce(np.asarray(r2.machine.edge), axis=0))


def _stop_handler(runner, view, lane):
    view.set_status(lane, StatusCode.OK)


def test_mesh_merge_matches_single_device(mesh):
    """make_mesh_merge == merge_coverage (union, per-lane credit,
    new-word report) on randomized bitmaps with a non-trivial aggregate
    and masked lanes — the reference set-union semantics survive
    sharding."""
    rng = np.random.default_rng(0xC07)
    cov = rng.integers(0, 1 << 32, (N_LANES, 24), dtype=np.uint32)
    edge = rng.integers(0, 1 << 32, (N_LANES, 40), dtype=np.uint32)
    # duplicate rows so prefix credit actually discriminates
    cov[3] = cov[1]
    edge[3] = edge[1]
    agg_cov = cov[5] & rng.integers(0, 1 << 32, 24, dtype=np.uint32)
    agg_edge = np.zeros(40, np.uint32)
    include = np.ones(N_LANES, bool)
    include[[2, 9]] = False

    want = jax.jit(merge_coverage)(agg_cov, agg_edge, cov, edge, include)
    got = make_mesh_merge(mesh)(
        jnp.asarray(agg_cov), jnp.asarray(agg_edge),
        shard_machine(jnp.asarray(cov), mesh),
        shard_machine(jnp.asarray(edge), mesh),
        shard_machine(jnp.asarray(include), mesh))
    for a, b, name in zip(got, want,
                          ("agg_cov", "agg_edge", "new_lane", "new_words")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} diverges on mesh")
    # sanity: the mask and the prefix credit both did something
    new_lane = np.asarray(want[2])
    assert not new_lane[2] and not new_lane[9]
    assert new_lane[1] and not new_lane[3]


def _campaign(mesh_devices, seed=0x5EED, batches=2, mutator="devmangle"):
    from wtf_tpu.analysis.trace import build_tlv_campaign

    loop = build_tlv_campaign(n_lanes=8, mutator=mutator, limit=20_000,
                              seed=seed, chunk_steps=128, overlay_slots=16,
                              mesh_devices=mesh_devices)
    for _ in range(batches):
        loop.run_one_batch()
    return loop


def test_mesh_campaign_devmangle_parity():
    """Acceptance: `--mesh-devices 8 --mutator devmangle` == the
    single-device campaign at equal seeds/execs — bit-identical merged
    coverage, crash set, corpus, AND devmut byte streams (the in-HBM
    generator sharded per-shard against the same hostref lane_seeds)."""
    a = _campaign(None)
    b = _campaign(8)
    assert a.stats.testcases == b.stats.testcases == 16
    assert a.stats.new_coverage == b.stats.new_coverage
    assert a.crash_names == b.crash_names
    assert a.corpus.digests == b.corpus.digests
    np.testing.assert_array_equal(np.asarray(a.backend._agg_cov),
                                  np.asarray(b.backend._agg_cov))
    np.testing.assert_array_equal(np.asarray(a.backend._agg_edge),
                                  np.asarray(b.backend._agg_edge))
    # the device-resident testcase stream is bit-exact across shardings
    wa, la = a.mutator.current_batch()
    wb, lb = b.mutator.current_batch()
    np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    fa = a.mutator.fetch(range(8))
    fb = b.mutator.fetch(range(8))
    assert fa == fb
    # mesh telemetry gauges + per-shard counters sum to the merged view
    reg = b.registry
    assert reg.gauge("mesh.devices").value == 8
    assert reg.gauge("mesh.lanes_per_shard").value == 1
    by_shard = reg.counter("device.shard_instructions").dump()
    assert sum(by_shard.values()) == \
        reg.counter("device.instructions").value > 0
    assert reg.counter("device.instructions").value == \
        a.registry.counter("device.instructions").value


def test_cli_mesh_flag_plumbs_to_backend():
    """--mesh-devices parses on campaign/fuzz, flows through the tuning
    kwargs, and create_backend routes it to the MeshBackend (0 = every
    local device)."""
    from wtf_tpu.backend import create_backend
    from wtf_tpu.cli import _backend_tuning_kwargs, build_parser
    from wtf_tpu.meshrun.backend import MeshBackend

    args = build_parser().parse_args(
        ["campaign", "--name", "demo_tlv", "--mesh-devices", "8"])
    assert _backend_tuning_kwargs(args)["mesh_devices"] == 8
    args = build_parser().parse_args(["fuzz", "--name", "demo_tlv"])
    assert "mesh_devices" not in _backend_tuning_kwargs(args)

    backend = create_backend("tpu", demo_tlv.build_snapshot(),
                             n_lanes=8, mesh_devices=0)
    assert isinstance(backend, MeshBackend)
    emu = create_backend("emu", demo_tlv.build_snapshot(), mesh_devices=8)
    assert not isinstance(emu, MeshBackend)


def test_mesh_backend_rejects_indivisible_lanes():
    from wtf_tpu.backend import create_backend

    backend = create_backend("tpu", demo_tlv.build_snapshot(),
                             n_lanes=6, mesh_devices=4)
    with pytest.raises(ValueError, match="divide"):
        backend.initialize()


def test_mesh_lint_rules_fire_on_seeded_violations():
    """The mesh rule family's checks, seeded directly (the clean run on
    the real tree is `wtf-tpu lint` / the slow full-lint test): a
    gather-class collective over budget fires mesh.collectives, a
    shard-count-dependent program fires mesh.shard-unstable, and the
    normalizer strips exactly the device-list noise."""
    from wtf_tpu.analysis.rules import (
        check_mesh_collectives, check_shard_stability,
        count_collective_ops, load_budgets, normalize_partitioned_hlo,
    )

    budget = load_budgets()["mesh_chunk"]
    assert budget["all-reduce"] == 1 and budget["total"] == 1

    hlo = ('  %ar = u32[160,32]{1,0} all-reduce(u32[160,32]{1,0} %x), '
           'replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%or\n'
           '  %ag = u32[16,24]{1,0} all-gather(u32[2,24]{1,0} %m), '
           'replica_groups=[8,1]<=[8], dimensions={0}\n')
    counts = count_collective_ops(hlo)
    assert counts == {"all-reduce": 1, "all-gather": 1, "all-to-all": 0,
                      "collective-permute": 0, "collective-broadcast": 0,
                      "reduce-scatter": 0, "total": 2}
    findings = check_mesh_collectives(counts, budget, entry="seeded")
    rules = {(f.rule, f.primitive) for f in findings}
    assert ("mesh.collectives", "all-gather") in rules
    assert ("mesh.collectives", "total") in rules

    eight = ('%p = u32[2,16]{1,0} parameter(0), '
             'sharding={devices=[8,1]<=[8]}\n'
             '%ar = pred[] all-reduce(%q), replica_groups={{0,1,2,3,4,5,6,7}}')
    four = eight.replace("[8,1]<=[8]", "[4,1]<=[4]").replace(
        "{{0,1,2,3,4,5,6,7}}", "{{0,1,2,3}}")
    assert normalize_partitioned_hlo(eight) == normalize_partitioned_hlo(four)
    assert check_shard_stability(eight, four, entry="seeded") == []
    drifted = four.replace("u32[2,16]", "u32[4,16]")
    bad = check_shard_stability(eight, drifted, entry="seeded")
    assert [f.rule for f in bad] == ["mesh.shard-unstable"]


def test_telemetry_report_mesh_section(tmp_path):
    """tools/telemetry_report.py surfaces the per-shard counters and
    their agreement with the merged device view."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    from telemetry_report import summarize

    events = tmp_path / "events.jsonl"
    metrics = {
        "campaign.testcases": 32,
        "device.instructions": 1792,
        "device.mem_faults": 0,
        "device.decode_misses": 32,
        "device.shard_instructions": {"0": 1000, "1": 792},
        "device.shard_lane_steps": {"1": 900, "0": 1100},
        "device.lane_steps": 2000,
        "mesh.wait_lane_steps": 500,
        "mesh.devices": 2,
        "mesh.lanes_per_shard": 8,
        "image.replicas": 2,
        "phase.seconds": {"execute": 1.0, "execute/cov-readback": 0.5,
                          "execute/cov-readback/mesh-merge": 0.25},
    }
    with events.open("w") as fh:
        fh.write(json.dumps({"ts": 1.0, "seq": 0, "type": "run-start"}) + "\n")
        fh.write(json.dumps({"ts": 2.0, "seq": 1, "type": "run-end",
                             "metrics": metrics}) + "\n")
    s = summarize(events)
    assert s["mesh"] == {
        "devices": 2, "lanes_per_shard": 8,
        "shard_instructions": {"0": 1000, "1": 792},
        "shard_instructions_sum": 1792, "merged_instructions": 1792,
        "shard_lane_steps": {"0": 1100, "1": 900},
        "wait_lane_steps": 500, "wait_share": 0.2,
        "merge_seconds": 0.25, "image_replicas": 2,
    }


@pytest.mark.slow
def test_mesh_campaign_ramp_parity_slow():
    """The larger ramp: 64 lanes x 6 batches (384 execs) with crashes
    possible; mesh and single-device runs stay bit-identical on
    coverage, crash names and corpus over the longer horizon."""
    from wtf_tpu.analysis.trace import build_tlv_campaign

    def run(mesh_devices):
        loop = build_tlv_campaign(n_lanes=64, mutator="devmangle",
                                  limit=20_000, seed=0xAB, chunk_steps=128,
                                  overlay_slots=16,
                                  mesh_devices=mesh_devices)
        for _ in range(6):
            loop.run_one_batch()
        return loop

    a = run(None)
    b = run(8)
    assert a.stats.testcases == b.stats.testcases == 384
    assert a.crash_names == b.crash_names
    assert a.corpus.digests == b.corpus.digests
    np.testing.assert_array_equal(np.asarray(a.backend._agg_cov),
                                  np.asarray(b.backend._agg_cov))
    np.testing.assert_array_equal(np.asarray(a.backend._agg_edge),
                                  np.asarray(b.backend._agg_edge))
