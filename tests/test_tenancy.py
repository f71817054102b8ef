"""Multi-tenant campaign tests (wtf_tpu/tenancy).

The two contracts of the subsystem, pinned bit-exactly:

  isolation   a campaign run as a lane-subset of a heterogeneous batch
              (stacked image table, tenant-tagged decode cache,
              per-tenant prefix-credit merges) is bit-identical —
              coverage planes, corpus stream, devmut byte streams,
              crash buckets — to the same campaign run alone;
  preemption  a tenant checkpointed at a batch boundary and restored
              into a DIFFERENT placement (different tenant index and
              lane range) finishes bit-identical to an uninterrupted
              run — the placement-free remap of tenancy/state.py.

Plus scheduler mechanics (jobs.json validation, priority/round-robin
placement, preemption events), seeded lint violations for the tenancy
budget rules, and the telemetry_report tenants section.
"""

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from wtf_tpu.harness.targets import Targets, load_builtin_targets
from wtf_tpu.interp.uoptable import DecodeCache, tag_key
from wtf_tpu.tenancy.backend import TenantSpec, create_tenancy_backend
from wtf_tpu.tenancy.image import build_batch_state, stack_images
from wtf_tpu.tenancy.loop import MultiTenantLoop, TenantRuntime
from wtf_tpu.tenancy.sched import Job, Scheduler, load_jobs
from wtf_tpu.tenancy.state import (
    extract_bits, restore_tenant, save_tenant, scatter_bits,
)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

LIMIT = 50_000
SEED_TLV = b"\x01\x04AAAA\x02\x08BBBBBBBB"
SEED_KERN = b"hello-world-123"


def _targets():
    load_builtin_targets()
    return Targets.instance()


def _build(cfg, n_lanes=None, mesh_devices=None, limit=LIMIT):
    """(backend, specs) for a tenant table of (name, target, quota)."""
    targets = _targets()
    specs = [TenantSpec(n, targets.get(t), targets.get(t).snapshot(), q)
             for n, t, q in cfg]
    n_lanes = n_lanes if n_lanes else sum(q for _, _, q in cfg)
    backend = create_tenancy_backend(specs, n_lanes, limit=limit,
                                     mesh_devices=mesh_devices)
    backend.initialize()
    for i, s in enumerate(specs):
        with backend.tenant_context(i):
            s.target.init(backend)
    return backend, specs


def _runtimes(backend, specs, cfg_mut):
    """TenantRuntimes for (name -> (mutator, seed, corpus seed))."""
    out, lane_lo = [], 0
    for i, spec in enumerate(specs):
        mut, seed, data = cfg_mut[spec.name]
        rt = TenantRuntime(spec, seed=seed, runs=1 << 20,
                           mutator_name=mut, max_len=256,
                           lane_lo=lane_lo)
        rt.corpus.add(data)
        out.append(rt)
        lane_lo += spec.lanes
    return out


def _fingerprint(backend, runtimes):
    out = {}
    for i, rt in enumerate(runtimes):
        cov, edge = backend.tenant_coverage_state(i)
        entries = backend.runner.cache.tenant_entries(i)
        out[rt.name] = {
            "local_cov": extract_bits(cov, [e[0] for e in entries]
                                      ).tobytes(),
            "edge": edge.tobytes(),
            "corpus": list(rt.corpus),
            "buckets": sorted(rt.crash_buckets),
            "rips": sorted(e[1] for e in entries),
        }
    return out


# ---------------------------------------------------------------------------
# stacked image table + tagged decode cache (host-level units)
# ---------------------------------------------------------------------------

def test_stack_images_routes_each_tenant_to_its_pages():
    targets = _targets()
    pms = [targets.get("demo_tlv").snapshot().physmem,
           targets.get("demo_kernel").snapshot().physmem]
    image = stack_images(pms)
    assert image.frame_table.shape[0] == 2
    table = np.asarray(image.frame_table)
    pages = np.asarray(image.pages)
    for t, pm in enumerate(pms):
        own = np.asarray(pm.image.frame_table)[0]
        own_pages = np.asarray(pm.image.pages)
        present = np.nonzero(own)[0]
        assert present.size, "snapshot has no mapped pages?"
        for pfn in present[:: max(1, present.size // 16)]:
            assert (pages[table[t, pfn]] == own_pages[own[pfn]]).all(), (
                f"tenant {t} pfn {pfn:#x} routed to wrong page")
        # pfns beyond this tenant's span resolve to the shared zero page
        span_t = own.shape[0]
        if span_t < table.shape[1]:
            assert (table[t, span_t:] == 0).all()


def test_decode_cache_tenant_tagged_keys():
    from wtf_tpu.cpu.decoder import decode

    cache = DecodeCache(capacity=64)
    rip = 0x1400_0000
    nop, ret = decode(b"\x90", rip), decode(b"\xc3", rip)
    i0 = cache.add(rip, nop, 5, 5, tenant=0)
    i1 = cache.add(rip, ret, 7, 7, tenant=1)
    assert i0 != i1, "two tenants at one VA must get distinct entries"
    assert cache.entry_index(rip, 0) == i0
    assert cache.entry_index(rip, 1) == i1
    assert cache.uop_at(rip, 0).raw == b"\x90"
    assert cache.uop_at(rip, 1).raw == b"\xc3"
    assert cache.rip_of(i0) == rip and cache.rip_of(i1) == rip
    # per-tenant breakpoints: arming tenant 1's does not touch tenant 0
    cache.set_breakpoint(rip, tenant=1)
    assert cache.has_breakpoint(rip, 1)
    assert not cache.has_breakpoint(rip, 0)
    assert cache.bp[i1] == 1 and cache.bp[i0] == 0
    # tenant_entries slices by tenant with global indices + real rips
    ents0 = cache.tenant_entries(0)
    ents1 = cache.tenant_entries(1)
    assert [(e[0], e[1]) for e in ents0] == [(i0, rip)]
    assert [(e[0], e[1]) for e in ents1] == [(i1, rip)]
    # checkpoint round-trip preserves tenant tags; tenant-0 entries stay
    # 4-tuples so pre-tenancy checkpoints load unchanged
    entries = cache.checkpoint_entries()
    assert len(entries[0]) == 4 and len(entries[1]) == 5
    fresh = DecodeCache(capacity=64)
    fresh.restore_entries(entries)
    assert fresh.entry_index(rip, 0) == i0
    assert fresh.entry_index(rip, 1) == i1
    assert fresh.uop_at(rip, 1).raw == b"\xc3"


def test_tag_key_is_identity_for_tenant_zero():
    assert tag_key(0x7FFF_1234) == 0x7FFF_1234
    assert tag_key(0x7FFF_1234, 3) != 0x7FFF_1234
    # untagging is the same xor
    assert tag_key(tag_key(0x7FFF_1234, 3), 3) == 0x7FFF_1234


def test_extract_scatter_bits_roundtrip():
    rng = np.random.default_rng(7)
    words = rng.integers(0, 1 << 32, size=8, dtype=np.uint64).astype(
        np.uint32)
    idxs = [3, 17, 64, 200, 255]
    local = extract_bits(words, idxs)
    back = scatter_bits(local, idxs, 8)
    for j, i in enumerate(idxs):
        want = (int(words[i >> 5]) >> (i & 31)) & 1
        assert ((int(local[j >> 5]) >> (j & 31)) & 1) == want
        assert ((int(back[i >> 5]) >> (i & 31)) & 1) == want


# ---------------------------------------------------------------------------
# isolation: mixed batch == solo, bit for bit
# ---------------------------------------------------------------------------

MUTS = {"alice": ("tlv", 42, SEED_TLV),
        "bob": ("mangle", 1337, SEED_KERN)}


def _campaign(cfg, batches=3, mesh_devices=None, capture_devmut=None,
              muts=None, limit=LIMIT):
    backend, specs = _build(cfg, mesh_devices=mesh_devices, limit=limit)
    runtimes = _runtimes(backend, specs, muts if muts else MUTS)
    loop = MultiTenantLoop(backend, runtimes, stats_every=1e9)
    for _ in range(batches):
        loop.run_one_batch()
        if capture_devmut is not None:
            for rt in runtimes:
                if rt.device:
                    words, lens = rt.mutator.current_batch()
                    capture_devmut.setdefault(rt.name, []).append(
                        (np.asarray(jax.device_get(words)).tobytes(),
                         np.asarray(jax.device_get(lens)).tobytes()))
    return backend, runtimes, _fingerprint(backend, runtimes)


def test_mixed_batch_isolation_bit_parity():
    _b1, _r1, solo_a = _campaign([("alice", "demo_tlv", 4)])
    _b2, _r2, solo_b = _campaign([("bob", "demo_kernel", 4)])
    backend, runtimes, mixed = _campaign(
        [("alice", "demo_tlv", 4), ("bob", "demo_kernel", 4)])
    # both tenants really executed their own base image
    for name in ("alice", "bob"):
        assert mixed[name]["rips"], f"{name} decoded nothing"
        assert any(b != 0 for b in mixed[name]["local_cov"])
    assert solo_a["alice"] == mixed["alice"]
    assert solo_b["bob"] == mixed["bob"]
    # the two images share VAs: the decode cache must hold them apart
    shared = set(mixed["alice"]["rips"]) & set(mixed["bob"]["rips"])
    cache = backend.runner.cache
    for rip in list(shared)[:4]:
        assert cache.entry_index(rip, 0) != cache.entry_index(rip, 1)


def test_mixed_batch_through_the_tail_equals_head_only(monkeypatch):
    """The chunk executor's narrow sweeps (8 lanes in groups of 2; the
    floor and the group count lowered for the test) move each lane's
    tenant selector with its row: a two-image batch leaves what the
    full-width executor leaves."""
    from wtf_tpu.interp import step

    cfg = [("alice", "demo_tlv", 4), ("bob", "demo_kernel", 4)]
    plain_backend, _r1, plain = _campaign(cfg, batches=2)
    monkeypatch.setattr(step, "TAIL_MIN_LANES", 8)
    monkeypatch.setattr(step, "TAIL_SHARE", 4)
    monkeypatch.setattr(step, "_CHUNK_CACHE", {})
    backend, _r2, tailed = _campaign(cfg, batches=2)
    assert tailed == plain
    counter = backend.registry.counter
    for key in ("device.instructions", "device.decode_misses",
                "device.lockstep_floor_steps", "runner.chunks"):
        assert counter(key).value == plain_backend.registry.counter(
            key).value, key
    assert 0 < counter("device.tail_lane_steps").value \
        < counter("device.lane_steps").value \
        < plain_backend.registry.counter("device.lane_steps").value
    assert plain_backend.registry.counter(
        "device.tail_lane_steps").value == 0


def test_devmangle_tenant_stream_bit_parity():
    muts = dict(MUTS, alice=("devmangle", 42, SEED_TLV))
    cap_solo: dict = {}
    cap_mix: dict = {}
    _b1, _r1, solo = _campaign([("alice", "demo_tlv", 4)],
                               capture_devmut=cap_solo, muts=muts)
    _b2, _r2, mixed = _campaign(
        [("alice", "demo_tlv", 4), ("bob", "demo_kernel", 4)],
        capture_devmut=cap_mix, muts=muts)
    # the generated byte stream itself is placement-invariant
    assert cap_solo["alice"] == cap_mix["alice"]
    assert solo["alice"] == mixed["alice"]


def test_three_tenant_mix_with_demo_pe():
    """The acceptance mix: demo_tlv + demo_kernel + demo_pe (real MSVC
    codegen) through ONE dispatch, each tenant bit-identical to its solo
    run.  Gated like test_pe_target on the census DLL."""
    import struct

    from wtf_tpu.harness import demo_pe

    if not demo_pe.available():
        pytest.skip("census DLL not present")
    benign = struct.pack("<Id", 4, 0.5) + struct.pack(
        "<12d", 1.0, 2.0, 3.0, 2.0, 3.0, 4.0, 3.0, 4.0, 5.0, 4.0, 5.0,
        6.0)
    muts = dict(MUTS, carol=("auto", 7, benign))
    cfg3 = [("alice", "demo_tlv", 4), ("bob", "demo_kernel", 4),
            ("carol", "demo_pe", 4)]
    limit = 2_000_000  # demo_pe runs real code (test_pe_target's budget)
    solos = {}
    for row in cfg3:
        _b, _r, fp = _campaign([row], batches=2, muts=muts, limit=limit)
        solos[row[0]] = fp[row[0]]
    _b, _r, mixed = _campaign(cfg3, batches=2, muts=muts, limit=limit)
    for name in ("alice", "bob", "carol"):
        assert mixed[name]["rips"], f"{name} decoded nothing"
        assert solos[name] == mixed[name], (
            f"{name} diverged between solo and the three-tenant mix")


def test_partial_plans_leave_unfilled_lanes_idle():
    backend, specs = _build([("alice", "demo_tlv", 4),
                             ("bob", "demo_kernel", 4)])
    results = backend.run_batch_tenants(
        [("host", [SEED_TLV]), ("host", [])])
    from wtf_tpu.core.results import Ok

    assert len(results) == 8
    assert all(isinstance(r, Ok) for r in results)
    # only alice's single active lane may have found coverage
    assert not any(backend.lane_found_new_coverage(lane)
                   for lane in range(4, 8))
    with pytest.raises(ValueError, match="5 testcases for 4 lanes"):
        backend.run_batch_tenants([("host", [b"x"] * 5), ("host", [])])
    with pytest.raises(ValueError, match="unknown plan kind"):
        backend.run_batch_tenants([("bogus", []), ("host", [])])


# ---------------------------------------------------------------------------
# preemption: checkpoint -> NEW placement (different tenant index/lane
# range) -> resume, bit-identical to uninterrupted
# ---------------------------------------------------------------------------

def test_preemption_resume_into_different_placement(tmp_path):
    # uninterrupted reference: alice alone for 4 batches
    _b, _r, want = _campaign([("alice", "demo_tlv", 4)], batches=4)

    # leg 1: alice alone, 2 batches, checkpoint
    backend1, specs1 = _build([("alice", "demo_tlv", 4)])
    rts1 = _runtimes(backend1, specs1, MUTS)
    rts1[0].checkpoint_dir = tmp_path / "alice"
    loop1 = MultiTenantLoop(backend1, rts1, stats_every=1e9)
    loop1.run_one_batch()
    loop1.run_one_batch()
    info = loop1.checkpoint_tenant(0)
    assert info and info["batches"] == 2

    # leg 2: alice re-placed as tenant 1 BEHIND bob (new tenant index,
    # new lane range) — the placement-free contract
    backend2, specs2 = _build([("bob", "demo_kernel", 4),
                               ("alice", "demo_tlv", 4)])
    rts2 = _runtimes(backend2, specs2, MUTS)
    rts2[1].checkpoint_dir = tmp_path / "alice"
    loop2 = MultiTenantLoop(backend2, rts2, stats_every=1e9)
    assert loop2.resume_tenant(1) == 2
    # bob idles (done-by-budget path not used; just plan him empty)
    rts2[0].runs = 0  # done => empty plan
    loop2.run_one_batch()
    loop2.run_one_batch()
    got = _fingerprint(backend2, rts2)["alice"]
    assert got == want["alice"], (
        "preempted+re-placed alice diverged from the uninterrupted run")


def test_restore_tenant_rejects_mismatched_placement(tmp_path):
    backend1, specs1 = _build([("alice", "demo_tlv", 4)])
    rts1 = _runtimes(backend1, specs1, MUTS)
    rts1[0].checkpoint_dir = tmp_path / "alice"
    loop1 = MultiTenantLoop(backend1, rts1, stats_every=1e9)
    loop1.run_one_batch()
    assert loop1.checkpoint_tenant(0)

    from wtf_tpu.resume.checkpoint import CheckpointError

    backend2, specs2 = _build([("alice", "demo_tlv", 8)])
    rt = _runtimes(backend2, specs2, MUTS)[0]
    with pytest.raises(CheckpointError, match="lanes"):
        restore_tenant(backend2, rt, 0, tmp_path / "alice")


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_load_jobs_validation(tmp_path):
    path = tmp_path / "jobs.json"
    path.write_text(json.dumps({"jobs": [
        {"name": "a", "target": "demo_tlv", "lanes": 4, "runs": 8},
        {"name": "b", "target": "demo_tlv", "lanes": 4, "runs": 8,
         "priority": 2},
    ]}))
    jobs = load_jobs(path)
    assert [j.name for j in jobs] == ["a", "b"]
    assert jobs[1].priority == 2 and jobs[0].seq == 0

    path.write_text(json.dumps([{"name": "a", "target": "t",
                                 "lanes": 4, "runs": 8, "lane": 9}]))
    with pytest.raises(ValueError, match="unknown fields"):
        load_jobs(path)
    path.write_text(json.dumps([{"name": "a", "target": "t"}]))
    with pytest.raises(ValueError, match="missing"):
        load_jobs(path)
    path.write_text(json.dumps([
        {"name": "a", "target": "t", "lanes": 4, "runs": 8},
        {"name": "a", "target": "t", "lanes": 4, "runs": 8}]))
    with pytest.raises(ValueError, match="duplicate"):
        load_jobs(path)
    with pytest.raises(ValueError, match="no placement"):
        Scheduler([Job(name="a", target="demo_tlv", lanes=64, runs=8)],
                  n_lanes=8, workdir=tmp_path)
    # names key tenant.<name>.* counters and name workdir subdirs: dots
    # would scramble the report's namespace split, separators escape
    # --workdir
    for bad in ("team.alice", "../other", "a/b", ""):
        path.write_text(json.dumps([{"name": bad, "target": "t",
                                     "lanes": 4, "runs": 8}]))
        with pytest.raises(ValueError, match="must match|missing"):
            load_jobs(path)
    with pytest.raises(ValueError, match="must match"):
        Scheduler([Job(name="x.y", target="demo_tlv", lanes=4, runs=8)],
                  n_lanes=8, workdir=tmp_path)


def test_scheduler_placement_priority_and_rotation(tmp_path):
    jobs = [Job(name="lo", target="demo_tlv", lanes=8, runs=8, seq=0),
            Job(name="hi", target="demo_tlv", lanes=8, runs=8,
                priority=1, seq=1),
            Job(name="mid", target="demo_tlv", lanes=8, runs=8, seq=2)]
    sched = Scheduler(jobs, n_lanes=8, workdir=tmp_path)
    # strict priority: hi owns the lanes until done, even after running
    assert [j.name for j in sched._place()] == ["hi"]
    jobs[1].last_round = 0
    assert [j.name for j in sched._place()] == ["hi"]
    # within a priority class, least-recently-run rotates (round-robin)
    jobs[1].done = True
    assert [j.name for j in sched._place()] == ["lo"]
    jobs[0].last_round = 1
    assert [j.name for j in sched._place()] == ["mid"]
    jobs[2].last_round = 2
    assert [j.name for j in sched._place()] == ["lo"]
    # two quota-4 jobs co-reside; a quota-8 job waits for a full budget
    small = [Job(name="x", target="demo_tlv", lanes=4, runs=8, seq=0),
             Job(name="y", target="demo_tlv", lanes=4, runs=8, seq=1),
             Job(name="z", target="demo_tlv", lanes=8, runs=8, seq=2)]
    sched2 = Scheduler(small, n_lanes=8, workdir=tmp_path)
    assert [j.name for j in sched2._place()] == ["x", "y"]


def test_scheduler_reuses_placement_across_rounds(tmp_path):
    """A solo job (nothing waiting, placement never changes) must keep
    its backend/loop live across quantum rounds — one build, no
    checkpoint-restore round trips between rounds."""
    from wtf_tpu.telemetry import Registry

    _targets()
    registry = Registry()
    jobs = [Job(name="alice", target="demo_tlv", lanes=8, runs=24,
                seed=42, mutator="tlv", max_len=256)]
    sched = Scheduler(jobs, n_lanes=8, workdir=tmp_path / "work",
                      limit=LIMIT, quantum=1, registry=registry)
    summary = sched.run()
    assert summary["alice"]["done"]
    assert sched.rounds == 3  # 24 runs / 8 lanes, 1 batch per round
    assert registry.counter("sched.builds").value == 1
    # per-round durability is kept: the quantum checkpoints still land
    assert registry.counter("tenant.alice.checkpoints").value == 3


def test_scheduler_preemption_and_report(tmp_path):
    inputs = tmp_path / "inputs"
    inputs.mkdir()
    (inputs / "seed").write_bytes(SEED_TLV)
    from wtf_tpu.telemetry import Registry, open_event_log

    _targets()
    registry = Registry()
    events = open_event_log(tmp_path / "tele")
    jobs = [Job(name="alice", target="demo_tlv", lanes=8, runs=24,
                seed=42, mutator="tlv", max_len=256, inputs=str(inputs)),
            Job(name="bob", target="demo_kernel", lanes=8, runs=16,
                seed=7, mutator="mangle", max_len=256)]
    sched = Scheduler(jobs, n_lanes=8, workdir=tmp_path / "work",
                      limit=LIMIT, quantum=1, registry=registry,
                      events=events)
    summary = sched.run()
    events.emit("run-end", metrics=registry.dump())
    events.close()
    assert summary["alice"]["done"] and summary["bob"]["done"]
    assert summary["alice"]["testcases"] == 24
    assert summary["bob"]["testcases"] == 16
    assert summary["alice"]["preemptions"] >= 1
    # final results checkpoints exist for DONE jobs too
    assert (tmp_path / "work" / "alice" / "checkpoint"
            / "checkpoint.json").exists()

    from telemetry_report import summarize

    s = summarize(tmp_path / "tele")
    ten = s["tenants"]
    assert set(ten["by_tenant"]) == {"alice", "bob"}
    assert ten["by_tenant"]["alice"]["testcases"] == 24
    assert ten["by_tenant"]["alice"]["batches"] == 3
    assert ten["sched"]["preemptions"] >= 1
    assert ten["sched"]["completions"] == 2


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

def test_mesh_tenancy_bit_parity():
    if len(jax.devices()) < 8:
        pytest.skip("needs the conftest's 8 virtual devices")
    cfg = [("alice", "demo_tlv", 8), ("bob", "demo_kernel", 8)]
    _b1, _r1, single = _campaign(cfg, batches=2)
    _b2, _r2, meshed = _campaign(cfg, batches=2, mesh_devices=8)
    assert single == meshed


# ---------------------------------------------------------------------------
# lint: tenancy budget rules (seeded violations)
# ---------------------------------------------------------------------------

def test_lint_tenant_mix_instability_fires():
    from wtf_tpu.analysis.rules import check_tenant_mix_stability

    same = "module @jit  {\n  foo\n}"
    assert check_tenant_mix_stability(same, same, entry="e") == []
    findings = check_tenant_mix_stability(
        same, same.replace("foo", "bar"), entry="e")
    assert [f.rule for f in findings] == ["budget.tenant-mix"]
    assert "tenant" in findings[0].message


def test_lint_tenant_budget_drift_fires(tmp_path):
    from wtf_tpu.analysis.rules import (
        TENANT_ENTRY, load_budgets, run_tenant_rules,
    )

    budgets = load_budgets()
    doctored = dict(budgets)
    doctored[TENANT_ENTRY] = dict(budgets[TENANT_ENTRY], gather=1)
    path = tmp_path / "budgets.json"
    path.write_text(json.dumps(doctored))
    findings, info = run_tenant_rules(budgets_path=path)
    rules = {f.rule for f in findings}
    assert "budget.kernel-count" in rules, (findings, info)
    # and against the checked-in budget the family is clean
    clean, info = run_tenant_rules()
    assert clean == [], clean
    assert info["tenant_counts"]["total"] == budgets[TENANT_ENTRY]["total"]
