"""Batched triage engine (wtf_tpu/triage) on the conftest's virtual
CPU devices.

The acceptance contract (ISSUE 11): minimize converges to a
known-minimal demo_tlv reproducer of the SAME crash bucket; distill's
per-testcase edge attribution matches a host recount exactly and its
minset preserves aggregate coverage; vbreak captures equal the EmuCpu
oracle state at the armed instruction; and all three are bit-identical
on a mesh vs a single device.  Plus the crash-bucket satellite: two
distinct crashers never merge buckets, even when their filename-grade
names collide.
"""

import json
import random
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

from wtf_tpu.backend.emu import EmuBackend
from wtf_tpu.backend.tpu import TpuBackend
from wtf_tpu.core.results import Crash, Ok
from wtf_tpu.fuzz.corpus import Corpus
from wtf_tpu.fuzz.loop import FuzzLoop
from wtf_tpu.fuzz.mutator import ByteMutator
from wtf_tpu.harness import demo_tlv
from wtf_tpu.meshrun import MeshBackend
from wtf_tpu.triage import (
    ReplayCore, distill, minimize, oracle_capture, perturbations, vbreak,
)
from wtf_tpu.triage.bucket import bucket_of

# same shapes as tests/test_meshrun.py so executor compiles share the
# in-process jit cache and the persistent compilation cache
SMALL = dict(uop_capacity=1 << 10, overlay_slots=16, edge_bits=12,
             chunk_steps=8)
N_LANES = 16
LIMIT = 20000

# The canonical crasher family: a type-3 record copies 32 bytes into an
# 8-byte stack buffer — payload offsets 16..23 smash the saved rbp,
# 24..31 the return address (demo_tlv._GUEST_ASM).  `ret` then fetches
# from 0x4141... (non-canonical) -> execute fault.
SMASH = bytes([3, 32]) + bytes(range(65, 89)) + b"\x41" * 8
CRASHER = b"\x01\x02XY" + SMASH + b"\x01\x03ZZZ"
MINIMAL = bytes([3, 32]) + bytes(24) + b"\x41" * 8

CORPUS = [
    b"\x01\x02XY",                  # type-1 only
    b"\x01\x03ABC",                 # type-1 only (coverage-subsumed)
    b"\x02\x08QQQQQQQQ",            # type-2 only
    b"\x01\x02XY\x02\x08WWWWWWWW",  # types 1+2 (covers both)
    b"\x03\x04abcd",                # type-3 short copy (no crash)
]


def _backend(cls=TpuBackend, **kwargs):
    backend = cls(demo_tlv.build_snapshot(), n_lanes=N_LANES, limit=LIMIT,
                  **SMALL, **kwargs)
    backend.initialize()
    demo_tlv.TARGET.init(backend)
    return backend


@pytest.fixture(scope="module")
def backend():
    return _backend()


@pytest.fixture(scope="module")
def mesh_backend():
    return _backend(cls=MeshBackend, mesh_devices=8)


@pytest.fixture(scope="module")
def emu_backend():
    backend = EmuBackend(demo_tlv.build_snapshot(), limit=LIMIT)
    backend.initialize()
    demo_tlv.TARGET.init(backend)
    return backend


def _reset_coverage(backend):
    """Zero the backend's aggregate bitmaps: tests asserting absolute
    new-coverage semantics must not see earlier tests' merges (the
    module-scoped backend trades isolation for compile reuse)."""
    cov, edge = backend.coverage_state()
    backend.restore_coverage_state(np.zeros_like(cov), np.zeros_like(edge))


# ---------------------------------------------------------------------------
# crash buckets
# ---------------------------------------------------------------------------

def test_distinct_crashers_never_merge_buckets(backend):
    """The satellite pin: (kind, faulting RIP, top-of-stack hash) keeps
    distinct crashers apart — including two whose filename-grade names
    COLLIDE (same fault address, different smashed stacks)."""
    # A/B: different smashed return addresses -> different faulting RIP
    a = bytes([3, 32]) + bytes(24) + b"\x41" * 8
    b = bytes([3, 32]) + bytes(24) + b"\x42" * 8
    # C/D: SAME return address (same Crash.name) but the copy runs past
    # the return slot, planting different bytes at [rsp..] -> the
    # top-of-stack hash must split them
    c = bytes([3, 40]) + bytes(24) + b"\x41" * 8 + b"\xAA" * 8
    d = bytes([3, 40]) + bytes(24) + b"\x41" * 8 + b"\xBB" * 8
    core = ReplayCore(backend, demo_tlv.TARGET)
    sweep = core.replay([a, b, c, d], want_buckets=True)
    assert all(isinstance(r, Crash) for r in sweep.results)
    assert sweep.results[2].name == sweep.results[3].name  # names collide
    buckets = [sweep.buckets[i] for i in range(4)]
    assert len(set(buckets)) == 4, buckets


def test_fuzz_loop_dedups_by_bucket(backend, tmp_path):
    """FuzzLoop's harvest and the triage helper share one bucket: the
    name-colliding pair lands as TWO buckets (and the crash event says
    new=True for each first sighting)."""
    c = bytes([3, 40]) + bytes(24) + b"\x41" * 8 + b"\xAA" * 8
    d = bytes([3, 40]) + bytes(24) + b"\x41" * 8 + b"\xBB" * 8
    loop = FuzzLoop(backend, demo_tlv.TARGET,
                    ByteMutator(random.Random(1), 128),
                    Corpus(), crashes_dir=tmp_path / "crashes")
    batch = [c, d]
    results = backend.run_batch(batch, demo_tlv.TARGET)
    loop._harvest_batch(results, batch)
    demo_tlv.TARGET.restore()
    backend.restore()
    assert len(loop.crash_names) == 1          # filenames collide...
    assert len(loop.crash_buckets) == 2        # ...buckets do not


# ---------------------------------------------------------------------------
# the crashing lanes' stack pages in one transfer (HostView.prefetch)
# ---------------------------------------------------------------------------

PAGE = 0x1000
STACK_PAGE = demo_tlv.STACK_TOP - 2 * PAGE      # mapped, as is the next one


def _smash(length: int, fill: int) -> bytes:
    """A type-3 record of `length` bytes: 24 reach the return address,
    the next 8 replace it (non-canonical: `ret` faults), the rest land
    above it, where `rsp` points when the lane is bucketed."""
    return bytes([3, length]) + bytes([fill]) * length


def _insert_at(backend, case) -> None:
    """(rsp or None, testcase): parse() entered with its return address
    at `rsp` (the snapshot's is the first word of a page)."""
    rsp, data = case
    demo_tlv.TARGET.insert_testcase(backend, data)
    if rsp is not None:
        backend.set_reg(4, rsp)
        backend.virt_write(rsp, demo_tlv.FINISH_GVA.to_bytes(8, "little"))


_AT = type("Target", (), {"insert_testcase": staticmethod(_insert_at)})

# after `ret`, rsp = the case's rsp + 8 and the bucket hashes 64 bytes there
PLAIN = [(None, _smash(32 + 8 * i, 0x41 + i)) for i in range(5)]
# rsp + 8 is 40 bytes under a page end and the copy runs 8 bytes into the
# next page: both pages of the window are dirty
CROSS_BOTH = (STACK_PAGE + PAGE - 48, _smash(80, 0x51))
# the copy stops at the return address: the window's second page is clean
CROSS_ONE = (STACK_PAGE + PAGE - 48, _smash(32, 0x52))
# the return address fills a page's last word: the window is the next
# page, which the lane never wrote
CLEAN = (STACK_PAGE + PAGE - 8, _smash(32, 0x53))
# the window runs off the mapped stack
NOSTACK = (demo_tlv.STACK_TOP + PAGE - 48, _smash(32, 0x54))
BENIGN_CASE = (None, b"\x01\x02XY")


def _row_counters(backend):
    stats = backend.runner.stats
    return stats["view_row_pulls"], stats["view_rows"]


def _buckets_lazy_then_prefetched(backend, cases):
    """Run `cases`, bucket every crashing lane from a fresh view on the
    lazy path, then again from a second fresh view after ONE
    `prefetch_stacks`.  -> (results, lazy buckets, prefetched buckets,
    (pulls, rows) of the lazy pass, of the prefetch, of the bucketing
    behind it), with the prefetched pages checked against the lazy
    view's, byte for byte."""
    from wtf_tpu.triage.bucket import prefetch_stacks

    results = backend.run_batch(cases, _AT)
    crashed = [lane for lane, r in enumerate(results)
               if isinstance(r, Crash)]
    marks = [_row_counters(backend)]
    backend._view = None
    lazy_view = backend._ensure_view()
    lazy = {lane: bucket_of(backend, lane, results[lane])
            for lane in crashed}
    marks.append(_row_counters(backend))
    backend._view = None
    view = backend._ensure_view()
    assert view is not lazy_view
    prefetch_stacks(backend, crashed)
    marks.append(_row_counters(backend))
    fetched = {lane: bucket_of(backend, lane, results[lane])
               for lane in crashed}
    marks.append(_row_counters(backend))
    assert backend._view is view
    for key, page in view._page_cache.items():
        assert page == lazy_view.page(*key), key
    demo_tlv.TARGET.restore()
    backend.restore()
    deltas = [(b[0] - a[0], b[1] - a[1]) for a, b in zip(marks, marks[1:])]
    return results, lazy, fetched, deltas


@pytest.fixture(params=["one-device", "mesh"])
def either_backend(request, backend, mesh_backend):
    return backend if request.param == "one-device" else mesh_backend


def test_prefetched_buckets_equal_the_lazy_path(either_backend):
    """N crashing lanes with a dirty stack page: N round trips on the
    lazy path, ONE after a prefetch, the same rows and the same buckets,
    and nothing left for `bucket_of` to fetch."""
    cases = PLAIN[:3] + [BENIGN_CASE] + PLAIN[3:]
    results, lazy, fetched, deltas = _buckets_lazy_then_prefetched(
        either_backend, cases)
    assert [isinstance(r, Crash) for r in results] == [
        True, True, True, False, True, True]
    assert fetched == lazy and len(set(lazy.values())) == 5
    assert not any(b.endswith("nostack") for b in lazy.values())
    assert deltas == [(5, 5), (1, 5), (0, 0)]


def test_prefetch_near_a_page_end_clean_pages_and_an_unreadable_stack(
        either_backend):
    """A window that crosses a page end takes a second round trip only
    if the lane dirtied the second page too; a clean page costs no row;
    a window that leaves the mapped stack is `nostack` as before."""
    cases = [PLAIN[0], CROSS_BOTH, CROSS_ONE, CLEAN, NOSTACK, BENIGN_CASE]
    results, lazy, fetched, deltas = _buckets_lazy_then_prefetched(
        either_backend, cases)
    assert all(isinstance(r, Crash) for r in results[:5])
    assert fetched == lazy and len(set(lazy.values())) == 5
    assert [b.endswith("nostack") for b in lazy.values()] == [
        False, False, False, False, True]
    # rows: lanes 0, 2 and 4 one each (NOSTACK's first page is fetched
    # before its second is found unmapped), CROSS_BOTH two, CLEAN none
    assert deltas[0] == (5, 5)
    assert deltas[1:] == [(2, 5), (0, 0)]


def test_prefetch_of_clean_stacks_dispatches_nothing(backend, monkeypatch):
    from wtf_tpu.triage.bucket import prefetch_stacks

    def refuse(rows):
        raise AssertionError("a row program for no dirty page")

    monkeypatch.setattr(backend.runner, "fetch_overlay_rows", refuse)
    results = backend.run_batch([CLEAN, CLEAN, BENIGN_CASE], _AT)
    before = _row_counters(backend)
    prefetch_stacks(backend, [0, 1])
    prefetch_stacks(backend, [])
    buckets = {bucket_of(backend, lane, results[lane]) for lane in (0, 1)}
    assert _row_counters(backend) == before
    assert len(buckets) == 1 and not buckets.pop().endswith("nostack")
    demo_tlv.TARGET.restore()
    backend.restore()


def test_prefetch_with_a_quarantined_lane(either_backend):
    """A quarantined lane is parked idle, is no crash and is fetched no
    row; its neighbours' buckets are what they are without it."""
    quarantined = either_backend.supervisor.quarantined
    _, alone, _, _ = _buckets_lazy_then_prefetched(
        either_backend, [PLAIN[0], PLAIN[2]])
    quarantined.add(1)
    try:
        results, lazy, fetched, deltas = _buckets_lazy_then_prefetched(
            either_backend, [PLAIN[0], PLAIN[1], PLAIN[2]])
    finally:
        quarantined.discard(1)
    assert isinstance(results[1], Ok) and sorted(lazy) == [0, 2]
    assert fetched == lazy
    assert [lazy[0], lazy[2]] == [alone[0], alone[1]]
    assert deltas == [(2, 2), (1, 2), (0, 0)]


def test_harvest_prefetches_before_its_crash_save_loop(backend, tmp_path):
    """`FuzzLoop._harvest_batch` hands the crashing lanes over once: one
    round trip for the batch's rows, under `harvest/crash-save`."""
    loop = FuzzLoop(backend, _AT, ByteMutator(random.Random(1), 128),
                    Corpus(), crashes_dir=tmp_path / "crashes")
    batch = PLAIN[:4] + [BENIGN_CASE]
    results = backend.run_batch(batch, _AT)
    before = _row_counters(backend)
    assert loop._harvest_batch(results, [d for _, d in batch]) == 4
    after = _row_counters(backend)
    demo_tlv.TARGET.restore()
    backend.restore()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 4)
    assert len(loop.crash_buckets) == 4


# ---------------------------------------------------------------------------
# minimize
# ---------------------------------------------------------------------------

def test_minimize_converges_to_known_minimal(backend):
    result = minimize(backend, demo_tlv.TARGET, CRASHER)
    assert result.data == MINIMAL
    assert result.from_len == len(CRASHER)
    assert len(result.data) < len(CRASHER)
    # the minimized reproducer still reproduces the SAME bucket (the
    # minimizer verified this internally; re-check independently)
    core = ReplayCore(backend, demo_tlv.TARGET)
    sweep = core.replay([CRASHER, result.data], want_buckets=True)
    assert sweep.buckets[0] == sweep.buckets[1] == result.bucket
    # "a handful of dispatches": bisection, not per-candidate replay
    assert result.dispatches <= 40
    assert result.candidates > len(CRASHER)  # real batched storm


def test_minimize_rejects_non_crasher(backend):
    with pytest.raises(ValueError, match="does not reproduce"):
        minimize(backend, demo_tlv.TARGET, b"\x01\x02XY")


# ---------------------------------------------------------------------------
# distill
# ---------------------------------------------------------------------------

def test_distill_attribution_matches_host_recount(backend):
    _reset_coverage(backend)
    result = distill(backend, demo_tlv.TARGET, CORPUS)
    sweep = result.sweep
    planes = np.concatenate([sweep.cov, sweep.edge], axis=1)
    credit = np.concatenate([sweep.credit_cov, sweep.credit_edge], axis=1)
    union = np.zeros(planes.shape[1], np.uint32)
    for i in range(len(CORPUS)):
        expected = planes[i] & ~union
        np.testing.assert_array_equal(
            credit[i], expected,
            err_msg=f"in-graph first-hit credit diverges at testcase {i}")
        union |= planes[i]
    # credit flags == the backend merge's new-coverage flags (the old
    # minset keep rule) — one prefix-credit semantics everywhere
    np.testing.assert_array_equal(sweep.new_lane, result.credit_bits > 0)


def test_distill_cover_is_exact_and_minimal(backend):
    result = distill(backend, demo_tlv.TARGET, CORPUS)
    # set-cover invariant: kept aggregate == full corpus aggregate
    assert result.kept_bits == result.total_bits > 0
    assert 0 < len(result.keep) < len(CORPUS)
    # exact attribution can only improve on prefix credit
    assert len(result.keep) <= len(result.prefix_keep)
    # subsumed seeds carry zero exact credit
    assert result.credit_bits[1] == 0


def test_minset_rides_the_replay_core(backend):
    """FuzzLoop.minset (campaign --runs 0) and distill share one
    execution path and one keep rule: minset's kept set == the
    prefix-credit indices, stats accounted as before."""
    _reset_coverage(backend)
    corpus = Corpus()
    for data in CORPUS:
        corpus.add(data)
    ordered = list(corpus)
    loop = FuzzLoop(backend, demo_tlv.TARGET,
                    ByteMutator(random.Random(1), 128), corpus)
    # CampaignStats counters live in the backend's (module-shared)
    # registry — assert the deltas this minset contributed
    testcases0 = loop.stats.testcases
    newcov0 = loop.stats.new_coverage
    kept = loop.minset(outputs_dir=None)
    result = distill(backend, demo_tlv.TARGET, ordered)
    from wtf_tpu.utils.hashing import hex_digest

    assert kept.digests == {hex_digest(ordered[i])
                            for i in result.prefix_keep}
    assert loop.stats.testcases - testcases0 == len(ordered)
    assert loop.stats.new_coverage - newcov0 == len(result.prefix_keep)


# ---------------------------------------------------------------------------
# vbreak
# ---------------------------------------------------------------------------

# `next_record` (the loop head `cmp r8, r9`): push+mov+sub+mov+lea+xor
# prefix = 18 bytes of _GUEST_CODE
NEXT_RECORD = demo_tlv.CODE_GVA + 18


def test_vbreak_capture_equals_oracle(backend, emu_backend):
    data = b"\x01\x02XY\x02\x08WWWWWWWW"
    testcases = perturbations(data, 4)
    captures, results = vbreak(backend, demo_tlv.TARGET, testcases,
                               NEXT_RECORD, hit=2)
    assert captures[0] is not None  # the unperturbed baseline captures
    for i, data_i in enumerate(testcases):
        oc = oracle_capture(emu_backend, demo_tlv.TARGET, data_i,
                            NEXT_RECORD, hit=2)
        c = captures[i]
        # a perturbation may divert before the 2nd arrival — device and
        # oracle must AGREE on that too
        assert (c is None) == (oc is None), f"capture parity, tc {i}"
        if c is None:
            continue
        assert isinstance(results[i], Ok)
        assert c.rip == oc.rip == NEXT_RECORD
        assert c.gpr == oc.gpr, f"gpr mismatch on testcase {i}"
        assert c.rflags == oc.rflags
        assert c.icount == oc.icount > 0
        assert c.mem == oc.mem and len(c.mem) > 0
    # the second arrival really is mid-parse: r8 advanced past record 0
    assert captures[0].gpr[8] > demo_tlv.INPUT_GVA


def test_vbreak_unreached_rip_reports_natural_result(backend):
    # a crasher never returns to the loop head a 3rd time
    captures, results = vbreak(backend, demo_tlv.TARGET, [MINIMAL],
                               NEXT_RECORD, hit=99)
    assert captures == [None]
    assert isinstance(results[0], Crash)
    # the armed bp is disarmed again: plain replay is unaffected
    sweep = ReplayCore(backend, demo_tlv.TARGET).replay([CORPUS[0]])
    assert isinstance(sweep.results[0], Ok)


def test_vbreak_collision_with_target_bp(backend):
    with pytest.raises(ValueError, match="already armed"):
        vbreak(backend, demo_tlv.TARGET, [CORPUS[0]], demo_tlv.FINISH_GVA)


# ---------------------------------------------------------------------------
# mesh bit-parity
# ---------------------------------------------------------------------------

def test_mesh_bit_parity_all_three(backend, mesh_backend):
    """--mesh-devices 8 vs single device: minimize returns the same
    bytes/bucket/dispatch count, distill the same keep sets and credit
    ledger, vbreak the same captures — bit-identical triage."""
    a = minimize(backend, demo_tlv.TARGET, CRASHER)
    b = minimize(mesh_backend, demo_tlv.TARGET, CRASHER)
    assert a.data == b.data == MINIMAL
    assert a.bucket == b.bucket
    assert (a.rounds, a.dispatches, a.simplified) == \
        (b.rounds, b.dispatches, b.simplified)

    da = distill(backend, demo_tlv.TARGET, CORPUS)
    db = distill(mesh_backend, demo_tlv.TARGET, CORPUS)
    assert da.keep == db.keep
    assert da.prefix_keep == db.prefix_keep
    np.testing.assert_array_equal(da.credit_bits, db.credit_bits)
    assert (da.total_bits, da.kept_bits) == (db.total_bits, db.kept_bits)

    data = b"\x01\x02XY\x02\x08WWWWWWWW"
    ca, _ = vbreak(backend, demo_tlv.TARGET, perturbations(data, 3),
                   NEXT_RECORD, hit=2)
    cb, _ = vbreak(mesh_backend, demo_tlv.TARGET, perturbations(data, 3),
                   NEXT_RECORD, hit=2)
    for x, y in zip(ca, cb):
        assert (x.gpr, x.rflags, x.icount, x.mem) == \
            (y.gpr, y.rflags, y.icount, y.mem)


# ---------------------------------------------------------------------------
# lint + report satellites
# ---------------------------------------------------------------------------

def test_lint_pins_triage_chunk_identity(monkeypatch):
    from wtf_tpu.analysis import rules
    from wtf_tpu.triage import replay

    assert rules.check_triage_chunk() == []
    monkeypatch.setattr(replay, "REPLAY_CHUNK_FACTORY",
                        lambda n, donate: None)
    found = rules.check_triage_chunk()
    assert [f.rule for f in found] == ["budget.triage-chunk"]


def test_lint_pins_triage_dtype_exports():
    """Every triage ported path has a recipe (dtype.unpinned fires for a
    seeded rogue export, stays silent for the real ones)."""
    from wtf_tpu.analysis.rules import run_dtype_family
    from wtf_tpu.triage import candidates

    clean = run_dtype_family(exports=dict(candidates.PORTED_LIMB_PATHS),
                             compile_paths=False)
    assert clean == []
    seeded = run_dtype_family(
        exports={**candidates.PORTED_LIMB_PATHS,
                 "triage.rogue_path": lambda x: x},
        compile_paths=False)
    assert [f.rule for f in seeded] == ["dtype.unpinned"]


def test_report_triage_section(tmp_path):
    from telemetry_report import summarize

    events = tmp_path / "events.jsonl"
    metrics = {
        "triage.candidates": 742, "triage.dispatches": 28,
        "triage.minimizations": 1, "triage.minimize_rounds": 3,
        "triage.bytes_removed": 9, "triage.minset_before": 5,
        "triage.minset_after": 2, "triage.captures": 4,
        "triage.crashes": 300,
    }
    with events.open("w") as fh:
        fh.write(json.dumps({"ts": 1.0, "seq": 0, "type": "run-start"})
                 + "\n")
        fh.write(json.dumps({"ts": 11.0, "seq": 1, "type": "run-end",
                             "metrics": metrics}) + "\n")
    s = summarize(events)
    tri = s["triage"]
    assert tri["candidates"] == 742
    assert tri["dispatches_per_minimization"] == 28.0
    assert tri["minset_before"] == 5 and tri["minset_after"] == 2
    assert tri["captures"] == 4
    # quiet campaigns stay quiet
    with events.open("w") as fh:
        fh.write(json.dumps({"ts": 1.0, "seq": 0, "type": "run-start"})
                 + "\n")
        fh.write(json.dumps({"ts": 2.0, "seq": 1, "type": "run-end",
                             "metrics": {}}) + "\n")
    assert summarize(events)["triage"] is None
