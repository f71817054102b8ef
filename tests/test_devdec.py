"""Device-resident x86 decode (wtf_tpu/interp/devdec.py).

The zero-host-steady-state contract: decode-cache misses inside a
megachunk window are serviced IN-GRAPH — page-walked 15-byte fetch,
batched decode, publish-order uop-table slot reservation — and the host
decoder stays the authoritative oracle: every device-published entry is
cross-checked bit-for-bit at harvest, encodings outside the device
subset park (stay NEED_DECODE) for in-order host service, and a
`--device-decode` campaign is byte-identical to the host-serviced
reference at equal seeds, single-device and on a mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wtf_tpu.analysis.trace import build_tlv_campaign
from wtf_tpu.cpu import uops as U
from wtf_tpu.cpu.decoder import decode
from wtf_tpu.cpu.uops import INT_FIELDS
from wtf_tpu.interp import devdec
from wtf_tpu.interp.uoptable import (
    M_BP, M_PFN0, M_PFN1, MU_DISP, MU_IMM, MU_RAW_HI, MU_RAW_LO,
    DecodeCache,
)
from wtf_tpu.mem.overlay import (
    LANE_AXES, lane as lane_view, lanes, overlay_init,
)
from wtf_tpu.mem.paging import translate, virt_read
from wtf_tpu.mem.physmem import IMAGE_IN_AXES, PhysMem, lane_image
from wtf_tpu.snapshot.synthetic import SyntheticSnapshotBuilder
from wtf_tpu.utils.hashing import hex_digest

MASK64 = (1 << 64) - 1
NEED_DECODE, RUNNING, PAGE_FAULT = 8, 0, 7

BUILD = dict(n_lanes=8, limit=20_000, chunk_steps=128, overlay_slots=16)

CODE = 0x140000000
# nop; mov eax,5; jnz +2; ret; inc ecx; ret   (all device-subset)
PROG = bytes.fromhex("90" "b805000000" "7502" "c3" "ffc1" "c3")
# a second chain: xor r8,r8; call +0; ret
PROG2 = bytes.fromhex("4d31c0" "e800000000" "c3")
X87 = bytes.fromhex("d8c1" "c3")          # fadd st(1): parks on device
# mov eax,5 placed 3 bytes before a page boundary -> the 5-byte
# encoding CROSSES into the next page (pfn1 != pfn0), then ret
SPLIT_OFF = 0xFFD


# -- fixture: synthetic snapshot + faithful host-service replication -------

@pytest.fixture(scope="module")
def snap():
    b = SyntheticSnapshotBuilder()
    b.write(CODE, PROG + b"\x00" * 16)
    b.write(CODE + 0x100, PROG2 + b"\x00" * 16)
    b.write(CODE + 0x200, X87 + b"\x00" * 16)
    b.write(CODE + SPLIT_OFF, bytes.fromhex("b805000000" "c3") + b"\x00" * 8)
    pages, cpu = b.build(rip=CODE, rsp=0x7FFE0F00)
    return PhysMem.from_pages(pages), int(cpu.cr3)


def _host_succs(u, at):
    nxt = (at + u.length) & MASK64
    if u.opc in (U.OPC_RET, U.OPC_IRET, U.OPC_HLT, U.OPC_INT,
                 U.OPC_INT1, U.OPC_INVALID, U.OPC_SYSCALL):
        return ()
    if u.opc == U.OPC_JMP:
        return ((nxt + u.imm) & MASK64,) if u.src_kind == U.K_IMM else ()
    if u.opc == U.OPC_JCC:
        return (nxt, (nxt + u.imm) & MASK64)
    if u.opc == U.OPC_CALL and u.src_kind == U.K_IMM:
        return (nxt, (nxt + u.imm) & MASK64)
    return (nxt,)


class _HostService:
    """runner._service_decode/_decode_at/_prefetch_block replicated over
    a synthetic snapshot — the parity reference for the device path."""

    def __init__(self, snap):
        self.mem, self.cr3 = snap

    def read(self, ov_lane, at, size):
        data, fault = virt_read(self.mem.image, ov_lane,
                                jnp.uint64(self.cr3), jnp.uint64(at), size)
        return bytes(np.asarray(data)), bool(fault)

    def pfn(self, ov_lane, at):
        t = translate(self.mem.image, ov_lane, jnp.uint64(self.cr3),
                      jnp.uint64(at))
        return int(t.gpa) >> 12, bool(t.ok)

    def decode_at(self, cache, ov_lane, rip):
        win, fault = self.read(ov_lane, rip, 15)
        pfn0, _ = self.pfn(ov_lane, rip)
        if fault:
            return False
        uop = decode(win, rip)
        pfn1, ok1 = self.pfn(ov_lane, (rip + max(uop.length - 1, 0))
                             & MASK64)
        if not ok1:
            pfn1 = pfn0
        cache.add(rip, uop, pfn0, pfn1)
        budget = 48
        work = list(_host_succs(uop, rip))
        while work and budget > 0:
            if cache.count >= cache.capacity - 64:
                return True
            at = work.pop()
            if cache.has(at):
                continue
            w, f = self.read(ov_lane, at, 15)
            p0, ok = self.pfn(ov_lane, at)
            if f or not ok:
                continue
            u = decode(w, at)
            if u.opc == U.OPC_INVALID:
                continue
            p1, ok1 = self.pfn(ov_lane, (at + max(u.length - 1, 0))
                               & MASK64)
            if not ok1:
                p1 = p0
            cache.add(at, u, p0, p1)
            budget -= 1
            work.extend(_host_succs(u, at))
        return True

    def service(self, cache, overlays, rips, statuses, upto):
        st = list(statuses)
        for lane in range(upto):
            if st[lane] != NEED_DECODE:
                continue
            ov_lane = lane_view(overlays, lane)
            rip = int(rips[lane])
            if not cache.has(rip):
                if not self.decode_at(cache, ov_lane, rip):
                    st[lane] = PAGE_FAULT
                    continue
            st[lane] = RUNNING
        return st

    def run_device(self, rips, statuses, seed_cache):
        n = len(rips)
        tab = seed_cache.device()
        overlays = overlay_init(n, 4)
        image = lane_image(self.mem.image, n)
        cr3s = jnp.full((n,), self.cr3, jnp.uint64)
        blocks = jax.vmap(
            devdec.lane_block,
            in_axes=(None, IMAGE_IN_AXES, LANE_AXES, 0, 0, 0, None, None),
        )(tab, image, lanes(overlays), cr3s, jnp.asarray(rips, jnp.uint64),
          jnp.asarray(statuses, jnp.int32), jnp.zeros((2,), jnp.uint64),
          jnp.int32(0))
        out = devdec.commit_blocks(
            tab, jnp.int32(seed_cache.count), blocks,
            jnp.asarray(statuses, jnp.int32), seed_cache.capacity)
        return out, overlays


def _assert_table_matches(cache, out, statuses_host, n_committed_lanes):
    """Device table == host cache bit for bit over the committed prefix:
    entry ORDER (coverage-bit identity), keys, every Uop field, disp/imm,
    raw bytes, pfns, bp — plus lane statuses and probe consistency."""
    assert int(out.count) == cache.count
    tab = out.tab
    rip_l = np.asarray(tab.rip_l)
    mi = np.asarray(tab.meta_i32)
    mu = np.asarray(tab.meta_u64)
    for i in range(cache.count):
        key = (int(rip_l[i, 0]) | (int(rip_l[i, 1]) << 32)) & MASK64
        assert key == int(cache.rip[i]), f"entry {i} key"
        uop = cache.uops[key]
        for f, nm in enumerate(INT_FIELDS):
            assert int(mi[i, f]) == int(getattr(uop, nm)), \
                f"entry {i} ({key:#x}) field {nm}"
        for col, val in ((M_PFN0, cache.pfn0[i]), (M_PFN1, cache.pfn1[i]),
                         (M_BP, cache.bp[i])):
            assert int(mi[i, col]) == int(val), f"entry {i} meta col {col}"
        for col, val in ((MU_DISP, cache.disp[i]), (MU_IMM, cache.imm[i]),
                         (MU_RAW_LO, cache.raw_lo[i]),
                         (MU_RAW_HI, cache.raw_hi[i])):
            assert int(mu[i, col]) == int(val), f"entry {i} u64 col {col}"
        assert int(devdec._probe_entry(tab.hash_tab,
                                       jnp.uint64(key))) == i
    st_dev = [int(s) for s in np.asarray(out.status)]
    assert st_dev[:n_committed_lanes] == statuses_host[:n_committed_lanes]


# -- randomized-encoding differential: decode_window vs host decoder ------

def test_decode_window_differential():
    """Every encoding the device decoder claims to know must decode
    bit-identically to cpu.decoder.decode — across opcode-map/modrm
    skeletons with random prefix/REX dressing, fully random windows
    (mostly invalid), and prefix-run truncation cases.  Unknown
    encodings park; they are allowed, mismatches are not."""
    rng = np.random.default_rng(0x77F)
    prefix_sets = [
        b"", b"\x66", b"\x67", b"\xf0", b"\xf2", b"\xf3", b"\x64",
        b"\x65", b"\x2e", b"\x66\xf3", b"\xf2\xf3", b"\x66\x67\x65",
        b"\xf0\x66", b"\x66\x66",
    ]
    rexes = [b"", b"\x40", b"\x48", b"\x41", b"\x44", b"\x42", b"\x4f",
             b"\x45", b"\x4c"]
    cases = []
    for m in (0, 1):
        for op in range(256):
            for _ in range(4):
                digit, mod = rng.integers(8), rng.integers(4)
                rm = int(rng.choice([0, 3, 4, 5]))
                modrm = (int(mod) << 6) | (int(digit) << 3) | rm
                pfx = prefix_sets[rng.integers(len(prefix_sets))]
                rex = rexes[rng.integers(len(rexes))]
                body = bytes([0x0F, op] if m else [op]) + bytes([modrm])
                tail = rng.integers(0, 256, 14, dtype=np.uint8).tobytes()
                cases.append((pfx + rex + body + tail)[:15])
    for _ in range(3000):
        cases.append(rng.integers(0, 256, 15, dtype=np.uint8).tobytes())
    for _ in range(1000):
        n = rng.integers(8, 15)
        pfx = bytes(rng.choice(
            [0x66, 0x67, 0xF0, 0xF2, 0xF3, 0x64, 0x2E], n))
        body = rng.integers(0, 256, 15, dtype=np.uint8).tobytes()
        cases.append((pfx + body)[:15])

    wins = np.frombuffer(b"".join(cases), np.uint8).reshape(len(cases), 15)
    out = jax.jit(jax.vmap(devdec.decode_window))(jnp.asarray(wins))
    known = np.asarray(out.known)
    f = np.asarray(out.f)
    disp = np.asarray(out.disp)
    imm = np.asarray(out.imm)
    assert known.sum() > len(cases) // 10  # the subset is not vacuous
    for i, win in enumerate(cases):
        if not known[i]:
            continue
        hu = decode(win, 0)
        for j, name in enumerate(INT_FIELDS):
            assert int(f[i, j]) == int(getattr(hu, name)), \
                f"win={win.hex()} field {name}"
        assert int(disp[i]) == hu.disp, f"win={win.hex()} disp"
        assert int(imm[i]) == hu.imm, f"win={win.hex()} imm"


# -- service differential: blocks+commit vs replicated host service -------

def test_service_all_device_lanes_with_duplicate(snap):
    """All-decodable lanes, one duplicate rip, one non-needy lane: the
    committed table is the host service bit for bit (dup publishes
    once, in first-lane order)."""
    hs = _HostService(snap)
    rips = [CODE, CODE + 0x100, CODE, CODE + 6]
    sts = [NEED_DECODE, NEED_DECODE, NEED_DECODE, RUNNING]
    cache = DecodeCache()
    out, ovs = hs.run_device(rips, sts, DecodeCache())
    host_st = hs.service(cache, ovs, rips, sts, len(rips))
    _assert_table_matches(cache, out, host_st, len(rips))


def test_service_page_fault_lane(snap):
    """A lane at an unmapped rip faults exactly like the host service:
    PAGE_FAULT status, fault_gva=rip, mem-fault counter bumped — and
    the lanes around it still commit."""
    hs = _HostService(snap)
    rips = [CODE + 0x100, 0xDEAD0000, CODE]
    sts = [NEED_DECODE] * 3
    cache = DecodeCache()
    out, ovs = hs.run_device(rips, sts, DecodeCache())
    host_st = hs.service(cache, ovs, rips, sts, len(rips))
    _assert_table_matches(cache, out, host_st, len(rips))
    assert bool(np.asarray(out.fault_mask)[1])
    assert int(np.asarray(out.fault_gva)[1]) == 0xDEAD0000
    assert int(np.asarray(out.mem_fault_inc)[1]) == 1


def test_service_park_all_rest(snap):
    """An encoding outside the device subset (x87) parks its lane AND
    every later needy lane — publish order is lane order, so nothing
    may leapfrog a parked lane.  Parked first => empty table; parked
    mid => the prefix commits and matches the host."""
    hs = _HostService(snap)
    out, _ = hs.run_device([CODE + 0x200, CODE],
                           [NEED_DECODE, NEED_DECODE], DecodeCache())
    assert int(out.count) == 0
    assert list(np.asarray(out.parked)) == [True, True]
    assert [int(s) for s in np.asarray(out.status)] == [NEED_DECODE] * 2

    cache = DecodeCache()
    out, ovs = hs.run_device([CODE, CODE + 0x200, CODE + 0x100],
                             [NEED_DECODE] * 3, DecodeCache())
    host_st = hs.service(cache, ovs, [CODE, CODE + 0x200, CODE + 0x100],
                         [NEED_DECODE] * 3, 1)
    _assert_table_matches(cache, out, host_st, 1)
    assert list(np.asarray(out.parked)) == [False, True, True]


def test_service_page_boundary_crossing(snap):
    """An encoding whose bytes straddle a page boundary publishes with
    pfn1 != pfn0 — the split-fetch pfn facts must match the host's
    per-byte translate walk exactly."""
    hs = _HostService(snap)
    rip = CODE + SPLIT_OFF
    cache = DecodeCache()
    out, ovs = hs.run_device([rip], [NEED_DECODE], DecodeCache())
    host_st = hs.service(cache, ovs, [rip], [NEED_DECODE], 1)
    _assert_table_matches(cache, out, host_st, 1)
    idx = int(np.asarray(out.count)) and 0
    mi = np.asarray(out.tab.meta_i32)
    assert int(mi[idx, M_PFN1]) == int(mi[idx, M_PFN0]) + 1


def test_service_warm_resume_and_smc_redecode(snap):
    """Warm start: lanes re-missing cached rips resume RUNNING without
    publishing (count unchanged).  SMC re-decode parity: a host
    cache.update (the SMC service path) rewrites the entry IN PLACE —
    same index — and the refreshed device table carries the updated
    fields, so a later device round still resumes against it."""
    hs = _HostService(snap)
    cache = DecodeCache()
    hs.service(cache, overlay_init(2, 4), [CODE, CODE + 0x100],
               [NEED_DECODE] * 2, 2)
    n0 = cache.count
    out, _ = hs.run_device([CODE, CODE + 0x100], [NEED_DECODE] * 2, cache)
    assert int(out.count) == n0
    assert [int(s) for s in np.asarray(out.status)] == [RUNNING] * 2

    # SMC: host re-decodes new bytes at CODE (inc ecx; ret lives there
    # in this fiction) and updates the shared entry in place
    new_uop = decode(bytes.fromhex("ffc1") + b"\x90" * 13, CODE)
    idx = cache.entry_index(CODE)
    cache.update(CODE, new_uop, cache.pfn0[idx], cache.pfn1[idx])
    assert cache.entry_index(CODE) == idx  # in-place, index stable
    out2, _ = hs.run_device([CODE, CODE + 0x100], [NEED_DECODE] * 2, cache)
    assert int(out2.count) == cache.count  # still no re-publish
    mi = np.asarray(out2.tab.meta_i32)
    for f, nm in enumerate(INT_FIELDS):
        assert int(mi[idx, f]) == int(getattr(new_uop, nm))


# -- campaign integration: --device-decode bit-identity -------------------

def _fingerprint(loop) -> dict:
    cov, edge = loop.backend.coverage_state()
    return {
        "cov": cov.tobytes(),
        "edge": edge.tobytes(),
        "cov_bits": loop._coverage(),
        "corpus_order": [hex_digest(d) for d in loop.corpus],
        "crashes": sorted(loop.crash_names),
        "buckets": sorted(loop.crash_buckets),
        "testcases": loop.stats.testcases,
        "timeouts": loop.stats.timeouts,
        "new_coverage": loop.stats.new_coverage,
    }


def _campaign(megachunk: int, runs: int, seed: int = 0x5EED, **kw):
    cfg = dict(BUILD)
    cfg.update(kw)
    loop = build_tlv_campaign(mutator="devmangle", seed=seed,
                              megachunk=megachunk, **cfg)
    loop.fuzz(runs)
    return loop


def test_device_decode_campaign_bit_identical():
    """The acceptance bar: a cold-cache `--device-decode` megachunk
    campaign is byte-identical to the host-serviced reference at equal
    seeds — coverage/edge bytes, corpus digests, crash buckets — with
    every decode entry device-published (zero host decode services),
    zero cross-check mismatches, and the checkpoint entry stream
    carrying identical indices."""
    runs = BUILD["n_lanes"] * 12
    ref = _campaign(4, runs)
    dd = _campaign(4, runs, device_decode=True)
    assert _fingerprint(dd) == _fingerprint(ref)
    reg = dd.backend.registry
    assert reg.counter("devdec.published").value > 0
    assert reg.counter("devdec.crosscheck_mismatches").value == 0
    assert reg.counter("devdec.zero_host_windows").value > 0
    # zero-host steady state on this target: the device serviced every
    # miss, the host decoder ran only as the cross-check oracle
    assert dd.backend.runner.stats["decodes"] == 0
    assert ref.backend.runner.stats["decodes"] > 0
    # device-published entries round-trip the checkpoint stream with
    # identical indices (coverage bit == entry index)
    ref_entries = list(ref.backend.runner.cache.checkpoint_entries())
    dd_entries = list(dd.backend.runner.cache.checkpoint_entries())
    assert dd_entries == ref_entries


def test_device_decode_pipelined_harvest_parity():
    """Pipelined harvest: steady-state windows prelaunch batch N+1
    before batch N's harvest completes; adopted speculative windows
    must leave the campaign byte-identical to the unpipelined reference
    (the prelaunch is dropped, not patched, on any operand drift)."""
    runs = BUILD["n_lanes"] * 24
    ref = _campaign(4, runs)
    dd = _campaign(4, runs, device_decode=True)
    assert _fingerprint(dd) == _fingerprint(ref)
    reg = dd.backend.registry
    assert reg.counter("megachunk.prelaunched").value > 0
    assert reg.counter("megachunk.prelaunch_hits").value > 0


def test_device_decode_mesh_parity():
    """Decode-slot parity on the forced 8-device mesh: the replicated
    commit (all-gathered blocks, identical sequential replay per shard)
    must yield the same entry indices — the campaign fingerprint and
    the decode cache match the single-device run exactly."""
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=8 (make mesh-smoke environment)")
    runs = BUILD["n_lanes"] * 6
    single = _campaign(3, runs, device_decode=True)
    mesh = _campaign(3, runs, mesh_devices=8, device_decode=True)
    assert _fingerprint(mesh) == _fingerprint(single)
    assert (list(mesh.backend.runner.cache.checkpoint_entries())
            == list(single.backend.runner.cache.checkpoint_entries()))
    assert mesh.backend.registry.counter(
        "devdec.crosscheck_mismatches").value == 0


@pytest.mark.slow
def test_device_decode_checkpoint_killpoint_sweep(tmp_path):
    """PR-8 crash-safety with device-published decode entries: kill at
    every interior batch boundary, resume, end bit-identical — the
    restored cache (device-published entries included) must rebuild
    the same uop-table indices or every later coverage bit shifts."""
    from wtf_tpu.resume import load_campaign, restore_campaign
    from wtf_tpu.testing.faultinject import fuzz_until_killed

    batches = 4
    runs = BUILD["n_lanes"] * batches
    ref = _campaign(4, runs, device_decode=True)
    ref_fp = _fingerprint(ref)
    assert ref_fp["cov_bits"] > 0

    for kill_at in range(1, batches):
        ckpt = tmp_path / f"kill{kill_at}"
        victim = build_tlv_campaign(mutator="devmangle", seed=0x5EED,
                                    megachunk=4, device_decode=True,
                                    **BUILD)
        victim.checkpoint_dir, victim.checkpoint_every = ckpt, 1
        fuzz_until_killed(victim, runs, kill_at_batch=kill_at)

        resumed = build_tlv_campaign(mutator="devmangle", seed=0x5EED,
                                     megachunk=4, device_decode=True,
                                     **BUILD)
        state, fell_back = load_campaign(ckpt)
        assert not fell_back
        assert restore_campaign(resumed, state, ckpt) == kill_at
        resumed.fuzz(runs)
        assert _fingerprint(resumed) == ref_fp, \
            f"kill at batch {kill_at}: state diverged"


# ---------------------------------------------------------------------------
# telemetry: the device-decode report section
# ---------------------------------------------------------------------------

def test_telemetry_report_device_decode_section(tmp_path):
    """The report surfaces the zero-host story: published entries,
    cross-check verdict, zero-host window lengths, and the harvest
    overlap share — and stays None for runs that never device-decoded."""
    import sys
    from pathlib import Path as _P

    sys.path.insert(0, str(_P(__file__).parent.parent / "tools"))
    import telemetry_report

    from wtf_tpu.telemetry import EventLog, Registry

    tdir = tmp_path / "telemetry"
    events = EventLog(tdir / "events.jsonl")
    registry = Registry()
    registry.counter("devdec.published").inc(53)
    registry.counter("devdec.serviced_lanes").inc(61)
    registry.counter("devdec.parked_lanes").inc(2)
    registry.counter("devdec.service_rounds").inc(9)
    registry.counter("devdec.zero_host_windows").inc(7)
    registry.counter("devdec.zero_host_batches").inc(89)
    registry.counter("devdec.crosscheck_mismatches").inc(0)
    registry.counter("runner.decodes").inc(0)
    registry.counter("megachunk.windows").inc(8)
    registry.counter("megachunk.prelaunched").inc(5)
    registry.counter("megachunk.prelaunch_hits").inc(4)
    registry.counter("megachunk.prelaunch_dropped").inc(1)
    events.emit("run-end", metrics=registry.dump())
    events.close()
    summary = telemetry_report.summarize(tdir)
    ddc = summary["device_decode"]
    assert ddc["published"] == 53
    assert ddc["crosscheck_mismatches"] == 0
    assert ddc["host_decode_services"] == 0
    assert ddc["zero_host_windows"] == 7
    assert ddc["zero_host_mean_batches"] == round(89 / 7, 1)
    assert ddc["harvest_overlap_share"] == 0.5
    telemetry_report._print_human(summary)  # must not raise

    # a host-serviced run has no devdec signal -> section stays None
    bare = tmp_path / "bare"
    events = EventLog(bare / "events.jsonl")
    registry = Registry()
    registry.counter("runner.decodes").inc(12)
    events.emit("run-end", metrics=registry.dump())
    events.close()
    assert telemetry_report.summarize(bare)["device_decode"] is None
