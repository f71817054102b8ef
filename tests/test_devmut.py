"""Device-resident mutation engine (wtf_tpu/devmut) tests.

Three layers:
  * engine property tests — the vectorized u32 generator vs the
    authoritative host reference (devmut/hostref.py), bit-for-bit, over
    randomized corpora/seeds, plus the in-bounds/well-formed invariants
    the acceptance criteria name
  * the fused insert seam — Runner.device_insert lands the generated
    bytes + ABI registers exactly where the host insert_testcase would
  * the campaign path — FuzzLoop's devmangle batches on demo_tlv:
    deterministic given a seed, coverage-finding, with the mutate phase
    measured under mutate/device (host share ~ dispatch only)

The coverage-parity-vs-host-mangle comparison runs a real two-campaign
A/B and lives in the slow tier.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from wtf_tpu.devmut import hostref
from wtf_tpu.devmut.corpus import DeviceCorpus
from wtf_tpu.devmut.engine import make_generate

MAX_LEN = 64            # bytes per testcase in the engine-level tests
WORDS = MAX_LEN // 4


def _slab(rng, slots=4, live=3):
    data = np.zeros((slots, WORDS), np.uint32)
    lens = np.zeros((slots,), np.int32)
    weights = np.zeros((slots,), np.uint32)
    for s in range(live):
        n = rng.randint(1, MAX_LEN + 1)
        buf = np.zeros(MAX_LEN, np.uint8)
        buf[:n] = rng.randint(0, 256, n).astype(np.uint8)
        data[s] = buf.view(np.uint32)
        lens[s] = n
        weights[s] = 1 + s
    cumw = np.cumsum(weights, dtype=np.uint64).astype(np.uint32)
    return data, lens, cumw


@pytest.mark.parametrize("seed", [0xDEAD_BEEF_1234, 7, (1 << 64) - 3])
def test_generate_matches_host_reference(seed):
    """The device batch is bit-for-bit the host reference's, every
    testcase is well-formed (1 <= len <= max_len, zero padding past
    len), and enough batches run that ALL 8 mangle ops are exercised."""
    rng = np.random.RandomState(seed & 0xFFFF)
    data, lens, cumw = _slab(rng)
    gen = make_generate(3)
    ops_seen = set()
    for batch in range(4):
        seeds = hostref.lane_seeds(seed, batch, 8)
        d_words, d_lens = gen(jnp.asarray(data), jnp.asarray(lens),
                              jnp.asarray(cumw), jnp.asarray(seeds))
        trace = []
        h_words, h_lens = hostref.host_generate(data, lens, cumw, seeds,
                                                rounds=3, op_trace=trace)
        ops_seen |= set(trace)
        assert (np.asarray(d_lens) == h_lens).all()
        assert (np.asarray(d_words) == h_words).all()
        # well-formed: in-bounds lengths, zero bytes past each length
        assert (h_lens >= 1).all() and (h_lens <= MAX_LEN).all()
        raw = np.ascontiguousarray(h_words).view(np.uint8)
        for lane in range(8):
            assert not raw[lane, h_lens[lane]:].any()
    # 4 batches x 8 lanes x 3 rounds = 96 draws: every op must appear
    assert ops_seen == set(range(hostref.N_OPS)), sorted(ops_seen)


def test_lane_seeds_match_scalar_spec():
    """The vectorized numpy lane-seed stream is bit-exact with the
    scalar splitmix formula (both device and host mirrors consume these
    seeds, so a silent drift here would not be caught downstream)."""
    from wtf_tpu.utils.hashing import MASK64, mix64

    for seed, batch, n in ((0, 0, 4), (0xDEAD_BEEF, 7, 33),
                           ((1 << 64) - 1, 2, 5)):
        got = hostref.lane_seeds(seed, batch, n)
        for lane in range(n):
            want = mix64((seed + hostref.GOLDEN
                          * (batch * n + lane + 1)) & MASK64)
            assert int(got[lane, 0]) == want & 0xFFFFFFFF
            assert int(got[lane, 1]) == want >> 32


def test_generate_deterministic_and_seed_sensitive():
    rng = np.random.RandomState(3)
    data, lens, cumw = _slab(rng)
    gen = make_generate(3)
    args = (jnp.asarray(data), jnp.asarray(lens), jnp.asarray(cumw))
    seeds = hostref.lane_seeds(0x1234, 0, 4)
    w1, l1 = gen(*args, jnp.asarray(seeds))
    w2, l2 = gen(*args, jnp.asarray(seeds))
    assert (np.asarray(w1) == np.asarray(w2)).all()
    assert (np.asarray(l1) == np.asarray(l2)).all()
    seeds2 = hostref.lane_seeds(0x1235, 0, 4)
    w3, _ = gen(*args, jnp.asarray(seeds2))
    assert (np.asarray(w1) != np.asarray(w3)).any()


def test_generate_empty_corpus_synthesizes_fresh():
    """Zero total weight -> the fresh-synthesis path (1..64 stream
    bytes), still bit-exact vs the host reference."""
    data = np.zeros((4, WORDS), np.uint32)
    lens = np.zeros((4,), np.int32)
    cumw = np.zeros((4,), np.uint32)
    seeds = hostref.lane_seeds(99, 0, 6)
    gen = make_generate(3)
    d_words, d_lens = gen(jnp.asarray(data), jnp.asarray(lens),
                          jnp.asarray(cumw), jnp.asarray(seeds))
    h_words, h_lens = hostref.host_generate(data, lens, cumw, seeds, 3)
    assert (np.asarray(d_words) == h_words).all()
    assert (np.asarray(d_lens) == h_lens).all()
    assert (h_lens >= 1).all()


def test_device_corpus_slab_semantics():
    """add/dedup/evict: zero padding in slots, favored entries out-rank
    plain seeds in the cumulative-weight table and survive eviction."""
    c = DeviceCorpus(slots=3, max_len=16)
    assert c.add(b"AAAA")
    assert not c.add(b"AAAA")          # content dup
    assert c.add(b"BBBBBBBB", weight=hostref.FAVOR_WEIGHT)
    assert c.add(b"CC")
    assert len(c) == 3
    # slot 0 bytes zero-padded to the slab width
    assert c._data[0].view(np.uint8)[:4].tobytes() == b"AAAA"
    assert not c._data[0].view(np.uint8)[4:].any()
    cum = c.cumulative_weights()
    assert cum.dtype == np.uint32
    assert list(cum) == [1, 1 + hostref.FAVOR_WEIGHT,
                         2 + hostref.FAVOR_WEIGHT]
    # full: the new entry evicts the LOWEST-weight slot (slot 0), and
    # the favored slot survives
    assert c.add(b"DDDD", weight=2)
    assert c._data[0].view(np.uint8)[:4].tobytes() == b"DDDD"
    assert c._data[1].view(np.uint8)[:8].tobytes() == b"BBBBBBBB"
    # truncation to max_len
    assert c.add(b"E" * 64)
    assert int(c._len[int(np.argmax(c._weight == 1))]) <= 16
    # duplicate re-add with a higher weight upgrades the slot
    c2 = DeviceCorpus(slots=2, max_len=16)
    c2.add(b"XX")
    assert not c2.add(b"XX", weight=hostref.FAVOR_WEIGHT)
    assert int(c2._weight[0]) == hostref.FAVOR_WEIGHT
    # device arrays re-upload only when dirtied
    _, _, _, synced = c2.arrays()
    assert synced
    _, _, _, synced = c2.arrays()
    assert not synced


def test_device_insert_seam_matches_host_insert():
    """Runner.device_insert writes exactly what demo_tlv's host
    insert_testcase would: bytes at INPUT_GVA through the lane's memory
    view, pointer in rsi, length in rdx — and host page writes to the
    same page still work afterwards (the overlay row is claimed, not
    leaked)."""
    from wtf_tpu.harness import demo_tlv
    from wtf_tpu.interp.runner import Runner

    runner = Runner(demo_tlv.build_snapshot(), n_lanes=2, chunk_steps=32,
                    overlay_slots=8)
    view = runner.view()
    pfns = [view.translate(0, demo_tlv.INPUT_GVA) >> 12]
    payloads = [b"\x01\x04AAAA", b"\x03\x30" + b"Z" * 0x30]
    words = np.zeros((2, 1024), np.uint32)
    lens = np.zeros((2,), np.int32)
    for lane, p in enumerate(payloads):
        buf = np.zeros(4096, np.uint8)
        buf[:len(p)] = np.frombuffer(p, dtype=np.uint8)
        words[lane] = buf.view(np.uint32)
        lens[lane] = len(p)
    runner.device_insert(jnp.asarray(words), jnp.asarray(lens), pfns,
                         demo_tlv.INPUT_GVA, len_gpr=2, ptr_gpr=6)
    view = runner.view()
    for lane, p in enumerate(payloads):
        assert view.virt_read(lane, demo_tlv.INPUT_GVA, len(p)) == p
        assert view.get_reg(lane, 2) == len(p)            # rdx
        assert view.get_reg(lane, 6) == demo_tlv.INPUT_GVA  # rsi
        # padded-slab contract: bytes past len read as zero
        tail = view.virt_read(lane, demo_tlv.INPUT_GVA + len(p), 16)
        assert tail == b"\x00" * 16
    # a later host write to the inserted page updates the SAME overlay
    # row in place (no duplicate pfn rows)
    view.virt_write(0, demo_tlv.INPUT_GVA, b"\xee\xff")
    runner.push(view)
    view = runner.view()
    assert view.virt_read(0, demo_tlv.INPUT_GVA, 4) == b"\xee\xffAA"
    assert int((np.asarray(runner.machine.overlay.pfn)[0]
                == pfns[0]).sum()) == 1


def test_device_insert_preserves_pushed_host_writes():
    """run_batch_device pushes init-time host writes BEFORE the in-graph
    insert; the insert must not clobber their overlay rows (writes
    outside the input region survive) and must WIN over a pushed write
    to the input region itself (stale duplicate-pfn rows are retired —
    lookups take the first match)."""
    from wtf_tpu.harness import demo_tlv
    from wtf_tpu.interp.runner import Runner

    runner = Runner(demo_tlv.build_snapshot(), n_lanes=2, chunk_steps=32,
                    overlay_slots=8)
    view = runner.view()
    pfns = [view.translate(0, demo_tlv.INPUT_GVA) >> 12]
    # init-time host state: a write OUTSIDE the insert region and a
    # stale write INSIDE it, both pushed before the insert (the
    # run_batch_device ordering)
    view.virt_write(0, demo_tlv.SCRATCH_GVA, b"INITDATA")
    view.virt_write(0, demo_tlv.INPUT_GVA, b"STALEINPUT")
    runner.push(view)
    payload = b"\x01\x02XY"
    words = np.zeros((2, 1024), np.uint32)
    buf = np.zeros(4096, np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    words[:] = buf.view(np.uint32)
    runner.device_insert(jnp.asarray(words),
                         jnp.asarray(np.full(2, len(payload), np.int32)),
                         pfns, demo_tlv.INPUT_GVA, len_gpr=2, ptr_gpr=6)
    view = runner.view()
    # the out-of-region init write survived the insert
    assert view.virt_read(0, demo_tlv.SCRATCH_GVA, 8) == b"INITDATA"
    # the testcase won the input region (stale pushed bytes retired)
    assert view.virt_read(0, demo_tlv.INPUT_GVA, 10) == \
        payload + b"\x00" * 6
    # no duplicate live row for the input pfn on lane 0
    assert int((np.asarray(runner.machine.overlay.pfn)[0]
                == pfns[0]).sum()) == 1
    assert not np.asarray(runner.machine.overlay.overflow).any()


def _campaign(seed=0x77F, batches=2):
    from wtf_tpu.analysis.trace import build_tlv_campaign

    loop = build_tlv_campaign(n_lanes=8, mutator="devmangle",
                              limit=20_000, seed=seed, chunk_steps=128,
                              overlay_slots=16)
    for _ in range(batches):
        loop.run_one_batch()
    return loop


def test_devmangle_campaign_runs_and_is_deterministic():
    """The acceptance path: a demo_tlv devmangle campaign executes,
    finds coverage, keeps the mutate HOST share near zero (the phase is
    the nested mutate/device fence), and replays exactly under the same
    seed."""
    loop_a = _campaign(seed=0x5EED)
    assert loop_a.stats.testcases == 16
    assert loop_a.stats.new_coverage > 0
    assert len(loop_a.mutator.corpus) > 0
    spans = loop_a.registry.spans
    mutate = spans.seconds("mutate")
    mutate_dev = spans.seconds("mutate/device")
    assert mutate_dev > 0.0
    # the mutate phase is the device fence: host share is dispatch-only
    assert mutate - mutate_dev < 0.25 * mutate + 0.05
    # insert is in-graph too
    assert spans.seconds("execute/insert/device") > 0.0
    # devmut telemetry namespace is live
    assert loop_a.registry.counter("devmut.batches").value == 3  # +prelaunch
    assert loop_a.registry.counter("devmut.generated").value == 24

    loop_b = _campaign(seed=0x5EED)
    assert loop_b.stats.testcases == loop_a.stats.testcases
    assert loop_b.stats.crashes == loop_a.stats.crashes
    assert loop_b.stats.timeouts == loop_a.stats.timeouts
    assert loop_b._coverage() == loop_a._coverage()
    assert loop_b.corpus.digests == loop_a.corpus.digests


def test_devmangle_requires_device_backend_and_spec():
    import random

    from wtf_tpu.backend.emu import EmuBackend
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.fuzz.loop import FuzzLoop
    from wtf_tpu.fuzz.mutator import create_mutator
    from wtf_tpu.harness import demo_tlv
    from wtf_tpu.harness.targets import Target

    mut = create_mutator("devmangle", random.Random(1), 64)
    backend = EmuBackend(demo_tlv.build_snapshot())
    backend.initialize()
    with pytest.raises(ValueError, match="tpu backend"):
        FuzzLoop(backend, demo_tlv.TARGET, mut, Corpus())
    # a target without the declarative insert spec fails with the fix
    bare = Target.__new__(Target)   # no registry side effects
    bare.name = "bare"
    bare.device_insert = None
    with pytest.raises(ValueError, match="device_insert"):
        mut.bind(backend, bare)


@pytest.mark.slow
def test_devmangle_coverage_parity_with_host_mangle():
    """Acceptance: at equal exec counts on demo_tlv, the device engine
    reaches at least the host mangle engine's edge coverage (both from
    the same single seed), and the campaign stream stays deterministic
    over a longer run."""
    from wtf_tpu.analysis.trace import build_tlv_campaign

    cov = {}
    for engine in ("mangle", "devmangle"):
        loop = build_tlv_campaign(n_lanes=8, mutator=engine, limit=20_000,
                                  seed=0xAB, chunk_steps=128,
                                  overlay_slots=16)
        for _ in range(12):
            loop.run_one_batch()
        assert loop.stats.testcases == 96
        cov[engine] = loop._coverage()
    assert cov["devmangle"] >= cov["mangle"], cov