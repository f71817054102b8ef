"""Megachunk window tests (wtf_tpu/fuzz/megachunk.py).

The acceptance contract (ISSUE 14): a devmangle campaign driven through
one-dispatch multi-batch windows is bit-identical to the batch-at-a-time
device loop at equal seeds — coverage/edge bytes, crash buckets, corpus
digests — for any window size, on a single device and on a mesh; the
PR-8 checkpoint/resume contract survives (kill at any batch boundary,
resume bit-identically); and the devmut seed stream is neither
double-generated nor skewed when generation moves in-graph
(bit-exactness vs hostref at any batch count).
"""

import jax
import numpy as np
import pytest

from wtf_tpu.analysis.trace import build_tlv_campaign
from wtf_tpu.resume import load_campaign, restore_campaign
from wtf_tpu.testing.faultinject import fuzz_until_killed
from wtf_tpu.utils.hashing import hex_digest

# test_devmut/test_resume shapes: compile-cache-shared across the suite
BUILD = dict(n_lanes=8, limit=20_000, chunk_steps=128, overlay_slots=16)


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
        # what the retired instructions did, counted in-graph: the window
        # folds per-batch totals of the block, the batch loop the block
        "device": {key: loop.registry.counter(f"device.{key}").value
                   for key in ("instructions", "store_steps",
                               "vector_steps", "levels_used")},
    }


def _campaign(megachunk: int, runs: int, seed: int = 0x5EED, **kw):
    cfg = dict(BUILD)
    cfg.update(kw)
    loop = build_tlv_campaign(mutator="devmangle", seed=seed,
                              megachunk=megachunk, **cfg)
    loop.fuzz(runs)
    return loop


def test_megachunk_window_bit_identical_to_batch_at_a_time():
    """The tentpole parity bar: a B=4 window campaign is byte-identical
    to the B=1 (one-batch-per-dispatch) campaign AND to the legacy
    prelaunch loop at equal seeds — aggregate coverage/edge bitmap
    BYTES, corpus digests in order, crash names/buckets, and every
    counter.

    12 batches, NOT a cold-cache-only handful: the campaign must run
    long enough that new-coverage finds land in IN-GRAPH batches (the
    find-stop seam), because that is where the slab schedule can skew —
    the next window's first batch must sample the slab WITHOUT the
    final harvested batch's finds (the legacy prelaunch lag), which a
    4-batch run whose only find is host-serviced never exercises."""
    runs = BUILD["n_lanes"] * 12
    fp1 = _fingerprint(_campaign(1, runs))
    fp4 = _fingerprint(_campaign(4, runs))
    assert fp4 == fp1
    legacy = _fingerprint(_campaign(0, runs))
    assert legacy == fp1
    assert fp1["cov_bits"] > 0 and fp1["testcases"] == runs
    assert 0 < fp1["device"]["store_steps"] < fp1["device"]["instructions"]
    assert fp1["new_coverage"] > 1  # finds beyond the cold-start window


def test_megachunk_batches_accounting_and_host_spans():
    """A window advances batches_done by its COMPLETED batch count, the
    devmut stream cursor matches (no double-generate), and the device
    wait is fenced under execute/device (the host-share measurement's
    denominator)."""
    runs = BUILD["n_lanes"] * 3
    loop = _campaign(3, runs)
    assert loop.batches_done == loop.mutator._batch
    assert loop.stats.testcases == loop.batches_done * BUILD["n_lanes"]
    secs = loop.registry.spans.seconds("execute/device")
    assert secs > 0.0
    # the window's span has device-step's two children: the jit call and
    # the fence, and nothing else
    inside = sum(loop.registry.spans.seconds(f"execute/device/{child}")
                 for child in ("dispatch", "wait"))
    assert 0.8 * secs <= inside <= secs
    # megachunk consumed the whole campaign: the legacy per-batch device
    # generation span must never have fired
    assert loop.registry.spans.seconds("mutate/device") == 0.0


def test_megachunk_seed_stream_bit_exact_vs_hostref():
    """The no-skew satellite: batch k generated in-graph inside a window
    equals hostref.host_generate(slab, seed, k) byte-for-byte — the
    stream is keyed on the ABSOLUTE batch index, so moving generation
    in-graph cannot double-generate or shift it."""
    from wtf_tpu.devmut import hostref
    from wtf_tpu.devmut.engine import make_generate

    runs = BUILD["n_lanes"] * 3
    loop = _campaign(3, runs)
    mut = loop.mutator
    # regenerate an arbitrary executed batch index through the ENGINE at
    # the as-uploaded slab view and compare with the host reference
    k = 1
    up = mut.corpus.uploaded_state()
    seeds = hostref.lane_seeds(mut.seed, k, mut.n_lanes)
    import jax.numpy as jnp

    cum = np.cumsum(up["weight"], dtype=np.uint64).astype(np.uint32)
    # host reference over the same slab view
    ref_words, ref_lens = hostref.host_generate(
        up["data"], up["lens"], cum, seeds, rounds=mut.rounds)
    dev_words, dev_lens = make_generate(mut.rounds)(
        jnp.asarray(up["data"]), jnp.asarray(up["lens"]),
        jnp.asarray(cum), jnp.asarray(seeds))
    assert np.array_equal(np.asarray(jax.device_get(dev_words)),
                          ref_words)
    assert np.array_equal(np.asarray(jax.device_get(dev_lens)), ref_lens)


def test_megachunk_requires_device_engine_and_limit():
    """Config surface: megachunk without devmangle / without a limit
    fails at construction, not deep into a campaign."""
    import random

    from wtf_tpu.backend import create_backend
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.fuzz.loop import FuzzLoop
    from wtf_tpu.fuzz.native_mutator import best_mangle_mutator
    from wtf_tpu.harness import demo_tlv

    rng = random.Random(7)
    backend = create_backend("tpu", demo_tlv.build_snapshot(),
                             n_lanes=2, limit=1000)
    backend.initialize()
    with pytest.raises(ValueError, match="devmangle"):
        FuzzLoop(backend, demo_tlv.TARGET,
                 best_mangle_mutator(rng, max_len=16), Corpus(rng=rng),
                 megachunk=4)
    from wtf_tpu.devmut.mutator import DevMangleMutator

    backend2 = create_backend("tpu", demo_tlv.build_snapshot(),
                              n_lanes=2, limit=0)
    backend2.initialize()
    demo_tlv.TARGET.init(backend2)
    with pytest.raises(ValueError, match="limit"):
        FuzzLoop(backend2, demo_tlv.TARGET,
                 DevMangleMutator(seed=1, max_len=64), Corpus(rng=rng),
                 megachunk=4)


@pytest.mark.slow
def test_megachunk_checkpoint_killpoint_sweep(tmp_path):
    """PR-8 crash-safety under megachunk windows: with a checkpoint at
    every batch boundary (the cadence caps each window to one batch, so
    every boundary is reachable), kill at EVERY interior boundary and
    resume — final state bit-identical to the uninterrupted windowed
    run."""
    batches = 4
    runs = BUILD["n_lanes"] * batches
    ref = _campaign(4, runs)
    ref_fp = _fingerprint(ref)
    assert ref_fp["cov_bits"] > 0

    for kill_at in range(1, batches):
        ckpt = tmp_path / f"kill{kill_at}"
        victim = build_tlv_campaign(mutator="devmangle", seed=0x5EED,
                                    megachunk=4, **BUILD)
        victim.checkpoint_dir, victim.checkpoint_every = ckpt, 1
        fuzz_until_killed(victim, runs, kill_at_batch=kill_at)

        resumed = build_tlv_campaign(mutator="devmangle", seed=0x5EED,
                                     megachunk=4, **BUILD)
        state, fell_back = load_campaign(ckpt)
        assert not fell_back
        assert restore_campaign(resumed, state, ckpt) == kill_at
        resumed.fuzz(runs)
        fp = _fingerprint(resumed)
        assert fp == ref_fp, f"kill at batch {kill_at}: state diverged"


def test_megachunk_mesh_parity():
    """Windows on a forced 8-device mesh (conftest forces the virtual
    mesh for the whole suite): the shard_map megachunk — whose
    loop-control scalars must be all-reduced so the shards' while_loops
    stay in lockstep — is bit-identical to the single-device one (and
    therefore to the legacy loop) at equal seeds."""
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=8 (make mesh-smoke environment)")
    runs = BUILD["n_lanes"] * 3
    fp_single = _fingerprint(_campaign(3, runs))
    fp_mesh = _fingerprint(_campaign(3, runs, mesh_devices=8))
    assert fp_mesh == fp_single
