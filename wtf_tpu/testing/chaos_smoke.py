"""`make chaos-smoke`: the seeded end-to-end recovery soak.

Two legs, both against the real seams with a deterministic FaultPlan:

  dist leg        master + one emu node over a unix socket; the node's
                  sockets take scheduled resets/partial frames
                  mid-campaign.  Asserts >=1 reconnect, >=1 reclaim, and
                  ZERO lost testcases: the master accounts exactly
                  seeds + runs results, its corpus dedup is exact.
  resume leg      a seeded demo_tlv devmangle campaign on the batched
                  tpu backend checkpoints every batch and is killed at a
                  batch boundary; the NEWEST checkpoint is then torn
                  (truncated) so the resume must detect the digest
                  mismatch and fall back to `.prev`.  Asserts the
                  resumed run's final coverage, crash set, corpus and
                  stats are bit-identical to an uninterrupted reference.

Exit 0 only when every assertion held.  Run via
`python -m wtf_tpu.testing.chaos_smoke [seed]`.
"""

from __future__ import annotations

import os
import random
import sys
import tempfile
import threading
from pathlib import Path

SEED = 0xC4A05


def _dist_leg(seed: int) -> dict:
    from wtf_tpu.backend import create_backend
    from wtf_tpu.dist import Client, Server
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.fuzz.mutator import TlvStructureMutator
    from wtf_tpu.harness import demo_tlv
    from wtf_tpu.telemetry import Registry
    from wtf_tpu.testing.faultinject import (
        FaultPlan, PARTIAL_SEND, RESET, chaos_dialing,
    )

    runs = 24
    with tempfile.TemporaryDirectory() as tmp:
        address = f"unix://{tmp}/master.sock"
        rng = random.Random(seed)
        corpus = Corpus(outputs_dir=Path(tmp) / "outputs", rng=rng)
        seeds = [b"\x01\x04AAAA\x02\x08BBBBBBBB", b"\x02\x02XY"]
        server = Server(address, TlvStructureMutator(rng, 128), corpus,
                        crashes_dir=Path(tmp) / "crashes", runs=runs,
                        coverage_path=Path(tmp) / "coverage.cov")
        server.paths = list(seeds)
        thread = threading.Thread(target=server.run,
                                  kwargs={"max_seconds": 120})
        thread.start()
        backend = create_backend("emu", demo_tlv.build_snapshot())
        backend.initialize()
        registry = Registry()
        # scripted, not rate-based: the node's op pattern is
        # send(hello)=0 then recv,recv,send per testcase, so sends land
        # on ops ≡ 0 (mod 3).  Socket 0 resets on its 4th result send
        # (master holds in-flight -> reclaim); the reconnect's socket
        # tears a result frame halfway (partial send -> torn frame on
        # the master, second reclaim); the next reconnect runs clean.
        plan = FaultPlan([{9: RESET}, {6: PARTIAL_SEND}, {}, {}, {}],
                         delay_secs=0.002)
        with chaos_dialing(plan):
            client = Client(backend, demo_tlv.TARGET, address,
                            registry=registry, max_retry_secs=30.0,
                            retry_rng=random.Random(seed ^ 0x5A))
            served = client.run()
        thread.join(timeout=120)
        assert not thread.is_alive(), "master did not finish"
        expected = len(seeds) + runs
        got = server.stats.testcases
        assert got == expected, \
            f"lost testcases: master accounted {got}, expected {expected}"
        assert server.mutations == runs, server.mutations
        retries = registry.counter("dist.retries").value
        reclaimed = server.registry.counter("dist.reclaimed").value
        assert retries >= 1, "chaos plan produced no reconnect"
        assert reclaimed >= 1, "chaos plan produced no reclaim"
        # exact server-side dedup: outputs/ is content-addressed and
        # every file's digest matches its name
        from wtf_tpu.utils.hashing import hex_digest

        for p in (Path(tmp) / "outputs").iterdir():
            assert hex_digest(p.read_bytes()) == p.name, p
        return {"served": served, "accounted": got, "retries": retries,
                "reclaimed": reclaimed, "faults": len(plan.fired)}


def _resume_leg(seed: int) -> dict:
    import numpy as np

    from wtf_tpu.analysis.trace import build_tlv_campaign
    from wtf_tpu.resume import load_campaign, restore_campaign
    from wtf_tpu.testing.faultinject import fuzz_until_killed, tear_file

    lanes, batches = 8, 4
    runs = lanes * batches
    build = dict(n_lanes=lanes, mutator="devmangle", limit=20_000,
                 seed=seed & 0xFFFF, chunk_steps=128, overlay_slots=16)

    # uninterrupted reference
    ref = build_tlv_campaign(**build)
    ref.fuzz(runs)
    ref_state = (ref._coverage(), sorted(ref.corpus.digests),
                 sorted(ref.crash_names), ref.stats.testcases,
                 np.asarray(ref.backend.coverage_state()[1]).sum())

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = Path(tmp) / "checkpoint"
        victim = build_tlv_campaign(**build)
        victim.checkpoint_dir = ckpt
        victim.checkpoint_every = 1
        fuzz_until_killed(victim, runs, kill_at_batch=2)
        # the kill also tore the newest checkpoint: the loader must
        # reject it by digest and fall back to .prev (batch 1)
        tear_file(ckpt / "checkpoint.json")
        state, fell_back = load_campaign(ckpt)
        assert fell_back, "torn newest checkpoint was not detected"
        resumed = build_tlv_campaign(**build)
        resumed.checkpoint_dir = ckpt
        resumed.checkpoint_every = 1
        batch = restore_campaign(resumed, state, ckpt)
        assert batch == 1, batch
        resumed.fuzz(runs)
        res_state = (resumed._coverage(), sorted(resumed.corpus.digests),
                     sorted(resumed.crash_names), resumed.stats.testcases,
                     np.asarray(resumed.backend.coverage_state()[1]).sum())
        assert res_state == ref_state, \
            f"resume parity broken:\n ref {ref_state}\n got {res_state}"
        return {"coverage": ref_state[0], "corpus": len(ref_state[1]),
                "resumed_from_batch": batch, "fell_back": fell_back}


def main(argv=None) -> int:
    seed = int((argv or sys.argv[1:] or [SEED])[0])
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(f"chaos-smoke seed={seed:#x}")
    dist = _dist_leg(seed)
    print(f"dist leg OK: {dist}")
    res = _resume_leg(seed)
    print(f"resume leg OK: {res}")
    print("chaos-smoke PASS (>=1 reconnect, >=1 reclaim, torn-checkpoint "
          "fallback, zero lost testcases, resume parity)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
