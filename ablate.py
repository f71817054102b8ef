"""Hot-path ablation harness: time one interpreter chunk on the real chip
across configs to localize the per-step cost (overlay probes vs uop-table
gathers vs lane scaling).  Not part of the framework — a measurement tool.

Usage: python ablate.py [config ...]; no args = all configs.
"""

import json
import sys
import time

import numpy as np

CONFIGS = {
    "base":      dict(n_lanes=1024, overlay_slots=128, uop_capacity=1 << 14),
    "slots16":   dict(n_lanes=1024, overlay_slots=16,  uop_capacity=1 << 14),
    "cap2k":     dict(n_lanes=1024, overlay_slots=128, uop_capacity=1 << 11),
    "lanes256":  dict(n_lanes=256,  overlay_slots=128, uop_capacity=1 << 14),
    "lanes4096": dict(n_lanes=4096, overlay_slots=128, uop_capacity=1 << 14),
}


def measure(name, cfg, chunk=512):
    # warm-runner + chunk-timing recipe shared with bench._microbench and
    # the linter (wtf_tpu/analysis/trace.py)
    from wtf_tpu.analysis.trace import build_tlv_runner, timed_chunk

    # long branchy run: fills the chunk
    r = build_tlv_runner(chunk_steps=chunk,
                         payload=b"\x01\x08AAAAAAAA" * 200, **cfg)
    t = timed_chunk(r)
    import jax

    print(json.dumps({
        "config": name, **cfg, "chunk": chunk,
        "platform": jax.devices()[0].platform,
        "compile_s": round(t["compile_s"], 1),
        "chunk_wall_s": round(t["warm_wall_s"], 4),
        "per_step_ms": round(t["warm_wall_s"] / chunk * 1e3, 3),
        "instr_per_s": round(t["instr"] / t["warm_wall_s"], 1),
    }), flush=True)


def fused_ab(n_lanes, limit, chunk, payload):
    """Fused-vs-XLA A/B core, shared by `ablate.py fused` and
    `bench.py --fused-compare`: the same warmed demo_tlv batch driven
    through Runner.run() with fused_step off vs on.  Returns
    {"off": col, "on": col} with cold wall, warm wall, instr/s, and (on)
    the kernel occupancy — both occupancy terms come from the device
    counter block (CTR_INSTR == icount by invariant), so the ratio is
    exactly retired-in-kernel / retired.  The `on` column also carries
    the park-reason split (fused_park_subset vs fused_park_mem): WHY
    lanes left the kernel, not just how often."""
    from wtf_tpu.analysis.trace import build_tlv_runner, insert_payload
    from wtf_tpu.interp.machine import (
        CTR_FUSED, CTR_INSTR, CTR_PARK_MEM, CTR_PARK_SUBSET,
    )

    cols = {}
    for mode in ("off", "on"):
        r = build_tlv_runner(n_lanes=n_lanes, chunk_steps=chunk,
                             payload=payload, limit=limit, fused_step=mode)
        t0 = time.time()
        r.run()                       # cold pass: compiles + decode fill
        cold_s = time.time() - t0
        r.restore()
        insert_payload(r, payload)
        t0 = time.time()
        r.run()
        warm_s = time.time() - t0
        ctr = r.device_counters()
        instr = int(ctr[:, CTR_INSTR].sum(dtype=np.uint64))
        col = {"compile_plus_run_s": round(cold_s, 3),
               "warm_wall_s": round(warm_s, 4),
               "instr_per_s": round(instr / warm_s, 1)}
        if mode == "on":
            fused = int(ctr[:, CTR_FUSED].sum(dtype=np.uint64))
            col["fused_occupancy"] = round(fused / max(instr, 1), 4)
            col["fused_park_subset"] = int(
                ctr[:, CTR_PARK_SUBSET].sum(dtype=np.uint64))
            col["fused_park_mem"] = int(
                ctr[:, CTR_PARK_MEM].sum(dtype=np.uint64))
        cols[mode] = col
    return cols


def measure_fused(n_lanes=None, limit=None, chunk=512):
    """Fused-Pallas-ladder A/B (ISSUE 4), reporting warm wall, instr/s,
    and the kernel occupancy.  On a real TPU this times the actual Mosaic
    kernel at campaign scale (1024 lanes); elsewhere the kernel runs
    under Pallas interpret mode — grid-point-by-grid-point emulation — so
    the default run scales down to stay minutes-scale."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if n_lanes is None:
        n_lanes = 1024 if on_tpu else 64
    if limit is None:
        limit = 20_000 if on_tpu else 5_000
    report = {"config": "fused", "n_lanes": n_lanes, "limit": limit,
              "chunk": chunk, "platform": jax.devices()[0].platform}
    cols = fused_ab(n_lanes, limit, chunk, b"\x01\x08AAAAAAAA" * 200)
    report["fused_off"] = cols["off"]
    report["fused_on"] = cols["on"]
    print(json.dumps(report), flush=True)


def measure_devmut(n_lanes=None, limit=100_000, seconds=10.0):
    """Host-mangle vs device-mangle A/B at matched lane counts (ISSUE 6):
    the same demo_tlv campaign driven through FuzzLoop with the best
    host engine vs the devmangle engine (wtf_tpu/devmut), reporting
    execs/s plus the mutate-phase split — total mutate seconds, the
    fenced device wait under mutate/device, and the residual HOST share,
    which is the number the device engine exists to collapse.  On the
    CPU stand-in the generation kernel competes with the interpreter for
    the same core, so execs/s parity is the expectation there; the
    mutate host-share collapse is the measured claim."""
    import jax

    from wtf_tpu.analysis.trace import build_tlv_campaign

    if n_lanes is None:
        n_lanes = 1024 if jax.default_backend() == "tpu" else 64
    cols = {}
    for mode, engine in (("host", "mangle"), ("device", "devmangle")):
        loop = build_tlv_campaign(n_lanes=n_lanes, mutator=engine,
                                  limit=limit, chunk_steps=512,
                                  overlay_slots=32)
        loop.run_one_batch()   # warmup: XLA compiles + decode servicing
        loop.run_one_batch()
        spans = loop.registry.spans
        c0 = loop.stats.testcases
        m0 = spans.seconds("mutate")
        d0 = spans.seconds("mutate/device")
        t0 = time.time()
        while time.time() - t0 < seconds:
            loop.run_one_batch()
        dt = time.time() - t0
        mutate_s = spans.seconds("mutate") - m0
        mutate_dev_s = spans.seconds("mutate/device") - d0
        cols[mode] = {
            "execs_per_s": round((loop.stats.testcases - c0) / dt, 2),
            "mutate_s": round(mutate_s, 4),
            "mutate_device_s": round(mutate_dev_s, 4),
            "mutate_host_s": round(mutate_s - mutate_dev_s, 4),
            "mutate_share_of_wall": round(mutate_s / dt, 4),
        }
    print(json.dumps({
        "config": "devmut", "n_lanes": n_lanes, "limit": limit,
        "platform": __import__("jax").devices()[0].platform,
        "host": cols["host"], "device": cols["device"],
    }), flush=True)


def measure_megachunk(n_lanes=None, limit=100_000, seconds=10.0,
                      window=16, warm_batches=16):
    """Megachunk host-share A/B (ISSUE 14): the same devmangle demo_tlv
    campaign through the batch-at-a-time device loop vs one-dispatch
    multi-batch windows (wtf_tpu/fuzz/megachunk), reporting execs/s and
    the fenced host/device wall split telemetry_report uses — host
    share of campaign wall = 1 - device-span seconds / wall.  The
    megachunk claim is that per-batch host work collapses to the status
    pull + harvest (<5% on the CPU stand-in; the acceptance metric).

    Fairness note: both modes warm to the SAME campaign maturity
    (`warm_batches` completed batches, not N loop calls — one megachunk
    call is up to `window` batches), because equal seeds only mean equal
    work at equal batch indices; demo_tlv testcases get deeper as the
    corpus matures.  In find-heavy stretches the window legitimately
    degrades toward one batch per dispatch (the find-stop rule IS the
    bit-exactness contract), so the measured host share is the honest
    blended number, not a best case."""
    import jax

    from wtf_tpu.analysis.trace import build_tlv_campaign
    from wtf_tpu.telemetry.spans import DEVICE_SPAN_LEAVES

    if n_lanes is None:
        n_lanes = 1024 if jax.default_backend() == "tpu" else 64
    cols = {}
    for mode, mega in (("batch", 0), ("megachunk", window)):
        loop = build_tlv_campaign(n_lanes=n_lanes, mutator="devmangle",
                                  limit=limit, chunk_steps=512,
                                  overlay_slots=32, megachunk=mega)
        # warmup: XLA compiles + decode servicing + equal maturity
        while loop.stats.testcases < warm_batches * n_lanes:
            loop.run_one_batch()
        children = loop.registry.counter("phase.seconds").children

        def dev_seconds():
            return sum(c.value for path, c in children.items()
                       if path.split("/")[-1] in DEVICE_SPAN_LEAVES)

        c0 = loop.stats.testcases
        d0 = dev_seconds()
        t0 = time.time()
        while time.time() - t0 < seconds:
            loop.run_one_batch()
        dt = time.time() - t0
        dev_s = dev_seconds() - d0
        cols[mode] = {
            "execs_per_s": round((loop.stats.testcases - c0) / dt, 2),
            "batches": int((loop.stats.testcases - c0) / n_lanes),
            "device_s": round(dev_s, 4),
            "host_s": round(max(dt - dev_s, 0.0), 4),
            "host_share_of_wall": round(max(dt - dev_s, 0.0) / dt, 4),
        }
    print(json.dumps({
        "config": "megachunk", "n_lanes": n_lanes, "limit": limit,
        "window": window, "warm_batches": warm_batches,
        "platform": jax.devices()[0].platform,
        "batch_at_a_time": cols["batch"], "megachunk": cols["megachunk"],
    }), flush=True)
    return cols


def measure_fused_mega(n_lanes=8, limit=20_000, window=3, batches=32,
                       seed=0x5EED):
    """Fused-window vs ladder-window A/B (the PR-19 tentpole): the same
    equal-seed devmangle demo_tlv campaign through megachunk windows
    whose quiesce body is the XLA ladder vs the Pallas fused kernel +
    bounded-resume leg.  Reports the WINDOW KERNEL COUNT each way — the
    ladder pays one full step-graph sweep (budgets.json `xla_step` total
    kernels) per in-window round, the fused body pays ONE pallas dispatch
    per round plus a short resume sweep — the donated bytes that stop
    copying through the kernel each dispatch, and the bit-identity
    verdict (coverage/edge bytes, corpus digests, crash buckets).  The
    kernel-count collapse is deterministic (counter-derived at equal
    seeds), so bench_guard treats it as an exact ratchet, not a noisy
    wall-clock number."""
    import jax

    from wtf_tpu.analysis.rules import BUDGET_ENTRY, load_budgets
    from wtf_tpu.analysis.trace import build_tlv_campaign
    from wtf_tpu.utils.hashing import hex_digest

    report = {"config": "fused-mega", "n_lanes": n_lanes, "limit": limit,
              "window": window, "batches": batches,
              "platform": jax.devices()[0].platform}
    # kernels per XLA ladder sweep: the checked-in step-graph pin
    per_sweep = int(load_budgets()[BUDGET_ENTRY]["total"])
    cols, fps = {}, {}
    for mode in ("ladder", "fused"):
        loop = build_tlv_campaign(
            n_lanes=n_lanes, mutator="devmangle", limit=limit,
            chunk_steps=128, overlay_slots=16, megachunk=window,
            seed=seed, fused_step="on" if mode == "fused" else "off")
        t0 = time.time()
        loop.fuzz(n_lanes * batches)
        dt = time.time() - t0
        reg = loop.registry
        sweeps = int(reg.counter("device.fused_window_xla_steps").value)
        rounds = int(reg.counter("device.fused_window_rounds").value)
        col = {
            "wall_s": round(dt, 2),
            "execs_per_s": round(loop.stats.testcases / dt, 2),
            "windows": int(reg.counter("megachunk.windows").value),
            "xla_sweeps": sweeps,
            "pallas_dispatches": rounds,
            "window_kernels": rounds + sweeps * per_sweep,
        }
        if mode == "fused":
            saved = int(
                reg.counter("device.fused_window_bytes_saved").value)
            col["bytes_saved"] = saved
            col["bytes_saved_per_dispatch"] = saved // max(rounds, 1)
        cols[mode] = col
        cov, edge = loop.backend.coverage_state()
        fps[mode] = {
            "cov": hex_digest(cov.tobytes()),
            "edge": hex_digest(edge.tobytes()),
            "cov_bits": loop._coverage(),
            "corpus": [hex_digest(d) for d in loop.corpus],
            "buckets": sorted(loop.crash_buckets),
            "testcases": loop.stats.testcases,
        }
    report["ladder"] = cols["ladder"]
    report["fused"] = cols["fused"]
    report["kernels_per_sweep"] = per_sweep
    report["kernel_reduction"] = round(
        cols["ladder"]["window_kernels"] /
        max(cols["fused"]["window_kernels"], 1), 2)
    report["bit_identical"] = fps["ladder"] == fps["fused"]
    print(json.dumps(report), flush=True)
    return report


def measure_decode(n_lanes=None, limit=100_000, seconds=10.0, window=16):
    """Device-decode A/B (the zero-host-steady-state tentpole): the
    same devmangle megachunk campaign host-serviced vs with
    `--device-decode` (wtf_tpu/interp/devdec), both from a COLD decode
    cache — the cold-start service storm is exactly the host cost the
    in-graph decoder removes.  Reports execs/s, the fenced host/device
    wall split, the host decode-service count (the A column's cost, the
    B column's zero), zero-host window share, and the pipelined-harvest
    overlap share (prelaunch adoptions / windows)."""
    import jax

    from wtf_tpu.analysis.trace import build_tlv_campaign
    from wtf_tpu.telemetry.spans import DEVICE_SPAN_LEAVES

    if n_lanes is None:
        n_lanes = 1024 if jax.default_backend() == "tpu" else 64
    cols = {}
    for mode, dd in (("host", False), ("device", True)):
        loop = build_tlv_campaign(n_lanes=n_lanes, mutator="devmangle",
                                  limit=limit, chunk_steps=512,
                                  overlay_slots=32, megachunk=window,
                                  device_decode=dd)
        def dev_seconds():
            # re-resolve: the per-leaf children only materialize once
            # their spans first fire (this A/B starts cold on purpose)
            children = loop.registry.counter("phase.seconds").children
            return sum(c.value for path, c in children.items()
                       if path.split("/")[-1] in DEVICE_SPAN_LEAVES)

        t0 = time.time()
        while time.time() - t0 < seconds:
            loop.run_one_batch()
        dt = time.time() - t0
        dev_s = dev_seconds()
        reg = loop.registry
        windows = reg.counter("megachunk.windows").value
        col = {
            "execs_per_s": round(loop.stats.testcases / dt, 2),
            "host_decode_services": loop.backend.runner.stats["decodes"],
            "device_s": round(dev_s, 4),
            "host_s": round(max(dt - dev_s, 0.0), 4),
            "host_share_of_wall": round(max(dt - dev_s, 0.0) / dt, 4),
            "windows": int(windows),
        }
        if dd:
            col["device_published"] = int(
                reg.counter("devdec.published").value)
            col["crosscheck_mismatches"] = int(
                reg.counter("devdec.crosscheck_mismatches").value)
            col["zero_host_windows"] = int(
                reg.counter("devdec.zero_host_windows").value)
            col["zero_host_batches"] = int(
                reg.counter("devdec.zero_host_batches").value)
            col["prelaunch_hits"] = int(
                reg.counter("megachunk.prelaunch_hits").value)
            col["harvest_overlap_share"] = round(
                col["prelaunch_hits"] / max(windows, 1), 4)
        cols[mode] = col
    print(json.dumps({
        "config": "decode", "n_lanes": n_lanes, "limit": limit,
        "window": window, "platform": jax.devices()[0].platform,
        "host_serviced": cols["host"], "device_decode": cols["device"],
    }), flush=True)
    return cols


def measure_lanes_ramp(seconds=None, limit=20_000):
    """The chips x lanes ramp (ROADMAP item 1 / ISSUE 7): devmangle
    campaigns through the meshrun driver at lanes x mesh-shard
    combinations, reporting execs/s and cov/edge bits at equal wall per
    cell — the scaling curve behind the SNIPPETS north-star chase
    (>=1000x bochscpu exec/s on a v5e-8 at equal edge coverage).

    On a real TPU the ramp runs lanes256..lanes4096 over 1 chip vs the
    whole mesh; on the forced-8-device CPU stand-in (MULTICHIP_r06) it
    scales down — there the claim is scaling MECHANICS (one process,
    one SPMD program, coverage merged on-chip), not throughput: all
    eight "chips" share the same cores, so execs/s parity with the
    single-device cell is the expectation, not a speedup."""
    import jax

    from wtf_tpu.analysis.trace import build_tlv_campaign

    on_tpu = jax.default_backend() == "tpu"
    if seconds is None:
        seconds = 10.0 if on_tpu else 4.0
    n_dev = len(jax.devices())
    lanes_list = (256, 1024, 4096) if on_tpu else (64, 256)
    shards_list = [1] + ([n_dev] if n_dev > 1 else [])
    cells = []
    for n_lanes in lanes_list:
        for shards in shards_list:
            if n_lanes % shards:
                continue
            loop = build_tlv_campaign(
                n_lanes=n_lanes, mutator="devmangle", limit=limit,
                chunk_steps=128, overlay_slots=16,
                mesh_devices=shards if shards > 1 else None)
            loop.run_one_batch()   # warmup: compiles + decode servicing
            c0 = loop.stats.testcases
            t0 = time.time()
            while time.time() - t0 < seconds:
                loop.run_one_batch()
            dt = time.time() - t0
            agg_edge = np.asarray(loop.backend._agg_edge)
            cell = {
                "lanes": n_lanes, "shards": shards,
                "execs_per_s": round((loop.stats.testcases - c0) / dt, 2),
                "cov_bits": loop._coverage(),
                "edge_bits": int(np.unpackbits(
                    agg_edge.view("uint8")).sum()),
                "testcases": loop.stats.testcases,
            }
            cells.append(cell)
            print(json.dumps({"config": "lanes-ramp", **cell}), flush=True)
    print(json.dumps({
        "config": "lanes-ramp-summary", "limit": limit,
        "seconds_per_cell": seconds, "devices": n_dev,
        "platform": jax.devices()[0].platform, "cells": cells,
    }), flush=True)
    return cells


def measure_tenants_ramp(seconds=None, limit=50_000, lanes_per_tenant=None):
    """The tenant-mix ramp (wtf_tpu/tenancy): MultiTenantLoop campaigns
    at 1..N co-resident tenants on one batch, equal wall per cell —
    execs/s, per-tenant coverage, and the per-tenant-merge overhead
    (the mixed batch runs T prefix-credit merges against the solo
    batch's one).  Emits a JSON table like `ablate.py lanes`.  demo_pe
    joins the 3-tenant cell only where its census DLL is present."""
    import jax

    from wtf_tpu.harness import demo_pe
    from wtf_tpu.harness.targets import Targets, load_builtin_targets
    from wtf_tpu.tenancy.backend import TenantSpec, create_tenancy_backend
    from wtf_tpu.tenancy.loop import MultiTenantLoop, TenantRuntime

    on_tpu = jax.default_backend() == "tpu"
    if seconds is None:
        seconds = 10.0 if on_tpu else 4.0
    if lanes_per_tenant is None:
        lanes_per_tenant = 256 if on_tpu else 16
    load_builtin_targets()
    targets = Targets.instance()
    rows = [("t0", "demo_tlv", "tlv", b"\x01\x04AAAA\x02\x08BBBBBBBB"),
            ("t1", "demo_kernel", "mangle", b"hello-world-123")]
    if demo_pe.available():
        rows.append(("t2", "demo_pe", "auto", demo_pe.BENIGN
                     if hasattr(demo_pe, "BENIGN") else b"\x00" * 16))
    cells = []
    for n_tenants in range(1, len(rows) + 1):
        cfg = rows[:n_tenants]
        specs = [TenantSpec(n, targets.get(t), targets.get(t).snapshot(),
                            lanes_per_tenant)
                 for n, t, _m, _s in cfg]
        backend = create_tenancy_backend(
            specs, lanes_per_tenant * n_tenants, limit=limit,
            chunk_steps=128, overlay_slots=16)
        backend.initialize()
        for i, s in enumerate(specs):
            with backend.tenant_context(i):
                s.target.init(backend)
        runtimes, lane_lo = [], 0
        for i, (n, _t, m, seed_data) in enumerate(cfg):
            rt = TenantRuntime(specs[i], seed=0x7E0 + i, runs=1 << 30,
                               mutator_name=m, max_len=256,
                               lane_lo=lane_lo)
            rt.corpus.add(seed_data)
            runtimes.append(rt)
            lane_lo += lanes_per_tenant
        loop = MultiTenantLoop(backend, runtimes, stats_every=1e9)
        loop.run_one_batch()   # warmup: compiles + decode servicing
        c0 = loop.stats.testcases
        t0 = time.time()
        while time.time() - t0 < seconds:
            loop.run_one_batch()
        dt = time.time() - t0
        cell = {
            "tenants": n_tenants,
            "lanes": lanes_per_tenant * n_tenants,
            "execs_per_s": round((loop.stats.testcases - c0) / dt, 2),
            "per_tenant": {
                rt.name: {
                    "execs": int(rt.stats["testcases"]),
                    "cov_bits": int(np.unpackbits(np.asarray(
                        backend.tenant_coverage_state(i)[0]).view(
                            "uint8")).sum()),
                    "new_coverage": int(rt.stats["new_coverage"]),
                }
                for i, rt in enumerate(runtimes)
            },
            # the per-tenant merge cost: T prefix-credit merges per
            # batch in the mixed cell vs the solo cell's one
            "cov_readback_s": round(
                loop.registry.spans.seconds("execute/cov-readback"), 4),
        }
        cells.append(cell)
        print(json.dumps({"config": "tenants-ramp", **cell}), flush=True)
    print(json.dumps({
        "config": "tenants-ramp-summary", "limit": limit,
        "seconds_per_cell": seconds,
        "platform": jax.devices()[0].platform, "cells": cells,
    }), flush=True)
    return cells


def measure_fleet(clients=None, runs_per_client=40, seed=0xF1EE7):
    """Fleet-tier client-count ramp (wtf_tpu/fleet/soak): the same
    deterministic soak workload at growing fan-out, measuring reactor
    throughput (results/s) and the delta-vs-whole-bitmap coverage wire
    ratio at each cell.  Paste the summary as FLEET_rNN.json."""
    import logging
    import tempfile

    logging.getLogger("wtf_tpu").setLevel(logging.ERROR)
    from wtf_tpu.fleet.soak import run_soak

    cells = []
    for n in (clients or (16, 64, 256)):
        with tempfile.TemporaryDirectory() as tmp:
            report = run_soak(tmp, clients=n,
                              runs_per_client=runs_per_client,
                              seed=seed, threads=min(16, max(n // 8, 1)),
                              min_ratio=1.0)
        cell = {k: report[k] for k in (
            "clients", "runs", "accounted", "wall_s", "results_per_s",
            "coverage", "retries", "reclaimed", "delta_cov_bytes",
            "bitmap_equiv_bytes", "delta_ratio", "full_resyncs")}
        cells.append(cell)
        print(json.dumps({"config": "fleet-ramp", **cell}), flush=True)
    print(json.dumps({
        "config": "fleet-ramp-summary",
        "runs_per_client": runs_per_client, "seed": seed,
        "cells": cells,
    }), flush=True)
    return cells


def measure_deep(n_lanes=1024, limit=10_000_000, seconds=30.0):
    """BASELINE-config-3-shaped end-to-end number (the same workload
    bench.py reports in its `deep` extras): mangle campaign on demo_spin
    with a 10M-instruction budget; prints execs/s + instr/s."""
    import random
    import struct

    import jax

    from wtf_tpu.backend import create_backend
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.fuzz.loop import FuzzLoop
    from wtf_tpu.fuzz.native_mutator import best_mangle_mutator
    from wtf_tpu.harness import demo_spin

    backend = create_backend("tpu", demo_spin.build_snapshot(),
                             n_lanes=n_lanes, limit=limit, chunk_steps=512,
                             overlay_slots=16)
    backend.initialize()
    demo_spin.TARGET.init(backend)
    rng = random.Random(0xD33B)
    corpus = Corpus(rng=rng)
    corpus.add(struct.pack("<I", min(limit // demo_spin.INSNS_PER_ITER,
                                     0xFFFF_FFFF)))
    loop = FuzzLoop(backend, demo_spin.TARGET,
                    best_mangle_mutator(rng, max_len=4), corpus)
    loop.run_one_batch()  # warmup
    i0, c0 = backend.stats["instructions"], loop.stats.testcases
    t0 = time.time()
    while time.time() - t0 < seconds:
        loop.run_one_batch()
    dt = time.time() - t0
    print(json.dumps({
        "config": "deep", "n_lanes": n_lanes, "limit": limit,
        "platform": jax.devices()[0].platform,
        "execs_per_s": round((loop.stats.testcases - c0) / dt, 2),
        "instr_per_s": round((backend.stats["instructions"] - i0) / dt, 1),
    }), flush=True)


if __name__ == "__main__":
    import faulthandler

    faulthandler.dump_traceback_later(
        int(__import__("os").environ.get("ABLATE_WATCHDOG", "240")), exit=True)
    names = sys.argv[1:] or list(CONFIGS) + ["deep", "fused", "devmut",
                                             "megachunk", "fused-mega",
                                             "decode", "lanes", "tenants",
                                             "fleet"]
    for n in names:
        if n == "deep":
            measure_deep()
        elif n == "fused":
            measure_fused()
        elif n == "devmut":
            measure_devmut()
        elif n == "megachunk":
            measure_megachunk()
        elif n == "fused-mega":
            measure_fused_mega()
        elif n == "decode":
            measure_decode()
        elif n == "lanes":
            measure_lanes_ramp()
        elif n == "tenants":
            measure_tenants_ramp()
        elif n == "fleet":
            measure_fleet()
        else:
            measure(n, CONFIGS[n])
        faulthandler.cancel_dump_traceback_later()
        faulthandler.dump_traceback_later(
            int(__import__("os").environ.get("ABLATE_WATCHDOG", "240")),
            exit=True)
