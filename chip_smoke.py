#!/usr/bin/env python3
"""Proof that wtf-tpu's main path starts and is right on a TPU.

    python chip_smoke.py                 # one chip: what the driver runs
    python chip_smoke.py --mesh          # four chips: the sharded campaign only
    python chip_smoke.py --rehearse-cpu  # sandbox rehearsal, proves nothing

One process (a chip belongs to one process).  It asserts a TPU first and
exits non-zero without one; any phase that raises or any comparison that
differs ends the run non-zero — nothing here catches and carries on.
Every phase prints one JSON object; the LAST stdout line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}`.

Phases, all on demo_tlv through `wtf_tpu.cli.main` at the README's
one-chip width (--lanes 1024 --limit 100000 --seed 5 --max_len 1024):

  default          campaign, default flags (host mutator, batch loop),
                   four batches
  device-resident  --mutator devmangle --megachunk 8 --device-decode, one
                   full window, then the same without --megachunk: equal
                   final lines and outputs/ digests (the tier-1 CPU pin,
                   on the chip)
  oracle           >= 32 sampled testcases replayed through the emu and
                   tpu backends `run` builds: status and coverage equal
  fp               SSE/x87 cases against the EmuCpu oracle, bit for bit;
                   on a TPU the float-valued ones must retire through
                   the host oracle (step.fp_on_host)

`--mesh` runs only the device-resident campaign on 4 devices
(--mesh-devices 4 --lanes 2048) against one device at the same seed and
prints per-device bytes_in_use; its last line has "count": 4.

Each campaign prints its wall, compile and steady seconds, execs/s, peak
HBM and the runner's fallbacks by opclass.

`--rehearse-cpu` skips ONLY the platform assertion and the last line and
shrinks the run to 16 lanes and two batches (four virtual devices with
--mesh: set XLA_FLAGS=--xla_force_host_platform_device_count=4).  It
exits 0 without the `ok` line, so it can never pass for a chip run.

Work directories go under ./chip_smoke_out/ (git-ignored).  The script
sets no platform, needs no network or git, and shapes are equal across
phases so each program compiles once per process.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import io
import json
import random
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"
RC_OK = (0, 2)      # campaign/run exit 2 when the guest crashed: a find

# (name, hosted-on-a-TPU, source, machine code, initial registers) — the
# grid of tests/test_step_fp.py / test_step_x87.py cut to one case per
# family; the code bytes are `as` output for the source beside them
# (tests/test_chip_smoke.py re-assembles and compares).
_SS = "movq xmm0, rax\nmovq xmm1, rcx\n{} xmm0, xmm1\nmovq rax, xmm0\nhlt"
_SS_HEX = "66480f6ec066480f6ec9{}c166480f7ec0f4"
_PI, _E = 0x400921FB54442D18, 0x4005BF0A8B145769
_PIF, _EF = 0x40490FDB, 0x402DF854
FP_CASES = [
    ("addss", False, _SS.format("addss"), _SS_HEX.format("f30f58"),
     {"rax": _PIF, "rcx": _EF}),
    ("mulss", False, _SS.format("mulss"), _SS_HEX.format("f30f59"),
     {"rax": _PIF, "rcx": _EF}),
    ("subps_maxps_cmpleps", False,
     "movq xmm0, rax\nmovq xmm1, rcx\npunpcklqdq xmm0, xmm1\n"
     "movq xmm2, rdx\nmovq xmm3, rsi\npunpcklqdq xmm2, xmm3\n"
     "subps xmm0, xmm2\nmaxps xmm2, xmm0\ncmpleps xmm0, xmm2\n"
     "movq rax, xmm0\nmovq rcx, xmm2\nhlt",
     "66480f6ec066480f6ec9660f6cc166480f6ed266480f6ede660f6cd30f5cc20f5f"
     "d00fc2c20266480f7ec066480f7ed1f4",
     {"rax": 0x402DF85440490FDB, "rcx": 0x7149F2CABFC00000,
      "rdx": 0x40490FDB402DF854, "rsi": 0x40E000003DCCCCCD}),
    ("ucomiss", False,
     "movq xmm0, rax\nmovq xmm1, rcx\nucomiss xmm0, xmm1\nsetb al\n"
     "seta cl\nhlt",
     "66480f6ec066480f6ec90f2ec10f92c00f97c1f4",
     {"rax": _PIF, "rcx": _EF}),
    ("shufps_unpcklpd", False,
     "movq xmm0, rax\nmovq xmm1, rcx\nshufps xmm0, xmm1, 0x1B\n"
     "unpcklpd xmm1, xmm0\nmovq rax, xmm0\nmovq rcx, xmm1\nhlt",
     "66480f6ec066480f6ec90fc6c11b660f14c866480f7ec066480f7ec9f4",
     {"rax": 0x1111111122222222, "rcx": 0x5555555566666666}),
    ("addsd", True, _SS.format("addsd"), _SS_HEX.format("f20f58"),
     {"rax": _PI, "rcx": _E}),
    ("mulsd", True, _SS.format("mulsd"), _SS_HEX.format("f20f59"),
     {"rax": _PI, "rcx": _E}),
    # 1.7e300 * 3.3e-290: operands a pair of f32s cannot even hold
    ("mulsd_wide", True, _SS.format("mulsd"), _SS_HEX.format("f20f59"),
     {"rax": 0x7E444ECD0D33972B, "rcx": 0x03D49504B6FCDDF7}),
    ("divsd", True, _SS.format("divsd"), _SS_HEX.format("f20f5e"),
     {"rax": _PI, "rcx": _E}),
    ("sqrtsd", True, _SS.format("sqrtsd"), _SS_HEX.format("f20f51"),
     {"rax": 0, "rcx": _PI}),
    ("divss", True, _SS.format("divss"), _SS_HEX.format("f30f5e"),
     {"rax": _PIF, "rcx": _EF}),
    ("sqrtss", True, _SS.format("sqrtss"), _SS_HEX.format("f30f51"),
     {"rax": 0, "rcx": _PIF}),
    ("cvtsi2sd_cvttsd2si", True,
     "pxor xmm0, xmm0\ncvtsi2sd xmm0, rcx\nmovq rdx, xmm0\n"
     "cvttsd2si rax, xmm0\nhlt",
     "660fefc0f2480f2ac166480f7ec2f2480f2cc0f4",
     {"rcx": 12345678901234567}),
    ("cvtss2sd_cvtsd2ss", True,
     "movq xmm1, rcx\ncvtss2sd xmm0, xmm1\nmovq rax, xmm0\n"
     "movq xmm2, rdx\ncvtsd2ss xmm3, xmm2\nmovq rsi, xmm3\nhlt",
     "66480f6ec9f30f5ac166480f7ec066480f6ed2f20f5ada66480f7edef4",
     {"rcx": _PIF, "rdx": _PI}),
    ("ucomisd", True,
     "movq xmm0, rax\nmovq xmm1, rcx\nucomisd xmm0, xmm1\nsetb al\n"
     "seta cl\nhlt",
     "66480f6ec066480f6ec9660f2ec10f92c00f97c1f4",
     {"rax": _PI, "rcx": _PI + 1}),
    ("x87_arith", True,
     "mov [rsp-8], rax\nmov [rsp-16], rcx\nfld qword ptr [rsp-8]\n"
     "fld qword ptr [rsp-16]\nfmul st, st(1)\nfaddp st(1), st\n"
     "fstp qword ptr [rsp-24]\nmov rdx, [rsp-24]\nhlt",
     "48894424f848894c24f0dd4424f8dd4424f0d8c9dec1dd5c24e8488b5424e8f4",
     {"rax": _PI, "rcx": _E}),
    ("x87_fild_fistp", True,
     "mov [rsp-8], rcx\nfild qword ptr [rsp-8]\nfld st(0)\n"
     "fistp dword ptr [rsp-16]\nfstp qword ptr [rsp-24]\n"
     "mov rdx, [rsp-24]\nmov eax, [rsp-16]\nhlt",
     "48894c24f8df6c24f8d9c0db5c24f0dd5c24e8488b5424e88b4424f0f4",
     {"rcx": 1234567}),
]


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def require(ok, *why) -> None:
    """A check that survives `python -O` (an `assert` does not)."""
    if not ok:
        raise AssertionError(" ".join(str(w) for w in why))


def dir_digest(path: Path) -> str:
    """One digest of a directory's file names and contents."""
    h = hashlib.sha256()
    for p in sorted(path.iterdir()) if path.is_dir() else []:
        h.update(p.name.encode() + b"\0")
        h.update(hashlib.sha256(p.read_bytes()).digest())
    return h.hexdigest()


class CompileClock:
    """Seconds JAX reports spending on compiles and persistent-cache
    reads (jax.monitoring duration events), summed since the last take()."""

    def __init__(self):
        import jax.monitoring

        self.seconds: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if "compil" in event:
            key = event.rsplit("/", 1)[-1]
            self.seconds[key] = self.seconds.get(key, 0.0) + secs

    # what a program's first call costs beyond running it (the cache's
    # `compile_time_saved_sec` is reported too, but it is not time spent)
    SPENT = ("backend_compile_duration", "jaxpr_trace_duration",
             "jaxpr_to_mlir_module_duration", "cache_retrieval_time_sec")

    def take(self) -> dict:
        out = {k: round(v, 3) for k, v in sorted(self.seconds.items())}
        out["spent"] = round(sum(self.seconds.get(k, 0.0)
                                 for k in self.SPENT), 3)
        self.seconds = {}
        return out


def run_cli(argv: list) -> tuple:
    """wtf_tpu.cli.main(argv) in this process; stdout is passed through
    and its last `#N cov: ..` line returned with the wall seconds."""
    from wtf_tpu import cli

    buf = io.StringIO()
    t0 = time.monotonic()
    with contextlib.redirect_stdout(buf):
        rc = cli.main([str(a) for a in argv])
    wall = time.monotonic() - t0
    text = buf.getvalue()
    sys.stdout.write(text)
    require(rc in RC_OK, f"{argv[0]} exited {rc}")
    finals = [ln for ln in text.splitlines() if ln.startswith("#")]
    return (finals[-1] if finals else ""), wall


def stable(line: str) -> str:
    """The `#N cov: .. corp: ..` line minus its two clock fields."""
    return re.sub(r" (exec/s|uptime): \S+", "", line)


def peak_bytes():
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def campaign(tag: str, lanes: int, batches: int, extra: list,
             clock: CompileClock) -> dict:
    """One seeded demo_tlv campaign into a fresh chip_smoke_out/<tag>/;
    returns what the phase prints and compares.  Steady seconds are the
    wall time minus what JAX spent tracing, lowering, compiling and
    reading its cache: on the chip that came within 3% of re-running the
    same campaign with every program compiled (PERF.md, PR 22)."""
    work = OUT / tag
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    line, wall = run_cli(
        ["campaign", "--name", "demo_tlv", "--backend", "tpu",
         "--target", work, "--telemetry-dir", work / "telem",
         "--lanes", lanes, "--runs", lanes * batches,
         "--limit", 100000, "--seed", 5, "--max_len", 1024, *extra])
    events = [json.loads(ln) for ln in
              (work / "telem" / "events.jsonl").read_text().splitlines()]
    metrics = next(e for e in events if e["type"] == "run-end")["metrics"]
    require(metrics["backend.batches"] >= batches
            and metrics["campaign.testcases"] == lanes * batches,
            "campaign fell short of its batches:", metrics)
    compiles = clock.take()
    steady = wall - compiles["spent"]
    return {
        "tag": tag, "final": line, "line": stable(line),
        "outputs": dir_digest(work / "outputs"),
        "n_outputs": len(list((work / "outputs").iterdir())),
        "crashes": dir_digest(work / "crashes"),
        "batches": metrics["backend.batches"],
        "execs": metrics["campaign.testcases"],
        "instructions": metrics["backend.instructions"],
        "wall_seconds": round(wall, 3), "compile_seconds": compiles,
        "steady_seconds": round(steady, 3),
        "steady_execs_per_second": round(lanes * batches / steady, 1),
        "peak_bytes_in_use": peak_bytes(),
        "fallbacks": metrics["runner.fallbacks"],
        "fallbacks_by_opclass": metrics["runner.fallbacks_by_opclass"],
        "max_chunk_steps": metrics["runner.max_chunk_steps"],
        # host-clock span totals, compiles included: where the wall went
        "span_seconds": {k: round(v, 2) for k, v in
                         metrics["phase.seconds"].items()},
    }


def same(a: dict, b: dict, what: str) -> None:
    for key in ("line", "outputs", "crashes"):
        require(a[key] == b[key],
                f"{what}: {key} differs between {a['tag']} and {b['tag']}: "
                f"{a[key]!r} != {b[key]!r}")


def phase_default(size, clock) -> None:
    from wtf_tpu import cli
    from wtf_tpu.fuzz.native_mutator import native_available
    from wtf_tpu.harness.targets import Targets, load_builtin_targets

    load_builtin_targets()
    target = Targets.instance().get("demo_tlv")
    mutator = type(cli._mutator_for(target, random.Random(5), 1024))
    emit(phase="default", mutator=mutator.__name__,
         native_mutator_built=native_available(),
         campaign=campaign("default", size.lanes, size.batches, [], clock))


DEVRES = ["--mutator", "devmangle", "--device-decode"]


def phase_device_resident(size, clock) -> None:
    """One full megachunk window, then the same batches one at a time."""
    window = campaign("devres-window", size.lanes, size.window,
                      DEVRES + ["--megachunk", size.window], clock)
    batch = campaign("devres-batch", size.lanes, size.window, DEVRES, clock)
    same(window, batch, "megachunk window != batch loop")
    emit(phase="device-resident", window=window, batch=batch)


def phase_oracle(size, min_sample: int, clock: CompileClock) -> None:
    """Replay a seeded sample of what the campaigns wrote through the two
    backends `run` builds; the EmuCpu oracle is the plain reference."""
    from wtf_tpu import cli
    from wtf_tpu.dist.client import run_testcase_and_restore
    from wtf_tpu.telemetry import Registry

    pool = [p for d in ("devres-window", "default")
            for sub in ("outputs", "crashes")
            for p in sorted((OUT / d / sub).iterdir())]
    sample = random.Random(5).sample(pool, min(len(pool), 32))
    require(len(sample) >= min_sample,
            f"only {len(sample)} testcases to replay, need {min_sample}")
    args = cli.build_parser().parse_args(
        ["run", "--name", "demo_tlv", "--input", str(OUT),
         "--limit", "100000", "--lanes", str(size.lanes)])
    target = cli._lookup_target(args)
    seen = {}
    for name in ("emu", "tpu"):
        backend = cli._build_backend(
            target, name, cli._paths_from(args), args.limit, args.lanes,
            registry=Registry(), tuning=cli._backend_tuning_kwargs(args))
        target.init(backend)
        seen[name] = [
            (p.name, str(result), sorted(cov)) for p in sample
            for result, cov in [run_testcase_and_restore(
                backend, target, p.read_bytes())]]
        seen[name].append(("aggregate", "",
                           sorted(backend.aggregate_coverage())))
    differ = [(e[0], e[1], t[1], len(e[2]), len(t[2]))
              for e, t in zip(seen["emu"], seen["tpu"]) if e != t]
    crashed = sum(r.startswith("crash") for _, r, _ in seen["emu"])
    emit(phase="oracle", replayed=len(sample), crashes=crashed,
         aggregate_coverage=len(seen["emu"][-1][2]), differ=differ,
         compile_seconds=clock.take())
    require(not differ, "device disagrees with the EmuCpu oracle:", differ)


def phase_fp(lanes: int, clock: CompileClock) -> None:
    """SSE/x87 differential against the EmuCpu oracle, bit for bit,
    through the tpu backend `run` builds (lane 0 runs, like a replay)."""
    import jax
    import numpy as np

    from wtf_tpu.backend import create_backend
    from wtf_tpu.core.results import Crash
    from wtf_tpu.cpu.emu import EmuCpu, EmuMem, GuestCrash
    from wtf_tpu.interp.step import fp_on_host
    from wtf_tpu.mem.physmem import PhysMem
    from wtf_tpu.snapshot.loader import Snapshot
    from wtf_tpu.snapshot.synthetic import SyntheticSnapshotBuilder

    code_base, stack_top = 0x1_4000_1000, 0x7FFF_F000
    hosted_here = fp_on_host()
    bad, fallbacks = [], {}
    for name, hosted, _src, code, regs in FP_CASES:
        def guest():
            b = SyntheticSnapshotBuilder()
            b.write(code_base, bytes.fromhex(code))
            b.map(stack_top - 0x4000, 0x5000)
            # padding: the image lands in demo_tlv's 32-page bucket, so
            # this phase reuses the campaigns' chunk program (no compile)
            b.map(0x2_0000_0000, 0x10000)
            pages, cpu = b.build(rip=code_base, rsp=stack_top - 0x100)
            for reg, value in regs.items():
                setattr(cpu, reg, value)
            return PhysMem.from_pages(pages), cpu

        emu = EmuCpu(EmuMem(guest()[0]), guest()[1])
        for _ in range(64):
            try:
                emu.step()
            except GuestCrash:
                break
        else:
            raise AssertionError(f"{name}: oracle never reached hlt")
        physmem, cpu = guest()
        backend = create_backend("tpu", Snapshot(physmem=physmem, cpu=cpu),
                                 limit=100000, n_lanes=lanes)
        backend.initialize()
        result = backend.run()
        require(isinstance(result, Crash), name, result,
                backend.runner.lane_errors)
        m = backend.runner.machine
        got = (np.asarray(m.gpr)[0], np.asarray(m.xmm)[0, :, :2],
               np.asarray(m.fpst)[0], np.asarray(m.rflags)[0] & 0xCD5)
        want = (np.array(emu.gpr, np.uint64),
                np.array(emu.xmm, np.uint64),
                np.array(emu.fpst, np.uint64),
                np.uint64(emu.rflags & 0xCD5))
        if not all((g == w).all() for g, w in zip(got, want)):
            bad.append(name)
        n_host = sum(dict(
            backend.runner.stats["fallbacks_by_opclass"]).values())
        fallbacks[name] = n_host
        # a float-valued case must retire on the host exactly where the
        # platform's floats are not the oracle's, and nowhere else
        require((n_host > 0) == (hosted and hosted_here),
                name, n_host, hosted, hosted_here)
    emit(phase="fp", platform=jax.default_backend(),
         fp_on_host=hosted_here, cases=len(FP_CASES),
         bit_identical=len(FP_CASES) - len(bad), differ=bad,
         oracle_retired=fallbacks, compile_seconds=clock.take())
    require(not bad, "FP cases differ from the oracle:", bad)


def phase_mesh(size, clock) -> None:
    import jax

    n = 4
    require(len(jax.devices()) >= n, jax.devices())
    window = DEVRES + ["--megachunk", size.window]
    mesh = campaign("mesh-window", size.lanes, size.window,
                    window + ["--mesh-devices", n], clock)
    per_device = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()[:n]]
    single = campaign("single-window", size.lanes, size.window, window,
                      clock)
    same(mesh, single, "4-device mesh != one device")
    emit(phase="mesh", devices=n, mesh=mesh, single=single,
         bytes_in_use_per_device=per_device)
    if jax.default_backend() != "cpu":      # the CPU client reports none
        require(all(per_device), "a device holds nothing:", per_device)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: the sharded device-resident "
                         "campaign against one device, nothing else")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="skip the TPU assertion and the last line, "
                         "16 lanes x 2 batches (sandbox rehearsal)")
    size = ap.parse_args()
    t0 = time.monotonic()

    import jax

    import wtf_tpu

    dev = jax.devices()[0]
    if not size.rehearse_cpu:
        require(dev.platform == "tpu",
                f"chip_smoke needs a TPU, jax found {jax.devices()}")
    # README's documented widths: 1024 lanes on one chip, 512 per chip on
    # a mesh; four batches of the default campaign, one full 8-batch
    # megachunk window of the device-resident one
    size.lanes, size.batches, size.window = (
        (16, 2, 2) if size.rehearse_cpu
        else (2048, 4, 8) if size.mesh else (1024, 4, 8))
    cache = Path(wtf_tpu.compile_cache_dir())
    n_before = len(list(cache.iterdir())) if cache.is_dir() else 0
    emit(phase="start", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), lanes=size.lanes, batches=size.batches,
         window=size.window,
         compile_cache=str(cache), cache_entries_before=n_before)
    clock = CompileClock()

    if size.mesh:
        phase_mesh(size, clock)
    else:
        phase_default(size, clock)
        phase_device_resident(size, clock)
        phase_oracle(size, 4 if size.rehearse_cpu else 32, clock)
        phase_fp(size.lanes, clock)
    emit(phase="end", seconds=round(time.monotonic() - t0, 1),
         peak_bytes_in_use=peak_bytes(),
         cache_entries_before=n_before,
         cache_entries_after=len(list(cache.iterdir()))
         if cache.is_dir() else 0)
    if size.rehearse_cpu:
        print("rehearsal only: no TPU was asserted, no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
