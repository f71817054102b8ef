"""tpu-wtf: a TPU-native, distributed, coverage-guided, snapshot-based fuzzer.

Brand-new framework with the capabilities of the reference fuzzer (m4drat/wtf,
see SURVEY.md): where the reference runs one testcase at a time inside
bochscpu/WHV/KVM, this framework executes *batches* of mutated testcases in
lockstep as a vmapped JAX x86-64 interpreter over an HBM-resident snapshot
image, with lane-masked divergent control flow, device-side coverage bitmaps,
and dirty-page restore as O(1) overlay reset.

Layering (mirrors SURVEY.md section 1's layer map, redesigned TPU-first):
  core/     - strong address types, CpuState, NT structs, results    (L1)
  snapshot/ - snapshot loaders: kdmp / raw / synthetic               (L1)
  mem/      - physical memory image, paging, per-lane dirty overlay  (L1/L2)
  cpu/      - decoder, uops, host oracle interpreter                 (L2)
  interp/   - the vmapped fetch-decode-execute x86-64 interpreter    (L2)
  backend/  - Backend contract + EmuBackend / TpuBackend             (L2)
  symbols/  - symbol store + address<->name (debugger layer)         (L3)
  harness/  - target registry, crash detection, guest-fs, demos      (L4)
  fuzz/     - corpus, mutators (python + native), dirwatch, loop     (L5)
  dist/     - master/node wire protocol + reactor                    (L5)
  meshrun/  - device mesh sharding, shard_map executors, mesh merge  (L5)
  resume/   - crash-safe campaign checkpoint/resume                  (L5)
  tenancy/  - multi-tenant batch + campaign scheduler                (L5)
  testing/  - deterministic chaos harness (fault injection)          (aux)
  trace/    - rip/cov/tenet trace writers                            (aux)
  native/   - on-demand-built C++ components (kdmp, mangle)          (aux)
  cli.py    - `master|fuzz|run|campaign|sched` subcommands           (L6)
  config.py - per-subcommand options objects + path conventions      (L6)
"""

import os
from pathlib import Path

import jax

# The guest is an x86-64 machine: 64-bit GPRs, 64-bit linear addresses.
# Enable x64 so uint64 is a real dtype everywhere (XLA lowers 64-bit integer
# ops to 32-bit pairs on TPU; correctness first, the hot path works on
# packed 32-bit limbs, interp/limbs.py).
jax.config.update("jax_enable_x64", True)


def compile_cache_dir() -> str:
    """Where this process keeps JAX's persistent compile cache.

    JAX_COMPILATION_CACHE_DIR, when set, is read by JAX itself and nothing
    in this repo sets another directory in code.  Otherwise it is ONE fixed
    path inside the checkout — never the home directory, a temporary name,
    a pid or a time: a cache that moves is a cache that never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parent.parent / ".jax_cache")


# Persistent XLA compilation cache: every program that contains the
# interpreter step costs about a minute and a half to compile (CPU and
# TPU alike) and its shapes recur across processes.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)

__version__ = "0.1.0"
