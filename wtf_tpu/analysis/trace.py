"""Trace capture: build the real entry points and lower/compile them.

Two jobs in one module:

  * the shape-only builders the linter, the tests and the smokes share
    (a demo_tlv Runner, a two-tenant Runner, a demo_tlv FuzzLoop);
  * HLO/StableHLO text capture for the rule engine
    (wtf_tpu/analysis/rules.py): lower a jitted entry point, compile it,
    hand the text to the rules.

Heavy imports (jax, the interpreter stack) stay inside functions so
importing this module never initializes a backend.
"""

from __future__ import annotations

import numpy as np


def build_tlv_runner(n_lanes: int, chunk_steps: int, **runner_kwargs):
    """A demo_tlv Runner in the linter's shape-only configuration: no
    decode warmup, no payload, nothing dispatched."""
    from wtf_tpu.harness import demo_tlv
    from wtf_tpu.interp.runner import Runner

    return Runner(demo_tlv.build_snapshot(), n_lanes=n_lanes,
                  chunk_steps=chunk_steps, **runner_kwargs)


def build_tenant_runner(quotas=(2, 2), order=("demo_tlv", "demo_kernel"),
                        chunk_steps: int = 16, **runner_kwargs):
    """A heterogeneous two-tenant Runner (wtf_tpu/tenancy) in the
    linter's shape-only configuration: demo_tlv + demo_kernel lanes
    behind ONE stacked image table, no decode warmup, no payload.
    `order` permutes the tenant table — the budget family lowers the
    chunk under both orders and pins the programs byte-identical (tenant
    identity is DATA; the compiled program depends only on shapes)."""
    from wtf_tpu.harness.targets import Targets, load_builtin_targets
    from wtf_tpu.interp.runner import Runner
    from wtf_tpu.tenancy.backend import TenantSpec

    load_builtin_targets()
    targets = Targets.instance()
    specs = []
    for name, lanes in zip(order, quotas):
        target = targets.get(name)
        specs.append(TenantSpec(name=name, target=target,
                                snapshot=target.snapshot(), lanes=lanes))
    runner = Runner(specs[0].snapshot, n_lanes=sum(quotas),
                    chunk_steps=chunk_steps, tenants=specs,
                    **runner_kwargs)
    return runner


def build_tlv_campaign(n_lanes: int = 64, mutator: str = "mangle",
                       limit: int = 100_000, seed: int = 0x77F,
                       max_len: int = 0x400, registry=None,
                       megachunk: int = 0, **backend_kwargs):
    """A demo_tlv FuzzLoop ready to run_one_batch(): tpu backend built
    and initialized, target init, one TLV seed in the corpus, and the
    mutation engine picked by name ("mangle" = best host engine;
    "devmangle" = the device-resident engine, wtf_tpu/devmut)."""
    import random

    from wtf_tpu.backend import create_backend
    from wtf_tpu.fuzz.corpus import Corpus
    from wtf_tpu.fuzz.loop import FuzzLoop
    from wtf_tpu.fuzz.mutator import create_mutator
    from wtf_tpu.fuzz.native_mutator import best_mangle_mutator
    from wtf_tpu.harness import demo_tlv
    from wtf_tpu.telemetry import Registry

    registry = registry if registry is not None else Registry()
    backend = create_backend("tpu", demo_tlv.build_snapshot(),
                             n_lanes=n_lanes, limit=limit,
                             registry=registry, **backend_kwargs)
    backend.initialize()
    demo_tlv.TARGET.init(backend)
    rng = random.Random(seed)
    corpus = Corpus(rng=rng)
    corpus.add(b"\x01\x04AAAA\x02\x08BBBBBBBB")
    mut = (best_mangle_mutator(rng, max_len) if mutator == "mangle"
           else create_mutator(mutator, rng, max_len))
    return FuzzLoop(backend, demo_tlv.TARGET, mut, corpus,
                    registry=registry, megachunk=megachunk)


# ---------------------------------------------------------------------------
# HLO / StableHLO capture for the rule engine
# ---------------------------------------------------------------------------

def lower_jit(fn, *args, donate_argnums=()):
    """jax.jit(fn, donate_argnums=...).lower(*args) — the pre-optimization
    StableHLO handle (`.as_text()` is the retrace-stability fingerprint;
    `.compile()` yields the optimized HLO the budget/dtype rules scan)."""
    import jax

    return jax.jit(fn, donate_argnums=donate_argnums).lower(*args)


def compiled_hlo(fn, *args, donate_argnums=()):
    """Optimized (post-XLA-pipeline) HLO text of fn(*args)."""
    return lower_jit(fn, *args,
                     donate_argnums=donate_argnums).compile().as_text()


def step_executor_lowering(runner, n_steps: int = 64, donate: bool = True,
                           perturb: bool = False, tail: int = None):
    """Lowered handle of the chunked XLA step ladder on this runner's
    operands.  `perturb=True` re-traces under perturbed-but-same-shape
    inputs (register values bumped, a different limit) — the
    signature-stability probe: both lowerings must produce identical
    StableHLO or something value-dependent leaked into the trace.
    `tail` is make_run_chunk's (None: the width this lane count takes).

    Each call traces FRESH (make_run_chunk(jit=False) + a new jit
    wrapper): jax's trace cache keys on function identity, so lowering
    the memoized executor twice would compare a cache hit against
    itself."""
    import jax
    import jax.numpy as jnp

    from wtf_tpu.interp.step import make_run_chunk

    tab = runner.cache.device()
    machine = runner.machine
    limit = jnp.uint64(0)
    if perturb:
        machine = machine._replace(
            gpr_l=machine.gpr_l + np.uint32(1),
            icount=machine.icount + np.uint64(7))
        limit = jnp.uint64(12345)
    run_chunk = make_run_chunk(n_steps, donate=donate, jit=False, tail=tail)
    jitted = jax.jit(run_chunk, donate_argnums=(2,) if donate else ())
    return jitted.lower(tab, runner.physmem.image, machine, limit)


def tenant_executor_lowering(runner, n_steps: int = 16,
                             donate: bool = False):
    """Lowered handle of the chunked step ladder on a heterogeneous
    runner's operands — `runner.image` (the stacked table + per-lane
    tenant selector), not `runner.physmem.image` (tenant 0's plain
    image).  Fresh trace per call, same reasoning as
    step_executor_lowering."""
    import jax
    import jax.numpy as jnp

    from wtf_tpu.interp.step import make_run_chunk

    run_chunk = make_run_chunk(n_steps, donate=donate, jit=False)
    jitted = jax.jit(run_chunk, donate_argnums=(2,) if donate else ())
    return jitted.lower(runner.cache.device(), runner.image,
                        runner.machine, jnp.uint64(0))


def megachunk_window_lowering(max_batches: int = 2, n_lanes: int = 4,
                              donate: bool = True, limit: int = 10_000):
    """Lower (without executing) ONE megachunk window program at the
    canonical budget shapes: a demo_tlv devmangle campaign's window with
    the requested donation policy.  Returns
    (lowered, args, fn): the jax .lower() handle of the window
    executable, the operand tuple it was lowered against (the donation
    rules index its pytree structure), and the window callable itself
    (the jaxpr census re-traces it).

    Lowering WITH donation is safe on the CPU backend — only EXECUTION
    of a donated program is unsound there (the PR-2 finding) — which is
    why the runtime policy gates on the backend while this helper pins
    the hardware posture statically."""
    import jax.numpy as jnp
    import numpy as np

    from wtf_tpu.fuzz.megachunk import NO_FINISH, make_megachunk

    loop = build_tlv_campaign(n_lanes=n_lanes, mutator="devmangle",
                              limit=limit, megachunk=max_batches)
    backend = loop.backend
    runner = backend.runner
    mutator = loop.mutator
    spec = mutator.spec
    n_pages = len(mutator.pfns)
    fn = make_megachunk(max_batches, n_pages, spec.len_gpr,
                        spec.ptr_gpr, mutator.rounds,
                        deliver=runner.deliver_exceptions,
                        devdec=runner.device_decode, donate=donate)
    finish = spec.finish_gva if spec.finish_gva is not None else NO_FINISH
    slab_first, slab_rest = mutator.window_slabs()
    seeds = mutator.window_seeds(max_batches)
    pfns = jnp.asarray(np.asarray(mutator.pfns, dtype=np.int32))
    gva_l = jnp.asarray(np.array(
        [spec.gva & 0xFFFF_FFFF, (spec.gva >> 32) & 0xFFFF_FFFF],
        dtype=np.uint32))
    args = (runner.device_tab(), runner.image, runner.machine,
            runner.template, slab_first, slab_rest, seeds, pfns, gva_l,
            jnp.uint64(finish), jnp.uint64(backend.limit),
            jnp.int32(max_batches), backend._agg_cov, backend._agg_edge,
            *runner.devdec_operands())
    return fn.lower(*args), args, fn
