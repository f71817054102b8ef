"""The graph-invariant rule engine: families of static checks.

Each rule traces a *real* entry point (the chunked XLA step ladder, the
ported u32-limb hot paths, the pack/unpack seams, overlay restore) into
jaxpr/StableHLO/optimized-HLO on the CPU backend — no chip — and walks
the result:

  dtype     zero u64/s64/f64/f32 primitives in the ported integer-core
            paths (the PR-2 contract, formerly ad-hoc string greps in
            tests/test_limbs.py); the pack/unpack seam may hold 64-bit
            values but only through free bitcasts; every path step.py
            exports as ported must have an argument recipe here or the
            lint fails (a newly ported path cannot dodge the pin)
  budget    data-dependent gather/dynamic-slice/dynamic-update-slice/
            scatter ops surviving in the compiled step ladder, pinned
            against analysis/budgets.json (the PERF.md round-8 "168
            surviving kernels" math as a regression gate); plus the
            triage-chunk identity pin — wtf_tpu/triage's replay core
            must dispatch this same ladder (zero new kernels) — and the
            tenancy pins (wtf_tpu/tenancy): the heterogeneous chunk's
            kernel census against the `tenant_chunk` budget entry, and
            program byte-stability across tenant permutations ("one
            compiled program per lane count regardless of tenant mix")
  recompile re-trace the executor under perturbed-but-same-shape inputs
            and flag signature instability; weak-typed executor operands
            (a python scalar passed where a committed dtype belongs —
            the jit-cache-split hazard); donation verification (every
            donated machine leaf actually aliased in the compiled
            output, and the Runner's CPU-donation gate intact — the
            PR-2 corruption class caught statically)
  mesh      the sharded chunk executor (wtf_tpu/meshrun) on a forced
            multi-device CPU mesh: cross-device collectives pinned to
            exactly the coverage all-reduce (no accidental resharding
            of machine state — zero all-gather/all-to-all/permute), and
            the compiled per-device program byte-stable across shard
            counts at equal lanes-per-shard.  When the ambient process
            has too few devices (plain `make lint`), the family re-runs
            itself in a subprocess with
            XLA_FLAGS=--xla_force_host_platform_device_count=8.
  supervise every device dispatch entry point routes through the
            Supervisor (wtf_tpu/supervise) — seam routing + enumeration
            completeness, by source inspection over SEAM_SITES
  telemetry no dispatch seam serializes the metric registry inline
            (snapshot / encode_telem / json.dumps in a per-chunk path) —
            the <1% observability-overhead bar holds because
            serialization rides the heartbeat/TAG_TELEM cadence; same
            SEAM_SITES enumeration as the supervise family
  state     every mutable attribute on the campaign objects is either
            carried by the checkpoint/restore/recovery field sets or
            declared derived/transient in analysis/contracts.json
            (wtf_tpu/analysis/contracts.py, on the shared dataflow
            engine in wtf_tpu/analysis/flow.py)
  transfer  no dispatch seam grows a hidden device->host sync: AST
            coercion census over SEAM_SITES against the contracts.json
            allowlist, plus (with --deep, or for free when the budget
            family runs) the jaxpr host-callback census of the
            steady-state programs pinned under budgets.json's
            `host_transfer` entry
  thread    attributes shared across declared host-thread roots
            (watchdog, prelaunch, reactor, reconnect…) must appear in
            the contracts.json ownership/lock table
  contracts the contract tables themselves: stale rows, undocumented
            reasons, unknown dispositions — the allowlist cannot rot

`run_lint` orchestrates all families and reports Findings; helpers are
public so tests can seed violations directly.
"""

from __future__ import annotations

import json
import re
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from wtf_tpu.analysis.findings import Finding
from wtf_tpu.analysis.trace import (
    build_tenant_runner, build_tlv_runner, compiled_hlo,
    step_executor_lowering, tenant_executor_lowering,
)

BUDGETS_PATH = Path(__file__).with_name("budgets.json")

# the data-dependent-index HLO ops TPU XLA cannot fuse across — the unit
# of the PERF.md performance model ("step wall is proportional to the
# number of gather-class kernels, not FLOPs")
DATA_DEP_OPS = ("gather", "dynamic-slice", "dynamic-update-slice", "scatter")

# canonical budget-trace configuration: op counts are static code sites
# (independent of n_lanes / n_steps — the chunk is a while_loop, not an
# unroll), but the pin is only meaningful against one fixed entry shape
BUDGET_ENTRY = "xla_step"
BUDGET_CONFIG = dict(n_lanes=4, chunk_steps=64, n_steps=64, donate=True)

# the chunk executor's narrow sweeps (step.make_run_chunk): the budget
# runner's 4 lanes take none, so the budget family lowers the same
# runner's chunk once more with groups of TAIL_WIDTH lanes asked for and
# holds the loop nest, the sort of the rows and their scatter back to the
# `tail_chunk` entry; the recompile family holds its donated machine
# leaves to alias through that scatter
TAIL_ENTRY = "tail_chunk"
TAIL_WIDTH = 2

# canonical heterogeneous-batch configuration (wtf_tpu/tenancy): the
# budget family lowers the SAME step ladder over a two-tenant stacked
# image table, counts its gather-class kernels against the
# `tenant_chunk` budget entry, and pins the compiled program
# byte-identical under a permuted tenant table — "one program per lane
# count regardless of tenant mix"
TENANT_ENTRY = "tenant_chunk"
TENANT_CONFIG = dict(n_steps=16, quotas=(2, 2),
                     order=("demo_tlv", "demo_kernel"),
                     uop_capacity=1 << 10, overlay_slots=8, edge_bits=12)

# the cross-device collective HLO ops the mesh family censuses: on the
# lane mesh the compiled chunk may hold exactly ONE — the coverage
# all-reduce; any gather-class op means machine state is being resharded
COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                  "collective-permute", "collective-broadcast",
                  "reduce-scatter")

# canonical mesh-trace configuration: the census is pinned against the
# 8-device arm; the 4-device arm (same lanes-per-shard) feeds the
# shard-count stability rule.  donate=False matches the real CPU
# dispatch policy (donation is unsound on XLA CPU — make_run_chunk).
MESH_ENTRY = "mesh_chunk"
MESH_DEVICES = 8
MESH_CONFIG = dict(n_steps=16, lanes_per_shard=2,
                   uop_capacity=1 << 10, overlay_slots=8, edge_bits=12)

# canonical megachunk window configuration (fuzz/megachunk.py, the
# program tests/test_chip_compile.py compiles for a v5e): the whole
# window program's data-dependent JAXPR census pinned as the
# `megachunk_window` entry, plus the donation rule: every
# machine/aggregate leaf of the donate-lowered window executable aliased
# in the compiled output (zero copy-through end to end).
MEGA_ENTRY = "megachunk_window"
MEGA_CONFIG = dict(n_lanes=4, max_batches=2, limit=10_000)

# canonical device-decode service configuration (wtf_tpu/interp/devdec):
# ONE in-graph service round — the vmapped per-lane decode blocks plus
# the sequential publish-order commit — lowered at the budget runner's
# shapes and pinned as its own entry.  The decode graph rides inside
# the megachunk window, so this census is the marginal gather-class
# kernel cost a `--device-decode` campaign pays per service round.
DECODE_ENTRY = "decode_service"
DECODE_BP_SLOTS = 8

FAMILIES = ("dtype", "budget", "recompile", "mesh", "supervise",
            "telemetry", "state", "transfer", "thread", "contracts")

# families that (re)write budgets.json vs analysis/contracts.json on
# --rebaseline — the guard below demands at least one of them
_BUDGET_FAMILIES = frozenset(("budget", "mesh", "transfer"))
_CONTRACT_FAMILIES = frozenset(("state", "transfer", "thread"))

_FORBID_64 = re.compile(r"\b(u64|s64|f64|f32)\[")
# jaxpr primitives that move/reshape bits without computing on them (the
# pack/unpack seam allowance; on CPU the width-changing bitcast itself
# legitimately LOWERS to shift/or arithmetic, so the contract is checked
# at the jaxpr level, before XLA expands it)
_SEAM_OK = frozenset((
    "bitcast_convert_type", "reshape", "transpose", "squeeze",
    "broadcast_in_dim", "convert_element_type",
))
_ALIAS_ENTRY = re.compile(r"\((\d+), \{[^)]*?\}(?:, [a-z\-]+)?\)")


# ---------------------------------------------------------------------------
# dtype family
# ---------------------------------------------------------------------------

def check_no_u64(fn, *args, entry: str) -> List[Finding]:
    """Compile fn(*args); any 64-bit (or float) typed op is a finding —
    the ported integer-core paths are u32/bool/i32-only by contract."""
    text = compiled_hlo(fn, *args)
    found: Dict[str, int] = {}
    for m in _FORBID_64.finditer(text):
        found[m.group(1)] = found.get(m.group(1), 0) + 1
    return [
        Finding(rule="dtype.no-u64", entry=entry, primitive=dtype,
                count=n,
                message=("64-bit/float op reintroduced in a ported "
                         "u32-limb path (XLA lowers it to a carry-chained "
                         "u32 pair on TPU)"))
        for dtype, n in sorted(found.items())
    ]


def check_seam_bitcast_only(fn, *args, entry: str) -> List[Finding]:
    """The pack/unpack seam may *hold* 64-bit values but must not compute
    on them: its jaxpr may contain only bitcast / data-movement
    primitives (the "free bitcast" contract XLA then lowers per
    platform)."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    bad: Dict[str, int] = {}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name not in _SEAM_OK:
            bad[name] = bad.get(name, 0) + 1
    return [
        Finding(rule="dtype.seam-bitcast-only", entry=entry, primitive=op,
                count=n,
                message=("pack/unpack seam performs arithmetic — the "
                         "seam contract is a free bitcast (data movement "
                         "only), nothing more"))
        for op, n in sorted(bad.items())
    ]


def _dtype_arg_recipes() -> Dict[str, Tuple]:
    """name -> (fn, args) for every ported path the dtype family pins.
    The step-level names must cover step.PORTED_LIMB_PATHS (and the
    devmut engine's devmut.PORTED_LIMB_PATHS) exactly; run_dtype_family
    fails the lint on any export without a recipe."""
    import jax.numpy as jnp

    from wtf_tpu.devmut import engine as DM
    from wtf_tpu.interp import limbs as L
    from wtf_tpu.interp import step as S
    from wtf_tpu.interp.uoptable import UopTable

    p = (jnp.uint32(0x55667788), jnp.uint32(0x11223344))
    q = (jnp.uint32(0xDEADBEEF), jnp.uint32(0x12345678))
    cin = jnp.bool_(True)
    n4 = jnp.int32(4)
    n8 = jnp.int32(8)
    s = jnp.uint32(33)
    rf = jnp.uint32(0x246)
    cap = 8
    tab = UopTable(
        rip_l=jnp.zeros((cap, 2), jnp.uint32),
        meta_i32=jnp.zeros((cap, 4), jnp.int32),
        meta_u64=jnp.zeros((cap, 4), jnp.uint32),
        hash_tab=jnp.full((cap * 4, 3), -1, jnp.int32),
    )
    recipes: Dict[str, Tuple] = {
        # limb library (interp/limbs.py public helpers)
        "limbs.adc64": (L.adc64, (p, q, cin)),
        "limbs.sbb64": (L.sbb64, (p, q, cin)),
        "limbs.shl64": (L.shl64, (p, s)),
        "limbs.shr64": (L.shr64, (p, s)),
        "limbs.sar64": (L.sar64, (p, s)),
        "limbs.rol64": (L.rol64, (p, s)),
        "limbs.mul64_lo": (L.mul64_lo, (p, q)),
        "limbs.umulhi64": (L.umulhi64, (p, q)),
        "limbs.smulhi64": (L.smulhi64, (p, q)),
        "limbs.splitmix64": (L.splitmix64, (p,)),
        "limbs.sext": (L.sext, (p, n4)),
        "limbs.flags_add": (L.flags_add, (p, q, p, n4, cin)),
        "limbs.flags_sub": (L.flags_sub, (p, q, p, n4, cin)),
        "limbs.eval_cond": (L.eval_cond, (rf, p, jnp.int32(5))),
        # step-level ported paths (step.PORTED_LIMB_PATHS)
        "step.alu_limb": (S.alu_limb, (jnp.int32(0), p, q, cin, n8, rf)),
        "step.unary_limb": (S.unary_limb,
                            (jnp.int32(0), p, jnp.bool_(False), n4, rf)),
        "step.shift_limb": (S.shift_limb,
                            (jnp.int32(4), jnp.int32(0), p, q, jnp.uint32(7),
                             jnp.uint32(3), jnp.uint32(2), cin, n8, rf)),
        "step.mul_limb": (S.mul_limb,
                          (jnp.int32(2), jnp.int32(0), p, q, p, q, n8, rf)),
        "step.ea_limb": (
            lambda d, b, i, sc, a32: S.ea_limb(
                d, b, S._scale_idx_l(i, sc), (jnp.uint32(0x1000),
                                              jnp.uint32(0)), a32),
            (p, q, p, n4, jnp.int32(0))),
        "step.scale_idx_l": (S._scale_idx_l, (p, n4)),
        "step.uop_lookup": (S.uop_lookup,
                            (tab, (jnp.uint32(0x1000), jnp.uint32(0x14)))),
    }
    # devmut engine paths (devmut.PORTED_LIMB_PATHS): tiny shapes — the
    # pin is about dtypes, not scale
    dm_data = jnp.zeros((4, 8), jnp.uint32)
    dm_lens = jnp.ones((4,), jnp.int32)
    dm_cumw = jnp.asarray([1, 2, 3, 4], jnp.uint32)
    dm_seeds = jnp.zeros((2, 2), jnp.uint32)
    recipes.update({
        "devmut.prng_next": (DM.prng_next, (p,)),
        "devmut.pick_slot": (DM.pick_slot,
                             (dm_cumw, jnp.asarray([5, 7], jnp.uint32))),
        "devmut.unpack_bytes": (DM.unpack_bytes, (dm_data,)),
        "devmut.pack_words": (DM.pack_words,
                              (jnp.zeros((2, 32), jnp.uint32),)),
        "devmut.generate": (
            lambda d, ln, c, s: DM.generate(d, ln, c, s, rounds=1),
            (dm_data, dm_lens, dm_cumw, dm_seeds)),
    })
    # triage candidate builds (triage.PORTED_LIMB_PATHS): the in-graph
    # minimizer ops run under the same pin as the devmut engine
    from wtf_tpu.triage import candidates as TC

    tc_words = jnp.zeros((8,), jnp.uint32)
    tc_ops = jnp.zeros((2,), jnp.int32)
    tc_u = jnp.zeros((2,), jnp.uint32)
    recipes.update({
        "triage.build_candidates": (
            TC.build_candidates,
            (tc_words, jnp.uint32(7), tc_ops, tc_u, tc_u)),
        "triage.zero_counts": (
            TC.zero_counts,
            (jnp.zeros((2, 8), jnp.uint32), jnp.ones((2,), jnp.int32))),
    })
    return recipes


def run_dtype_family(exports: Optional[Dict] = None,
                     compile_paths: bool = True) -> List[Finding]:
    """All dtype rules: no-u64 over every enumerated ported path, the
    seam bitcast-only check, and the completeness check that every path
    step.py exports (`exports`, default step.PORTED_LIMB_PATHS) has a
    recipe here — the mechanism that forces a newly ported path under
    the pin.  compile_paths=False runs only the completeness check (the
    compiles are the expensive part; tests that seed an unpinned export
    don't need them)."""
    import jax.numpy as jnp

    from wtf_tpu.devmut import engine as DM
    from wtf_tpu.interp import limbs as L
    from wtf_tpu.interp import step as S
    from wtf_tpu.triage import candidates as TC

    if exports is None:
        exports = {**S.PORTED_LIMB_PATHS, **DM.PORTED_LIMB_PATHS,
                   **TC.PORTED_LIMB_PATHS}
    recipes = _dtype_arg_recipes()
    findings: List[Finding] = []
    for name in sorted(exports):
        if name not in recipes:
            findings.append(Finding(
                rule="dtype.unpinned", entry=name,
                message=("step.PORTED_LIMB_PATHS exports a ported path "
                         "with no argument recipe in "
                         "analysis.rules._dtype_arg_recipes — add one so "
                         "the zero-u64 pin covers it")))
    if not compile_paths:
        return findings
    # Fast path: ONE compile of every recipe bundled into a tuple-valued
    # module (tuple outputs keep each path live, so a u64 in any entry
    # survives into the scanned text).  Only when that sweep finds a
    # violation do the entries recompile individually, to attach the
    # exact entry point to the finding — clean runs (CI, tier-1) pay a
    # single XLA pipeline instead of ~20.
    names = sorted(recipes)
    fns = [recipes[n][0] for n in names]

    def combined(argsets):
        return tuple(fn(*a) for fn, a in zip(fns, argsets))

    quick = check_no_u64(combined, [recipes[n][1] for n in names],
                         entry="ported-paths(combined)")
    if quick:
        localized: List[Finding] = []
        for name in names:
            fn, args = recipes[name]
            localized.extend(check_no_u64(fn, *args, entry=name))
        findings.extend(localized if localized else quick)
    # the seam itself: free bitcasts only
    v64 = jnp.arange(4, dtype=jnp.uint64)
    v32 = jnp.zeros((4, 2), jnp.uint32)
    findings.extend(check_seam_bitcast_only(
        L.pack_u64, v32, entry="limbs.pack_u64"))
    findings.extend(check_seam_bitcast_only(
        L.unpack_u64, v64, entry="limbs.unpack_u64"))
    return findings


# ---------------------------------------------------------------------------
# budget family
# ---------------------------------------------------------------------------

def count_data_dependent_ops(hlo_text: str) -> Dict[str, int]:
    """Occurrences of each gather-class op in optimized HLO text (plus
    "total") — the kernel-count currency of PERF.md's model."""
    counts = {}
    for name in DATA_DEP_OPS:
        pat = re.compile(r"(?<![\w\-])" + re.escape(name) + r"\(")
        counts[name] = len(pat.findall(hlo_text))
    counts["total"] = sum(counts.values())
    return counts


def walk_note() -> str:
    """What a pinned chunk program's entry says of its page walk: the
    census counts gather OPERATIONS, which the width of the walk does not
    move, so the pin names the width (a level of the walk gathers six
    indices an address: the frame table, the image's two limbs, the
    overlay's valid byte and its two limbs)."""
    from wtf_tpu.mem.paging import K_WALK

    return (f" / one walk of K_WALK={K_WALK} addresses: "
            f"{4 * 6 * K_WALK} gathered indices a lane-step")


def step_note() -> str:
    """`walk_note`, and what the entry says of the store: the census
    counts OPERATIONS, which rise when the store's third word gets a
    conditional of its own, so the pin names the indices every pass
    pays (two words of five, the frame table's two, the row claims'
    four; the commit's two limbs and valid byte a word), as the chip's
    compile counts them (tests/test_chip_compile.py)."""
    return (walk_note() + " / store: 16 gathered and 6 scattered indices "
            "a lane-step, the third word under `store-spill`")


def check_budget(counts: Dict[str, int], budget: Dict[str, int],
                 entry: str) -> List[Finding]:
    """Exact pin: any drift (up OR down) is a finding — an improvement
    must be re-baselined consciously (see PERF.md round 9), a regression
    must be explained or fixed."""
    findings = []
    for name in list(DATA_DEP_OPS) + ["total"]:
        got = counts.get(name, 0)
        want = budget.get(name)
        if want is None or got == want:
            continue
        direction = "over" if got > want else "under"
        findings.append(Finding(
            rule="budget.kernel-count", entry=entry, primitive=name,
            count=got, budget=want,
            message=(f"data-dependent `{name}` kernel count {direction} "
                     "the checked-in budget — if the change is "
                     "intentional, re-baseline with `python -m "
                     "wtf_tpu.analysis --rebaseline` and record why in "
                     "PERF.md")))
    return findings


# the opcode: the first word before a "(" after the result's shape (a
# tuple shape, as a sort of two operands has, holds spaces and no such word)
_HLO_OP = re.compile(r" = .*?\s([a-z][\w\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _indexed_ops(hlo_text: str):
    """(opcode, op_name, instruction line) of every indexed operation of
    optimized HLO text (`telemetry.xprof.INDEXED_OPS`: the gather class
    and the sort)."""
    from wtf_tpu.telemetry.xprof import INDEXED_OPS

    for line in hlo_text.splitlines():
        op = _HLO_OP.search(line)
        if op is None or op.group(1) not in INDEXED_OPS:
            continue
        name = _HLO_OP_NAME.search(line)
        yield op.group(1), name.group(1) if name else "", line.strip()[:200]


def unscoped_indexed_ops(hlo_text: str) -> List[Tuple[str, str]]:
    """(opcode, instruction line) of every indexed operation whose
    `op_name` holds no scope of THE list (`telemetry.xprof.SCOPES`):
    device time that a profiler window could not lay at any stage's door
    (tools/xprof_scopes.py)."""
    from wtf_tpu.telemetry.xprof import scope_of

    return [(op, line) for op, name, line in _indexed_ops(hlo_text)
            if scope_of(name) is None]


def _findings_by_opcode(ops, rule: str, entry: str, message) -> List[Finding]:
    """One finding an opcode of `ops` ((opcode, line) pairs), budget 0;
    `message(n, opcode)` words it."""
    by_op: Dict[str, int] = {}
    for opcode, _ in ops:
        by_op[opcode] = by_op.get(opcode, 0) + 1
    return [Finding(rule=rule, entry=entry, primitive=opcode, count=n,
                    budget=0, message=message(n, opcode))
            for opcode, n in sorted(by_op.items())]


def check_indexed_ops_scoped(hlo_text: str, entry: str) -> List[Finding]:
    """The chunk program holds no indexed operation outside a named
    scope (PR 39: 36 gathers, 6 scatters, 3 dynamic-slices and 13
    dynamic-update-slices of `xla_step` stood in none)."""
    return _findings_by_opcode(
        unscoped_indexed_ops(hlo_text), "budget.unscoped-indexed-op", entry,
        lambda n, opcode: (
            f"{n} `{opcode}` operation(s) whose op_name holds no "
            "scope of telemetry.xprof.SCOPES: put the code under a "
            "jax.named_scope of the list (or add the name to it)"))


def vector_unit_indexed_ops(hlo_text: str) -> List[Tuple[str, str]]:
    """(opcode, instruction line) of every indexed operation whose
    `op_name` holds a scope of the vector unit
    (`telemetry.xprof.VECTOR_SCOPES`; interp/step.py reads and writes its
    files by selects over their rows): a gather or a scatter there is
    priced by the lane in every sweep, whether or not a lane is at a
    vector instruction (PERF.md section 6, PR 41)."""
    from wtf_tpu.telemetry.xprof import VECTOR_SCOPES

    unit = re.compile(
        r"(?<![\w-])(" + "|".join(VECTOR_SCOPES) + r")(?![\w-])")
    return [(op, line) for op, name, line in _indexed_ops(hlo_text)
            if unit.search(name)]


def check_vector_unit(hlo_text: str, entry: str) -> List[Finding]:
    """The vector unit indexes nothing (PR 41: 25 gathers and 4 scatters
    stood under its scopes, 309 us of a 1,260-us group sweep)."""
    return _findings_by_opcode(
        vector_unit_indexed_ops(hlo_text), "budget.vector-unit-indexed",
        entry,
        lambda n, opcode: (
            f"{n} `{opcode}` operation(s) under the vector unit's "
            "scopes: a table indexed by the lane where a select over "
            "its rows would do"))


def decode_service_program(runner):
    """(fn, args) of one devdec service round (compute_blocks +
    commit_blocks) at `runner`'s shapes — the `decode_service` census
    subject, also compiled for a described TPU by
    tests/test_chip_compile.py."""
    import jax.numpy as jnp

    from wtf_tpu.interp import devdec
    from wtf_tpu.mem.physmem import lane_image

    capacity = runner.cache.capacity

    def service(tab, image, machine, count, bp_keys, n_bp):
        blocks = devdec.compute_blocks(tab, image, machine, bp_keys, n_bp)
        return devdec.commit_blocks(tab, count, blocks, machine.status,
                                    capacity)

    return service, (
        runner.cache.device(),
        lane_image(runner.physmem.image, runner.n_lanes),
        runner.machine, jnp.int32(0),
        jnp.zeros(DECODE_BP_SLOTS, jnp.uint64), jnp.int32(0))


def decode_service_lowering(runner):
    """decode_service_program lowered for this process's backend."""
    import jax

    fn, args = decode_service_program(runner)
    return jax.jit(fn).lower(*args)


def run_decode_rules(runner, budgets_path: Optional[Path] = None,
                     rebaseline: bool = False):
    """The `decode_service` kernel-count pin (budget family)."""
    text = decode_service_lowering(runner).compile().as_text()
    counts = count_data_dependent_ops(text)
    info = {"decode_counts": counts,
            "entry": f"decode_service(1 round) / demo_tlv / "
                     f"n_lanes={runner.n_lanes}"}
    findings: List[Finding] = []
    if not rebaseline:
        budget = load_budgets(budgets_path).get(DECODE_ENTRY, {})
        findings = check_budget(counts, budget, entry=info["entry"])
    return findings, info


def _iter_eqns(jxp):
    """Depth-first over a jaxpr's equations, descending into every
    sub-jaxpr carried in params (while/cond/scan/pjit/custom calls)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def sub_jaxprs(v):
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from sub_jaxprs(x)

    for eqn in jxp.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                yield from _iter_eqns(sub)


def count_data_dependent_eqns(jaxpr) -> Dict[str, int]:
    """JAXPR-level analogue of count_data_dependent_ops: gather-class
    primitives counted across every sub-jaxpr (the megachunk window's
    census — static code sites, before XLA's pipeline)."""
    jxp = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    counts = {name: 0 for name in DATA_DEP_OPS}
    for eqn in _iter_eqns(jxp):
        name = eqn.primitive.name
        if name == "gather":
            counts["gather"] += 1
        elif name == "dynamic_slice":
            counts["dynamic-slice"] += 1
        elif name == "dynamic_update_slice":
            counts["dynamic-update-slice"] += 1
        elif name.startswith("scatter"):
            counts["scatter"] += 1
    counts["total"] = sum(counts.values())
    return counts


def check_window_donation_aliasing(compiled, args,
                                   donated: Sequence[int],
                                   entry: str) -> List[Finding]:
    """check_donation_aliasing generalized to the megachunk window
    executable: every leaf of every donated operand position must appear
    in the compiled module's input_output_alias map.  `args` is the full
    operand tuple the window was lowered against; `donated` the
    positional donate_argnums (megachunk.WINDOW_DONATE_ARGNUMS).  A
    donated leaf jit's DCE pruned outright is still a finding — the
    buffer is invalidated with no in-place reuse."""
    import jax

    text = compiled.as_text()
    header = text[:text.index("\n")]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                  header)
    aliased = ({int(g.group(1)) for g in _ALIAS_ENTRY.finditer(m.group(1))}
               if m else set())
    kept = getattr(getattr(compiled, "_executable", None),
                   "_kept_var_idx", None)
    findings: List[Finding] = []
    base = 0
    names = ("tab", "image", "machine", "template", "slab_first",
             "slab_rest", "seeds", "pfns", "gva_l", "finish", "limit",
             "n_batches", "agg_cov", "agg_edge", "count", "bp_keys",
             "n_bp")
    for pos, arg in enumerate(args):
        flat = jax.tree_util.tree_flatten_with_path(arg)[0]
        for i, (path, _leaf) in enumerate(flat):
            param = base + i
            if pos in donated:
                if kept is not None and param not in kept:
                    shifted = -1  # pruned outright: never aliased
                elif kept is not None:
                    shifted = sum(1 for k in kept if k < param)
                else:
                    shifted = param
                if shifted not in aliased:
                    arg_name = (names[pos] if pos < len(names)
                                else f"arg{pos}")
                    findings.append(Finding(
                        rule="recompile.window-donation-unaliased",
                        entry=entry,
                        primitive=(f"{arg_name}"
                                   f"{jax.tree_util.keystr(path)} "
                                   f"(param {param})"),
                        message=("donated window operand leaf not "
                                 "aliased in the compiled megachunk — "
                                 "the buffer is invalidated without the "
                                 "in-place reuse; the overlay/machine "
                                 "planes would copy through the window "
                                 "executable")))
        base += len(flat)
    return findings


def run_megachunk_rules(budgets_path: Optional[Path] = None,
                        rebaseline: bool = False
                        ) -> Tuple[List[Finding], Dict]:
    """The megachunk-window pins, one trace for both:

      1. the `megachunk_window` kernel census (jaxpr-level) against
         budgets.json;
      2. the window executable, LOWERED with donation (safe on CPU —
         only execution is unsound there), aliases every donated
         machine/aggregate leaf in its compiled output.

    Returns (findings, info) with the measured counts for run_lint's
    rebaseline merge."""
    import jax

    from wtf_tpu.analysis.trace import megachunk_window_lowering
    from wtf_tpu.fuzz.megachunk import WINDOW_DONATE_ARGNUMS

    cfg = MEGA_CONFIG
    entry = (f"megachunk(max_batches={cfg['max_batches']}, donate=True) "
             f"/ demo_tlv / n_lanes={cfg['n_lanes']}" + step_note())
    lowered, args, fn = megachunk_window_lowering(
        max_batches=cfg["max_batches"], n_lanes=cfg["n_lanes"],
        donate=True, limit=cfg["limit"])
    jaxpr = jax.make_jaxpr(fn)(*args)
    counts = count_data_dependent_eqns(jaxpr)
    findings: List[Finding] = []
    if not rebaseline:
        budget = load_budgets(budgets_path).get(MEGA_ENTRY, {})
        findings = check_budget(counts, budget, entry=entry)
    findings.extend(check_window_donation_aliasing(
        lowered.compile(), args, WINDOW_DONATE_ARGNUMS, entry=entry))
    # the raw jaxpr rides along (popped before JSON) so the transfer
    # family's host-callback census never re-traces the window
    return findings, {"mega_counts": counts, "entry": entry,
                      "jaxpr": jaxpr}


def check_triage_chunk() -> List[Finding]:
    """The triage replay core must dispatch the SAME compiled step
    ladder the campaign runs — zero new gather/DS/DUS kernels beyond the
    pinned budget.  Statically: its declared chunk-executor factory is
    step.make_run_chunk by identity (ReplayCore drives Runner.run, whose
    `_chunk_callable` memoizes that factory), and the core defines no
    private executor seam.  Re-pointing either is a real kernel-budget
    event and must be re-baselined consciously."""
    from wtf_tpu.interp.step import make_run_chunk
    from wtf_tpu.triage import replay as TR

    findings = []
    if TR.REPLAY_CHUNK_FACTORY is not make_run_chunk:
        findings.append(Finding(
            rule="budget.triage-chunk", entry="triage.replay",
            primitive="REPLAY_CHUNK_FACTORY",
            message=("triage's replay chunk no longer resolves to "
                     "step.make_run_chunk — the triage path would "
                     "compile its own step program outside the pinned "
                     "168-kernel budget; route it through the Runner "
                     "dispatch seam or re-baseline")))
    private = [name for name in ("_chunk_callable", "chunk_executor",
                                 "device_tab")
               if name in vars(TR.ReplayCore)]
    if private:
        findings.append(Finding(
            rule="budget.triage-chunk", entry="triage.replay.ReplayCore",
            primitive=", ".join(private),
            message=("ReplayCore overrides the Runner dispatch seam — "
                     "triage batches must run the campaign's own chunk "
                     "executors (budget + mesh census coverage), not a "
                     "private program")))
    return findings


# ---------------------------------------------------------------------------
# supervise family
# ---------------------------------------------------------------------------

def check_supervised_seams(sites: Optional[Dict[str, str]] = None
                           ) -> List[Finding]:
    """Every device dispatch entry point must route through the
    supervisor (wtf_tpu/supervise) — the recovery/watchdog/chaos
    contract is only as strong as its seam coverage, so the enumeration
    is an export hook (supervise.SEAM_SITES, the PORTED_LIMB_PATHS
    mechanism): a new dispatch seam must be listed there AND its listed
    site must contain a `supervisor.dispatch("<seam>"...)` routing
    call.  AST-level on the shared dataflow engine (analysis/flow.py) —
    the seams include paths (mesh) a CPU lint run never
    executes, and the AST walk cannot false-hit on comments or
    docstrings the way the retired regex could.  `sites` parameterizes
    the enumeration for rule tests."""
    from wtf_tpu.analysis import flow

    if sites is None:
        from wtf_tpu.supervise import SEAM_SITES

        sites = SEAM_SITES
    findings: List[Finding] = []
    for seam, site in sorted(sites.items()):
        try:
            info = flow.resolve_site(site)
        except Exception as e:  # unresolvable site IS the finding
            findings.append(Finding(
                rule="supervise.seam-routing", entry=site, primitive=seam,
                message=(f"supervised seam site unresolvable ({e}) — "
                         "supervise.SEAM_SITES must name the live "
                         "module:Class.method of every dispatch seam")))
            continue
        if seam not in flow.dispatch_seams(info.node):
            findings.append(Finding(
                rule="supervise.seam-routing", entry=site, primitive=seam,
                file=info.file, line=info.lineno,
                message=(f"dispatch seam {seam!r} does not route through "
                         "Supervisor.dispatch — a hang/error/poison here "
                         "would bypass watchdog + rebuild-and-replay "
                         "recovery; route the call or update "
                         "supervise.SEAM_SITES")))
    return findings


def check_telemetry_seams(sites: Optional[Dict[str, str]] = None
                          ) -> List[Finding]:
    """No supervised dispatch seam may serialize the metric registry
    inline: `.snapshot()` walks every metric, `encode_telem`/`json.dumps`
    pay JSON, and the seams run once per chunk — the <1% overhead bar
    (PERF.md) holds because serialization rides the heartbeat/TAG_TELEM
    cadence (seconds) instead.  AST-level (flow.serialization_calls —
    counter bumps like `.inc()`/`.set()` are O(1) dict ops and welcome
    anywhere; registry snapshots / telem encoding / json.dumps are
    O(registry)+JSON and are not), over the same supervise.SEAM_SITES
    enumeration the routing rule walks, so a new dispatch seam is
    covered the moment it is enumerated.  `sites` parameterizes the
    enumeration for rule tests."""
    from wtf_tpu.analysis import flow

    if sites is None:
        from wtf_tpu.supervise import SEAM_SITES

        sites = SEAM_SITES
    findings: List[Finding] = []
    for seam, site in sorted(sites.items()):
        try:
            info = flow.resolve_site(site)
        except Exception:
            # unresolvable sites are the supervise family's finding;
            # double-reporting here would just duplicate the signal
            continue
        calls = flow.serialization_calls(info.node)
        if calls:
            hits = sorted({pat for pat, _ in calls})
            findings.append(Finding(
                rule="telemetry.seam-serialization", entry=site,
                primitive=f"{seam}: {', '.join(hits)}",
                count=len(hits), file=info.file,
                line=min(ln for _, ln in calls),
                message=("dispatch seam serializes telemetry inline — "
                         "registry snapshots / telem encoding are "
                         "O(registry)+JSON and this seam runs per chunk; "
                         "move the serialization to the heartbeat or "
                         "TAG_TELEM cadence (counter bumps stay, "
                         "serialization goes)")))
    return findings


def check_seam_enumeration() -> List[Finding]:
    """Completeness of the export hook itself: the known dispatch-seam
    surface (the Runner seam methods MeshRunner re-points, the megachunk
    window, devmut generate) must each be claimed by some SEAM_SITES
    entry — deleting a seam's enumeration to dodge the routing rule is
    itself a finding."""
    from wtf_tpu.supervise import SEAM_SITES

    claimed = set(SEAM_SITES)
    required = {"chunk", "device-insert", "devmut-generate", "megachunk",
                "device-decode"}
    missing = sorted(required - claimed)
    return [Finding(
        rule="supervise.seam-enumeration", entry="supervise.SEAM_SITES",
        primitive=seam,
        message=(f"dispatch seam {seam!r} dropped from "
                 "supervise.SEAM_SITES — the routing rule no longer "
                 "covers it"))
        for seam in missing]


def _first_diff_line(text_a: str, text_b: str) -> Tuple[int, str]:
    """(0-based line index, detail) of the first differing line between
    two lowerings; (-1, "length mismatch") when one is a prefix of the
    other.  Shared by the byte-stability rules."""
    for i, (la, lb) in enumerate(zip(text_a.splitlines(),
                                     text_b.splitlines())):
        if la != lb:
            return i, la.strip()[:80]
    return -1, "length mismatch"


def check_tenant_mix_stability(text_a: str, text_b: str,
                               entry: str) -> List[Finding]:
    """The heterogeneous batch's serving contract, statically: at a
    given lane count the chunk executor must lower to the SAME program
    bytes for any tenant mix — tenant identity is pure data (the
    per-lane selector + stacked table contents), never a traced value.
    The probe permutes the tenant TABLE (demo_tlv+demo_kernel vs
    demo_kernel+demo_tlv: same shapes, different contents and lane
    assignment); a diff means a tenant-mix-dependent value is baked into
    the trace and every mix would compile its own program."""
    if text_a == text_b:
        return []
    i, detail = _first_diff_line(text_a, text_b)
    return [Finding(
        rule="budget.tenant-mix", entry=entry,
        primitive=f"line {i + 1}: {detail}",
        message=("the compiled step ladder differs across tenant "
                 "permutations at equal lane count — tenant identity "
                 "leaked into the traced program; heterogeneous batches "
                 "must share ONE compiled program per lane count"))]


def run_tenant_rules(budgets_path: Optional[Path] = None,
                     rebaseline: bool = False) -> Tuple[List[Finding],
                                                        Dict]:
    """The tenancy half of the budget family: image-table kernel census
    + tenant-mix program stability.  Returns (findings, info) with the
    measured counts for run_lint's rebaseline merge."""
    cfg = TENANT_CONFIG
    entry = (f"make_run_chunk({cfg['n_steps']}, donate=False) / "
             f"{'+'.join(cfg['order'])} / quotas={list(cfg['quotas'])}"
             + step_note())
    kwargs = dict(chunk_steps=cfg["n_steps"],
                  uop_capacity=cfg["uop_capacity"],
                  overlay_slots=cfg["overlay_slots"],
                  edge_bits=cfg["edge_bits"])
    runner = build_tenant_runner(quotas=cfg["quotas"], order=cfg["order"],
                                 **kwargs)
    lowered = tenant_executor_lowering(runner, n_steps=cfg["n_steps"])
    permuted = build_tenant_runner(quotas=cfg["quotas"],
                                   order=cfg["order"][::-1], **kwargs)
    lowered_p = tenant_executor_lowering(permuted, n_steps=cfg["n_steps"])
    findings = check_tenant_mix_stability(
        lowered.as_text(), lowered_p.as_text(), entry=entry)
    counts = count_data_dependent_ops(lowered.compile().as_text())
    if not rebaseline:
        budget = load_budgets(budgets_path).get(TENANT_ENTRY, {})
        findings.extend(check_budget(counts, budget, entry=entry))
    return findings, {"tenant_counts": counts, "entry": entry}


def check_chunk_donation(compiled, compiled_text: str, runner,
                         entry: str) -> List[Finding]:
    """`check_donation_aliasing` for a chunk executor compiled on
    `runner`'s operands (tab, image, machine, limit)."""
    import jax

    n_prefix = len(jax.tree_util.tree_leaves(runner.cache.device())) \
        + len(jax.tree_util.tree_leaves(runner.physmem.image))
    n_machine = len(jax.tree_util.tree_leaves(runner.machine))
    # jit DCEs unused flat args (e.g. tab.rip_l once uop_lookup's
    # probe verifies against the hash rows' own key limbs), shifting
    # the compiled param numbering the alias map indexes
    kept = getattr(getattr(compiled, "_executable", None),
                   "_kept_var_idx", None)
    dropped = (frozenset(i for i in range(n_prefix + n_machine)
                         if i not in kept)
               if kept is not None else frozenset())
    return check_donation_aliasing(compiled_text, runner.machine, n_prefix,
                                   entry=entry, dropped_args=dropped)


def run_tail_rules(runner, families, budgets_path: Optional[Path] = None,
                   rebaseline: bool = False) -> Tuple[List[Finding], Dict]:
    """The chunk executor with its narrow sweeps on, at the budget
    runner's shapes (ONE compile for both families): the kernel census
    against the `tail_chunk` entry (budget) and the alias coverage of the
    donated machine through the rows' scatter back (recompile)."""
    entry = (f"make_run_chunk({BUDGET_CONFIG['n_steps']}, "
             f"donate={BUDGET_CONFIG['donate']}, tail={TAIL_WIDTH}) / "
             f"demo_tlv / n_lanes={BUDGET_CONFIG['n_lanes']}" + step_note())
    compiled = step_executor_lowering(
        runner, n_steps=BUDGET_CONFIG["n_steps"],
        donate=BUDGET_CONFIG["donate"], tail=TAIL_WIDTH).compile()
    text = compiled.as_text()
    findings: List[Finding] = []
    info: Dict = {"entry": entry}
    if "budget" in families:
        info["tail_counts"] = count_data_dependent_ops(text)
        if not rebaseline:
            budget = load_budgets(budgets_path).get(TAIL_ENTRY, {})
            findings.extend(check_budget(info["tail_counts"], budget,
                                         entry=entry))
        findings.extend(check_indexed_ops_scoped(text, entry=entry))
        findings.extend(check_vector_unit(text, entry=entry))
    if "recompile" in families:
        findings.extend(check_chunk_donation(compiled, text, runner, entry))
    return findings, info


def apply_rebaseline(budgets: Dict, measured: Dict,
                     allow_regression: bool = False) -> Dict:
    """Merge freshly measured budget entries over the checked-in ones —
    RATCHETED: an entry whose `total` INCREASED over the checked-in
    value is refused (ValueError naming every offender) unless
    `allow_regression`.  Every decrement is a wall-clock win on TPU
    (step cost tracks kernel count), so a giveback must be a conscious,
    named act.  Returns the merged dict; pure, so tests can pin the
    ratchet without paying a trace."""
    regressions = [
        (name, budgets[name].get("total"), entry.get("total"))
        for name, entry in measured.items()
        if name in budgets
        and entry.get("total", 0) > budgets[name].get("total", 0)]
    if regressions and not allow_regression:
        detail = ", ".join(f"{n}: {old} -> {new}"
                           for n, old, new in regressions)
        raise ValueError(
            f"--rebaseline would RAISE a kernel/collective budget "
            f"({detail}); the pin is a ratchet — re-run with "
            f"--allow-regression and record why in PERF.md, or fix "
            f"the regression")
    merged = dict(budgets)
    merged.update(measured)
    return merged


def load_budgets(path: Optional[Path] = None) -> Dict:
    path = Path(path) if path else BUDGETS_PATH
    return json.loads(path.read_text())


def save_budgets(budgets: Dict, path: Optional[Path] = None) -> Path:
    path = Path(path) if path else BUDGETS_PATH
    path.write_text(json.dumps(budgets, indent=2, sort_keys=True) + "\n")
    return path


# ---------------------------------------------------------------------------
# recompile family
# ---------------------------------------------------------------------------

def weak_typed_leaves(args) -> List[Tuple[str, str]]:
    """(path, dtype) for every weak-typed leaf in an argument pytree —
    each is a python scalar crossing the jit boundary where a committed
    dtype belongs, and a second call site with the strong dtype splits
    the jit cache (one executor shape, two compiles)."""
    import jax

    out = []
    flat = jax.tree_util.tree_flatten_with_path(args)[0]
    for path, leaf in flat:
        aval = jax.typeof(leaf)
        if getattr(aval, "weak_type", False):
            out.append((jax.tree_util.keystr(path), str(aval.dtype)))
    return out


def check_strong_inputs(args, entry: str) -> List[Finding]:
    return [
        Finding(rule="recompile.weak-type", entry=entry,
                primitive=f"{path}: {dtype} (weak)",
                message=("weak-typed executor operand — a python scalar "
                         "leaked to the dispatch seam; pass a committed "
                         "dtype (jnp.uint64(...) etc.) or the jit cache "
                         "splits per caller convention"))
        for path, dtype in weak_typed_leaves(args)
    ]


def check_signature_stable(text_a: str, text_b: str,
                           entry: str) -> List[Finding]:
    """Two lowerings of the same executor under perturbed-but-same-shape
    inputs must be byte-identical StableHLO; a diff means a traced VALUE
    (python int capture, host-dependent constant) entered the graph —
    every such value is a silent retrace per distinct value."""
    if text_a == text_b:
        return []
    i, detail = _first_diff_line(text_a, text_b)
    return [Finding(
        rule="recompile.signature-unstable", entry=entry,
        primitive=f"line {i + 1}: {detail}",
        message=("re-tracing under perturbed same-shape inputs changed "
                 "the lowered module — a runtime value is captured in "
                 "the trace and will force a recompile per value"))]


def check_runner_donation_policy(runner, entry: str = "interp.runner"
                                 ) -> List[Finding]:
    """The PR-2 CPU-donation-corruption class, statically: on the CPU
    backend the Runner must not request donation (XLA CPU's buffer reuse
    corrupts live machine leaves on this graph); off-CPU it must (HBM
    in-place updates are the point)."""
    import jax

    expected = jax.default_backend() != "cpu"
    if bool(runner._donate) == expected:
        return []
    return [Finding(
        rule="recompile.donation-policy", entry=entry,
        primitive=f"_donate={runner._donate} on {jax.default_backend()}",
        message=("Runner donation gate violated: donation must be OFF on "
                 "the CPU backend (donated machine buffers corrupt there "
                 "— the PR-2 failure class) and ON elsewhere"))]


def check_donation_aliasing(compiled_text: str, machine,
                            n_prefix_params: int, entry: str,
                            dropped_args=frozenset()) -> List[Finding]:
    """Every leaf of the donated machine argument must appear in the
    compiled module's input_output_alias map; an unaliased donated
    buffer is invalidated without the in-place win, and any host view of
    it reads garbage.

    `dropped_args`: original flat-argument indices jit's dead-code
    elimination pruned from the compiled module (kept_var_idx's
    complement) — compiled param numbering skips them, so every dropped
    index below a machine leaf shifts its param down by one.  A donated
    machine leaf that is ITSELF dropped is still a finding (the buffer
    is invalidated with no reuse)."""
    import jax

    header = compiled_text[:compiled_text.index("\n")]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry_computation", header)
    aliased = ({int(g.group(1)) for g in _ALIAS_ENTRY.finditer(m.group(1))}
               if m else set())
    flat = jax.tree_util.tree_flatten_with_path(machine)[0]
    findings = []
    for i, (path, _leaf) in enumerate(flat):
        param = n_prefix_params + i
        if param in dropped_args:
            shifted = -1  # pruned outright: never aliased
        else:
            shifted = param - sum(1 for d in dropped_args if d < param)
        if shifted not in aliased:
            findings.append(Finding(
                rule="recompile.donation-unaliased", entry=entry,
                primitive=f"machine{jax.tree_util.keystr(path)} "
                          f"(param {param})",
                message=("donated machine leaf not aliased in the "
                         "compiled output — donation invalidates the "
                         "buffer with no in-place reuse; host code "
                         "holding a view of it reads poison")))
    return findings


# ---------------------------------------------------------------------------
# mesh family
# ---------------------------------------------------------------------------

def count_collective_ops(hlo_text: str) -> Dict[str, int]:
    """Occurrences of each cross-device collective in partitioned HLO
    text (plus "total") — the interconnect-traffic currency of the mesh
    cost model (PERF.md round 11)."""
    counts = {}
    for name in COLLECTIVE_OPS:
        pat = re.compile(r"(?<![\w\-])" + re.escape(name) + r"[\.\w]*\(")
        counts[name] = len(pat.findall(hlo_text))
    counts["total"] = sum(counts.values())
    return counts


def check_mesh_collectives(counts: Dict[str, int], budget: Dict[str, int],
                           entry: str) -> List[Finding]:
    """Exact pin against budgets.json's `mesh_chunk` entry: the sharded
    chunk's only collective is the coverage all-reduce.  A gather-class
    op appearing means machine state is crossing the interconnect —
    an accidental reshard, the regression this family exists to catch."""
    findings = []
    for name in list(COLLECTIVE_OPS) + ["total"]:
        got = counts.get(name, 0)
        want = budget.get(name)
        if want is None or got == want:
            continue
        direction = "over" if got > want else "under"
        findings.append(Finding(
            rule="mesh.collectives", entry=entry, primitive=name,
            count=got, budget=want,
            message=(f"cross-device `{name}` count {direction} the "
                     "checked-in mesh budget — the compiled chunk's only "
                     "collective is the coverage all-reduce; anything "
                     "else reshards machine state over the interconnect. "
                     "If intentional, re-baseline with `python -m "
                     "wtf_tpu.analysis --rebaseline` and record why in "
                     "PERF.md")))
    return findings


# partitioned-HLO details that legitimately vary with the mesh size
# (device lists in sharding annotations / replica groups) — stripped
# before the shard-count stability comparison
_MESH_NORMALIZE = (
    (re.compile(r"sharding=\{[^{}]*\}"), "sharding={...}"),
    (re.compile(r"replica_groups=\{\{[^{}]*\}(,\{[^{}]*\})*\}"),
     "replica_groups={...}"),
    (re.compile(r"replica_groups=\{[^{}]*\}"), "replica_groups={...}"),
    (re.compile(r"replica_groups=\[[^\]]*\]<=\[\d+\]"),
     "replica_groups=[...]"),
    (re.compile(r"num_partitions=\d+"), "num_partitions=N"),
)


def normalize_partitioned_hlo(text: str) -> str:
    for pat, repl in _MESH_NORMALIZE:
        text = pat.sub(repl, text)
    return text


def check_shard_stability(text_a: str, text_b: str,
                          entry: str) -> List[Finding]:
    """Two compiled mesh chunks at EQUAL lanes-per-shard but different
    shard counts must be byte-identical per-device programs once the
    device-list annotations are normalized; a diff means a shard-count-
    dependent value leaked into the trace and every mesh resize pays a
    silent recompile of a *different* program."""
    na, nb = normalize_partitioned_hlo(text_a), normalize_partitioned_hlo(
        text_b)
    if na == nb:
        return []
    for i, (la, lb) in enumerate(zip(na.splitlines(), nb.splitlines())):
        if la != lb:
            detail = la.strip()[:80]
            break
    else:
        detail, i = "length mismatch", -1
    return [Finding(
        rule="mesh.shard-unstable", entry=entry,
        primitive=f"line {i + 1}: {detail}",
        message=("the compiled per-device chunk differs across shard "
                 "counts at equal lanes-per-shard — a mesh-size-dependent "
                 "value is baked into the traced program"))]


def _mesh_chunk_compiled(n_shards: int) -> str:
    """Compiled partitioned HLO of the mesh chunk executor at
    MESH_CONFIG's lanes-per-shard over `n_shards` devices."""
    import jax
    import jax.numpy as jnp

    from wtf_tpu.meshrun.executor import make_mesh_chunk
    from wtf_tpu.meshrun.mesh import make_mesh, replicate, shard_machine

    cfg = MESH_CONFIG
    runner = build_tlv_runner(
        n_lanes=cfg["lanes_per_shard"] * n_shards,
        chunk_steps=cfg["n_steps"],
        uop_capacity=cfg["uop_capacity"],
        overlay_slots=cfg["overlay_slots"], edge_bits=cfg["edge_bits"])
    mesh = make_mesh(n_shards)
    machine = shard_machine(runner.machine, mesh)
    tab = replicate(runner.cache.device(), mesh)
    image = replicate(runner.physmem.image, mesh)
    # jit=False: a fresh shard_map closure per lowering, same reasoning
    # as step_executor_lowering's fresh-trace requirement
    fn = jax.jit(make_mesh_chunk(cfg["n_steps"], mesh, donate=False,
                                 jit=False))
    return fn.lower(tab, image, machine,
                    jnp.uint64(0)).compile().as_text()


def _mesh_family_subprocess(budgets_path: Optional[Path],
                            rebaseline: bool) -> Tuple[List[Finding], Dict]:
    """Re-run ONLY the mesh family in a child interpreter with the
    forced 8-device CPU platform (the ambient process has too few
    devices and jax device topology is fixed at backend init)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # force EXACTLY MESH_DEVICES: an ambient flag pinning a smaller
    # count must be overridden, not preserved, or the child is just as
    # device-poor as the parent and the family reports unavailable
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count"
                f"={MESH_DEVICES}").strip()
    if env.get("WTF_LINT_MESH_SUBPROC"):
        return [Finding(
            rule="mesh.unavailable", entry=MESH_ENTRY,
            message=(f"mesh family needs >= {MESH_DEVICES} devices but "
                     "the forced-device subprocess still sees too few — "
                     "platform cannot host a virtual mesh"))], {}
    env["WTF_LINT_MESH_SUBPROC"] = "1"
    cmd = [sys.executable, "-m", "wtf_tpu.analysis", "--families", "mesh",
           "--json"]
    if budgets_path is not None:
        cmd += ["--budgets", str(budgets_path)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1800)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if line is None:
        return [Finding(
            rule="mesh.unavailable", entry=MESH_ENTRY,
            message=("forced-8-device mesh subprocess produced no JSON "
                     f"(rc={proc.returncode}): "
                     f"{(proc.stderr or proc.stdout)[-200:]}"))], {}
    out = json.loads(line)
    findings = [Finding(**{k: f.get(k) for k in
                           ("rule", "entry", "message", "primitive",
                            "count", "budget", "file", "line")})
                for f in out.get("findings", [])]
    if rebaseline:
        # parent is re-pinning: the measured counts matter, drift
        # findings against the OLD budget don't
        findings = [f for f in findings if f.rule != "mesh.collectives"]
    return findings, {"collective_counts": out.get("collective_counts"),
                      "entry": out.get("mesh_entry")}


def run_mesh_family(budgets_path: Optional[Path] = None,
                    rebaseline: bool = False) -> Tuple[List[Finding], Dict]:
    """All mesh rules.  Returns (findings, info) where info carries the
    measured collective census (for run_lint's rebaseline merge and the
    `analysis.mesh_collectives` telemetry gauges)."""
    import jax

    if len(jax.devices()) < MESH_DEVICES:
        return _mesh_family_subprocess(budgets_path, rebaseline)
    entry = (f"make_mesh_chunk({MESH_CONFIG['n_steps']}, donate=False) / "
             f"demo_tlv / {MESH_DEVICES} shards x "
             f"{MESH_CONFIG['lanes_per_shard']} lanes" + step_note())
    text_full = _mesh_chunk_compiled(MESH_DEVICES)
    text_half = _mesh_chunk_compiled(MESH_DEVICES // 2)
    counts = count_collective_ops(text_full)
    findings = check_shard_stability(text_full, text_half, entry=entry)
    if not rebaseline:
        budget = load_budgets(budgets_path).get(MESH_ENTRY, {})
        findings.extend(check_mesh_collectives(counts, budget, entry=entry))
    return findings, {"collective_counts": counts, "entry": entry}


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def run_lint(families: Optional[Sequence[str]] = None,
             budgets_path: Optional[Path] = None,
             rebaseline: bool = False,
             allow_regression: bool = False,
             deep: bool = False,
             contracts_path: Optional[Path] = None,
             registry=None, events=None) -> Tuple[List[Finding], Dict]:
    """Run the requested rule families (default: all) against the real
    tree on the current (CPU) backend.  Returns (findings, info); wires
    results into the telemetry registry under `analysis.*` and emits one
    `lint-finding` event per finding when an event sink is given.

    `deep` forces the transfer family's jaxpr host-callback census even
    when the budget family (whose window trace it would reuse) is
    not co-selected; without either, the transfer family runs its cheap
    AST rule only.

    The kernel-count pin is a RATCHET: `rebaseline` re-pins measured
    counts as usual, but REFUSES to record a budget whose `total`
    INCREASED over the checked-in value unless `allow_regression` is
    set — every decrement is a wall-clock win on TPU (step cost tracks
    kernel count), so giving one back must be a conscious, named act
    (`--allow-regression`, recorded in PERF.md).  contracts.json gets
    the same treatment through apply_contracts_rebaseline: entry GROWTH
    (a new undispositioned attribute / hidden coercion / shared write)
    is refused without `allow_regression`."""
    from wtf_tpu.telemetry import NULL, Registry

    registry = registry if registry is not None else Registry()
    events = events if events is not None else NULL
    families = list(families) if families else list(FAMILIES)
    unknown = set(families) - set(FAMILIES)
    if unknown:
        raise ValueError(f"unknown lint families: {sorted(unknown)} "
                         f"(known: {list(FAMILIES)})")
    if rebaseline and not (_BUDGET_FAMILIES
                           | _CONTRACT_FAMILIES) & set(families):
        raise ValueError(
            "--rebaseline rewrites the kernel-count/collective budgets "
            "and the contract tables, which only the budget/mesh/"
            "transfer and state/transfer/thread families measure — drop "
            "the families filter or include one of them")
    findings: List[Finding] = []
    info: Dict = {"families": families, "seconds": {}, "entries": []}
    # entries re-measured this run; merged over the checked-in file on
    # --rebaseline so a partial family filter never drops the others
    measured_budgets: Dict[str, Dict] = {}

    needs_trace = {"budget", "recompile"} & set(families)
    runner = None
    if needs_trace:
        t0 = time.time()
        runner = build_tlv_runner(
            n_lanes=BUDGET_CONFIG["n_lanes"],
            chunk_steps=BUDGET_CONFIG["chunk_steps"])
        lowered = step_executor_lowering(
            runner, n_steps=BUDGET_CONFIG["n_steps"],
            donate=BUDGET_CONFIG["donate"])
        info["seconds"]["trace"] = round(time.time() - t0, 1)
        info["entries"].append(
            f"make_run_chunk({BUDGET_CONFIG['n_steps']}, "
            f"donate={BUDGET_CONFIG['donate']}) / demo_tlv / "
            f"n_lanes={BUDGET_CONFIG['n_lanes']}" + step_note())

    if "dtype" in families:
        t0 = time.time()
        findings.extend(run_dtype_family())
        info["seconds"]["dtype"] = round(time.time() - t0, 1)

    compiled = None
    compiled_text = None
    mega_jaxpr = None
    if "budget" in families:
        t0 = time.time()
        compiled = lowered.compile()
        compiled_text = compiled.as_text()
        counts = count_data_dependent_ops(compiled_text)
        info["kernel_counts"] = counts
        if rebaseline:
            measured_budgets[BUDGET_ENTRY] = {
                "entry": info["entries"][0], **counts}
        else:
            budget = load_budgets(budgets_path).get(BUDGET_ENTRY, {})
            findings.extend(check_budget(counts, budget,
                                         entry=info["entries"][0]))
        findings.extend(check_indexed_ops_scoped(
            compiled_text, entry=info["entries"][0]))
        findings.extend(check_vector_unit(
            compiled_text, entry=info["entries"][0]))
        for name, value in counts.items():
            registry.gauge("analysis.kernel_count").labels(name).set(value)
        # the triage replay core rides the same compiled ladder: its
        # kernel contribution is ZERO by identity, checked statically
        findings.extend(check_triage_chunk())
        # heterogeneous batches (wtf_tpu/tenancy): image-table kernel
        # census + one-program-per-lane-count across tenant mixes
        tenant_findings, tenant_info = run_tenant_rules(
            budgets_path=budgets_path, rebaseline=rebaseline)
        findings.extend(tenant_findings)
        counts_t = tenant_info["tenant_counts"]
        info["tenant_kernel_counts"] = counts_t
        info["entries"].append(tenant_info["entry"])
        if rebaseline:
            measured_budgets[TENANT_ENTRY] = {
                "entry": tenant_info["entry"], **counts_t}
        for name, value in counts_t.items():
            registry.gauge("analysis.tenant_kernel_count").labels(
                name).set(value)
        # device-decode service graph (interp/devdec): its own pin —
        # the marginal kernel cost of a `--device-decode` service round
        decode_findings, decode_info = run_decode_rules(
            runner, budgets_path=budgets_path, rebaseline=rebaseline)
        findings.extend(decode_findings)
        counts_d = decode_info["decode_counts"]
        info["decode_kernel_counts"] = counts_d
        info["entries"].append(decode_info["entry"])
        if rebaseline:
            measured_budgets[DECODE_ENTRY] = {
                "entry": decode_info["entry"], **counts_d}
        for name, value in counts_d.items():
            registry.gauge("analysis.decode_kernel_count").labels(
                name).set(value)
        # megachunk window (fuzz/megachunk.py): jaxpr census plus the
        # window-executable donation aliasing rule
        mega_findings, mega_info = run_megachunk_rules(
            budgets_path=budgets_path, rebaseline=rebaseline)
        findings.extend(mega_findings)
        mega_jaxpr = mega_info.pop("jaxpr", None)
        counts_m = mega_info["mega_counts"]
        info["mega_kernel_counts"] = counts_m
        info["entries"].append(mega_info["entry"])
        if rebaseline:
            measured_budgets[MEGA_ENTRY] = {
                "entry": mega_info["entry"], **counts_m}
        for name, value in counts_m.items():
            registry.gauge("analysis.mega_kernel_count").labels(
                name).set(value)
        info["seconds"]["budget"] = round(time.time() - t0, 1)

    if "recompile" in families:
        t0 = time.time()
        entry = info["entries"][0]
        # weak-typed operands at the dispatch seam (what Runner.run passes)
        operands = runner.executor_operands()
        findings.extend(check_strong_inputs(operands, entry=entry))
        # retrace under perturbed same-shape inputs
        perturbed = step_executor_lowering(
            runner, n_steps=BUDGET_CONFIG["n_steps"],
            donate=BUDGET_CONFIG["donate"], perturb=True)
        findings.extend(check_signature_stable(
            lowered.as_text(), perturbed.as_text(), entry=entry))
        # overlay restore: same stability contract, cheap trace (fresh
        # jit wrappers — the memoized executor would hit the trace cache)
        from wtf_tpu.interp.machine import machine_restore_impl
        from wtf_tpu.analysis.trace import lower_jit

        ra = lower_jit(lambda m, t: machine_restore_impl(m, t),
                       runner.machine, runner.template).as_text()
        rb = lower_jit(lambda m, t: machine_restore_impl(m, t),
                       runner.machine._replace(
                           icount=runner.machine.icount + 3),
                       runner.template).as_text()
        findings.extend(check_signature_stable(
            ra, rb, entry="machine_restore"))
        info["entries"].append("machine_restore")
        # donation: policy gate + alias coverage of the donated executor
        findings.extend(check_runner_donation_policy(runner))
        if compiled_text is None:
            compiled = lowered.compile()
            compiled_text = compiled.as_text()
        findings.extend(check_chunk_donation(
            compiled, compiled_text, runner, entry))
        info["seconds"]["recompile"] = round(time.time() - t0, 1)

    if needs_trace:
        t0 = time.time()
        tail_findings, tail_info = run_tail_rules(
            runner, families, budgets_path=budgets_path,
            rebaseline=rebaseline)
        findings.extend(tail_findings)
        info["entries"].append(tail_info["entry"])
        counts_tail = tail_info.get("tail_counts")
        if counts_tail:
            info["tail_kernel_counts"] = counts_tail
            if rebaseline:
                measured_budgets[TAIL_ENTRY] = {
                    "entry": tail_info["entry"], **counts_tail}
        info["seconds"]["tail"] = round(time.time() - t0, 1)

    if "mesh" in families:
        t0 = time.time()
        mesh_findings, mesh_info = run_mesh_family(
            budgets_path=budgets_path, rebaseline=rebaseline)
        findings.extend(mesh_findings)
        counts = mesh_info.get("collective_counts")
        if counts:
            info["collective_counts"] = counts
            info["mesh_entry"] = mesh_info.get("entry")
            for name, value in counts.items():
                registry.gauge("analysis.mesh_collectives").labels(
                    name).set(value)
            if rebaseline:
                measured_budgets[MESH_ENTRY] = {
                    "entry": mesh_info.get("entry"), **counts}
        if mesh_info.get("entry"):
            info["entries"].append(mesh_info["entry"])
        info["seconds"]["mesh"] = round(time.time() - t0, 1)

    if "supervise" in families:
        t0 = time.time()
        findings.extend(check_supervised_seams())
        findings.extend(check_seam_enumeration())
        from wtf_tpu.supervise import SEAM_SITES

        info["entries"].append(
            f"supervise.SEAM_SITES ({len(SEAM_SITES)} seams)")
        info["seconds"]["supervise"] = round(time.time() - t0, 1)

    if "telemetry" in families:
        t0 = time.time()
        findings.extend(check_telemetry_seams())
        from wtf_tpu.supervise import SEAM_SITES

        info["entries"].append(
            f"telemetry over SEAM_SITES ({len(SEAM_SITES)} seams)")
        info["seconds"]["telemetry"] = round(time.time() - t0, 1)

    # contract families (state/transfer/thread/contracts) share ONE
    # pure-AST analysis pass over the tree (analysis/contracts.py on
    # the flow.py engine) — milliseconds, no device work
    contract_fams = ({"state", "transfer", "thread", "contracts"}
                     & set(families))
    if contract_fams:
        from wtf_tpu.analysis import contracts as CT

        t0 = time.time()
        con = CT.load_contracts(contracts_path)
        state_a = CT.analyze_state()
        transfer_a = CT.analyze_transfer()
        thread_a = CT.analyze_thread()
        info["seconds"]["contract-analysis"] = round(time.time() - t0, 1)

        if "state" in families:
            t0 = time.time()
            if not rebaseline:
                findings.extend(CT.check_state_contracts(
                    con, analysis=state_a))
            n_mut = sum(len(a["mutable"]) for a in state_a.values())
            n_cov = sum(len(set(a["mutable"]) & a["covered"])
                        for a in state_a.values())
            registry.gauge("analysis.state_attrs").labels(
                "mutable").set(n_mut)
            registry.gauge("analysis.state_attrs").labels(
                "covered").set(n_cov)
            info["entries"].append(
                f"state surface ({len(state_a)} classes, "
                f"{n_mut} mutable attrs)")
            info["seconds"]["state"] = round(time.time() - t0, 1)

        if "transfer" in families:
            t0 = time.time()
            if not rebaseline:
                findings.extend(CT.check_transfer_seams(
                    con, analysis=transfer_a))
            # the jaxpr census re-traces nothing when the budget family
            # already ran (mega_jaxpr + runner ride along); standalone
            # it is the expensive part, so it hides behind --deep
            census = None
            if deep or rebaseline or mega_jaxpr is not None:
                census = CT.measure_transfer_census(
                    runner=runner, mega_jaxpr=mega_jaxpr)
                info["transfer_census"] = census
                for name, value in census.items():
                    registry.gauge("analysis.transfer_census").labels(
                        name).set(value)
                if rebaseline:
                    measured_budgets[CT.TRANSFER_ENTRY] = {
                        "entry": CT.TRANSFER_CENSUS_ENTRY, **census}
                else:
                    budget = load_budgets(budgets_path).get(
                        CT.TRANSFER_ENTRY, {})
                    findings.extend(CT.check_transfer_census(
                        census, budget,
                        budgets_file=str(budgets_path or BUDGETS_PATH)))
            info["entries"].append(
                "transfer over SEAM_SITES"
                + (" + jaxpr census" if census is not None else ""))
            info["seconds"]["transfer"] = round(time.time() - t0, 1)

        if "thread" in families:
            t0 = time.time()
            if not rebaseline:
                findings.extend(CT.check_thread_contracts(
                    con, analysis=thread_a))
            n_shared = sum(len(a["shared"]) for a in thread_a.values())
            registry.gauge("analysis.thread_shared_attrs").set(n_shared)
            info["entries"].append(
                f"thread roots ({len(thread_a)} classes, "
                f"{n_shared} shared attrs)")
            info["seconds"]["thread"] = round(time.time() - t0, 1)

        if "contracts" in families:
            t0 = time.time()
            if not rebaseline:
                findings.extend(CT.check_contract_hygiene(
                    con, state_a, transfer_a, thread_a))
            for section in CT.SECTIONS:
                n = sum(len(v) for v in con.get(section, {}).values())
                registry.gauge("analysis.contract_entries").labels(
                    section).set(n)
            info["entries"].append("contracts.json hygiene")
            info["seconds"]["contracts"] = round(time.time() - t0, 1)

        if rebaseline and _CONTRACT_FAMILIES & set(families):
            needed = CT.needed_contracts(state_a, transfer_a, thread_a)
            merged = CT.apply_contracts_rebaseline(
                con, needed, allow_regression=allow_regression)
            info["contracts_written"] = str(
                CT.save_contracts(merged, contracts_path))

    if rebaseline and measured_budgets:
        budgets = apply_rebaseline(load_budgets(budgets_path),
                                   measured_budgets,
                                   allow_regression=allow_regression)
        info["budgets_written"] = str(save_budgets(budgets, budgets_path))

    # telemetry: analysis.* namespace + one event per finding
    registry.gauge("analysis.families_run").set(len(families))
    for f in findings:
        registry.counter("analysis.findings").labels(f.rule).inc()
        events.emit("lint-finding", **f.as_dict())
    info["n_findings"] = len(findings)
    return findings, info
