"""Graph-invariant linter tests (wtf_tpu/analysis).

Two layers:

  * negative paths (ISSUE 5 satellite): each rule family gets a seeded
    violation — a u64 op in a "ported" path, a gather over budget, a
    weak-typed operand / value captured in a trace, a donated window operand
    left unaliased — and must fire its NAMED rule with actionable provenance
    (rule + entry point + primitive);
  * clean paths: the cheap families (donation policy, seam) and the
    megachunk window's pins against the real tree; the full `run_lint`
    (which compiles the step ladder, ~30s) runs in the slow tier — tier-1
    covers the dtype family through tests/test_limbs.py instead.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wtf_tpu.analysis.findings import Finding
from wtf_tpu.analysis.rules import (
    check_budget, check_donation_aliasing, check_no_u64,
    check_runner_donation_policy, check_seam_bitcast_only,
    check_signature_stable, check_strong_inputs,
    check_window_donation_aliasing, count_data_dependent_ops,
    load_budgets, run_dtype_family, run_lint, run_megachunk_rules,
)
from wtf_tpu.analysis.trace import compiled_hlo, lower_jit

P = (jnp.uint32(0x55667788), jnp.uint32(0x11223344))


# ---------------------------------------------------------------------------
# dtype family
# ---------------------------------------------------------------------------

def test_no_u64_rule_fires_on_seeded_u64_op():
    """A 64-bit add smuggled into a 'ported' path must fire dtype.no-u64
    with the dtype named and the entry point attached."""
    def bad(p):
        wide = p[0].astype(jnp.uint64) | (p[1].astype(jnp.uint64) << 32)
        return wide + jnp.uint64(1)

    findings = check_no_u64(bad, P, entry="seeded.bad_path")
    assert findings, "seeded u64 op not detected"
    assert all(f.rule == "dtype.no-u64" for f in findings)
    assert any(f.primitive == "u64" for f in findings)
    assert all(f.entry == "seeded.bad_path" for f in findings)


def test_no_u64_rule_clean_on_limb_path():
    from wtf_tpu.interp import limbs as L

    assert check_no_u64(L.add64, P, P, entry="limbs.add64") == []


def test_seam_rule_allows_bitcast_forbids_arith():
    from wtf_tpu.interp import limbs as L

    v32 = jnp.zeros((4, 2), jnp.uint32)
    assert check_seam_bitcast_only(L.pack_u64, v32,
                                   entry="limbs.pack_u64") == []

    def leaky(x32):
        return L.pack_u64(x32) + jnp.uint64(1)   # arithmetic on the seam

    findings = check_seam_bitcast_only(leaky, v32, entry="seeded.seam")
    assert any(f.rule == "dtype.seam-bitcast-only" and f.primitive == "add"
               for f in findings), findings


def test_unpinned_ported_path_is_a_finding():
    """A path exported via step.PORTED_LIMB_PATHS without an argument
    recipe in the analyzer must fail the lint, not silently dodge the
    zero-u64 pin."""
    from wtf_tpu.interp import step as S

    exports = dict(S.PORTED_LIMB_PATHS)
    exports["step.freshly_ported_thing"] = lambda x: x
    # compile_paths=False: the completeness check alone (the compiled
    # no-u64 sweep over the real recipes runs in test_limbs / the lint)
    findings = run_dtype_family(exports=exports, compile_paths=False)
    assert [(f.rule, f.entry) for f in findings] == [
        ("dtype.unpinned", "step.freshly_ported_thing")]
    assert run_dtype_family(compile_paths=False) == []


# ---------------------------------------------------------------------------
# budget family
# ---------------------------------------------------------------------------

def test_budget_rule_fires_on_extra_gather():
    """A real mini-compile with a data-dependent gather, checked against
    a zero budget: the rule must name the op kind, the measured count,
    and the pinned value."""
    def gathery(img, idx):
        return img[idx] + img[idx + 1]

    text = compiled_hlo(gathery, jnp.arange(64, dtype=jnp.int32),
                        jnp.int32(3))
    counts = count_data_dependent_ops(text)
    assert counts["total"] >= 1, counts
    budget = {k: 0 for k in counts}
    findings = check_budget(counts, budget, entry="seeded.gathery")
    assert findings
    f = findings[-1]           # the "total" row
    assert f.rule == "budget.kernel-count"
    assert f.primitive == "total"
    assert f.count == counts["total"] and f.budget == 0
    assert "rebaseline" in f.message


def test_budget_rule_fires_on_improvement_too():
    """The pin is exact: dropping below budget is also a finding (force a
    conscious re-baseline), and a matching tree is clean."""
    counts = {"gather": 2, "dynamic-slice": 0, "dynamic-update-slice": 0,
              "scatter": 0, "total": 2}
    assert check_budget(counts, dict(counts), entry="e") == []
    low = check_budget(counts, {**counts, "gather": 5, "total": 5},
                       entry="e")
    assert {f.primitive for f in low} == {"gather", "total"}
    assert all("under" in f.message for f in low)


def test_checked_in_budget_matches_perf_record():
    """analysis/budgets.json pins the step ladder at what PERF.md
    records (CPU compile of the installed XLA): 91 surviving
    data-dependent ops, 68 gathers / no dynamic-slice / 13
    dynamic-update-slices / 10 scatters.  PR 44 added two scatters, the
    taken side of the `store-spill` conditional (the store's third word,
    one a plane): the census counts operations, and the indices every
    pass pays FELL (the entry lines; tests/test_chip_compile.py counts
    them: 21 -> 16 gathered, 9 -> 6 scattered by the commit; here the
    CPU's compile keeps the two words' reads in the gathers that read
    three).  PR 41 took 24 gathers and 4
    scatters out with the vector unit's (117: 92/0/13/12 before): the XMM
    file, the x87 stack and the byte class's shuffles are selects over
    their rows and their sixteen bytes.  PR 39 took the three
    dynamic-slices out: the compiler made them of `mul`'s read of rax at a
    constant index and gave them no `op_name`, so no scope could name
    them; rax's limbs come by a static slice now.  PR 26's rebaseline read
    104 (76/3/13/12); PR 38 took the sixteen more: the two limbs of a word
    come from a gather each (`overlay.image_words`, `read_data`: the same
    indices, and no kernel to pack the pair), eight in the walk, two in
    the windows, six in the store's read-modify-write.  PR 22's 108
    (78/3/13/14) held the overlay claim's two row reads and two row
    clears, which went when the validity plane became a restore-time
    memset (ROADMAP S14); the 165 (78/59/28/0) of the earlier record was
    the same step under an older XLA, which kept as slices what this one
    lowers to scatters or folds away."""
    budget = load_budgets()["xla_step"]
    assert budget["total"] == 91
    assert (budget["gather"], budget["dynamic-slice"],
            budget["dynamic-update-slice"], budget["scatter"]) == (
        68, 0, 13, 10)
    # the tenant ladder is the SAME program over a stacked image table
    assert load_budgets()["tenant_chunk"]["total"] == 91
    # the in-graph decode service compiles as its own pinned graph
    # (round 18) so decoder growth is a lint finding, not silent fusion
    assert load_budgets()["decode_service"]["total"] == 308


@pytest.fixture(scope="module")
def chunk_programs():
    """Optimized HLO text of the `xla_step` and `tail_chunk` entries (the
    budget family's two compiles of the chunk program, CPU)."""
    from wtf_tpu.analysis.rules import BUDGET_CONFIG, TAIL_WIDTH
    from wtf_tpu.analysis.trace import (
        build_tlv_runner, step_executor_lowering)

    runner = build_tlv_runner(BUDGET_CONFIG["n_lanes"],
                              BUDGET_CONFIG["chunk_steps"])
    return {entry: step_executor_lowering(
        runner, n_steps=BUDGET_CONFIG["n_steps"],
        donate=BUDGET_CONFIG["donate"], tail=tail).compile().as_text()
        for entry, tail in (("xla_step", None), ("tail_chunk", TAIL_WIDTH))}


@pytest.mark.parametrize("opcode", [
    "gather", "scatter", "dynamic-slice", "dynamic-update-slice", "sort"])
@pytest.mark.parametrize("entry", ["xla_step", "tail_chunk"])
def test_chunk_program_has_no_indexed_operation_outside_a_scope(
        chunk_programs, entry, opcode):
    """PR 39's measure of done: in the optimized HLO of the chunk program
    no gather, scatter, dynamic-slice, dynamic-update-slice or sort is
    left whose `op_name` holds no scope of the list that
    tools/xprof_scopes.py sums a window by (`telemetry.xprof.SCOPES`);
    before, `xla_step` held 36 / 6 / 3 / 13 such.  The operations are
    there to be counted, and the narrowing program sorts."""
    from wtf_tpu.analysis.rules import unscoped_indexed_ops
    from wtf_tpu.telemetry.xprof import INDEXED_OPS

    assert opcode in INDEXED_OPS
    text = chunk_programs[entry]
    bare = [line for op, line in unscoped_indexed_ops(text) if op == opcode]
    assert not bare, "\n".join(bare)
    present = len(re.findall(rf" {re.escape(opcode)}\(", text))
    if opcode == "sort":
        assert (present > 0) == (entry == "tail_chunk")
    elif opcode != "dynamic-slice" or entry == "tail_chunk":
        assert present > 0


@pytest.mark.parametrize("entry", ["xla_step", "tail_chunk"])
def test_the_vector_unit_indexes_nothing(chunk_programs, entry):
    """The vector unit's scopes hold no gather, scatter or dynamic slice
    in the optimized chunk program: the XMM file and the x87 stack are
    read and written by selects over their rows, the byte class shuffles
    by selects over its sixteen bytes (PR 41; 25 gathers and 4 scatters
    stood there, 309 us of a 1,260-us group sweep on the chip).  The
    scopes themselves are still in the program."""
    from wtf_tpu.analysis.rules import vector_unit_indexed_ops
    from wtf_tpu.telemetry.xprof import VECTOR_SCOPES

    text = chunk_programs[entry]
    assert vector_unit_indexed_ops(text) == []
    for scope in VECTOR_SCOPES:
        assert re.search(rf"[(/]{scope}[)/]", text), scope
    # the step stands in the program once: the byte class under ONE path
    # of loops (a second `_sweep` site would trace and compile it twice)
    # (the compiler names some of a path's operations from the innermost
    # call on: one spelling with the prefix, one without)
    sites = {site.split("closed_call/")[-1].replace("jit(run_chunk)/", "")
             for site in re.findall(
                 r'op_name="([^"]*?)/vmap\(sse-bytes\)', text)}
    assert len(sites) == 1, sites


def test_the_unit_census_finds_a_gather_in_a_vector_scope():
    from wtf_tpu.analysis.rules import (
        check_vector_unit, vector_unit_indexed_ops)

    text = """
  %g.1 = u32[4,1,1]{2,1,0} gather(%a, %b), metadata={op_name="jit(run_chunk)/while/body/vmap(vector-file)/gather"}
  %f.2 = u64[4]{0} fusion(%g.1), kind=kLoop, calls=%fused.3, metadata={op_name="jit(run_chunk)/while/body/vmap(sse-bytes)/select_n"}
  %s.3 = u64[4,8]{1,0} scatter(%st, %i, %v), metadata={op_name="jit(run_chunk)/while/body/vmap(x87)/vector-file/scatter"}
  %g.4 = u64[4,1]{1,0} gather(%r, %i), metadata={op_name="jit(run_chunk)/while/body/vmap(regfile-read)/gather"}
"""
    assert [op for op, _ in vector_unit_indexed_ops(text)] \
        == ["gather", "scatter"]
    assert [(f.rule, f.primitive, f.count)
            for f in check_vector_unit(text, "e")] == [
        ("budget.vector-unit-indexed", "gather", 1),
        ("budget.vector-unit-indexed", "scatter", 1)]


def test_the_census_finds_an_operation_outside_every_scope():
    from wtf_tpu.analysis.rules import (
        check_indexed_ops_scoped, unscoped_indexed_ops)

    text = """
  %gather.1 = u64[4,1,1]{2,1,0} gather(%a, %b), metadata={op_name="jit(run_chunk)/while/body/vmap()/gather"}
  %gather.2 = u64[4,1,1]{2,1,0} gather(%a, %b), metadata={op_name="jit(run_chunk)/while/body/vmap(regfile-read)/gather"}
  %dynamic-slice.81 = u32[4,1,2]{2,1,0} dynamic-slice(%p, %c, %i, %c), dynamic_slice_sizes={4,1,2}
  %add.3 = u32[4]{0} add(%x, %y), metadata={op_name="jit(run_chunk)/while/body/vmap()/add"}
  %sort.2 = s32[4]{0} sort(%k), metadata={op_name="jit(run_chunk)/while/body/level-sort/sort"}
  %sort.3 = (pred[4]{0}, s32[4]{0}) sort(%k, %i), dimensions={0}, metadata={op_name="jit(run_chunk)/while/body/jit(argsort)/sort"}
  %fusion.4 = u32[256,2]{1,0:T(8,128)S(1)} fusion(%x), kind=kLoop, calls=%fused_computation.4
"""
    assert [op for op, _ in unscoped_indexed_ops(text)] == [
        "gather", "dynamic-slice", "sort"]
    findings = check_indexed_ops_scoped(text, entry="e")
    assert [(f.rule, f.primitive, f.count) for f in findings] == [
        ("budget.unscoped-indexed-op", "dynamic-slice", 1),
        ("budget.unscoped-indexed-op", "gather", 1),
        ("budget.unscoped-indexed-op", "sort", 1)]


def test_rebaseline_is_a_ratchet():
    """--rebaseline refuses to record a budget INCREASE without
    --allow-regression (ISSUE 14): decrements re-pin freely, increments
    raise naming every offending entry, and allow_regression=True
    records them consciously."""
    from wtf_tpu.analysis import apply_rebaseline

    old = {"xla_step": {"total": 166, "gather": 79},
           "mesh_chunk": {"total": 1}}
    # a decrease (and a brand-new entry) merge freely
    merged = apply_rebaseline(old, {"xla_step": {"total": 150},
                                    "new_entry": {"total": 9}})
    assert merged["xla_step"]["total"] == 150
    assert merged["new_entry"]["total"] == 9
    # an increase is refused, naming the entry and both totals
    with pytest.raises(ValueError, match="xla_step: 166 -> 170"):
        apply_rebaseline(old, {"xla_step": {"total": 170}})
    # ... unless consciously allowed
    merged = apply_rebaseline(old, {"xla_step": {"total": 170}},
                              allow_regression=True)
    assert merged["xla_step"]["total"] == 170


# ---------------------------------------------------------------------------
# recompile family
# ---------------------------------------------------------------------------

def test_weak_type_rule_fires_on_python_scalar_operand():
    findings = check_strong_inputs((jnp.zeros(3, jnp.uint32), 1.5),
                                   entry="seeded.executor")
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "recompile.weak-type"
    assert "weak" in f.primitive and f.entry == "seeded.executor"
    # committed dtypes are clean
    assert check_strong_inputs(
        (jnp.zeros(3, jnp.uint32), jnp.uint64(5)), entry="e") == []


def test_signature_instability_rule_fires_on_value_capture():
    """A python value captured by the trace (the retrace-per-value
    hazard) shows up as differing lowerings of the 'same' executor."""
    state = {"k": 1.0}

    def capturing(x):
        return x * state["k"]

    # fresh lambda per lowering: jax's trace cache keys on function
    # identity, and the real probe (trace.step_executor_lowering) re-jits
    # a fresh closure for the same reason
    x = jnp.zeros(4, jnp.float32)
    text_a = lower_jit(lambda v: capturing(v), x).as_text()
    state["k"] = 2.0
    text_b = lower_jit(lambda v: capturing(v), x).as_text()
    findings = check_signature_stable(text_a, text_b,
                                      entry="seeded.capturing")
    assert len(findings) == 1
    assert findings[0].rule == "recompile.signature-unstable"
    # and a pure function is stable under perturbed same-shape inputs
    pure = lambda x: x * 2  # noqa: E731
    ta = lower_jit(pure, jnp.zeros(4)).as_text()
    tb = lower_jit(pure, jnp.full(4, 9.0)).as_text()
    assert check_signature_stable(ta, tb, entry="e") == []


def test_donation_policy_rule():
    class FakeRunner:
        _donate = jax.default_backend() != "cpu"

    assert check_runner_donation_policy(FakeRunner()) == []
    FakeRunner._donate = not FakeRunner._donate
    findings = check_runner_donation_policy(FakeRunner())
    assert len(findings) == 1
    assert findings[0].rule == "recompile.donation-policy"


def test_donation_aliasing_rule_fires_on_unaliased_leaf():
    """A donated pytree whose leaves do NOT all alias into the output
    (here: a donated arg the function drops entirely) must be flagged
    with the leaf path in the finding."""
    def drops_donated(dropped, kept):
        return {"out": kept * 2}

    donated = {"buf": jnp.zeros(128, jnp.uint32)}
    text = lower_jit(drops_donated, donated, jnp.ones(128),
                     donate_argnums=(0,)).compile().as_text()
    findings = check_donation_aliasing(text, donated, 0,
                                       entry="seeded.drops_donated")
    assert len(findings) == 1
    assert findings[0].rule == "recompile.donation-unaliased"
    assert "buf" in findings[0].primitive


# ---------------------------------------------------------------------------
# the megachunk window's pins
# ---------------------------------------------------------------------------

def test_window_pins_clean_on_the_ladder_window():
    """The window the chip runs (tests/test_chip_compile.py compiles the
    same program for a v5e) is what budgets.json pins: its jaxpr census
    equals the `megachunk_window` entry, and lowered with donation every
    donated machine/aggregate leaf is aliased in the compiled output."""
    findings, info = run_megachunk_rules()
    assert findings == [], [str(f) for f in findings]
    pinned = dict(load_budgets()["megachunk_window"])
    assert pinned.pop("entry") == info["entry"]
    assert info["mega_counts"] == pinned
    assert set(pinned) == {"gather", "dynamic-slice",
                           "dynamic-update-slice", "scatter", "total"}


def test_window_donation_rule_fires_on_unaliased_leaf():
    """A donated window operand no output can reuse (no result of its
    shape) is invalidated without in-place reuse:
    recompile.window-donation-unaliased names the operand; the donated
    operands that ARE reused stay silent."""
    # jit matches donors to results by shape: give the machine its own
    args = tuple(jnp.zeros((8 if i == 2 else 4,), jnp.uint32)
                 for i in range(14))

    def window(*ops):
        # machine (2): only a scalar survives; agg_cov/agg_edge (12, 13)
        # update in place
        return ops[2].sum(), ops[12] + 1, ops[13] + 1

    compiled = lower_jit(window, *args, donate_argnums=(2, 12, 13)).compile()
    findings = check_window_donation_aliasing(
        compiled, args, (2, 12, 13), entry="seeded.window")
    assert [f.rule for f in findings] == [
        "recompile.window-donation-unaliased"]
    assert findings[0].primitive.startswith("machine")
    assert findings[0].entry == "seeded.window"


# ---------------------------------------------------------------------------
# telemetry report: compile events per executor shape + churn warning
# ---------------------------------------------------------------------------

def _report(path):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import telemetry_report

    return telemetry_report.summarize(path)


def test_report_surfaces_compile_shapes_and_churn(tmp_path, capsys):
    """ISSUE 5 satellite: >1 compile for one executor shape is shape-churn
    and must surface as a warning, not stay buried in the JSONL."""
    events = [
        {"ts": 1.0, "seq": 0, "type": "run-start", "subcommand": "t"},
        {"ts": 1.1, "seq": 1, "type": "compile", "chunk_steps": 64,
         "donate": False},
        {"ts": 1.2, "seq": 2, "type": "compile", "chunk_steps": 1024,
         "donate": False},
        {"ts": 1.3, "seq": 3, "type": "compile", "chunk_steps": 64,
         "donate": False},
        {"ts": 2.0, "seq": 4, "type": "run-end", "metrics": {}},
    ]
    path = tmp_path / "events.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in events))
    summary = _report(path)
    assert summary["compiles"]["total"] == 3
    assert summary["compiles"]["by_shape"]["chunk_steps=64,donate=False"] == 2
    assert summary["compile_shape_churn"] == {
        "chunk_steps=64,donate=False": 2}

    import telemetry_report

    telemetry_report._print_human(summary)
    out = capsys.readouterr().out
    assert "shape-churn" in out and "compiled 2x" in out


def test_report_no_churn_for_distinct_shapes(tmp_path):
    events = [
        {"ts": 1.0, "seq": 0, "type": "run-start", "subcommand": "t"},
        {"ts": 1.1, "seq": 1, "type": "compile", "chunk_steps": 64,
         "donate": False},
        {"ts": 1.2, "seq": 2, "type": "compile", "kind": "megachunk",
         "batches": 8, "lanes": 64},
        {"ts": 2.0, "seq": 3, "type": "run-end", "metrics": {}},
    ]
    path = tmp_path / "events.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in events))
    summary = _report(path)
    assert summary["compiles"]["total"] == 2
    assert summary["compile_shape_churn"] == {}


# ---------------------------------------------------------------------------
# findings plumbing + full lint
# ---------------------------------------------------------------------------

def test_finding_formats_provenance():
    f = Finding(rule="budget.kernel-count", entry="xla_step",
                primitive="gather", message="over", count=90, budget=81)
    assert f.as_dict() == {"rule": "budget.kernel-count",
                           "entry": "xla_step", "primitive": "gather",
                           "message": "over", "count": 90, "budget": 81}
    s = str(f)
    assert "gather" in s and "90" in s and "81" in s


def test_lint_cli_one_cheap_family_with_telemetry(tmp_path, capsys):
    """The CLI path end to end on the cheap family: clean exit, CLEAN
    line, and a well-formed events.jsonl (run-start / run-end)."""
    from wtf_tpu.analysis import main

    rc = main(["--families", "supervise", "--telemetry-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "CLEAN" in out

    from wtf_tpu.telemetry import read_events

    types = [r["type"] for r in read_events(tmp_path / "events.jsonl")]
    assert types[0] == "run-start" and types[-1] == "run-end"


def test_lint_unknown_family_rejected():
    with pytest.raises(ValueError, match="unknown lint families"):
        run_lint(families=["nonsense"])


def test_rebaseline_without_budget_family_rejected():
    """--rebaseline with a families filter that skips `budget` must fail
    loudly, not silently leave the pin stale."""
    with pytest.raises(ValueError, match="rebaseline"):
        run_lint(families=["supervise"], rebaseline=True)


@pytest.mark.slow
def test_full_lint_clean_on_tree(tmp_path):
    """The acceptance gate: every family against the real tree —
    compiles the step ladder and the sharded mesh chunk (~minutes on
    the 1-core box), so slow tier; tier-1 covers dtype via test_limbs,
    the negative paths above, mesh via test_meshrun, and the
    contract families via test_flow."""
    from wtf_tpu.analysis.rules import FAMILIES
    from wtf_tpu.telemetry import Registry

    registry = Registry()
    findings, info = run_lint(registry=registry)
    assert findings == [], [str(f) for f in findings]
    assert info["kernel_counts"]["total"] == \
        load_budgets()["xla_step"]["total"]
    assert info["tail_kernel_counts"]["total"] == \
        load_budgets()["tail_chunk"]["total"]
    assert registry.dump().get("analysis.families_run") == len(FAMILIES)
