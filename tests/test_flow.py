"""Shared dataflow engine + contract families (wtf_tpu/analysis/flow.py,
wtf_tpu/analysis/contracts.py).

Two layers, mirroring test_analysis.py:

  * negative paths: every contract-family violation class is SEEDED —
    an uncheckpointed mutable attribute, a hidden `.item()` inside a
    doctored dispatch seam, a transfer-census drift, an unlocked
    cross-thread write, a stale/undocumented contracts.json row — and
    must fire its NAMED rule with file:line provenance;
  * clean paths: the engine primitives against the real tree, the
    contracts.json ratchet semantics, and (slow tier) the full
    `--deep` contract pass clean with the census matching the
    budgets.json `host_transfer` pin.
"""

import ast
import importlib
import textwrap

import pytest

from wtf_tpu.analysis import contracts as CT
from wtf_tpu.analysis import flow
from wtf_tpu.analysis.findings import Finding, to_sarif
from wtf_tpu.analysis.rules import (
    check_supervised_seams, check_telemetry_seams, load_budgets, run_lint,
)


def _tmp_module(tmp_path, monkeypatch, name, src):
    """Materialize an importable throwaway module.  Names must be unique
    per test: flow's AST caches key on the module name."""
    (tmp_path / f"{name}.py").write_text(textwrap.dedent(src))
    monkeypatch.syspath_prepend(str(tmp_path))
    importlib.invalidate_caches()
    return name


# ---------------------------------------------------------------------------
# engine primitives
# ---------------------------------------------------------------------------

def test_resolve_site_real_tree():
    info = flow.resolve_site("wtf_tpu.interp.runner:Runner.run")
    assert info.qualname == "Runner.run"
    assert info.file.endswith("runner.py")
    assert info.lineno > 0
    assert isinstance(info.node, ast.FunctionDef)


def test_resolve_site_unresolvable_raises():
    with pytest.raises(Exception):
        flow.resolve_site("wtf_tpu.interp.runner:Runner.no_such_method")
    with pytest.raises(Exception):
        flow.resolve_site("wtf_tpu.no_such_module:X.y")


def test_attribute_writes_cover_compound_targets():
    node = ast.parse(textwrap.dedent("""
        def f(self, xs):
            self.a = 1
            self.b, self.c = 1, 2
            self.d += 1
            for self.e in xs:
                pass
            with open("x") as self.g:
                pass
    """)).body[0]
    attrs = {a for a, _ in flow.attribute_writes(node, "self")}
    assert attrs == {"a", "b", "c", "d", "e", "g"}


def test_attribute_writes_nested_scope_flag():
    node = ast.parse(textwrap.dedent("""
        def f(self):
            self.outer = 1
            def inner():
                self.inner_attr = 2
    """)).body[0]
    flat = {a for a, _ in flow.attribute_writes(node, "self",
                                                include_nested=False)}
    deep = {a for a, _ in flow.attribute_writes(node, "self")}
    assert flat == {"outer"}
    assert deep == {"outer", "inner_attr"}


def test_call_classifiers():
    node = ast.parse(textwrap.dedent("""
        def f(self, x):
            self.supervisor.dispatch("chunk", x)
            y = x.item()
            z = float(x)
            k = bool(True)          # constant arg: not a coercion
            w = np.asarray(x)
            g = jax.device_get(x)
            payload = json.dumps({})
            snap = self.registry.snapshot()
    """)).body[0]
    assert flow.dispatch_seams(node) == {"chunk"}
    coercions = {k for k, _ in flow.coercion_calls(node)}
    assert coercions == {".item()", "float()", "np.asarray()",
                         "jax.device_get()"}
    serial = {k for k, _ in flow.serialization_calls(node)}
    assert serial == {"json.dumps(", ".snapshot("}


def test_thread_root_closure_excludes_other_roots(tmp_path, monkeypatch):
    mod = _tmp_module(tmp_path, monkeypatch, "flowmod_roots", """
        class Srv:
            def run(self):
                self._helper()
                self.stop()          # calls ANOTHER root's entry: not
                                     # absorbed into this root's closure
            def _helper(self):
                self.polled = self.flag
            def stop(self):
                self.flag = True
    """)
    acc = flow.thread_root_accesses(mod, "Srv",
                                    {"reactor": ["run"],
                                     "control": ["stop"]})
    assert "flag" in acc["reactor"]["reads"]      # via _helper
    assert "flag" not in acc["reactor"]["writes"]  # stop() stayed out
    assert "flag" in acc["control"]["writes"]


# ---------------------------------------------------------------------------
# seeded violations: state family
# ---------------------------------------------------------------------------

def test_state_uncheckpointed_fires_with_provenance(tmp_path, monkeypatch):
    mod = _tmp_module(tmp_path, monkeypatch, "flowmod_state", """
        class Camp:
            def __init__(self):
                self.a = 0
            def step(self):
                self.cursor = 1
            def checkpoint_state(self):
                return {"a": self.a}
    """)
    surface = {f"{mod}:Camp": [(mod, "Camp.checkpoint_state", "self")]}
    findings = CT.check_state_contracts({"state": {}}, surface=surface)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "state.uncheckpointed"
    assert f.primitive == "cursor"
    assert f.file.endswith("flowmod_state.py")
    assert f.line == 6  # the `self.cursor = 1` write
    assert "flowmod_state.py:6" in str(f)
    # a declared disposition clears it; a junk kind does not
    declared = {"state": {f"{mod}:Camp": {
        "cursor": {"kind": "transient", "reason": "per-step"}}}}
    assert CT.check_state_contracts(declared, surface=surface) == []
    junk = {"state": {f"{mod}:Camp": {
        "cursor": {"kind": "whatever", "reason": "x"}}}}
    assert len(CT.check_state_contracts(junk, surface=surface)) == 1


def test_state_extractor_coverage_counts_both_directions(
        tmp_path, monkeypatch):
    """restore_state WRITES through the param; that is coverage too."""
    mod = _tmp_module(tmp_path, monkeypatch, "flowmod_state2", """
        class Camp:
            def bump(self):
                self.n = 1
            @staticmethod
            def restore_state(camp, blob):
                camp.n = blob["n"]
    """)
    surface = {f"{mod}:Camp": [(mod, "Camp.restore_state", "camp")]}
    assert CT.check_state_contracts({"state": {}}, surface=surface) == []


# ---------------------------------------------------------------------------
# seeded violations: transfer family
# ---------------------------------------------------------------------------

def test_hidden_item_in_doctored_seam_fires(tmp_path, monkeypatch):
    mod = _tmp_module(tmp_path, monkeypatch, "flowmod_seam", """
        def seam(x):
            return x.item()
    """)
    findings = CT.check_transfer_seams({"transfer": {}},
                                       sites={"s": f"{mod}:seam"})
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "transfer.hidden-sync"
    assert f.primitive == ".item()"
    assert f.count == 1 and f.budget == 0
    assert f.file.endswith("flowmod_seam.py") and f.line == 3
    # an allowlist row with a matching count absorbs it
    allowed = {"transfer": {f"{mod}:seam": [
        {"call": ".item()", "count": 1, "reason": "doc'd harvest"}]}}
    assert CT.check_transfer_seams(allowed,
                                   sites={"s": f"{mod}:seam"}) == []


def test_transfer_census_drift_fires():
    budget = load_budgets()["host_transfer"]
    measured = {k: v for k, v in budget.items() if k != "entry"}
    measured["megachunk_window"] += 4
    measured["total"] += 4
    findings = CT.check_transfer_census(measured, budget)
    rules = {(f.rule, f.primitive) for f in findings}
    assert ("transfer.census-drift", "megachunk_window") in rules
    assert ("transfer.census-drift", "total") in rules
    assert len(findings) == 2  # the in-budget programs stay silent
    assert all(f.file == "budgets.json" for f in findings)
    # at or under the pin: clean
    ok = {k: v for k, v in budget.items() if k != "entry"}
    assert CT.check_transfer_census(ok, budget) == []


# ---------------------------------------------------------------------------
# seeded violations: thread family
# ---------------------------------------------------------------------------

def test_unlocked_shared_write_fires(tmp_path, monkeypatch):
    mod = _tmp_module(tmp_path, monkeypatch, "flowmod_thread", """
        class Srv:
            def run(self):
                while not self._stop:
                    pass
            def stop(self):
                self._stop = True
    """)
    surface = {f"{mod}:Srv": {"reactor": ("run",), "control": ("stop",)}}
    findings = CT.check_thread_contracts({"thread": {}}, surface=surface)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "thread.unlocked-shared-write"
    assert f.primitive == "_stop"
    assert f.file.endswith("flowmod_thread.py") and f.line == 7
    # a declared owner (or lock) clears it
    declared = {"thread": {f"{mod}:Srv": {
        "_stop": {"owner": "control", "reason": "GIL-atomic flag"}}}}
    assert CT.check_thread_contracts(declared, surface=surface) == []


# ---------------------------------------------------------------------------
# seeded violations: contracts family (table hygiene)
# ---------------------------------------------------------------------------

def test_stale_and_undocumented_entries_fire(tmp_path, monkeypatch):
    mod = _tmp_module(tmp_path, monkeypatch, "flowmod_hyg", """
        class Camp:
            def step(self):
                self.cursor = 1
    """)
    surface = {f"{mod}:Camp": []}
    state_a = CT.analyze_state(surface)
    con = {"state": {f"{mod}:Camp": {
        "deleted_attr": {"kind": "transient", "reason": "was real once"},
        "cursor": {"kind": "transient", "reason": ""},
    }}, "transfer": {}, "thread": {}}
    findings = CT.check_contract_hygiene(con, state_a, {}, {})
    by_rule = {(f.rule, f.primitive) for f in findings}
    assert ("contracts.stale-entry", "deleted_attr") in by_rule
    assert ("contracts.undocumented", "cursor") in by_rule


def test_overcounted_transfer_row_is_stale(tmp_path, monkeypatch):
    mod = _tmp_module(tmp_path, monkeypatch, "flowmod_hyg2", """
        def seam(x):
            return x.item()
    """)
    transfer_a = CT.analyze_transfer({"s": f"{mod}:seam"})
    con = {"state": {}, "thread": {}, "transfer": {f"{mod}:seam": [
        {"call": ".item()", "count": 3, "reason": "r"}]}}
    findings = CT.check_contract_hygiene(con, {}, transfer_a, {})
    assert [f.rule for f in findings] == ["contracts.stale-entry"]
    assert findings[0].count == 1 and findings[0].budget == 3


# ---------------------------------------------------------------------------
# the contracts.json ratchet
# ---------------------------------------------------------------------------

def test_contracts_rebaseline_refuses_growth():
    old = {"state": {}, "transfer": {}, "thread": {}}
    needed = {"state": {"m:C": {"x": {"kind": "transient", "reason": ""}}},
              "transfer": {}, "thread": {}}
    with pytest.raises(ValueError, match="GROW.*state:m:C.x"):
        CT.apply_contracts_rebaseline(old, needed)
    merged = CT.apply_contracts_rebaseline(old, needed,
                                           allow_regression=True)
    assert merged["state"]["m:C"]["x"]["reason"] == ""


def test_contracts_rebaseline_carries_reasons_and_shrinks():
    old = {"state": {"m:C": {
        "x": {"kind": "derived", "reason": "documented"},
        "gone": {"kind": "transient", "reason": "dead"}}},
        "transfer": {"m:f": [
            {"call": ".item()", "count": 2, "reason": "harvest"}]},
        "thread": {}}
    needed = {"state": {"m:C": {
        "x": {"kind": "transient", "reason": ""}}},
        "transfer": {"m:f": [
            {"call": ".item()", "count": 1, "reason": ""}]},
        "thread": {}}
    merged = CT.apply_contracts_rebaseline(old, needed)
    # old disposition + reason survive; the stale row drops; the
    # transfer count tightens to the measured value
    assert merged["state"]["m:C"]["x"] == \
        {"kind": "derived", "reason": "documented"}
    assert "gone" not in merged["state"]["m:C"]
    assert merged["transfer"]["m:f"] == [
        {"call": ".item()", "count": 1, "reason": "harvest"}]


def test_checked_in_contracts_fully_documented():
    """Zero undocumented allowlist entries in the shipped tables."""
    con = CT.load_contracts()
    for cls, attrs in con["state"].items():
        for attr, d in attrs.items():
            assert d["kind"] in CT.STATE_KINDS, (cls, attr)
            assert d["reason"].strip(), (cls, attr)
    for site, rows in con["transfer"].items():
        for row in rows:
            assert row["reason"].strip(), (site, row["call"])
    for cls, attrs in con["thread"].items():
        for attr, d in attrs.items():
            assert d.get("owner") or d.get("lock"), (cls, attr)
            assert d["reason"].strip(), (cls, attr)


# ---------------------------------------------------------------------------
# migrated seam rules keep their pins, now with provenance
# ---------------------------------------------------------------------------

def test_migrated_supervise_rule_has_provenance():
    bad = {"chunk": "wtf_tpu.supervise.ladder:DegradationLadder.apply"}
    findings = check_supervised_seams(sites=bad)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "supervise.seam-routing" and "chunk" in f.message
    assert f.file.endswith("ladder.py") and f.line > 0


def test_migrated_telemetry_rule_keeps_primitive_shape():
    bad = {"exports": "wtf_tpu.fleet.telemetry:FleetTelemetry.write_exports"}
    findings = check_telemetry_seams(sites=bad)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "telemetry.seam-serialization"
    assert "json.dumps(" in f.primitive
    assert f.file.endswith("telemetry.py") and f.line > 0


# ---------------------------------------------------------------------------
# findings plumbing: provenance + SARIF
# ---------------------------------------------------------------------------

def test_finding_provenance_optional_in_dict_and_str():
    bare = Finding(rule="r", entry="e", message="m")
    assert "file" not in bare.as_dict() and "(None" not in str(bare)
    located = Finding(rule="r", entry="e", message="m",
                      file="a/b.py", line=7)
    assert located.as_dict()["file"] == "a/b.py"
    assert str(located).endswith("(a/b.py:7)")


def test_sarif_document_shape():
    doc = to_sarif([
        Finding(rule="state.uncheckpointed", entry="e", message="m",
                file="a/b.py", line=7),
        Finding(rule="budget.kernel-count", entry="e", message="m"),
    ])
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "wtf-tpu-lint"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == \
        {"state.uncheckpointed", "budget.kernel-count"}
    with_loc, without_loc = run["results"]
    assert with_loc["locations"][0]["physicalLocation"]["region"][
        "startLine"] == 7
    assert "locations" not in without_loc


# ---------------------------------------------------------------------------
# clean paths on the real tree
# ---------------------------------------------------------------------------

def test_contract_families_clean_ast_only():
    """The cheap (no-census) contract pass must stay clean and fast in
    tier-1: the checked-in tables exactly disposition the live tree."""
    findings, info = run_lint(
        families=["state", "transfer", "thread", "contracts"])
    assert findings == [], [str(f) for f in findings]
    assert "transfer_census" not in info  # census hides behind --deep


@pytest.mark.slow
def test_contract_families_clean_deep():
    """The full --deep pass: AST rules + the jaxpr host-transfer census,
    clean against the pins and inside the 60s wall budget (ISSUE 20)."""
    from wtf_tpu.telemetry import Registry

    registry = Registry()
    findings, info = run_lint(
        families=["state", "transfer", "thread", "contracts"],
        deep=True, registry=registry)
    assert findings == [], [str(f) for f in findings]
    pinned = {k: v for k, v in load_budgets()["host_transfer"].items()
              if k != "entry"}
    assert info["transfer_census"] == pinned
    assert sum(info["seconds"].values()) < 60
    dump = registry.dump()
    assert dump["analysis.transfer_census"]["total"] == pinned["total"]
