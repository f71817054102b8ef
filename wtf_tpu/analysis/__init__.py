"""Graph-invariant linter: static analysis of the hot-path contracts.

The snapshot→execute→restore loop's fast path is defined by graph-shape
invariants (zero u64 in the ported integer core, a pinned count of
data-dependent gather kernels per step, no recompile hazards, donation
aliasing).  This package
traces the real entry points into jaxpr/StableHLO/optimized HLO on the
CPU backend — statically, no chip — and walks them with a rule engine,
so a regression shows up as a named lint failure with provenance.

Entry points:
    python -m wtf_tpu.analysis [--json] [--families ...] [--rebaseline]
    python -m wtf_tpu lint ...          (same flags, telemetry-wired)
    wtf-tpu lint ...                    (installed console script)

Rule families (wtf_tpu/analysis/rules.py): dtype, budget, recompile,
mesh, supervise, telemetry, plus the dataflow contract families
(wtf_tpu/analysis/contracts.py on the shared engine in flow.py): state,
transfer, thread, contracts.  Kernel/collective/transfer budgets live in
wtf_tpu/analysis/budgets.json and the state/transfer/thread allowlists
in wtf_tpu/analysis/contracts.json; re-baseline with `--rebaseline` when
a PR legitimately changes them (PERF.md rounds 9 and 21 document the
procedure — both files are ratchets: growth needs --allow-regression).
`--deep` adds the jaxpr host-transfer census to a transfer-family run
that skips the budget family; `--sarif OUT.json` additionally writes
the findings as SARIF 2.1.0 for review-annotation pipelines.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional

from wtf_tpu.analysis.findings import Finding  # noqa: F401
from wtf_tpu.analysis.rules import (  # noqa: F401
    FAMILIES, apply_rebaseline, check_budget, check_mesh_collectives,
    check_no_u64,
    check_seam_bitcast_only, check_seam_enumeration, check_shard_stability,
    check_signature_stable,
    check_strong_inputs, check_supervised_seams, count_collective_ops,
    count_data_dependent_ops,
    run_dtype_family, run_lint, run_mesh_family,
)


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="wtf_tpu.analysis",
        description="graph-invariant linter (hot-path contracts, CPU-only)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output (one JSON object)")
    p.add_argument("--families", default=None,
                   help=f"comma list from {','.join(FAMILIES)} "
                        "(default: all)")
    p.add_argument("--budgets", default=None,
                   help="alternate budgets.json (default: the checked-in "
                        "wtf_tpu/analysis/budgets.json)")
    p.add_argument("--rebaseline", action="store_true",
                   help="measure the kernel-count budget and REWRITE the "
                        "budget file instead of checking it (record why "
                        "in PERF.md).  Ratcheted: refuses to record a "
                        "total INCREASE without --allow-regression")
    p.add_argument("--allow-regression", action="store_true",
                   help="let --rebaseline record a kernel/collective "
                        "budget increase (a conscious perf giveback — "
                        "name the reason in PERF.md)")
    p.add_argument("--telemetry-dir", default=None,
                   help="write lint findings as events.jsonl records")
    p.add_argument("--deep", action="store_true",
                   help="run the transfer family's jaxpr host-transfer "
                        "census even without the budget family (whose "
                        "window trace it would otherwise reuse)")
    p.add_argument("--sarif", default=None, metavar="OUT.json",
                   help="also write the findings as a SARIF 2.1.0 "
                        "document (file:line provenance mapped to "
                        "physical locations)")
    return p


def lint_main(families=None, budgets=None, rebaseline: bool = False,
              allow_regression: bool = False,
              as_json: bool = False, deep: bool = False,
              sarif: Optional[str] = None,
              registry=None, events=None,
              out=None) -> int:
    """Run the lint and print results; returns the process exit code
    (0 clean, 1 findings).  Shared by `python -m wtf_tpu.analysis` and
    the `wtf-tpu lint` subcommand (which supplies telemetry wiring)."""
    out = out or sys.stdout
    # The lint's contracts are CPU-platform facts (HLO counts, donation
    # policy), so where JAX_PLATFORMS names nothing the default pins the
    # CPU backend.  WTF_LINT_PLATFORM overrides: "native" leaves the
    # ambient jax platform untouched, any other value is passed to jax
    # verbatim (e.g. "tpu").
    import os

    platform = os.environ.get("WTF_LINT_PLATFORM", "cpu")
    if platform != "native" and not os.environ.get("JAX_PLATFORMS"):
        import jax

        jax.config.update("jax_platforms", platform)
    t0 = time.time()
    try:
        findings, info = run_lint(families=families, budgets_path=budgets,
                                  rebaseline=rebaseline,
                                  allow_regression=allow_regression,
                                  deep=deep,
                                  registry=registry, events=events)
    except ValueError as e:
        # operator-facing refusals (the rebaseline ratchet, bad family
        # lists) print as clean one-liners, not tracebacks
        print(f"wtf-tpu lint: {e}", file=out)
        return 1
    wall = round(time.time() - t0, 1)
    if sarif:
        from pathlib import Path

        from wtf_tpu.analysis.findings import to_sarif

        Path(sarif).write_text(
            json.dumps(to_sarif(findings), indent=2) + "\n")
    if as_json:
        print(json.dumps({
            "clean": not findings, "wall_seconds": wall,
            "findings": [f.as_dict() for f in findings], **info,
        }), file=out)
    else:
        for f in findings:
            print(f"FAIL {f}", file=out)
        counts = info.get("kernel_counts")
        if counts:
            print("kernel counts: " + " ".join(
                f"{k}={v}" for k, v in counts.items()), file=out)
        collectives = info.get("collective_counts")
        if collectives:
            print("mesh collectives: " + " ".join(
                f"{k}={v}" for k, v in collectives.items()), file=out)
        census = info.get("transfer_census")
        if census:
            print("transfer census: " + " ".join(
                f"{k}={v}" for k, v in census.items()), file=out)
        if "budgets_written" in info:
            print(f"re-baselined -> {info['budgets_written']}", file=out)
        if "contracts_written" in info:
            print(f"re-baselined -> {info['contracts_written']}",
                  file=out)
        state = ("CLEAN" if not findings
                 else f"{len(findings)} finding(s)")
        print(f"wtf-tpu lint: {state} "
              f"({','.join(info['families'])}; {wall}s)", file=out)
    return 0 if not findings else 1


def main(argv: Optional[List[str]] = None) -> int:
    args = _build_parser().parse_args(argv)
    families = args.families.split(",") if args.families else None
    from wtf_tpu.telemetry import Registry, open_event_log

    registry = Registry()
    events = open_event_log(args.telemetry_dir)
    events.emit("run-start", subcommand="lint",
                argv=list(argv) if argv is not None else sys.argv[1:])
    try:
        return lint_main(families=families, budgets=args.budgets,
                         rebaseline=args.rebaseline,
                         allow_regression=args.allow_regression,
                         as_json=args.json, deep=args.deep,
                         sarif=args.sarif,
                         registry=registry, events=events)
    finally:
        events.emit("run-end", metrics=registry.dump())
        events.close()
