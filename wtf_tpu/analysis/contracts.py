"""Whole-program contract families: state, transfer, thread, contracts.

Three dynamic guarantees of the stack are pinned statically here, on the
shared dataflow engine (wtf_tpu/analysis/flow.py):

  * **state** — bit-identical checkpoint/resume (PR 8/13) requires that
    every mutable attribute on the campaign objects is either carried by
    the checkpoint field sets or consciously declared derived/transient.
    The family enumerates `self.X = ...` writes outside `__init__` per
    class, subtracts what the checkpoint/restore/recovery-snapshot
    extractors touch, and demands a disposition in `contracts.json` for
    the rest (`state.uncheckpointed`).
  * **transfer** — the zero-host steady state (PR 14/19) requires that
    no dispatch seam grows a hidden device→host sync.  AST rule: every
    `.item()` / `float()` / `bool()` / `np.asarray()` /
    `jax.device_get()` call inside a supervise.SEAM_SITES function must
    match an allowlist row (`transfer.hidden-sync`), and the jaxpr-level
    host-callback census of the steady-state programs is pinned in
    budgets.json (`transfer.census-drift`).
  * **thread** — the watchdog/prelaunch/reactor/reconnect paths run on
    real host threads.  Attributes shared across declared thread roots
    (written by one root, written or read by another) must appear in an
    ownership/lock table (`thread.unlocked-shared-write`).
  * **contracts** — the tables themselves are audited: entries naming
    deleted attributes or unmatched allowlist rows are
    `contracts.stale-entry`, entries without a reason are
    `contracts.undocumented` — an allowlist you can't grow silently and
    can't let rot.

`contracts.json` is a RATCHET with budgets.json semantics (PR 12):
`--rebaseline` regenerates the tables but REFUSES to add entries unless
`--allow-regression` is passed, and new entries land with an empty
reason — which the contracts family flags until a human documents them.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from wtf_tpu.analysis import flow
from wtf_tpu.analysis.findings import Finding

CONTRACTS_PATH = Path(__file__).with_name("contracts.json")

# contracts.json section names / valid state dispositions
SECTIONS = ("state", "transfer", "thread")
STATE_KINDS = ("derived", "transient", "config", "rebind")

# ---------------------------------------------------------------------------
# the analyzed surfaces
# ---------------------------------------------------------------------------

_CKPT = "wtf_tpu.resume.checkpoint"
_SUP = "wtf_tpu.supervise.supervisor"

# class site -> the checkpoint/restore/recovery extractors whose
# attribute accesses (through the named parameter) count as coverage.
# checkpoint_state READS what it saves; restore_state WRITES what it
# reinstates; the recovery snapshot (Supervisor.pre_batch/recover) and
# the campaign checkpoint (_campaign_state/restore_campaign) reach into
# the loop/backend from outside — both directions count.
STATE_SURFACE: Dict[str, List[Tuple[str, str, str]]] = {
    "wtf_tpu.interp.runner:Runner": [
        ("wtf_tpu.interp.runner", "Runner.checkpoint_state", "self"),
        ("wtf_tpu.interp.runner", "Runner.restore_state", "self"),
    ],
    "wtf_tpu.meshrun.runner:MeshRunner": [
        ("wtf_tpu.interp.runner", "Runner.checkpoint_state", "self"),
        ("wtf_tpu.interp.runner", "Runner.restore_state", "self"),
    ],
    "wtf_tpu.fuzz.loop:FuzzLoop": [
        (_CKPT, "_campaign_state", "loop"),
        (_CKPT, "restore_campaign", "loop"),
        (_SUP, "Supervisor.pre_batch", "loop"),
        (_SUP, "Supervisor.recover", "loop"),
    ],
    "wtf_tpu.fuzz.mutator:ByteMutator": [
        ("wtf_tpu.fuzz.mutator", "Mutator.checkpoint_state", "self"),
        ("wtf_tpu.fuzz.mutator", "Mutator.restore_state", "self"),
    ],
    "wtf_tpu.fuzz.mutator:MangleMutator": [
        ("wtf_tpu.fuzz.mutator", "Mutator.checkpoint_state", "self"),
        ("wtf_tpu.fuzz.mutator", "Mutator.restore_state", "self"),
    ],
    "wtf_tpu.fuzz.mutator:TlvStructureMutator": [
        ("wtf_tpu.fuzz.mutator", "Mutator.checkpoint_state", "self"),
        ("wtf_tpu.fuzz.mutator", "Mutator.restore_state", "self"),
    ],
    "wtf_tpu.devmut.mutator:DevMangleMutator": [
        ("wtf_tpu.devmut.mutator",
         "DevMangleMutator.checkpoint_state", "self"),
        ("wtf_tpu.devmut.mutator",
         "DevMangleMutator.restore_state", "self"),
    ],
    "wtf_tpu.devmut.corpus:DeviceCorpus": [
        ("wtf_tpu.devmut.corpus", "DeviceCorpus.checkpoint_state", "self"),
        ("wtf_tpu.devmut.corpus", "DeviceCorpus.uploaded_state", "self"),
        ("wtf_tpu.devmut.corpus", "DeviceCorpus.restore", "self"),
    ],
    "wtf_tpu.backend.tpu:TpuBackend": [
        ("wtf_tpu.backend.tpu", "TpuBackend.coverage_state", "self"),
        ("wtf_tpu.backend.tpu",
         "TpuBackend.restore_coverage_state", "self"),
    ],
    "wtf_tpu.meshrun.backend:MeshBackend": [
        ("wtf_tpu.backend.tpu", "TpuBackend.coverage_state", "self"),
        ("wtf_tpu.backend.tpu",
         "TpuBackend.restore_coverage_state", "self"),
        ("wtf_tpu.meshrun.backend",
         "MeshBackend.restore_coverage_state", "self"),
    ],
    f"{_SUP}:Supervisor": [
        (_SUP, "Supervisor.pre_batch", "self"),
        (_SUP, "Supervisor.recover", "self"),
    ],
    # the PR-18 node-telemetry mixin checkpoints NOTHING by design —
    # every mutable attribute needs an explicit disposition
    "wtf_tpu.dist.client:_NodeTelemetry": [],
}

# class site -> thread roots: each root is one real host-thread entry
# point (the function a thread starts in, or the surface another thread
# calls into), closed over self.method() calls but never into another
# root's entry functions.
THREAD_SURFACE: Dict[str, Dict[str, Sequence[str]]] = {
    # dispatcher thread vs the bounded-wait watchdog waiter thread
    f"{_SUP}:Supervisor": {
        "dispatcher": ("dispatch",),
        "watchdog": ("_bounded_wait.waiter",),
    },
    # single-threaded selector reactor vs the drain surface, which the
    # SIGTERM handler or any embedding thread may call
    "wtf_tpu.dist.server:Server": {
        "reactor": ("run",),
        "control": ("request_drain",),
    },
    # a soak worker thread owns its links; the reconnect path re-enters
    # the socket state from inside the serve loop
    "wtf_tpu.dist.client:MasterLink": {
        "serve": ("connect", "recv_work", "send", "send_delta",
                  "send_telem", "close"),
        "reconnect": ("_reconnect",),
    },
    # megachunk window driver vs the pipelined-harvest prelaunch seam
    "wtf_tpu.backend.tpu:TpuBackend": {
        "window": ("run_megachunk",),
        "prelaunch": ("_dispatch_window",),
    },
}

# transfer census subjects: steady-state programs whose jaxpr-level
# host-callback count is pinned in budgets.json under `host_transfer`
TRANSFER_ENTRY = "host_transfer"
TRANSFER_CENSUS_ENTRY = ("jaxpr host-transfer census (callback/infeed/"
                         "outfeed/device_put) over steady-state programs"
                         " / demo_tlv / n_lanes=4")
TRANSFER_PROGRAMS = ("megachunk_window", "devmut_generate",
                     "device_insert", "decode_service")


# ---------------------------------------------------------------------------
# contracts.json I/O + ratchet
# ---------------------------------------------------------------------------

def load_contracts(path: Optional[Path] = None) -> Dict:
    p = Path(path) if path else CONTRACTS_PATH
    if not p.exists():
        return {s: {} for s in SECTIONS}
    doc = json.loads(p.read_text())
    for s in SECTIONS:
        doc.setdefault(s, {})
    return doc


def save_contracts(contracts: Dict, path: Optional[Path] = None) -> Path:
    p = Path(path) if path else CONTRACTS_PATH
    p.write_text(json.dumps(contracts, indent=2, sort_keys=True) + "\n")
    return p


def _entry_keys(contracts: Dict) -> set:
    """Flat (section, owner, entry) key set — the ratchet's unit of
    growth.  Transfer rows key on their call kind."""
    keys = set()
    for cls, attrs in contracts.get("state", {}).items():
        for attr in attrs:
            keys.add(("state", cls, attr))
    for site, rows in contracts.get("transfer", {}).items():
        for row in rows:
            keys.add(("transfer", site, row.get("call")))
    for cls, attrs in contracts.get("thread", {}).items():
        for attr in attrs:
            keys.add(("thread", cls, attr))
    return keys


def apply_contracts_rebaseline(contracts: Dict, needed: Dict,
                               allow_regression: bool = False) -> Dict:
    """Merge regenerated contract tables over the checked-in ones — a
    RATCHET: entries that are no longer needed drop silently (every
    drop is a contract getting stronger), but a NEW entry is allowlist
    growth — a new undispositioned attribute, hidden coercion, or
    shared write — and is refused unless `allow_regression` names the
    act.  Existing reasons/dispositions are carried over; genuinely new
    entries land with whatever skeleton `needed` carries (empty reasons,
    which the contracts family keeps flagging until documented)."""
    grown = sorted(_entry_keys(needed) - _entry_keys(contracts))
    if grown and not allow_regression:
        what = ", ".join(f"{s}:{owner}.{entry}"
                         for s, owner, entry in grown[:6])
        more = f" (+{len(grown) - 6} more)" if len(grown) > 6 else ""
        raise ValueError(
            f"rebaseline would GROW the contract allowlist ({what}{more})"
            " — each new entry is a new undispositioned mutable "
            "attribute, hidden host coercion, or unlocked shared write; "
            "fix the code or document the disposition and re-run with "
            "--allow-regression")
    merged: Dict = {s: {} for s in SECTIONS}
    for cls, attrs in needed.get("state", {}).items():
        old = contracts.get("state", {}).get(cls, {})
        merged["state"][cls] = {
            attr: old.get(attr, skel) for attr, skel in attrs.items()}
    for site, rows in needed.get("transfer", {}).items():
        old_rows = {r.get("call"): r
                    for r in contracts.get("transfer", {}).get(site, [])}
        out = []
        for row in rows:
            kept = dict(old_rows.get(row["call"], row))
            kept["call"] = row["call"]
            kept["count"] = row["count"]
            out.append(kept)
        merged["transfer"][site] = out
    for cls, attrs in needed.get("thread", {}).items():
        old = contracts.get("thread", {}).get(cls, {})
        merged["thread"][cls] = {
            attr: old.get(attr, skel) for attr, skel in attrs.items()}
    return merged


# ---------------------------------------------------------------------------
# tree analysis (pure AST — shared by all four families)
# ---------------------------------------------------------------------------

def _split_site(site: str) -> Tuple[str, str]:
    mod, _, cls = site.partition(":")
    return mod, cls


def analyze_state(surface: Optional[Dict] = None) -> Dict[str, Dict]:
    """Per class: the full write surface, the mutable subset (written
    outside __init__) with first-write provenance, and the covered set
    the extractors reach."""
    surface = STATE_SURFACE if surface is None else surface
    out: Dict[str, Dict] = {}
    for cls_site, extractors in surface.items():
        mod, cls = _split_site(cls_site)
        writes = flow.class_attribute_writes(mod, cls)
        mutable: Dict[str, Tuple[str, int]] = {}
        for attr, sites in writes.items():
            outside = [(m, ln) for m, ln in sites
                       if m not in ("__init__", "__post_init__")]
            if outside:
                mutable[attr] = min(outside, key=lambda s: s[1])
        covered = set()
        for ex_mod, ex_qual, ex_param in extractors:
            info = flow.function_index(ex_mod).get(ex_qual)
            if info is None:
                raise KeyError(
                    f"state extractor {ex_mod}:{ex_qual} not found "
                    f"(STATE_SURFACE for {cls_site})")
            covered |= flow.function_param_accesses(info, ex_param)
        out[cls_site] = {"writes": writes, "mutable": mutable,
                         "covered": covered,
                         "file": flow.module_file(mod)}
    return out


def analyze_transfer(sites: Optional[Dict[str, str]] = None) -> Dict:
    """Per seam site: measured coercion calls {kind: [lineno…]} plus
    file provenance.  Unresolvable sites are skipped — the supervise
    family owns that finding."""
    if sites is None:
        from wtf_tpu.supervise import SEAM_SITES

        sites = SEAM_SITES
    out: Dict[str, Dict] = {}
    for site in sorted(set(sites.values())):
        try:
            info = flow.resolve_site(site)
        except Exception:
            continue
        calls: Dict[str, List[int]] = {}
        for kind, lineno in flow.coercion_calls(info.node):
            calls.setdefault(kind, []).append(lineno)
        out[site] = {"calls": calls, "file": info.file,
                     "lineno": info.lineno}
    return out


def analyze_thread(surface: Optional[Dict] = None) -> Dict[str, Dict]:
    """Per class: per-root access sets plus the shared-attribute set
    (written by one root, written or read by another)."""
    surface = THREAD_SURFACE if surface is None else surface
    out: Dict[str, Dict] = {}
    for cls_site, roots in surface.items():
        mod, cls = _split_site(cls_site)
        accesses = flow.thread_root_accesses(
            mod, cls, {r: list(q) for r, q in roots.items()})
        shared: Dict[str, Dict] = {}
        for root, acc in accesses.items():
            for attr, lines in acc["writes"].items():
                for other, oacc in accesses.items():
                    if other == root:
                        continue
                    if (attr in oacc["writes"]
                            or attr in oacc["reads"]):
                        entry = shared.setdefault(
                            attr, {"writers": {}, "line": min(lines)})
                        entry["writers"][root] = min(lines)
        out[cls_site] = {"accesses": accesses, "shared": shared,
                         "file": flow.module_file(mod)}
    return out


# ---------------------------------------------------------------------------
# the state family
# ---------------------------------------------------------------------------

def check_state_contracts(contracts: Optional[Dict] = None,
                          surface: Optional[Dict] = None,
                          analysis: Optional[Dict] = None
                          ) -> List[Finding]:
    """`state.uncheckpointed`: a mutable attribute with neither
    checkpoint coverage nor a declared disposition."""
    contracts = load_contracts() if contracts is None else contracts
    analysis = analyze_state(surface) if analysis is None else analysis
    table = contracts.get("state", {})
    findings: List[Finding] = []
    for cls_site in sorted(analysis):
        a = analysis[cls_site]
        declared = table.get(cls_site, {})
        for attr in sorted(a["mutable"]):
            if attr in a["covered"]:
                continue
            disp = declared.get(attr)
            if disp and disp.get("kind") in STATE_KINDS:
                continue
            method, lineno = a["mutable"][attr]
            findings.append(Finding(
                rule="state.uncheckpointed", entry=cls_site,
                primitive=attr, file=a["file"], line=lineno,
                message=(f"mutable attribute `{attr}` (written in "
                         f"{method}) is neither carried by the "
                         "checkpoint/restore/recovery field sets nor "
                         "declared derived/transient in contracts.json "
                         "— a resumed campaign would silently diverge; "
                         "checkpoint it or document the disposition")))
    return findings


# ---------------------------------------------------------------------------
# the transfer family
# ---------------------------------------------------------------------------

def check_transfer_seams(contracts: Optional[Dict] = None,
                         sites: Optional[Dict[str, str]] = None,
                         analysis: Optional[Dict] = None
                         ) -> List[Finding]:
    """`transfer.hidden-sync`: a host-coercion call inside a dispatch
    seam beyond what the harvest/readback allowlist declares."""
    contracts = load_contracts() if contracts is None else contracts
    analysis = analyze_transfer(sites) if analysis is None else analysis
    table = contracts.get("transfer", {})
    findings: List[Finding] = []
    for site in sorted(analysis):
        a = analysis[site]
        allowed = {row.get("call"): int(row.get("count", 0))
                   for row in table.get(site, [])}
        for kind in sorted(a["calls"]):
            lines = a["calls"][kind]
            if len(lines) <= allowed.get(kind, 0):
                continue
            over = sorted(lines)[allowed.get(kind, 0):]
            findings.append(Finding(
                rule="transfer.hidden-sync", entry=site, primitive=kind,
                count=len(lines), budget=allowed.get(kind, 0),
                file=a["file"], line=over[0],
                message=(f"{kind} coercion inside a dispatch seam "
                         "beyond the harvest/readback allowlist — a "
                         "hidden device->host sync here re-serializes "
                         "the zero-host steady state; batch the "
                         "readback through the documented harvest or "
                         "allowlist it with a reason")))
    return findings


def count_host_transfers(jaxpr) -> int:
    """Host-callback-class primitives in a jaxpr (sub-jaxprs
    included): pure/io/debug callbacks, infeed/outfeed, and
    explicit device_put — everything that moves data across the
    host/device boundary inside a steady-state program."""
    from wtf_tpu.analysis.rules import _iter_eqns

    jxp = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    n = 0
    for eqn in _iter_eqns(jxp):
        name = eqn.primitive.name
        if ("callback" in name or name in ("infeed", "outfeed")
                or name == "device_put"):
            n += 1
    return n


def measure_transfer_census(runner=None, mega_jaxpr=None) -> Dict[str, int]:
    """The device->host transfer census of the steady-state programs.
    `mega_jaxpr` reuses the budget family's window trace when both
    families run; `runner` reuses its demo_tlv runner."""
    import jax
    import jax.numpy as jnp

    from wtf_tpu.analysis import trace
    from wtf_tpu.analysis.rules import DECODE_BP_SLOTS, MEGA_CONFIG

    counts: Dict[str, int] = {}

    if mega_jaxpr is None:
        cfg = MEGA_CONFIG
        lowered, args, fn = trace.megachunk_window_lowering(
            max_batches=cfg["max_batches"], n_lanes=cfg["n_lanes"],
            donate=True, limit=cfg["limit"])
        mega_jaxpr = jax.make_jaxpr(fn)(*args)
    counts["megachunk_window"] = count_host_transfers(mega_jaxpr)

    from wtf_tpu.devmut import engine as DM

    dm_data = jnp.zeros((4, 8), jnp.uint32)
    dm_lens = jnp.ones((4,), jnp.int32)
    dm_cumw = jnp.asarray([1, 2, 3, 4], jnp.uint32)
    dm_seeds = jnp.zeros((2, 2), jnp.uint32)
    gen_jaxpr = jax.make_jaxpr(
        lambda d, ln, c, s: DM.generate(d, ln, c, s, rounds=1))(
        dm_data, dm_lens, dm_cumw, dm_seeds)
    counts["devmut_generate"] = count_host_transfers(gen_jaxpr)

    if runner is None:
        runner = trace.build_tlv_runner(n_lanes=4, chunk_steps=16)

    from wtf_tpu.interp.runner import _make_device_insert

    n_pages, width = 2, 8
    ins = _make_device_insert(n_pages, width, 7, 6, False, masked=False)
    ins_jaxpr = jax.make_jaxpr(ins)(
        runner.machine,
        jnp.zeros((runner.n_lanes, width), jnp.uint32),
        jnp.ones((runner.n_lanes,), jnp.int32),
        jnp.zeros((n_pages,), jnp.int32),
        jnp.zeros((2,), jnp.uint32))
    counts["device_insert"] = count_host_transfers(ins_jaxpr)

    from wtf_tpu.interp import devdec
    from wtf_tpu.mem.physmem import lane_image

    capacity = runner.cache.capacity

    def service(tab, image, machine, count, bp_keys, n_bp):
        blocks = devdec.compute_blocks(tab, image, machine, bp_keys, n_bp)
        return devdec.commit_blocks(tab, count, blocks, machine.status,
                                    capacity)

    dec_jaxpr = jax.make_jaxpr(service)(
        runner.cache.device(),
        lane_image(runner.physmem.image, runner.n_lanes),
        runner.machine, jnp.int32(0),
        jnp.zeros(DECODE_BP_SLOTS, jnp.uint64), jnp.int32(0))
    counts["decode_service"] = count_host_transfers(dec_jaxpr)

    counts["total"] = sum(counts.values())
    return counts


def check_transfer_census(measured: Dict[str, int],
                          budget: Dict,
                          budgets_file: str = "budgets.json"
                          ) -> List[Finding]:
    """`transfer.census-drift`: a steady-state program's host-callback
    count exceeds the pin.  A decrease is re-pinned with
    --rebaseline; lint only fails on growth."""
    findings: List[Finding] = []
    for prog in list(TRANSFER_PROGRAMS) + ["total"]:
        if prog not in measured:
            continue
        pinned = budget.get(prog)
        if pinned is None or measured[prog] <= int(pinned):
            continue
        findings.append(Finding(
            rule="transfer.census-drift", entry=TRANSFER_ENTRY,
            primitive=prog, count=measured[prog], budget=int(pinned),
            file=budgets_file, line=1,
            message=("host-callback/transfer ops appeared in a "
                     "steady-state program's jaxpr — the zero-host "
                     "loop now syncs per window; remove the callback "
                     "or re-baseline with the regression documented")))
    return findings


# ---------------------------------------------------------------------------
# the thread family
# ---------------------------------------------------------------------------

def check_thread_contracts(contracts: Optional[Dict] = None,
                           surface: Optional[Dict] = None,
                           analysis: Optional[Dict] = None
                           ) -> List[Finding]:
    """`thread.unlocked-shared-write`: an attribute written by one
    thread root and touched by another with no declared owner/lock."""
    contracts = load_contracts() if contracts is None else contracts
    analysis = analyze_thread(surface) if analysis is None else analysis
    table = contracts.get("thread", {})
    findings: List[Finding] = []
    for cls_site in sorted(analysis):
        a = analysis[cls_site]
        declared = table.get(cls_site, {})
        for attr in sorted(a["shared"]):
            entry = declared.get(attr)
            if entry and (entry.get("owner") or entry.get("lock")):
                continue
            writers = a["shared"][attr]["writers"]
            findings.append(Finding(
                rule="thread.unlocked-shared-write", entry=cls_site,
                primitive=attr, file=a["file"],
                line=a["shared"][attr]["line"],
                message=(f"`{attr}` is written from thread root(s) "
                         f"{sorted(writers)} and touched from another "
                         "root with no declared ownership/lock in "
                         "contracts.json — an unlocked cross-thread "
                         "write; serialize it or declare the owner "
                         "and discipline")))
    return findings


# ---------------------------------------------------------------------------
# the contracts family (table hygiene)
# ---------------------------------------------------------------------------

def check_contract_hygiene(contracts: Optional[Dict] = None,
                           state_analysis: Optional[Dict] = None,
                           transfer_analysis: Optional[Dict] = None,
                           thread_analysis: Optional[Dict] = None
                           ) -> List[Finding]:
    """The tables themselves under lint: `contracts.stale-entry` for
    rows naming deleted attributes/calls, `contracts.undocumented` for
    rows without a reason, `contracts.unknown-kind` for dispositions
    outside the vocabulary."""
    contracts = load_contracts() if contracts is None else contracts
    state_analysis = (analyze_state() if state_analysis is None
                      else state_analysis)
    transfer_analysis = (analyze_transfer() if transfer_analysis is None
                         else transfer_analysis)
    thread_analysis = (analyze_thread() if thread_analysis is None
                       else thread_analysis)
    findings: List[Finding] = []

    for cls_site in sorted(contracts.get("state", {})):
        entries = contracts["state"][cls_site]
        known = state_analysis.get(cls_site)
        for attr in sorted(entries):
            disp = entries[attr] or {}
            if known is None or attr not in known["writes"]:
                findings.append(Finding(
                    rule="contracts.stale-entry", entry=cls_site,
                    primitive=attr,
                    message=("contracts.json state entry names an "
                             "attribute no longer assigned on the class"
                             " — delete the row (stale allowlist rows "
                             "hide future regressions under a familiar "
                             "name)")))
                continue
            if disp.get("kind") not in STATE_KINDS:
                findings.append(Finding(
                    rule="contracts.unknown-kind", entry=cls_site,
                    primitive=attr,
                    message=(f"state disposition kind "
                             f"{disp.get('kind')!r} is not one of "
                             f"{list(STATE_KINDS)}")))
            if not str(disp.get("reason") or "").strip():
                findings.append(Finding(
                    rule="contracts.undocumented", entry=cls_site,
                    primitive=attr,
                    message=("state disposition has no reason — every "
                             "allowlist row must say WHY the attribute "
                             "may skip the checkpoint")))

    for site in sorted(contracts.get("transfer", {})):
        rows = contracts["transfer"][site]
        measured = transfer_analysis.get(site, {}).get("calls", {})
        for row in rows:
            kind = row.get("call")
            n = len(measured.get(kind, []))
            if site not in transfer_analysis or n == 0:
                findings.append(Finding(
                    rule="contracts.stale-entry", entry=site,
                    primitive=kind,
                    message=("transfer allowlist row matches no call in "
                             "the seam anymore — delete it")))
            elif n < int(row.get("count", 0)):
                findings.append(Finding(
                    rule="contracts.stale-entry", entry=site,
                    primitive=kind, count=n,
                    budget=int(row.get("count", 0)),
                    message=("transfer allowlist row allows more "
                             f"{kind} calls than the seam contains — "
                             "tighten the count (the ratchet only "
                             "tightens itself on --rebaseline)")))
            if not str(row.get("reason") or "").strip():
                findings.append(Finding(
                    rule="contracts.undocumented", entry=site,
                    primitive=kind,
                    message=("transfer allowlist row has no reason — "
                             "every allowed coercion must name its "
                             "harvest/readback purpose")))

    for cls_site in sorted(contracts.get("thread", {})):
        entries = contracts["thread"][cls_site]
        known = thread_analysis.get(cls_site)
        for attr in sorted(entries):
            row = entries[attr] or {}
            touched = known is not None and any(
                attr in acc["writes"] or attr in acc["reads"]
                for acc in known["accesses"].values())
            if not touched:
                findings.append(Finding(
                    rule="contracts.stale-entry", entry=cls_site,
                    primitive=attr,
                    message=("thread ownership row names an attribute "
                             "no thread root touches anymore — delete "
                             "it")))
                continue
            roots = set(known["accesses"]) | {"any"}
            if row.get("owner") not in roots:
                findings.append(Finding(
                    rule="contracts.unknown-kind", entry=cls_site,
                    primitive=attr,
                    message=(f"thread owner {row.get('owner')!r} is not "
                             f"a declared root of the class "
                             f"({sorted(set(known['accesses']))}) or "
                             "'any'")))
            if not str(row.get("reason") or "").strip():
                findings.append(Finding(
                    rule="contracts.undocumented", entry=cls_site,
                    primitive=attr,
                    message=("thread ownership row has no reason — "
                             "declare the lock/discipline that makes "
                             "the sharing safe")))
    return findings


# ---------------------------------------------------------------------------
# rebaseline skeleton generation
# ---------------------------------------------------------------------------

def needed_contracts(state_analysis: Optional[Dict] = None,
                     transfer_analysis: Optional[Dict] = None,
                     thread_analysis: Optional[Dict] = None) -> Dict:
    """The minimal tables the current tree requires — what
    `--rebaseline` merges (under the growth ratchet) over the
    checked-in file.  New entries carry empty reasons on purpose."""
    state_analysis = (analyze_state() if state_analysis is None
                      else state_analysis)
    transfer_analysis = (analyze_transfer() if transfer_analysis is None
                         else transfer_analysis)
    thread_analysis = (analyze_thread() if thread_analysis is None
                       else thread_analysis)
    needed: Dict = {"state": {}, "transfer": {}, "thread": {}}
    for cls_site, a in state_analysis.items():
        attrs = {attr: {"kind": "transient", "reason": ""}
                 for attr in sorted(a["mutable"])
                 if attr not in a["covered"]}
        if attrs:
            needed["state"][cls_site] = attrs
    for site, a in transfer_analysis.items():
        rows = [{"call": kind, "count": len(lines), "reason": ""}
                for kind, lines in sorted(a["calls"].items())]
        if rows:
            needed["transfer"][site] = rows
    for cls_site, a in thread_analysis.items():
        attrs = {attr: {"owner": "", "reason": ""}
                 for attr in sorted(a["shared"])}
        if attrs:
            needed["thread"][cls_site] = attrs
    return needed
