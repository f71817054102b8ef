"""The megachunk: a whole multi-batch fuzz window as ONE compiled program.

The batch-at-a-time device loop (fuzz/loop.py `_run_one_batch_device`)
still consults the host between every phase of every batch: devmut
generation, the fused insert, the chunk ladder, the coverage merge, and
the overlay restore are five separate dispatches with host glue between
them.  This module folds them into one compiled multi-batch program — the
Concordia posture ROADMAP item 2(b) names: per batch, IN-GRAPH,

    restore -> devmut generate -> device insert -> run to quiescence ->
    finish-breakpoint rewrite -> prefix-credit coverage merge

iterated under a `lax.while_loop` for up to `n_batches` batches, so the
host's per-batch share collapses to the status pull and the harvest of
crash/new-coverage lanes.  The window returns early exactly when the host
is genuinely needed:

  * a batch ends with a SERVICEABLE lane (decode miss, SMC, oracle
    fallback, a non-finish breakpoint, deliverable fault): the machine
    comes back mid-batch and the ordinary Runner.run servicing loop
    finishes that batch — the cold-start path, byte-identical to the
    batch-at-a-time loop's servicing because it IS that loop;
  * a batch finds NEW COVERAGE: the window runs at most ONE more batch
    and stops, so the host can fold the finds into the corpus slab
    before any batch that is entitled to see them is generated;
  * a batch has a NON-CLEAN terminal (crash/fault/overlay-full): the
    window stops right there, so the machine the host reads for crash
    naming and stack-hash bucketing is exactly that batch's final state.

Slab schedule (the PR-6 prelaunch lag, preserved exactly): batch k's
generation samples the slab with finds from batches <= k-2.  The window
therefore takes TWO slab views — `slab_first` for its first batch,
`slab_rest` (the current host slab) for the batches after — and the
find-stop rule above guarantees no batch inside a window ever needs a
slab newer than `slab_rest`.  `slab_first` is the view the harvest
PINNED just before the previous window's FINAL batch's corpus adds
(DevMangleMutator.snapshot_entitled_slab): the next window's first
batch is absolute batch m+1 where m was that final batch, so its
entitlement is finds <= m-1 — exactly the pre-m's-adds state, and
exactly when the legacy prelaunch would have sampled it.  With
`n_batches=1` the program IS the batch-at-a-time device loop's
schedule, which is what the parity tests pin (tests/test_megachunk.py:
12-batch campaigns with finds in IN-GRAPH batches, B=4 vs B=1 vs the
legacy loop, byte-identical).

The finish-breakpoint rewrite is the declarative form of the stop
handler every wtf-style target plants at its return address
(`b.stop(Ok())`): a lane parked at BREAKPOINT with rip ==
`DeviceInsertSpec.finish_gva` becomes OK in-graph, bit-for-bit what the
host handler would have done (the breakpointed instruction never
executes, no coverage bit, no icount).  Targets with richer handlers
simply park the batch to the host path — correct, just not fused.

The mesh variant wraps the SAME body in shard_map: machine/template/
seeds lane-sharded, slabs and the uop table replicated, the per-batch
merge the shard-aware prefix-credit core (meshrun/reduce
.mesh_merge_local), and the loop-control scalars (stop/find/incomplete)
all-reduced so every shard's while_loop stays in lockstep.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from wtf_tpu.core.results import StatusCode
from wtf_tpu.interp.machine import Machine, N_CTRS, machine_restore_impl
from wtf_tpu.interp.runner import device_insert_impl
from wtf_tpu.interp.step import step_lanes
from wtf_tpu.interp.uoptable import UopTable
from wtf_tpu.mem.physmem import MemImage, lane_image
from wtf_tpu.meshrun.reduce import merge_coverage, mesh_merge_local

_RUNNING = int(StatusCode.RUNNING)
_OK = int(StatusCode.OK)
_TIMEDOUT = int(StatusCode.TIMEDOUT)
_CR3 = int(StatusCode.CR3_CHANGE)
_OVF = int(StatusCode.OVERLAY_FULL)
_BP = int(StatusCode.BREAKPOINT)

# statuses the host servicing loop owns; PAGE_FAULT/DIVIDE_ERROR join
# when the campaign delivers guest exceptions (runner.deliver_exceptions)
SERVICEABLE_BASE = (int(StatusCode.NEED_DECODE), int(StatusCode.SMC),
                    int(StatusCode.UNSUPPORTED), int(StatusCode.BREAKPOINT))
SERVICEABLE_DELIVER = SERVICEABLE_BASE + (int(StatusCode.PAGE_FAULT),
                                          int(StatusCode.DIVIDE_ERROR))

# rip sentinel for "no declarative finish breakpoint": unaligned-odd and
# non-canonical-adjacent, unreachable as an armed-breakpoint rip
NO_FINISH = 1

_MEGA_CACHE: dict = {}


class MegaSnap(NamedTuple):
    """Per-batch harvest snapshot carried for the last two processed
    batches: the generated testcase words/lens, so crash/new-coverage
    lanes' bytes are fetchable without regenerating the batch.  Crash
    DETAIL never needs snapshotting — a non-clean terminal stops the
    window, so the live machine IS that batch's final state."""

    words: jax.Array       # uint32[L, W]
    lens: jax.Array        # int32[L]


class MegaOut(NamedTuple):
    machine: Machine
    agg_cov: jax.Array
    agg_edge: jax.Array
    batches: jax.Array       # int32: COMPLETED batches this window
    incomplete: jax.Array    # bool: machine is mid-batch `batches`
    statuses: jax.Array      # int32[B, L]; -1 = batch not completed
    new_flags: jax.Array     # bool[B, L] per-batch new-coverage credit
    ctr_sums: jax.Array      # uint64[B, N_CTRS] per-batch counter totals
    new_words: jax.Array     # uint32[cov_w] last completed batch's delta
    prev: MegaSnap
    cur: MegaSnap
    # --device-decode outputs (== inputs when the window was built
    # without devdec): the post-window table with device-published rows,
    # its live entry count (-1 when devdec off), and i32[4] stats
    # (serviced lanes, published entries, parked lanes, service rounds)
    tab: UopTable
    count: jax.Array
    dd_stats: jax.Array


def _snap(words, lens) -> MegaSnap:
    return MegaSnap(words=words, lens=lens)


def _make_body(max_batches: int, n_pages: int, len_gpr: int, ptr_gpr: int,
               rounds: int, deliver: bool, merge_fn, any_fn, sum_fn,
               devdec_on: bool = False, gather_fn=None,
               lane_base_fn=None):
    """The window body shared by the single-device and mesh programs.
    `merge_fn` is the batch coverage merge, `any_fn` a (possibly
    cross-shard) boolean any, `sum_fn` a (possibly psum'd) per-batch
    counter total (its CTR_SWEPT column is the window's sweep count).

    With `devdec_on` the window grows three operands — the live decode
    cache count, the padded pending-breakpoint key vector, and its live
    length — and decode misses are serviced IN-GRAPH (interp/devdec):
    each quiesce that leaves NEED_DECODE lanes runs a service round
    (per-lane block decode + walk, then a sequential global commit that
    replays the host service's publish order exactly), re-quiescing
    until every miss is serviced or parked.  Parked lanes stay
    NEED_DECODE, so the ordinary early-return -> host service path picks
    them up — bit-identical tables either way.  On a mesh, `gather_fn`
    all-gathers the per-shard blocks so every shard runs the SAME
    replicated commit (slot reservation is shard-correct by
    construction: one deterministic global order, no per-shard
    partitioning to reconcile), and `lane_base_fn` locates the shard's
    lane span in the committed global arrays."""
    from wtf_tpu.devmut.engine import generate
    from wtf_tpu.interp import devdec as DD
    from wtf_tpu.interp.machine import CTR_MEM_FAULT

    insert = device_insert_impl(n_pages, len_gpr, ptr_gpr)
    serviceable = SERVICEABLE_DELIVER if deliver else SERVICEABLE_BASE
    _ND = int(StatusCode.NEED_DECODE)
    B = max_batches
    @jax.named_scope("run-quiesce")
    def run_quiesce(tab, image, m, limit):
        """The run-chunk ladder folded in: step until NO lane is
        RUNNING (decode misses, breakpoints and terminals all leave
        RUNNING, and a nonzero instruction budget bounds the rest —
        the driver enforces limit > 0 before building a megachunk)."""

        def cond(mm):
            return jnp.any(mm.status == jnp.int32(_RUNNING))

        def qbody(mm):
            return step_lanes(tab, image, mm, limit)

        return lax.while_loop(cond, qbody, m)

    def _window(tab: UopTable, image: MemImage, machine: Machine,
                template: Machine, slab_first: Tuple, slab_rest: Tuple,
                seeds, pfns, gva_l, finish_l, limit, n_batches,
                agg_cov, agg_edge, dd) -> MegaOut:
        n_lanes = machine.status.shape[0]
        image = lane_image(image, n_lanes)
        n_words = slab_first[0].shape[1]
        statuses0 = jnp.full((B, n_lanes), -1, jnp.int32)
        flags0 = jnp.zeros((B, n_lanes), bool)
        ctrs0 = jnp.zeros((B, N_CTRS), jnp.uint64)
        snap0 = MegaSnap(
            words=jnp.zeros((n_lanes, n_words), jnp.uint32),
            lens=jnp.zeros((n_lanes,), jnp.int32))
        nw0 = jnp.zeros_like(agg_cov)
        if devdec_on:
            count0, bp_keys, n_bp = dd
            capacity = tab.rip_l.shape[0]
            lane_base = (lane_base_fn(n_lanes) if lane_base_fn is not None
                         else jnp.int32(0))

            def gather(tree):
                if gather_fn is None:
                    return tree
                return jax.tree.map(gather_fn, tree)

            def lane_slice(a):
                if gather_fn is None:
                    return a
                return lax.dynamic_slice_in_dim(a, lane_base, n_lanes, 0)

            @jax.named_scope("decode-service")
            def service(tabst, cnt, m, dstats):
                """In-graph decode-miss service rounds around the
                quiesce: compute per-lane blocks against the round-start
                table, commit them in global lane order (replicated on a
                mesh), apply this shard's lane deltas, re-quiesce.
                Stops when no un-parked lane is NEED_DECODE."""

                def scond(c):
                    _tabst, _cnt, m, _dstats, parked = c
                    return any_fn((m.status == jnp.int32(_ND)) & ~parked)

                def sbody(c):
                    tabst, cnt, m, dstats, parked = c
                    tl = tab._replace(
                        hash_tab=tabst[0], rip_l=tabst[1],
                        meta_i32=tabst[2], meta_u64=tabst[3])
                    blocks = DD.compute_blocks(tl, image, m, bp_keys, n_bp)
                    out = DD.commit_blocks(tl, cnt, gather(blocks),
                                           gather(m.status), capacity)
                    fm = lane_slice(out.fault_mask)
                    m2 = m._replace(
                        status=lane_slice(out.status),
                        fault_gva=jnp.where(
                            fm, lane_slice(out.fault_gva), m.fault_gva),
                        fault_write=jnp.where(
                            fm, jnp.int32(0), m.fault_write),
                        ctr=m.ctr.at[:, CTR_MEM_FAULT].add(
                            lane_slice(out.mem_fault_inc)))
                    dstats2 = dstats + jnp.concatenate(
                        [out.stats, jnp.ones((1,), jnp.int32)])
                    m3 = run_quiesce(out.tab, image, m2, limit)
                    return ((out.tab.hash_tab, out.tab.rip_l,
                             out.tab.meta_i32, out.tab.meta_u64),
                            out.count, m3, dstats2,
                            parked | lane_slice(out.parked))

                parked0 = jnp.zeros((n_lanes,), bool)
                tabst, cnt, m, dstats, _parked = lax.while_loop(
                    scond, sbody, (tabst, cnt, m, dstats, parked0))
                return tabst, cnt, m, dstats

        def cond(carry):
            b, stop = carry[0], carry[1]
            return (b < n_batches) & ~stop

        def body(carry):
            (b, _stop, incomplete, find_b, m, agg_c, agg_e, sts, flags,
             ctrs, nw, prev, cur, tabst, cnt, dstats) = carry
            tab_b = (tab._replace(hash_tab=tabst[0], rip_l=tabst[1],
                                  meta_i32=tabst[2], meta_u64=tabst[3])
                     if devdec_on else tab)
            first = b == 0
            data = jnp.where(first, slab_first[0], slab_rest[0])
            lens_s = jnp.where(first, slab_first[1], slab_rest[1])
            cumw = jnp.where(first, slab_first[2], slab_rest[2])
            m = machine_restore_impl(m, template)
            words, lens = generate(data, lens_s, cumw, seeds[b],
                                   rounds=rounds)
            m = insert(m, words, lens, pfns, gva_l)
            m = run_quiesce(tab_b, image, m, limit)
            if devdec_on:
                tabst, cnt, m, dstats = service(tabst, cnt, m, dstats)
                tab_b = tab._replace(
                    hash_tab=tabst[0], rip_l=tabst[1], meta_i32=tabst[2],
                    meta_u64=tabst[3])
            # declarative stop: BREAKPOINT at the finish rip == the
            # host handler's stop(Ok()) — pre-execution, so no icount /
            # coverage for the breakpointed instruction, like the host
            st = jnp.where((m.status == jnp.int32(_BP))
                           & (m.rip == finish_l), jnp.int32(_OK), m.status)
            m = m._replace(status=st)

            svc = jnp.zeros_like(st, bool)
            for s in serviceable:
                svc = svc | (st == jnp.int32(s))
            need_service = any_fn(svc)
            complete = ~need_service

            include = ((st != jnp.int32(_TIMEDOUT))
                       & (st != jnp.int32(_OVF)))
            agg_c2, agg_e2, new_lane, new_w = merge_fn(
                agg_c, agg_e, m.cov, m.edge, include)
            agg_c3 = jnp.where(complete, agg_c2, agg_c)
            agg_e3 = jnp.where(complete, agg_e2, agg_e)
            new_lane = new_lane & complete
            clean = ((st == jnp.int32(_OK)) | (st == jnp.int32(_TIMEDOUT))
                     | (st == jnp.int32(_CR3)))
            crashy = complete & any_fn(~clean)
            has_cov_find = complete & any_fn(new_lane)
            find_b2 = jnp.where(has_cov_find & (find_b >= B), b, find_b)

            sts2 = sts.at[b].set(jnp.where(complete, st, sts[b]))
            flags2 = flags.at[b].set(new_lane)
            ctrs2 = ctrs.at[b].set(jnp.where(
                complete, sum_fn(m.ctr), ctrs[b]))
            nw2 = jnp.where(complete, new_w, nw)
            prev2, cur2 = cur, _snap(words, lens)
            b2 = b + complete.astype(jnp.int32)
            # find-stop: after a new-coverage find at batch j the window
            # may run j+1 (its slab view is still entitled) and must then
            # return so the host folds the finds before j+2 generates; a
            # non-clean terminal stops immediately so the live machine
            # stays that batch's final state for crash naming/bucketing
            stop2 = need_service | crashy \
                | (complete & (b + 1 > find_b2 + 1))
            return (b2, stop2, incomplete | need_service, find_b2, m,
                    agg_c3, agg_e3, sts2, flags2, ctrs2, nw2, prev2,
                    cur2, tabst, cnt, dstats)

        if devdec_on:
            tabst0 = (tab.hash_tab, tab.rip_l, tab.meta_i32, tab.meta_u64)
            cnt0 = count0
        else:
            # devdec off: zero-size sentinels keep ONE carry structure
            tabst0 = ()
            cnt0 = jnp.int32(-1)
        dstats0 = jnp.zeros((4,), jnp.int32)
        init = (jnp.int32(0), jnp.bool_(False), jnp.bool_(False),
                jnp.int32(B), machine, agg_cov, agg_edge, statuses0,
                flags0, ctrs0, nw0, snap0, snap0, tabst0, cnt0, dstats0)
        (b, _stop, incomplete, _fb, m, agg_c, agg_e, sts, flags, ctrs,
         nw, prev, cur, tabst, cnt, dstats) = lax.while_loop(
            cond, body, init)
        tab_out = (tab._replace(hash_tab=tabst[0], rip_l=tabst[1],
                                meta_i32=tabst[2], meta_u64=tabst[3])
                   if devdec_on else tab)
        return MegaOut(machine=m, agg_cov=agg_c, agg_edge=agg_e,
                       batches=b, incomplete=incomplete, statuses=sts,
                       new_flags=flags, ctr_sums=ctrs, new_words=nw,
                       prev=prev, cur=cur, tab=tab_out, count=cnt,
                       dd_stats=dstats)

    if devdec_on:
        def window(tab, image, machine, template, slab_first, slab_rest,
                   seeds, pfns, gva_l, finish_l, limit, n_batches,
                   agg_cov, agg_edge, count, bp_keys, n_bp):
            return _window(tab, image, machine, template, slab_first,
                           slab_rest, seeds, pfns, gva_l, finish_l,
                           limit, n_batches, agg_cov, agg_edge,
                           (count, bp_keys, n_bp))
    else:
        def window(tab, image, machine, template, slab_first, slab_rest,
                   seeds, pfns, gva_l, finish_l, limit, n_batches,
                   agg_cov, agg_edge):
            return _window(tab, image, machine, template, slab_first,
                           slab_rest, seeds, pfns, gva_l, finish_l,
                           limit, n_batches, agg_cov, agg_edge, None)

    return window


# window operand positions donated through the executable: machine (2),
# agg_cov (12), agg_edge (13).  tab/image/template/slabs are shared
# across windows and never donated.
WINDOW_DONATE_ARGNUMS = (2, 12, 13)


def make_megachunk(max_batches: int, n_pages: int, len_gpr: int,
                   ptr_gpr: int, rounds: int, deliver: bool,
                   devdec: bool = False, donate: bool = None):
    """Build (or fetch) the jitted single-device megachunk window:
    (tab, image, machine, template, slab_first, slab_rest, seeds[B,L,2],
    pfns, gva_l, finish, limit, n_batches, agg_cov, agg_edge
    [, count, bp_keys, n_bp when devdec]) -> MegaOut.

    `donate=None` follows the repo donation policy (off on the XLA CPU
    backend where donation is unsound — step.make_run_chunk's caveat,
    the PR-2 finding; on elsewhere): with donation the machine and
    aggregate planes update in place across the whole window instead of
    copying through the executable."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    key = ("1dev", max_batches, n_pages, len_gpr, ptr_gpr, rounds,
           deliver, devdec, donate)
    cached = _MEGA_CACHE.get(key)
    if cached is not None:
        return cached

    def sum_fn(ctr):
        return jnp.sum(ctr.astype(jnp.uint64), axis=0)

    body = _make_body(max_batches, n_pages, len_gpr, ptr_gpr, rounds,
                      deliver, merge_fn=merge_coverage, any_fn=jnp.any,
                      sum_fn=sum_fn, devdec_on=devdec)
    fn = jax.jit(body, donate_argnums=WINDOW_DONATE_ARGNUMS if donate
                 else ())
    _MEGA_CACHE[key] = fn
    return fn


def make_mesh_megachunk(max_batches: int, n_pages: int, len_gpr: int,
                        ptr_gpr: int, rounds: int, deliver: bool, mesh,
                        devdec: bool = False, donate: bool = None):
    """The megachunk window per shard under shard_map: machine/template/
    seed-stream/snapshots lane-sharded, slabs + uop table + aggregates
    replicated, the per-batch merge the shard-aware prefix-credit core,
    and every loop-control scalar all-reduced so the shards' while_loops
    stay in lockstep (identical trip counts, matched collectives).

    With `devdec`, decode-miss service rounds all-gather the per-shard
    lane blocks and run ONE replicated sequential commit, so the table
    (and its slot/coverage-bit order) stays bit-identical on every shard
    AND to the single-device program — slots never partition by shard."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from wtf_tpu.meshrun.executor import IMAGE_SPEC
    from wtf_tpu.meshrun.mesh import LANE_AXIS

    if donate is None:
        donate = jax.default_backend() != "cpu"
    key = ("mesh", max_batches, n_pages, len_gpr, ptr_gpr, rounds,
           deliver, mesh, devdec, donate)
    cached = _MEGA_CACHE.get(key)
    if cached is not None:
        return cached

    def any_fn(x):
        return lax.pmax(jnp.any(x).astype(jnp.int32), LANE_AXIS) > 0

    def sum_fn(ctr):
        # the TPU compiler lowers only 32-bit sum all-reduces (a u64
        # psum is UNIMPLEMENTED in its 64-bit rewrite): four 16-bit
        # chunks ride ONE u32 psum and recombine with wrapping u64
        # shifts — exact for any mesh up to 65536 shards
        local = jnp.sum(ctr.astype(jnp.uint64), axis=0)
        chunks = jnp.stack([(local >> jnp.uint64(16 * i))
                            & jnp.uint64(0xFFFF) for i in range(4)])
        s = lax.psum(chunks.astype(jnp.uint32),
                     LANE_AXIS).astype(jnp.uint64)
        return (s[0] + (s[1] << jnp.uint64(16)) + (s[2] << jnp.uint64(32))
                + (s[3] << jnp.uint64(48)))

    def merge_fn(agg_cov, agg_edge, cov, edge, include):
        return mesh_merge_local(agg_cov, agg_edge, cov, edge, include,
                                LANE_AXIS)

    def gather_fn(a):
        return lax.all_gather(a, LANE_AXIS, axis=0, tiled=True)

    def lane_base_fn(n_local):
        return lax.axis_index(LANE_AXIS).astype(jnp.int32) * n_local

    body = _make_body(max_batches, n_pages, len_gpr, ptr_gpr, rounds,
                      deliver, merge_fn=merge_fn, any_fn=any_fn,
                      sum_fn=sum_fn, devdec_on=devdec,
                      gather_fn=gather_fn if devdec else None,
                      lane_base_fn=lane_base_fn if devdec else None)
    lane_snap = MegaSnap(words=P(LANE_AXIS), lens=P(LANE_AXIS))
    in_specs = (P(), IMAGE_SPEC, P(LANE_AXIS), P(LANE_AXIS),
                (P(), P(), P()), (P(), P(), P()), P(None, LANE_AXIS),
                P(), P(), P(), P(), P(), P(), P())
    if devdec:
        in_specs = in_specs + (P(), P(), P())
    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=MegaOut(
            machine=P(LANE_AXIS), agg_cov=P(), agg_edge=P(),
            batches=P(), incomplete=P(), statuses=P(None, LANE_AXIS),
            new_flags=P(None, LANE_AXIS), ctr_sums=P(), new_words=P(),
            prev=lane_snap, cur=lane_snap, tab=P(), count=P(),
            dd_stats=P()),
        check_vma=False),
        donate_argnums=WINDOW_DONATE_ARGNUMS if donate else ())
    _MEGA_CACHE[key] = fn
    return fn
