"""TpuBackend: the batched device interpreter behind the Backend contract.

Where every reference backend runs ONE testcase per `Run()` inside one
VM/emulator, this backend runs a whole *batch* — one testcase per device
lane — per `run_batch()`.  The single-testcase `run()` facade (lane 0) keeps
the reference's `Backend_t` calling convention for the run/trace subcommands
and for harness code that doesn't care about batching.

Lane binding: register/memory accessors operate on the backend's *current*
lane.  During `run_batch` insertion and breakpoint dispatch the backend is
bound to the lane being serviced, so unmodified target modules
(`insert_testcase(backend, data)`, `handler(backend)`) work per-lane exactly
like the reference's globals-based harness code (fuzzer_hevd.cc:20-59).

Coverage: per-lane device bitmaps OR-merged into device-resident aggregate
bitmaps after each batch; a lane "found new coverage" iff its bitmap has a
bit outside the aggregate (reference semantics: set-union merge on the
master, server.h:816-854).  Timeout lanes are excluded from the merge — the
reference client revokes their coverage before reporting (client.cc:122-125).
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from wtf_tpu.backend.base import Backend, BreakpointHandler
from wtf_tpu.core.results import (
    Cr3Change, Crash, Ok, OverlayFull, TestcaseResult, Timedout,
)
from wtf_tpu.core.results import StatusCode
from wtf_tpu.interp.machine import CTR_INSTR
from wtf_tpu.interp.runner import HostView, Runner
# the ONE coverage merge (reference master's set-union semantics,
# server.h:816-854) — shared with the mesh backend, which swaps in the
# shard-aware variant of the same core (meshrun/reduce.py)
from wtf_tpu.meshrun.reduce import merge_coverage
from wtf_tpu.snapshot.loader import Snapshot
from wtf_tpu import telemetry
from wtf_tpu.telemetry import Registry, StatsDict
from wtf_tpu.utils.hashing import splitmix64

MASK64 = (1 << 64) - 1


class TpuBackend(Backend):
    def __init__(self, snapshot: Snapshot, n_lanes: int = 64,
                 limit: int = 0, registry: Optional[Registry] = None,
                 events=None, **runner_kwargs):
        self.snapshot = snapshot
        self.symbols = snapshot.symbols
        self.n_lanes = n_lanes
        self.limit = limit
        # Telemetry: ONE registry shared with the Runner (and, when the
        # campaign driver defaults to it, the fuzz loop) so phase spans
        # nest and the heartbeat dump carries everything
        self.registry, self.events = telemetry.resolve(
            registry=registry, events=events)
        # Self-healing device runtime (wtf_tpu/supervise): the supervisor
        # outlives the Runner it guards — initialize() hands it to every
        # (re)built Runner so dispatch indices, ladder state and the
        # quarantine set survive recovery rebuilds.
        from wtf_tpu.supervise import Supervisor

        self.supervisor = Supervisor(
            registry=self.registry, events=self.events,
            enabled=bool(runner_kwargs.pop("supervise", False)),
            dispatch_timeout=float(
                runner_kwargs.pop("dispatch_timeout", 0.0) or 0.0),
            promote_after=int(runner_kwargs.pop("promote_after", 8)),
            max_batch_retries=int(
                runner_kwargs.pop("max_batch_retries", 4)),
            quarantine_threshold=int(
                runner_kwargs.pop("quarantine_threshold", 3)))
        self.supervisor._backend = self
        self._runner_kwargs = runner_kwargs
        self.runner: Optional[Runner] = None
        self.breakpoints: Dict[int, BreakpointHandler] = {}
        self._view: Optional[HostView] = None
        self._lane = 0
        self._lane_results: Dict[int, TestcaseResult] = {}
        self._agg_cov = None
        self._agg_edge = None
        # pipelined harvest: a speculatively dispatched next megachunk
        # window (out, signature) — adopted by the next run_megachunk
        # call when its parameters match, dropped (unread, side-effect
        # free) otherwise
        self._mega_inflight = None
        # the batch coverage merge — the mesh backend swaps in the
        # shard-aware variant (same semantics, one all_gather)
        self._merge = merge_coverage
        self._last_new_words: Optional[np.ndarray] = None
        self._trace_request = None
        # per-campaign counters (reference BochscpuRunStats_t role,
        # bochscpu_backend.h:17-45) — registry-backed dict facade
        self.stats = StatsDict(self.registry, "backend",
                               fields=("batches", "testcases",
                                       "instructions", "result_pulls"))

    # -- lifecycle ---------------------------------------------------------
    def initialize(self) -> None:
        self.runner = Runner(self.snapshot, self.n_lanes,
                             registry=self.registry, events=self.events,
                             supervisor=self.supervisor,
                             **self._runner_kwargs)
        m = self.runner.machine
        self._agg_cov = jnp.zeros_like(m.cov[0])
        self._agg_edge = jnp.zeros_like(m.edge[0])

    # -- lane binding ------------------------------------------------------
    @contextmanager
    def _bound(self, view: HostView, lane: int):
        old = (self._view, self._lane)
        self._view, self._lane = view, lane
        try:
            yield
        finally:
            self._view, self._lane = old

    def _ensure_view(self) -> HostView:
        if self._view is None:
            self._view = self.runner.view()
        return self._view

    # -- batch execution ---------------------------------------------------
    def run_batch(
        self,
        insert: Optional[Sequence] = None,
        target=None,
    ) -> List[TestcaseResult]:
        """Run a batch of testcases (one per lane; lists longer than
        n_lanes run as several device rounds with a restore in between;
        shorter lists leave trailing lanes idle)."""
        if insert is not None and len(insert) > self.n_lanes:
            results: List[TestcaseResult] = []
            flags: List[bool] = []
            for start in range(0, len(insert), self.n_lanes):
                if start > 0:
                    target.restore()
                    self.restore()
                chunk = insert[start:start + self.n_lanes]
                results.extend(self.run_batch(chunk, target))
                flags.extend(self._new_lane[:len(chunk)])
            self._new_lane = np.array(flags)
            return results
        runner = self.runner
        runner.limit = self.limit
        self._lane_results = {}
        spans = self.registry.spans
        with spans.span("insert"):
            view = self._ensure_view()
            n_active = self.n_lanes
            if insert is not None:
                n_active = len(insert)
                quarantined = runner.supervisor.quarantined
                for lane, data in enumerate(insert):
                    if lane in quarantined:
                        # poisoned lane parked idle (tenancy mask idiom):
                        # no insert, terminal status, and _finish_batch's
                        # include mask keeps it out of the coverage merge
                        view.set_status(lane, StatusCode.OK)
                        continue
                    with self._bound(view, lane):
                        target.insert_testcase(self, data)
                for lane in range(n_active, self.n_lanes):
                    view.set_status(lane, StatusCode.OK)  # idle lanes
            runner.push(view)
            self._view = None
        statuses = runner.run(bp_handler=self._dispatch_bp)
        self._finish_batch(statuses, n_active)
        return self._map_results(statuses, n_active)

    def _finish_batch(self, statuses, n_active: int) -> None:
        """Post-run batch accounting shared by run_batch and
        run_batch_device: coverage merge on device (timeouts revoked like
        the reference client, and OVERLAY_FULL lanes excluded — they ran
        on truncated memory, their coverage is not trustworthy), backend
        counters, and the once-per-burst device-counter fold."""
        runner = self.runner
        # integrity gate BEFORE anything consumes the machine: a poisoned
        # status would crash StatusCode() in result mapping, poisoned
        # planes would credit coverage.  Raises LanePoisoned (the fuzz
        # loop's supervision wrapper replays the batch); inert when the
        # supervisor is disabled.
        runner.supervisor.raise_if_poisoned(runner, "batch")
        qmask = runner.supervisor.quarantine_mask()
        with self.registry.spans.span("cov-readback") as sp:
            m = runner.machine
            keep = ((statuses != int(StatusCode.TIMEDOUT))
                    & (statuses != int(StatusCode.OVERLAY_FULL))
                    & (np.arange(self.n_lanes) < n_active))
            if qmask is not None:
                keep &= ~qmask  # quarantined lanes never credit coverage
            include = jnp.asarray(keep)
            (self._agg_cov, self._agg_edge, new_lane,
             new_words) = self._merge(
                self._agg_cov, self._agg_edge, m.cov, m.edge, include)
            self._new_lane = np.asarray(new_lane)
            self._last_new_words = np.asarray(new_words)
            self.stats["batches"] += 1
            self.stats["testcases"] += n_active
            self.stats["instructions"] += int(
                np.asarray(m.icount)[:n_active].sum())
            # fold the device telemetry block exactly once per burst
            runner.fold_device_counters()
            sp.fence(self._agg_cov)

    def run_batch_device(self, mutator, target) -> List[TestcaseResult]:
        """One batch whose testcases were generated ON DEVICE (wtf_tpu/
        devmut): insertion is a single in-graph overlay/register update
        (Runner.device_insert) instead of per-lane target.insert_testcase
        calls — mutate→insert→execute with no host round-trip for the
        testcase bytes.  `mutator` is a bound DevMangleMutator whose
        take_batch() already ran; every lane is active."""
        words, lens = mutator.current_batch()
        spec = mutator.spec
        return self.run_batch_words(words, lens, mutator.pfns, spec)

    def run_batch_words(self, words, lens, pfns,
                        spec) -> List[TestcaseResult]:
        """The device-generated batch driver shared by the devmangle fuzz
        path and the triage replay core (wtf_tpu/triage): `words`
        (u32[L, W]) / `lens` (i32[L]) device arrays — a devmut generate
        output, or triage's in-graph candidate builds — land in every
        lane's overlay through Runner.device_insert and the batch runs
        with every lane active.  `spec` is the target's
        DeviceInsertSpec, `pfns` the input region's page frames."""
        runner = self.runner
        runner.limit = self.limit
        self._lane_results = {}
        spans = self.registry.spans
        with spans.span("insert"):
            # host state staged through the backend view (e.g. init-time
            # register/memory writes a target made before the first
            # batch) must land, exactly as run_batch's push does —
            # BEFORE device_insert so the testcase wins any overlap
            if self._view is not None:
                runner.push(self._view)
                self._view = None
            qmask = runner.supervisor.quarantine_mask()
            with spans.span("device") as sp:
                if qmask is None:
                    runner.device_insert(words, lens, pfns, spec.gva,
                                         spec.len_gpr, spec.ptr_gpr)
                else:
                    # masked insert (tenancy idiom) + park the poisoned
                    # lanes terminal so the run loop never steps them
                    from wtf_tpu.supervise import integrity

                    runner.device_insert(words, lens, pfns, spec.gva,
                                         spec.len_gpr, spec.ptr_gpr,
                                         active=~qmask)
                    runner.machine = integrity.mask_idle(
                        runner.machine, qmask)
                sp.fence(runner.machine.status)
        statuses = runner.run(bp_handler=self._dispatch_bp)
        self._finish_batch(statuses, self.n_lanes)
        return self._map_results(statuses, self.n_lanes)

    def run_megachunk(self, mutator, target, max_batches: int,
                      n_batches: int):
        """ONE megachunk window (wtf_tpu/fuzz/megachunk.py): up to
        `n_batches` whole fuzz batches — restore, devmut generation,
        insert, the run ladder, the finish-breakpoint rewrite and the
        coverage merge — in one compiled dispatch; the host touches the
        window only for the status pull and the crash/new-coverage
        harvest.  `max_batches` is the compiled buffer size (stable
        across calls so the program compiles once); `n_batches <=
        max_batches` is this window's effective budget (checkpoint
        cadence / runs-budget capping).

        Returns a list of (results, new_flags, datas) per PROCESSED
        batch, in batch order: `results` the per-lane TestcaseResults,
        `new_flags` the prefix-credit new-coverage flags, `datas` the
        fetched bytes of crash/new-coverage lanes.  A batch that needed
        host servicing is finished through the ordinary Runner.run loop
        before being returned — the cold-start path IS the legacy loop.
        """
        import jax

        runner = self.runner
        if not self.limit:
            raise ValueError(
                "megachunk windows need a nonzero --limit: the in-graph "
                "run ladder quiesces on the instruction budget")
        runner.limit = self.limit
        self._lane_results = {}
        spans = self.registry.spans
        spec = mutator.spec
        n_pages = len(mutator.pfns)
        fn = runner.megachunk_callable(max_batches, n_pages,
                                       spec.len_gpr, spec.ptr_gpr,
                                       mutator.rounds)
        key = ("megachunk", max_batches, n_pages, self.n_lanes,
               mutator.rounds, runner.exec_sig, runner._donate)
        from wtf_tpu.interp.runner import _DISPATCHED_EXECUTORS

        if key not in _DISPATCHED_EXECUTORS:
            _DISPATCHED_EXECUTORS.add(key)
            self.events.emit("compile", kind="megachunk",
                             batches=max_batches, lanes=self.n_lanes)
        # host state staged through the backend view (init-time target
        # writes) must land BEFORE the window, like run_batch_words
        view_was_clean = self._view is None
        if self._view is not None:
            runner.push(self._view)
            self._view = None
        self.registry.counter("megachunk.windows").inc()
        # pipelined harvest, adopt side: if the previous call prelaunched
        # this exact window, its execution has been overlapping that
        # call's harvest accounting — fence the (mostly elapsed) wait
        # instead of dispatching
        out = None
        if self._mega_inflight is not None:
            p_out, p_sig = self._mega_inflight
            self._mega_inflight = None
            sig = self._mega_signature(mutator, max_batches, n_batches,
                                       n_pages)
            if view_was_clean and p_sig == sig:
                out = p_out
                self.registry.counter("megachunk.prelaunch_hits").inc()
            else:
                # the speculation missed (window size changed, host state
                # intervened): the dispatch is pure, dropping its outputs
                # unread discards it completely
                self.registry.counter("megachunk.prelaunch_dropped").inc()
        if out is None:
            out = self._dispatch_window(fn, mutator, spec, n_pages,
                                        max_batches, n_batches,
                                        runner.machine, self._agg_cov,
                                        self._agg_edge, wait=True)
        else:
            # a prelaunched window: its `dispatch` was timed where it was
            # enqueued (_dispatch_window); what is left is the fence
            with spans.span("device"), spans.span("wait") as sp:
                sp.fence(out.batches)
        runner.machine = out.machine
        self._agg_cov = out.agg_cov
        self._agg_edge = out.agg_edge
        # integrity gate before the harvest and before the mutator cursor
        # advances: a LanePoisoned raise here leaves the window fully
        # replayable (consume_window not yet called)
        runner.supervisor.raise_if_poisoned(runner, "megachunk")
        # devdec harvest: back-fill device-published decode entries into
        # the host cache BEFORE anything can re-service those rips (the
        # incomplete path's Runner.run below rebuilds the dispatch table
        # from the cache — missing rows would re-publish at new indices
        # and corrupt the coverage-bit mapping)
        published = 0
        if runner.device_decode:
            published = self._harvest_device_decode(out)
        with spans.span("status-pull"):
            # the window's one pull of its per-batch rows
            self._last_new_words = np.asarray(
                jax.device_get(out.new_words))
            b_done = int(jax.device_get(out.batches))
            incomplete = bool(jax.device_get(out.incomplete))
            statuses = np.asarray(jax.device_get(out.statuses))
            flags = np.asarray(jax.device_get(out.new_flags))
            ctr_sums = np.asarray(jax.device_get(out.ctr_sums))
        processed = b_done + (1 if incomplete else 0)
        mutator.consume_window(processed)
        if runner.device_decode and not incomplete:
            # a complete window needed ZERO host decode services — the
            # zero-host steady state PERF.md round 18 measures; length =
            # batches the window carried without coming up for air
            self.registry.counter("devdec.zero_host_windows").inc()
            self.registry.counter("devdec.zero_host_batches").inc(b_done)
        # pipelined harvest, launch side: a complete window with no finds
        # and no freshly published decode entries leaves every operand of
        # the next window already determined (slab unchanged — crashes
        # never enter the corpus — and machine/aggregates device-
        # resident), so dispatch it NOW and let it execute under the
        # harvest accounting below.  Finds must NOT prelaunch: the next
        # window's first batch is entitled to them, and its slab view is
        # only pinned during the loop's harvest.  Supervised or mesh
        # campaigns keep the synchronous schedule (recovery rebuilds and
        # multi-chip placement interact badly with in-flight windows),
        # and so do DONATED windows: a dropped prelaunch discards its
        # outputs, but donation has already consumed its input buffers —
        # adopting nothing would leave the live machine invalidated.
        if (not incomplete and published == 0
                and not flags[:b_done].any()
                and not runner.supervisor.enabled
                and not runner._donate
                and runner.exec_sig == ()):
            n_out = self._dispatch_window(
                fn, mutator, spec, n_pages, max_batches, n_batches,
                out.machine, out.agg_cov, out.agg_edge, wait=False)
            self._mega_inflight = (n_out, self._mega_signature(
                mutator, max_batches, n_batches, n_pages))
            self.registry.counter("megachunk.prelaunched").inc()

        batches = []
        for b in range(b_done):
            row = statuses[b]
            frow = flags[b]
            runner.fold_counter_totals(ctr_sums[b])
            # one `results` span per batch: the mapping and the fetch of
            # the finds' bytes
            with spans.span("results"):
                if b == b_done - 1 and not incomplete:
                    # the live machine IS this batch's final state (the
                    # window stops on any non-clean terminal), so crash
                    # naming reads it exactly like run_batch's path
                    results = [self._map_result(lane, row[lane])
                               for lane in range(self.n_lanes)]
                else:
                    # interior batches are clean by the stop rule
                    results = [self._result_from_fields(
                        StatusCode(int(row[lane])), 0, 0, 0, "")
                        for lane in range(self.n_lanes)]
                snap = out.cur if b == processed - 1 else out.prev
                datas = {}
                wanted = [lane for lane in range(self.n_lanes)
                          if frow[lane]
                          or isinstance(results[lane], Crash)]
                if wanted:
                    mutator.set_current(snap.words, snap.lens)
                    datas = mutator.fetch(wanted)
            self._new_lane = frow
            self.stats["batches"] += 1
            self.stats["testcases"] += self.n_lanes
            self.stats["instructions"] += int(ctr_sums[b][CTR_INSTR])
            batches.append((results, frow, datas))

        if incomplete:
            # finish the in-flight batch through the ordinary servicing
            # loop (decode/SMC/oracle/breakpoints), then account it the
            # host way — this IS the batch-at-a-time path
            statuses_fin = runner.run(bp_handler=self._dispatch_bp)
            self._finish_batch(statuses_fin, self.n_lanes)
            with spans.span("results"):
                results = [self._map_result(lane, statuses_fin[lane])
                           for lane in range(self.n_lanes)]
                frow = np.asarray(self._new_lane)
                mutator.set_current(out.cur.words, out.cur.lens)
                wanted = [lane for lane in range(self.n_lanes)
                          if frow[lane]
                          or isinstance(results[lane], Crash)]
                datas = mutator.fetch(wanted) if wanted else {}
            batches.append((results, frow, datas))
        return batches

    def _dispatch_window(self, fn, mutator, spec, n_pages: int,
                         max_batches: int, n_batches: int, machine,
                         agg_cov, agg_edge, wait: bool):
        """Dispatch one megachunk window against explicit machine/
        aggregate operands — shared by the synchronous path and the
        pipelined-harvest prelaunch (which passes the JUST-finished
        window's device-side outputs and wait=False so the dispatch
        queues behind nothing)."""
        from wtf_tpu.fuzz.megachunk import NO_FINISH

        runner = self.runner
        finish = spec.finish_gva if spec.finish_gva is not None \
            else NO_FINISH
        slab_first, slab_rest = mutator.window_slabs()
        seeds = mutator.window_seeds(max_batches)
        slab_first, slab_rest, seeds = runner.megachunk_place(
            slab_first, slab_rest, seeds)
        pfns = jnp.asarray(np.asarray(mutator.pfns, dtype=np.int32))
        gva_l = jnp.asarray(np.array(
            [spec.gva & 0xFFFF_FFFF, (spec.gva >> 32) & 0xFFFF_FFFF],
            dtype=np.uint32))
        spans = self.registry.spans
        with spans.span("device"):
            # `dispatch` and `wait` as under execute/device-step
            # (Runner.run): the jit call, then the fence
            with spans.span("dispatch"):
                out = runner.supervisor.dispatch(
                    "megachunk", fn,
                    runner.device_tab(), runner.image, machine,
                    runner.template, slab_first, slab_rest, seeds, pfns,
                    gva_l, jnp.uint64(finish), jnp.uint64(self.limit),
                    jnp.int32(n_batches), agg_cov, agg_edge,
                    *runner.devdec_operands(),
                    window=n_batches, wait=wait, sync=lambda o: o.batches)
            if wait:
                with spans.span("wait") as sp:
                    sp.fence(out.batches)
        return out

    def _mega_signature(self, mutator, max_batches: int, n_batches: int,
                        n_pages: int):
        """Everything a speculative window's operands were derived from:
        a prelaunched window is adopted only when the next call's
        signature is identical (same window size, same stream cursor,
        same decode cache, same breakpoint set, same limit)."""
        cache = self.runner.cache
        return (max_batches, n_batches, n_pages, mutator._batch,
                self.limit, cache.count, frozenset(cache.pending_bps))

    def _harvest_device_decode(self, out) -> int:
        """Adopt the window's device-published decode entries into the
        host cache (publish order preserved — coverage bit i IS entry
        index i) with the host decoder as cross-checking oracle, and
        fold the in-graph service stats.  Returns the number of adopted
        entries."""
        runner = self.runner
        cache = runner.cache
        dd = np.asarray(jax.device_get(out.dd_stats))
        reg = self.registry
        reg.counter("devdec.serviced_lanes").inc(int(dd[0]))
        reg.counter("devdec.published").inc(int(dd[1]))
        reg.counter("devdec.parked_lanes").inc(int(dd[2]))
        reg.counter("devdec.service_rounds").inc(int(dd[3]))
        new_count = int(jax.device_get(out.count))
        start = cache.count
        if new_count < start:
            raise RuntimeError(
                f"device decode count went backwards: {new_count} < "
                f"host cache {start}")
        if new_count == start:
            return 0
        rip_rows, mi_rows, mu_rows = jax.device_get(
            (out.tab.rip_l[start:new_count],
             out.tab.meta_i32[start:new_count],
             out.tab.meta_u64[start:new_count]))
        mismatches = cache.adopt_device_entries(
            rip_rows, mi_rows, mu_rows, start, new_count)
        reg.counter("devdec.crosscheck_mismatches").inc(mismatches)
        if mismatches:
            self.events.emit("devdec-mismatch", entries=new_count - start,
                             mismatches=mismatches)
        return new_count - start

    # -- checkpoint/resume (wtf_tpu/resume) --------------------------------
    def coverage_state(self):
        """(cov words, edge words) aggregate bitmaps as host arrays — the
        coverage half of a campaign checkpoint.  Bit indices are decode-
        cache entry indices; the checkpoint carries the cache alongside
        (Runner.checkpoint_state) so they stay meaningful."""
        return (np.asarray(jax.device_get(self._agg_cov)),
                np.asarray(jax.device_get(self._agg_edge)))

    def restore_coverage_state(self, cov: np.ndarray,
                               edge: np.ndarray) -> None:
        """Install checkpointed aggregate bitmaps.  The mesh backend
        overrides placement (aggregates live replicated on every chip).

        Drops any pipelined-harvest prelaunch in flight: a window
        dispatched against pre-restore mutator/cache state could
        otherwise be adopted after the restore if its signature happens
        to match (the signature pins batch cursor and cache count, not
        the restored slab/aggregate contents)."""
        self._agg_cov = jnp.asarray(cov)
        self._agg_edge = jnp.asarray(edge)
        self._mega_inflight = None

    def lane_found_new_coverage(self, lane: int) -> bool:
        return bool(self._new_lane[lane])

    def lane_coverage(self, lane: int) -> Set[int]:
        """This lane's executed-RIP set from its device bitmap (valid after
        run_batch, before restore).  Edge-hash coverage stays device-side;
        the wire protocol reports RIP coverage like the reference's
        robin_set<Gva_t> (client.cc:187-200).  Indexed on device first so
        only the wanted lane's row transfers — on a mesh the [lanes,
        words] plane spans shards and a full gather per harvested lane
        would dominate the crash-fetch path."""
        cov = np.asarray(jax.device_get(self.runner.machine.cov[lane]))
        return set(self.runner.cache.rips_of_bits(cov))

    def lane_cov_words(self, lane: int) -> np.ndarray:
        """This lane's raw coverage bitmap words (device-indexed pull,
        no address decode) — what the WTF3 delta path ships instead of
        the decoded RIP set: bit i is decode-cache entry i, so the
        fleet cursor's XOR against the last-acked aggregate is the whole
        delta extraction (wtf_tpu/fleet/delta.BitmapDeltaCursor)."""
        return np.asarray(jax.device_get(self.runner.machine.cov[lane]))

    def lane_result_detail(self, lane: int) -> str:
        return self.runner.lane_errors.get(lane, "")

    def _bp_handler(self, lane: int, rip: int):
        """Handler lookup for a lane stopped at `rip` — the seam the
        multi-tenant backend re-keys by (tenant, rip) so two base images
        sharing a virtual address dispatch to their own targets."""
        return self.breakpoints.get(rip)

    def _dispatch_bp(self, runner: Runner, view: HostView, lane: int) -> None:
        rip = view.get_rip(lane)
        handler = self._bp_handler(lane, rip)
        if handler is None:
            runner.lane_errors[lane] = f"unexpected breakpoint @ {rip:#x}"
            view.set_status(lane, StatusCode.HARD_ERROR)
            return
        with self._bound(view, lane):
            handler(self)
            if lane in self._lane_results:
                # handler called stop(): park the lane terminally
                result = self._lane_results[lane]
                view.set_status(lane, _result_status(result))

    def _result_from_fields(self, status: StatusCode, gva: int, write: int,
                            rip: int, detail: str) -> TestcaseResult:
        """Terminal status + crash-naming fields -> TestcaseResult — the
        ONE mapping shared by the per-lane machine read (_map_result) and
        the megachunk window's batch rows, so the two dispatch paths name
        crashes identically."""
        if status == StatusCode.OK:
            return Ok()
        if status == StatusCode.TIMEDOUT:
            return Timedout()
        if status == StatusCode.CR3_CHANGE:
            return Cr3Change()
        if status == StatusCode.CRASH:
            return Crash(f"crash-int-{gva:#x}")
        if status == StatusCode.PAGE_FAULT:
            if gva == rip and not write:
                kind = "execute"  # fetch-address fault (A/V-execute analog)
            else:
                kind = "write" if write else "read"
            return Crash(f"crash-{kind}-{gva:#x}")
        if status == StatusCode.DIVIDE_ERROR:
            return Crash(f"crash-de-{rip:#x}")
        if status == StatusCode.OVERLAY_FULL:
            return OverlayFull()
        if status == StatusCode.HARD_ERROR:
            return Crash(f"crash-{detail.split()[0]}")
        raise AssertionError(f"unmapped terminal status {status!r}")

    def _map_results(self, statuses, n_active: int) -> List[TestcaseResult]:
        """The batch's result list, under ONE `results` span: the mapping
        pulls the batch's view once if a lane's name needs machine
        fields, and that host time belongs to no other span of
        `execute`."""
        with self.registry.spans.span("results"):
            return [self._map_result(lane, statuses[lane])
                    for lane in range(n_active)]

    def _map_result(self, lane: int, status_val: int) -> TestcaseResult:
        if lane in self._lane_results:
            return self._lane_results[lane]
        status = StatusCode(int(status_val))
        if status in (StatusCode.OK, StatusCode.TIMEDOUT,
                      StatusCode.CR3_CHANGE):
            return self._result_from_fields(status, 0, 0, 0, "")
        r = self._result_view().r
        return self._result_from_fields(
            status, int(r["fault_gva"][lane]),
            int(r["fault_write"][lane]) & 1,    # above bit 0: iretq's latch
            int(r["rip"][lane]),
            self.runner.lane_errors.get(lane, "hard-error"))

    def _result_view(self) -> HostView:
        """The finished batch's view, for the fields a crash is named
        from: ONE device-to-host transfer a batch (`backend.result_pulls`
        counts it when the mapping is what builds the view), and the one
        the harvest's `bucket_of` reads until `restore`.  Only a view
        built after `Runner.run` returned holds the final machine."""
        if self._view is None:
            self.stats["result_pulls"] += 1
        view = self._ensure_view()
        assert view.fresh(), \
            "result mapping on a view from before the batch's last dispatch"
        return view

    # -- Backend facade (single testcase == lane 0) ------------------------
    def run(self) -> TestcaseResult:
        if self._trace_request is not None:
            return self._run_traced()
        view = self._ensure_view()
        for lane in range(1, self.n_lanes):
            view.set_status(lane, StatusCode.OK)
        self.runner.limit = self.limit
        self._lane_results = {}
        runner = self.runner
        runner.push(view)
        self._view = None
        statuses = runner.run(bp_handler=self._dispatch_bp)
        m = runner.machine
        include = jnp.asarray(
            (statuses != int(StatusCode.TIMEDOUT))
            & (statuses != int(StatusCode.OVERLAY_FULL))
            & (np.arange(self.n_lanes) == 0))
        self._agg_cov, self._agg_edge, new_lane, new_words = self._merge(
            self._agg_cov, self._agg_edge, m.cov, m.edge, include)
        self._new_lane = np.asarray(new_lane)
        self._last_new_words = np.asarray(new_words)
        self.stats["batches"] += 1
        self.stats["testcases"] += 1
        self.stats["instructions"] += int(np.asarray(m.icount)[0])
        self.runner.fold_device_counters()
        return self._map_result(0, statuses[0])

    def _run_traced(self) -> TestcaseResult:
        """rip/cov trace runs go through the oracle for exact per-step
        ordering (the reference's rip traces are bochscpu-only the same way,
        wtf.cc:180-185); device state is untouched."""
        from wtf_tpu.backend.emu import EmuBackend

        path, trace_type = self._trace_request
        self._trace_request = None
        emu = EmuBackend(self.snapshot, limit=self.limit)
        emu.initialize()
        emu.breakpoints = dict(self.breakpoints)
        # replay lane-0 pending state (testcase insertion) onto the oracle:
        # memory writes plus the FULL device-resident register set, so the
        # trace follows the same path the run it reproduces would take
        view = self._ensure_view()
        for (lane, pfn), page in sorted(view.pending.items()):
            if lane == 0:
                emu.cpu.mem.phys_write(pfn << 12, bytes(page))
        cpu = emu.cpu
        cpu.gpr = [int(v) for v in view.r["gpr"][0]]
        cpu.rip = int(view.r["rip"][0])
        cpu.rflags = int(view.r["rflags"][0])
        for name in ("fs_base", "gs_base", "kernel_gs_base", "cr0", "cr2",
                     "cr3", "cr4", "cr8", "lstar", "star", "sfmask", "efer",
                     "tsc", "fpcw", "fpsw", "fptw", "mxcsr"):
            setattr(cpu, name, int(view.r[name][0]))
        cpu.cs_sel = int(view.r["cs"][0])
        cpu.ss_sel = int(view.r["ss"][0])
        cpu.fpst = [int(v) for v in view.r["fpst"][0]]
        cpu.fptop = (int(view.r["fpsw"][0]) >> 11) & 7
        for i in range(16):
            cpu.xmm[i][0] = int(view.r["xmm"][0, i, 0])
            cpu.xmm[i][1] = int(view.r["xmm"][0, i, 1])
            cpu.ymmh[i][0] = int(view.r["xmm"][0, i, 2])
            cpu.ymmh[i][1] = int(view.r["xmm"][0, i, 3])
        cpu.icount = int(view.r["icount"][0])
        cpu.rdrand_state = int(view.r["rdrand"][0])
        self._view = None
        emu.set_trace_file(path, trace_type)
        return emu.run()

    def restore(self) -> None:
        self._view = None
        self.runner.restore()

    def stop(self, result: TestcaseResult) -> None:
        self._lane_results[self._lane] = result

    # -- registers / memory (current lane) ---------------------------------
    @property
    def current_lane(self) -> int:
        return self._lane

    def get_reg(self, idx: int) -> int:
        return self._ensure_view().get_reg(self._lane, idx)

    def set_reg(self, idx: int, value: int) -> None:
        self._ensure_view().set_reg(self._lane, idx, value)

    def get_xmm(self, idx: int) -> int:
        r = self._ensure_view().r["xmm"]
        return int(r[self._lane, idx, 0]) | (int(r[self._lane, idx, 1]) << 64)

    def set_xmm(self, idx: int, value: int) -> None:
        r = self._ensure_view().r["xmm"]
        r[self._lane, idx, 0] = np.uint64(value & (1 << 64) - 1)
        r[self._lane, idx, 1] = np.uint64((value >> 64) & (1 << 64) - 1)

    def get_rip(self) -> int:
        return self._ensure_view().get_rip(self._lane)

    def set_rip(self, value: int) -> None:
        self._ensure_view().set_rip(self._lane, value)

    def get_rflags(self) -> int:
        return int(self._ensure_view().r["rflags"][self._lane])

    def get_icount(self) -> int:
        return int(self._ensure_view().r["icount"][self._lane])

    def virt_translate(self, gva: int, write: bool = False) -> int:
        return self._ensure_view().translate(self._lane, gva, write)

    def inject_exception(self, vector: int, error_code: int = 0,
                         cr2: Optional[int] = None) -> None:
        from wtf_tpu.cpu.interrupts import deliver_exception
        from wtf_tpu.interp.runner import _LaneCtx

        ctx = _LaneCtx(self._ensure_view(), self._lane,
                       self.runner.cpu0_of(self._lane))
        deliver_exception(ctx, vector, error_code, cr2)

    def virt_read(self, gva: int, size: int) -> bytes:
        return self._ensure_view().virt_read(self._lane, gva, size)

    def virt_write(self, gva: int, data: bytes) -> None:
        self._ensure_view().virt_write(self._lane, gva, data)

    # -- breakpoints -------------------------------------------------------
    def set_breakpoint(self, gva: int, handler: BreakpointHandler) -> None:
        self.breakpoints[gva] = handler
        self.runner.cache.set_breakpoint(gva)

    # -- coverage ----------------------------------------------------------
    def last_new_coverage(self) -> Set[int]:
        if self._last_new_words is None:
            return set()
        return set(self.runner.cache.rips_of_bits(self._last_new_words))

    def aggregate_coverage(self) -> Set[int]:
        """All RIPs covered so far this campaign (decoded from the device
        aggregate bitmap)."""
        return set(self.runner.cache.rips_of_bits(np.asarray(self._agg_cov)))

    def revoke_last_new_coverage(self) -> None:
        if self._last_new_words is not None:
            self._agg_cov = self._agg_cov & ~jnp.asarray(self._last_new_words)
            self._last_new_words = None

    # -- misc ---------------------------------------------------------------
    def rdrand(self) -> int:
        view = self._ensure_view()
        nxt = splitmix64(int(view.r["rdrand"][self._lane]))
        view.r["rdrand"][self._lane] = np.uint64(nxt)
        return nxt

    def set_trace_file(self, path, trace_type: str) -> None:
        if trace_type in ("rip", "cov", "tenet"):
            self._trace_request = (path, trace_type)
        else:
            raise ValueError(f"unsupported trace type {trace_type!r}")

    def print_run_stats(self) -> None:
        s = self.runner.stats
        from wtf_tpu.utils.human import number_to_human as h

        print(f"[tpu] lanes={self.n_lanes} "
              f"testcases={h(self.stats['testcases'])} "
              f"batches={self.stats['batches']} "
              f"instructions={h(self.stats['instructions'])} "
              f"chunks={s['chunks']} decodes={s['decodes']} "
              f"fallbacks={s['fallbacks']} "
              f"smc={s['smc_updates']} bp_dispatches={s['bp_dispatches']}")
        by_class = s.get("fallbacks_by_opclass", {})
        if by_class:
            # attribution for the fallback total (VERDICT r5 item 3):
            # which instruction classes keep leaving the device path
            top = ", ".join(
                f"{name}={count}" for name, count in sorted(
                    by_class.items(), key=lambda kv: -kv[1])[:10])
            print(f"[tpu] fallbacks by opclass: {top}")


def _result_status(result: TestcaseResult) -> StatusCode:
    if isinstance(result, Ok):
        return StatusCode.OK
    if isinstance(result, Timedout):
        return StatusCode.TIMEDOUT
    if isinstance(result, Cr3Change):
        return StatusCode.CR3_CHANGE
    if isinstance(result, OverlayFull):
        return StatusCode.OVERLAY_FULL
    return StatusCode.CRASH
