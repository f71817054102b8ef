"""The standalone fuzz loop: mutate -> batch-execute -> harvest.

This is the single-process campaign driver — the reference needs a master
process + N client processes even on one machine (README.md:34-110); here
one process drives a whole device batch, and the distributed mode
(dist/client.py speaking to dist/server.py) reuses the same harvest logic
per node.

Per batch (the batched RunTestcaseAndRestore, client.cc:88-180):
  1. draw one testcase per lane from the mutator (corpus-seeded)
  2. backend.run_batch: insert + run every lane
  3. harvest: new-coverage lanes -> corpus + mutator cross-over seed;
     crashes -> crashes/<name>; timeouts already coverage-revoked
  4. target.restore + backend.restore

Telemetry: every batch phase is a span (mutate / execute / harvest /
restore tile run_one_batch; heartbeat and checkpoint the rest of the
loop, so the top-level totals account for the campaign's wall-clock),
harvest's children (account / crash-save / corpus-add, fetch on the
device path) tile harvest, counters live in the metrics registry behind
`CampaignStats`, and crash / new-coverage / timeout / heartbeat records
land in the JSONL event log when one is wired.
"""

from __future__ import annotations

import functools
import os
import time
from pathlib import Path
from typing import Optional

from wtf_tpu.core.results import (
    Cr3Change, Crash, OverlayFull, TestcaseResult, Timedout,
)
from wtf_tpu.fuzz.corpus import Corpus
from wtf_tpu.fuzz.mutator import Mutator
from wtf_tpu import telemetry
from wtf_tpu.telemetry import NULL, Registry
from wtf_tpu.utils.hashing import hex_digest
from wtf_tpu.utils.human import seconds_to_human


def _campaign_counter(name: str):
    """Property proxying one `campaign.<name>` registry counter, so the
    reference-shaped attribute API (`stats.crashes += 1`) stays while the
    value lives in the registry (one namespace for the heartbeat line,
    the JSONL dump, and print_run_stats)."""
    key = f"campaign.{name}"

    def fget(self):
        return self.registry.counter(key).value

    def fset(self, value):
        self.registry.counter(key).set(value)

    return property(fget, fset)


class CampaignStats:
    """Counters behind the status line (reference ServerStats_t / client
    stats, server.h:24-240, client.cc:7-84), registry-backed."""

    testcases = _campaign_counter("testcases")
    crashes = _campaign_counter("crashes")
    # crashes/<name> is made once a name: each crash either wrote it or
    # found the name held (the two sum to `crashes` where a crashes_dir is
    # set, less the saves that failed and, under a store, the blobs it
    # dropped for a known bucket: `error` events, fleet.bucket_dedup)
    crash_files_written = _campaign_counter("crash_files_written")
    crash_saves_skipped = _campaign_counter("crash_saves_skipped")
    timeouts = _campaign_counter("timeouts")
    cr3s = _campaign_counter("cr3s")
    overlay_fulls = _campaign_counter("overlay_fulls")
    new_coverage = _campaign_counter("new_coverage")

    def __init__(self, registry: Optional[Registry] = None):
        self.registry = registry if registry is not None else Registry()
        self.start = time.time()
        self.last_print = 0.0

    def execs_per_sec(self) -> float:
        dt = time.time() - self.start
        return self.testcases / dt if dt > 0 else 0.0

    def account(self, result: TestcaseResult) -> bool:
        """Count one testcase result; returns True for a crash (saving /
        requeueing is the caller's business).  The ONE accounting path
        shared by fuzz, minset, the dist master, and the dist clients."""
        self.testcases += 1
        if isinstance(result, Timedout):
            self.timeouts += 1
        elif isinstance(result, Cr3Change):
            self.cr3s += 1
        elif isinstance(result, OverlayFull):
            self.overlay_fulls += 1
        elif isinstance(result, Crash):
            self.crashes += 1
            return True
        return False

    def line(self, corpus_len: Optional[int] = None,
             cov: Optional[int] = None) -> str:
        """The human heartbeat line (format stable — downstream eyeballs
        and scripts parse it).  cov/corp are omitted by callers that
        don't track them (dist clients)."""
        uptime = seconds_to_human(time.time() - self.start)
        ovf = f" ovf: {self.overlay_fulls}" if self.overlay_fulls else ""
        mid = ""
        if cov is not None:
            mid += f"cov: {cov} "
        if corpus_len is not None:
            mid += f"corp: {corpus_len} "
        return (f"#{self.testcases} {mid}"
                f"exec/s: {self.execs_per_sec():.1f} "
                f"crash: {self.crashes} timeout: {self.timeouts} "
                f"cr3: {self.cr3s}{ovf} uptime: {uptime}")

    def maybe_heartbeat(self, events, registry=None, line_fn=None,
                        every: float = 10.0, print_stats: bool = False,
                        **fields) -> Optional[str]:
        """Throttled heartbeat — the ONE emission path shared by the fused
        loop, the dist master, and the dist nodes: at most one per `every`
        seconds, print() the human line when asked (print, not logging —
        the line must reach stdout even for library callers that never
        configure logging), and land a JSONL heartbeat record carrying
        the full registry dump.  Returns the line when one was emitted."""
        if not print_stats and (events is None or type(events) is type(NULL)):
            # nobody consumes the line: skip building it — line_fn can
            # cost a device coverage readback.  Exact-type check: EventLog
            # SUBCLASSES NullEventLog and must not match.
            return None
        now = time.time()
        if now - self.last_print < every:
            return None
        self.last_print = now
        line = line_fn() if line_fn is not None else self.line()
        if print_stats:
            print(line)
        events.heartbeat(registry, line=line, **fields)
        return line


class FuzzLoop:
    def __init__(
        self,
        backend,
        target,
        mutator: Mutator,
        corpus: Corpus,
        crashes_dir: Optional[Path] = None,
        batch_size: Optional[int] = None,
        stats_every: float = 10.0,
        registry: Optional[Registry] = None,
        events=None,
        checkpoint_dir: Optional[Path] = None,
        checkpoint_every: int = 0,
        store=None,
        megachunk: int = 0,
        xprof_dir: Optional[Path] = None,
        xprof_seconds: float = 0.25,
        xprof_seed: int = 0,
    ):
        self.backend = backend
        self.target = target
        self.mutator = mutator
        self.corpus = corpus
        self.crashes_dir = Path(crashes_dir) if crashes_dir else None
        if self.crashes_dir:
            self.crashes_dir.mkdir(parents=True, exist_ok=True)
        self.batch_size = batch_size or getattr(backend, "n_lanes", 1)
        # default onto the BACKEND's registry/events so runner spans nest
        # under this loop's execute phase and one dump carries everything
        self.registry, self.events = telemetry.resolve(
            backend, registry, events)
        # device-resident mutation engine (wtf_tpu/devmut): the whole
        # mutate->insert phase moves in-graph and batches run through
        # _run_one_batch_device.  bind() raises early for backends or
        # targets that can't take the device path.
        self.mutate_on_device = bool(getattr(mutator, "is_device", False))
        if self.mutate_on_device:
            mutator.bind(backend, target, registry=self.registry,
                         events=self.events)
            mutator.seed_from(corpus)
        # one-dispatch multi-batch windows (wtf_tpu/fuzz/megachunk.py):
        # generation + insert + the run ladder + the coverage merge +
        # restore fused into ONE compiled program per up-to-`megachunk`
        # batches; host work per batch collapses to the status pull and
        # the crash/new-coverage harvest
        self.megachunk = int(megachunk or 0)
        if self.megachunk:
            if not self.mutate_on_device:
                raise ValueError(
                    "--megachunk needs the device mutation engine "
                    "(--mutator devmangle): generation must live "
                    "in-graph for the window to fuse it")
            if not hasattr(backend, "run_megachunk"):
                raise ValueError(
                    "--megachunk requires the batched tpu backend")
            if not getattr(backend, "limit", 0):
                raise ValueError(
                    "--megachunk needs a nonzero --limit: the in-graph "
                    "run ladder quiesces on the instruction budget")
        self._runs_budget = 0
        self.stats = CampaignStats(self.registry)
        self.stats_every = stats_every
        self.crash_names = set()
        if self.crashes_dir:
            # registered here so that every heartbeat carries the pair
            self.registry.counter("campaign.crash_files_written")
            self.registry.counter("campaign.crash_saves_skipped")
        # triage-grade crash dedup (wtf_tpu/triage/bucket.py): found
        # crashes bucket by (kind, faulting RIP, top-of-stack hash), not
        # by output filename — two bugs faulting on the same wild
        # address stay distinct, and the minimizer's "same crash"
        # test agrees with the harvest's.  crash_names keeps tracking
        # the reference-shaped filenames the saves land under.
        self.crash_buckets = set()
        # overlay-exhausted testcases get ONE honest re-run (they executed
        # on truncated memory); a second exhaustion drops them — the input
        # genuinely needs more dirty pages than the lane has slots
        self._requeue: list = []
        self._requeue_digests = set()
        # crash-safe checkpointing (wtf_tpu/resume): every
        # `checkpoint_every` batches the minimal resumable state lands in
        # `checkpoint_dir` atomically; a kill at any point costs at most
        # one checkpoint interval, and --resume replays bit-identically
        self.checkpoint_dir = Path(checkpoint_dir) if checkpoint_dir else None
        self.checkpoint_every = checkpoint_every
        self.batches_done = 0
        # content-addressed corpus/crash store (wtf_tpu/fleet/store):
        # when attached, finds and crashes are journaled there and the
        # flat outputs//crashes/ dirs become hardlink views
        self.store = store
        if store is not None and getattr(corpus, "store", None) is None:
            corpus.store = store
        # elastic-campaign policy hook (wtf_tpu/fleet/elastic): a
        # callable(loop) -> Optional[int] consulted at every batch
        # boundary; returning a device count checkpoints the campaign
        # (PR-8 format) and hands control back to the driver, which
        # re-places it onto that many devices and resumes bit-identically
        self.reshard_policy = None
        self.reshard_to: Optional[int] = None
        # self-healing device runtime (wtf_tpu/supervise): when the
        # backend's supervisor is armed, every batch runs under the
        # snapshot -> dispatch -> (recover + replay)* -> post_batch
        # wrapper in run_one_batch; the ladder attaches lazily at the
        # first batch (the backend may not be initialized yet here)
        self.supervisor = getattr(backend, "supervisor", None)
        # --xprof-dir: ONE bounded jax.profiler window (telemetry/xprof.py)
        # opened inside the first steady-state batch, at a share of the
        # previous batch's length drawn from `xprof_seed` — device-level
        # truth under the span paths, which every span annotates into it
        self.xprof = None
        if xprof_dir:
            import random

            from wtf_tpu.telemetry.xprof import ProfilerWindow

            self.xprof = ProfilerWindow(
                xprof_dir, xprof_seconds,
                share=random.Random(xprof_seed).random(),
                open_path=self.registry.spans.open_path)
        if self.checkpoint_every and not hasattr(backend, "coverage_state"):
            # fail at construction, not at the first cadence hit deep
            # into a campaign (the checkpoint needs the batched backend's
            # device state seams)
            raise ValueError(
                "checkpointing requires the batched tpu backend "
                "(--backend=tpu); this backend has no coverage_state seam")

    def run_first(self, testcases) -> None:
        """Testcases to run as they are, ahead of the mutator's: what a
        campaign's corpus starts with (inputs/ files, a target's own
        seeds).  It stands in outputs/, which the oracle replays, so the
        campaign has to have run it; the reference master replays its
        seeds before it mutates (server.h:399-414)."""
        self._requeue.extend(testcases)

    def _account(self, data: bytes, result: TestcaseResult,
                 requeue: bool = False) -> bool:
        """Per-result accounting shared by fuzz and minset: counters via
        CampaignStats, optional overlay-full requeue.  True for a crash
        (the caller saves it: `_save_crash_lane`)."""
        if self.stats.account(result):
            return True
        if requeue and isinstance(result, OverlayFull):
            digest = hex_digest(data)
            if digest not in self._requeue_digests:
                self._requeue_digests.add(digest)
                self._requeue.append(data)
        return False

    def _save_crash_lane(self, lane: Optional[int], data: bytes,
                         result: Crash) -> None:
        """Bucket + save one crash (an fsync'd file and an event)."""
        from wtf_tpu.triage.bucket import bucket_of

        self._save_crash(data, result, bucket_of(self.backend, lane, result))

    def _harvest_batch(self, results, datas, requeue: bool = False,
                       flags=None) -> int:
        """The ONE harvest body shared by the host, device and megachunk
        batch paths, in three passes so each is ONE child span of
        `harvest` (never a span per lane): `account` (result accounting
        + optional overlay-full requeue + the new-coverage count),
        `crash-save` (bucket + save of every crashing lane, behind ONE
        `stack-prefetch` of the pages their buckets hash),
        `corpus-add` (the new-coverage -> corpus/mutator/event chain).
        `datas` maps lane -> bytes (a list, or the device paths' dict of
        fetched lanes);
        `flags` overrides the backend's last-batch new-coverage flags
        for callers harvesting several batches at once (the megachunk
        window's per-batch rows).  Returns the crash count."""
        from wtf_tpu.triage.bucket import prefetch_stacks

        spans = self.registry.spans
        if isinstance(datas, dict):
            def data_of(lane):
                return datas.get(lane, b"")
        else:
            data_of = datas.__getitem__
        crashed, fresh = [], []
        with spans.span("account"):
            for lane, result in enumerate(results):
                if self._account(data_of(lane), result, requeue=requeue):
                    crashed.append(lane)
                if (self.backend.lane_found_new_coverage(lane)
                        if flags is None else flags[lane]):
                    self.stats.new_coverage += 1
                    fresh.append(lane)
        with spans.span("crash-save"):
            with spans.span("stack-prefetch"):
                prefetch_stacks(self.backend, crashed)
            for lane in crashed:
                self._save_crash_lane(lane, data_of(lane), results[lane])
        with spans.span("corpus-add"):
            for lane in fresh:
                data = data_of(lane)
                if self.corpus.add(data):
                    self.mutator.on_new_coverage(data)
                    self.events.emit("new-coverage",
                                     digest=hex_digest(data),
                                     size=len(data))
        return len(crashed)

    def _emit_timeouts(self, timeouts_before: int) -> None:
        """Aggregated: one record per batch, not one per timed-out lane."""
        timeouts = self.stats.timeouts - timeouts_before
        if timeouts:
            self.events.emit("timeout", count=timeouts)

    def _restore_batch(self) -> None:
        with self.registry.spans.span("restore"):
            self.target.restore()
            self.backend.restore()

    def _supervised(self):
        """The armed supervisor, ladder attached — or None (the common,
        unsupervised case)."""
        sup = self.supervisor
        if sup is None or not sup.enabled:
            return None
        if sup.ladder is None:
            sup.attach_loop(self)
        return sup

    def run_one_batch(self) -> int:
        """Returns the number of crashes found in this batch (for a
        megachunk window: in the whole window; the window's extra
        completed batches advance `batches_done` internally).

        Under supervision (wtf_tpu/supervise) the batch body runs inside
        the recovery wrapper: a DispatchFailure (hang, device error,
        poisoned lane) rebuilds the device plane from the batch-boundary
        snapshot and REPLAYS the batch — bit-identical, because the
        failed attempt consumed no host randomness and its decode work
        is a prefix of the same deterministic stream."""
        sup = self._supervised()
        if sup is None:
            return self._dispatch_batch()
        from wtf_tpu.supervise import DispatchFailure

        sup.pre_batch(self)
        attempts = 0
        while True:
            try:
                crashes = self._dispatch_batch()
            except DispatchFailure as failure:
                attempts += 1
                if attempts > sup.max_batch_retries:
                    raise
                sup.recover(self, failure)
                continue
            sup.post_batch(self)
            return crashes

    def _dispatch_batch(self) -> int:
        if self.mutate_on_device:
            sup = self.supervisor
            if self.megachunk and not (
                    sup is not None and sup.megachunk_disabled):
                return self._run_megachunk_window()
            return self._run_one_batch_device()
        spans = self.registry.spans
        with spans.span("mutate"):
            requeued, self._requeue = (self._requeue[:self.batch_size],
                                       self._requeue[self.batch_size:])
            fresh = self.batch_size - len(requeued)
            if hasattr(self.mutator, "get_new_batch"):
                # native engines mutate the whole batch in one C call
                testcases = requeued + (self.mutator.get_new_batch(
                    self.corpus, fresh) if fresh else [])
            else:
                testcases = requeued + [
                    self.mutator.get_new_testcase(self.corpus)
                    for _ in range(fresh)]
        with spans.span("execute"):
            results = self.backend.run_batch(testcases, self.target)
        timeouts_before = self.stats.timeouts
        with spans.span("harvest"):
            crashes = self._harvest_batch(results, testcases, requeue=True)
        self._emit_timeouts(timeouts_before)
        self._restore_batch()
        return crashes

    def _run_one_batch_device(self) -> int:
        """The devmangle batch: generation + insertion are device
        programs, so `mutate`'s HOST share is dispatch overhead and the
        device wait is measured under the nested `mutate/device` span.
        Double-buffered: batch N+1's generation is prelaunched at the top
        of N's harvest, so by N+1's mutate fence the work has been
        overlapping host-side harvest/restore/heartbeat wall-clock (the
        slab it samples is as of N-1's finds — the one-batch lag of a
        pipelined generator).  Host code only pulls the lanes the
        harvest wants (crashes, new coverage); overlay-full requeue does
        not apply — the stream has no host bytes to requeue."""
        spans = self.registry.spans
        with spans.span("mutate"):
            with spans.span("device") as sp:
                _, lens = self.mutator.take_batch()
                sp.fence(lens)
        with spans.span("execute"):
            results = self.backend.run_batch_device(self.mutator,
                                                    self.target)
        timeouts_before = self.stats.timeouts
        with spans.span("harvest"):
            with spans.span("fetch"):
                # double-buffer: batch N+1 generates while we harvest
                # batch N
                self.mutator.prelaunch()
                wanted = [lane for lane, result in enumerate(results)
                          if self.backend.lane_found_new_coverage(lane)
                          or isinstance(result, Crash)]
                datas = self.mutator.fetch(wanted)
            crashes = self._harvest_batch(results, datas)
        self._emit_timeouts(timeouts_before)
        self._restore_batch()
        return crashes

    def _run_megachunk_window(self) -> int:
        """One megachunk window: up to `self.megachunk` whole batches in
        ONE compiled dispatch (restore/mutate/insert/execute/reduce all
        in-graph), then a host harvest of just the batches' finds.  The
        effective window is capped so batch boundaries still line up
        with the checkpoint cadence and the runs budget — a `--resume`
        from any such boundary stays bit-identical (PR-8 contract)."""
        spans = self.registry.spans
        # legacy->window handoff (megachunk re-promotion after a
        # degradation episode): a prelaunched legacy batch in flight is
        # discarded and the cursor rewound, so the window regenerates
        # the same stream index in-graph (DevMangleMutator.cancel_pending
        # — skipping it would skip one batch of the deterministic stream)
        self.mutator.cancel_pending()
        window = self.megachunk
        if self.checkpoint_every:
            window = min(window, self.checkpoint_every
                         - self.batches_done % self.checkpoint_every)
        if self._runs_budget:
            remaining = self._runs_budget - self.stats.testcases
            lanes = self.batch_size
            window = min(window, max(1, -(-int(remaining) // lanes)))
        with spans.span("execute"):
            # the mark draws the WHOLE one-dispatch window in the trace
            # timeline (--trace-out) — its extent against the device
            # leaves inside run_megachunk is the visual form of the
            # zero-host claim.  A trace-only mark, not a nested span:
            # the device wait must keep recording under the flat
            # execute/device path the host-share accounting reads.
            with spans.trace_mark("megachunk-window"):
                batches = self.backend.run_megachunk(
                    self.mutator, self.target, self.megachunk, window)
        crashes = 0
        timeouts_before = self.stats.timeouts
        with spans.span("harvest"):
            for j, (results, flags, datas) in enumerate(batches):
                if j == len(batches) - 1:
                    # pin the NEXT window's entitled slab view BEFORE
                    # the final batch's finds enter the corpus — the
                    # legacy prelaunch samples batch k+1's slab at
                    # exactly this point of batch k's harvest, and the
                    # bit-identical claim rides on reproducing it.  (The
                    # finds' bytes were fetched inside the window's
                    # execute/results; this is a slab upload.)
                    with spans.span("slab-sync"):
                        self.mutator.snapshot_entitled_slab()
                crashes += self._harvest_batch(results, datas, flags=flags)
        self._emit_timeouts(timeouts_before)
        with spans.span("restore"):
            # machine restore is in-graph (each batch's first phase);
            # only the target's host-side state rolls back here, ONCE
            # per window — megachunk targets are declarative-insert
            # targets whose restore carries no per-batch host state
            self.target.restore()
        # the caller (fuzz) advances batches_done by one per
        # run_one_batch; fold this window's extra completed batches in
        self.batches_done += len(batches) - 1
        return crashes

    def _save_crash(self, data: bytes, result: Crash,
                    bucket: Optional[str] = None) -> None:
        name = result.name or f"crash-{hex_digest(data)[:16]}"
        bucket = bucket or name
        new = bucket not in self.crash_buckets
        self.crash_buckets.add(bucket)
        self.crash_names.add(name)
        if self.crashes_dir:
            self._save_crash_file(name, data, bucket)
        self.events.emit("crash", name=name, size=len(data), new=new,
                         bucket=bucket)

    @functools.cached_property
    def _crash_files(self) -> set:
        """Names whose file stands in crashes_dir: those found there at
        this process's first crash (an earlier run's, a resumed
        campaign's; atomicio's tmp files are dot-names and never count)
        and those written since.  ONE listing, not a stat a crashing
        lane: on a network filesystem a stat is a round trip (0.2 ms on
        the chip machine's, 0.8 s a batch; PERF.md, PR 35).  Not taken
        from crash_names (the checkpoint's too), which also lists the
        names whose save failed."""
        return {name for name in os.listdir(self.crashes_dir)
                if not name.startswith(".")}

    def _save_crash_file(self, name: str, data: bytes, bucket: str) -> None:
        """crashes/<name> is made ONCE a campaign, as upstream's and the
        dist master's: the first testcase seen under the name stands
        (either reproduces it).  A save that failed leaves the name to
        its next crash: a full disk loses nothing once space returns."""
        from wtf_tpu.utils.atomicio import atomic_write_bytes

        try:
            # atomic (tmp+fsync+rename): a kill mid-save must not
            # leave a torn repro, and a full disk must not abort the
            # campaign from inside the harvest loop (same contract
            # as the dist master's crash save).  With a store the
            # blob is journaled content-addressed (bucket-deduped)
            # and crashes/<name> becomes a view of it — names stay
            # reference-shaped for the single-process driver.  EVERY
            # crash reaches the store: a held name may hide a new bucket.
            if self.store is not None:
                digest, _ = self.store.put(data, kind="crash",
                                           name=name, bucket=bucket)
            if name in self._crash_files:
                self.stats.crash_saves_skipped += 1
                return
            if self.store is None:
                atomic_write_bytes(self.crashes_dir / name, data)
            elif self.store.has(digest):
                self.store.link_into(self.crashes_dir, digest, name=name)
            else:
                # the store holds the bucket under another name and kept
                # no blob (fleet.bucket_dedup): no file, neither counter
                return
        except OSError as e:
            import logging

            logging.getLogger(__name__).warning(
                "crash save failed for %r: %s", name, e)
            self.events.emit("error", kind="crash-save", name=name,
                             detail=str(e))
            return
        self._crash_files.add(name)
        self.stats.crash_files_written += 1

    def _peek(self, name: str):
        """Counter value WITHOUT registering it — the heartbeat must not
        seed zero-valued metrics into dumps of campaigns that never
        touched the subsystem."""
        metric = self.registry._metrics.get(name)
        return metric.value if metric is not None else 0

    def steady_state_fields(self) -> dict:
        """The PR-14 zero-host steady-state numbers, as heartbeat fields
        — live visibility for the claim telemetry_report proves
        post-mortem.  Empty for campaigns that never ran a window."""
        fields = {}
        windows = self._peek("megachunk.windows")
        if windows:
            fields["zero_host_window_rate"] = round(
                self._peek("devdec.zero_host_windows") / windows, 3)
        prelaunched = self._peek("megachunk.prelaunched")
        if prelaunched:
            fields["prelaunch_hits"] = self._peek(
                "megachunk.prelaunch_hits")
            fields["prelaunch_dropped"] = self._peek(
                "megachunk.prelaunch_dropped")
        crosschecks = self._peek("devdec.crosscheck_mismatches")
        if self._peek("devdec.published") or crosschecks:
            fields["devdec_crosscheck_mismatches"] = crosschecks
        return fields

    def _steady_line_suffix(self, fields: dict) -> str:
        """The same numbers on the human line — shown only when the
        campaign runs windows, so plain-campaign line format is
        untouched."""
        out = ""
        if "zero_host_window_rate" in fields:
            out += f" zh: {fields['zero_host_window_rate']:.0%}"
        if "prelaunch_hits" in fields:
            launched = self._peek("megachunk.prelaunched")
            out += f" pre: {fields['prelaunch_hits']}/{launched}"
            if fields.get("prelaunch_dropped"):
                out += f"(-{fields['prelaunch_dropped']})"
        return out

    def _heartbeat(self, print_stats: bool) -> None:
        """stats_every cadence: the stable human line + one JSONL
        heartbeat carrying the full registry dump (per-phase span totals
        included) + an atomic status.json refresh next to the event log
        (what `wtf-tpu status` tails on a live local campaign)."""
        # a top-level span: the coverage readback behind the line and the
        # status.json write are wall clock between two batches.  The
        # record's own dump is taken inside it, so a heartbeat's seconds
        # show from the next record on.
        with self.registry.spans.span("heartbeat"):
            fields = (self.supervisor.heartbeat_fields()
                      if self.supervisor is not None
                      and self.supervisor.enabled else {})
            steady = self.steady_state_fields()
            fields.update(steady)
            line = self.stats.maybe_heartbeat(
                self.events, self.registry,
                lambda: self.stats.line(len(self.corpus), self._coverage())
                + self._steady_line_suffix(steady),
                every=self.stats_every, print_stats=print_stats, **fields)
            if line is not None:
                self._write_status(line)

    def _write_status(self, line: str) -> None:
        """status.json beside events.jsonl, atomically replaced every
        heartbeat — readers (wtf-tpu status --watch) always see either
        the previous complete document or this one, never a torn
        middle.  Best-effort like every telemetry side channel."""
        path = getattr(self.events, "path", None)
        if path is None:
            return
        import json

        from wtf_tpu.utils.atomicio import atomic_write_text

        doc = {"kind": "campaign", "ts": time.time(), "line": line,
               "batches": self.batches_done,
               "metrics": self.registry.dump()}
        try:
            atomic_write_text(Path(path).parent / "status.json",
                              json.dumps(doc, default=str), fsync=False)
        except OSError:
            pass

    def _emit_xprof(self, record: Optional[dict]) -> None:
        if record is None:
            return
        if "error" in record:
            import logging

            logging.getLogger(__name__).warning(
                "xprof trace unavailable: %s", record["error"])
            self.events.emit("error", kind="xprof", detail=record["error"])
            return
        self.events.emit("xprof", batch=self.batches_done, **record)

    def minset(self, outputs_dir, print_stats: bool = False) -> Corpus:
        """`--runs=0` mode: replay the seed corpus exactly once — no
        mutation — and write the coverage-increasing subset to outputs/
        (the reference master's minset, server.h:552-556; seeds are
        visited biggest-first per Corpus.load_dir, so the subset is
        coverage-minimal under that ordering).  Returns the kept Corpus
        (callers prune subsumed stale files with its digest set).

        Runs on the triage batch-replay core (wtf_tpu/triage/replay.py)
        — minset and `triage distill` share ONE batched execution path;
        the accounting, span names, keep rule (first-hit credit via the
        backend's batch merge) and printed stats are unchanged."""
        from wtf_tpu.triage.replay import ReplayCore

        # Corpus handles digest-named persistence + dedup; outputs_dir=None
        # (no outputs configured) counts without writing
        kept = Corpus(outputs_dir=outputs_dir)
        core = ReplayCore(self.backend, self.target,
                          registry=self.registry, events=self.events,
                          batch_size=self.batch_size)

        def harvest(start, batch, results):
            for lane, (data, result) in enumerate(zip(batch, results)):
                if self._account(data, result):
                    self._save_crash_lane(lane, data, result)
                if self.backend.lane_found_new_coverage(lane):
                    self.stats.new_coverage += 1
                    kept.add(data)

        core.replay(list(self.corpus), on_batch=harvest,
                    after_batch=lambda: self._heartbeat(print_stats))
        return kept

    def fuzz(self, runs: int, print_stats: bool = False,
             stop_on_crash: bool = False) -> CampaignStats:
        """Run until `runs` testcases executed (0 = forever; the CLI maps
        --runs=0 to `minset` instead, matching the reference)."""
        self.reshard_to = None
        self._runs_budget = runs
        try:
            while runs == 0 or self.stats.testcases < runs:
                if self.xprof is not None:
                    self._emit_xprof(
                        self.xprof.at_batch_boundary(self.batches_done))
                found = self.run_one_batch()
                self.batches_done += 1
                self._maybe_checkpoint()
                if self._maybe_reshard():
                    break
                self._heartbeat(print_stats)
                if stop_on_crash and found:
                    break
        finally:
            if self.xprof is not None:
                self._emit_xprof(self.xprof.finish())
        return self.stats

    def _maybe_reshard(self) -> bool:
        """The elastic-campaign policy hook (wtf_tpu/fleet/elastic): at
        each batch boundary the policy may name a new device count; the
        loop then checkpoints (PR-8 format — placement-free) and stops,
        leaving `reshard_to` for the driver to rebuild against.  True
        when a reshard was requested."""
        if self.reshard_policy is None:
            return False
        want = self.reshard_policy(self)
        if want is None:
            return False
        if self.checkpoint_dir is None:
            raise ValueError("resharding needs a checkpoint_dir")
        from wtf_tpu.resume import save_campaign

        self.reshard_to = int(want)
        # count BEFORE the save: the checkpoint's counter state carries
        # the reshard tally across placements (telemetry continuity)
        self.registry.counter("campaign.reshards").inc()
        self.events.emit("reshard", batch=self.batches_done,
                         devices=self.reshard_to,
                         testcases=self.stats.testcases)
        with self.registry.spans.span("checkpoint"):
            save_campaign(self, self.checkpoint_dir)
        return True

    def _maybe_checkpoint(self) -> None:
        """--checkpoint-every cadence: persist the resumable state at the
        batch boundary (wtf_tpu/resume).  Best-effort like every other
        persistence side channel — a full disk degrades checkpointing
        with a warning + error event, it never aborts the campaign."""
        if not (self.checkpoint_dir and self.checkpoint_every):
            return
        if self.batches_done % self.checkpoint_every:
            return
        from wtf_tpu.resume import save_campaign

        spans = self.registry.spans
        before = spans.seconds("checkpoint")
        try:
            with spans.span("checkpoint"):
                info = save_campaign(self, self.checkpoint_dir)
        except OSError as e:
            import logging

            logging.getLogger(__name__).warning(
                "checkpoint write failed at batch %d: %s",
                self.batches_done, e)
            self.events.emit("error", kind="checkpoint-write",
                             batch=self.batches_done, detail=str(e))
            return
        self.registry.counter("campaign.checkpoints").inc()
        self.events.emit("checkpoint", batch=self.batches_done,
                         bytes=info["bytes"], path=info["path"],
                         seconds=round(spans.seconds("checkpoint")
                                       - before, 4))

    def _coverage(self) -> int:
        try:
            import numpy as np

            return int(np.count_nonzero(
                np.unpackbits(
                    np.asarray(self.backend._agg_cov).view("uint8"))))
        except Exception:
            return len(getattr(self.backend, "_aggregate_cov", ()))
