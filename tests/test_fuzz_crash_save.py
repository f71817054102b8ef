"""A crash name's file is written once a campaign (FuzzLoop._save_crash).

`crashes/<name>` holds the FIRST testcase seen under the name, as
upstream's and the dist master's do; every crashing lane still gets its
`crash` event.  A name is held once its file stands in the crashes dir:
written by this process, or found there (one listing) at the process's
first crash, an earlier run's or a resumed campaign's; a save that
failed leaves it unheld, so the name's next crash tries again.
The fast cases run the real harvest over a backend that only hands out
results; the kill and resume cases run demo_tlv campaigns.
"""

import random
from pathlib import Path

import pytest

from wtf_tpu.analysis.trace import build_tlv_campaign
from wtf_tpu.core.results import Crash, Ok
from wtf_tpu.fuzz.corpus import Corpus
from wtf_tpu.fuzz.loop import FuzzLoop
from wtf_tpu.fuzz.mutator import ByteMutator
from wtf_tpu.resume import load_campaign, restore_campaign
from wtf_tpu.testing.faultinject import (
    FaultPlan, chaos_checkpoint_io, fuzz_until_killed,
)
from wtf_tpu.utils import atomicio


class _Results:
    """As much of a backend as the harvest asks of one: no register or
    memory seam, so a crash's bucket is its name."""

    n_lanes = 4

    def lane_found_new_coverage(self, lane):
        return False


class _Events:
    def __init__(self):
        self.records = []

    def emit(self, type, **fields):  # noqa: A002
        self.records.append({"type": type, **fields})

    def heartbeat(self, *args, **fields):
        pass

    def of(self, kind):
        return [r for r in self.records if r["type"] == kind]


@pytest.fixture
def writes(monkeypatch):
    """Every call of atomic_write_bytes, by destination name."""
    calls = []
    real = atomicio.atomic_write_bytes

    def counted(path, data, fsync=True):
        calls.append(Path(path).name)
        real(path, data, fsync=fsync)

    monkeypatch.setattr(atomicio, "atomic_write_bytes", counted)
    return calls


def _loop(crashes_dir, store=None):
    return FuzzLoop(_Results(), None, ByteMutator(random.Random(1), 64),
                    Corpus(), crashes_dir=crashes_dir, events=_Events(),
                    store=store)


def _harvest(loop, *lanes):
    """One batch: (name or None, testcase) per lane."""
    results = [Crash(name) if name else Ok() for name, _ in lanes]
    return loop._harvest_batch(results, [data for _, data in lanes])


def _second_crash_under_a_held_name(crashes_dir):
    loop = _loop(crashes_dir)
    _harvest(loop, ("crash-a", b"first"), (None, b"fine"))
    _harvest(loop, (None, b"fine"), ("crash-a", b"second"))
    return loop, {"crash-a": b"first"}, ["crash-a"], 0


def _same_name_twice_in_one_batch(crashes_dir):
    loop = _loop(crashes_dir)
    _harvest(loop, ("crash-a", b"first"), ("crash-b", b"other"),
             ("crash-a", b"second"), ("crash-a", b"third"))
    return loop, {"crash-a": b"first", "crash-b": b"other"}, \
        ["crash-a", "crash-b"], 0


def _name_found_on_disk_at_start(crashes_dir):
    crashes_dir.mkdir()
    (crashes_dir / "crash-a").write_bytes(b"an earlier run's")
    # a tmp file a killed writer left is no saved file
    (crashes_dir / ".crash-b.tmp.123").write_bytes(b"torn")
    loop = _loop(crashes_dir)
    _harvest(loop, ("crash-a", b"first"), ("crash-b", b"other"))
    (crashes_dir / ".crash-b.tmp.123").unlink()
    return loop, {"crash-a": b"an earlier run's", "crash-b": b"other"}, \
        ["crash-b"], 0


def _name_restored_by_resume(crashes_dir):
    """What `--resume` does to a fresh loop: the checkpoint's
    crash_names come back, and they also list a name whose save failed.
    Held is what stands in crashes/, not what the checkpoint lists."""
    killed = _loop(crashes_dir)
    _harvest(killed, ("crash-a", b"first"))
    with chaos_checkpoint_io(FaultPlan(write_faults=range(100))):
        _harvest(killed, ("crash-b", b"lost to a full disk"))
    checkpointed = sorted(killed.crash_names)
    assert checkpointed == ["crash-a", "crash-b"]
    loop = _loop(crashes_dir)
    loop.crash_names = set(checkpointed)
    loop.crash_buckets = set(killed.crash_buckets)
    loop.registry.restore_counters(
        killed.registry.counters_state(("campaign.",)))
    _harvest(loop, ("crash-a", b"second"), ("crash-b", b"other"))
    loop.events.records[:0] = killed.events.records
    return loop, {"crash-a": b"first", "crash-b": b"other"}, \
        ["crash-a", "crash-b", "crash-b"], 1


def _save_failed_with_enospc_then_retried(crashes_dir):
    loop = _loop(crashes_dir)
    plan = FaultPlan(write_faults=[0])
    with chaos_checkpoint_io(plan):
        _harvest(loop, ("crash-a", b"first"))      # ENOSPC: no file
        assert not (crashes_dir / "crash-a").exists()
        _harvest(loop, ("crash-a", b"second"))     # space is back
        _harvest(loop, ("crash-a", b"third"))
    assert plan.count_fired("write-fault") == 1
    error, = loop.events.of("error")
    assert error["kind"] == "crash-save" and error["name"] == "crash-a"
    assert "ENOSPC" in error["detail"]
    # the first testcase whose save went through stands
    return loop, {"crash-a": b"second"}, ["crash-a", "crash-a"], 1


@pytest.mark.parametrize("scenario", [
    _second_crash_under_a_held_name,
    _same_name_twice_in_one_batch,
    _name_found_on_disk_at_start,
    _name_restored_by_resume,
    _save_failed_with_enospc_then_retried,
], ids=lambda f: f.__name__.strip("_"))
def test_a_crash_names_file_is_written_once(scenario, tmp_path, writes):
    crashes_dir = tmp_path / "crashes"
    loop, on_disk, written, failed = scenario(crashes_dir)
    # one atomic write per name (one more for a save that failed)...
    assert writes == written
    # ...and what stands under a name is the first testcase seen under it
    assert {p.name: p.read_bytes() for p in crashes_dir.iterdir()} == on_disk
    # every crashing lane still has its event, in lane order
    stats = loop.stats
    events = loop.events.of("crash")
    assert len(events) == stats.crashes
    assert {e["name"] for e in events} == loop.crash_names == set(on_disk)
    news = [e["name"] for e in events if e["new"]]
    assert sorted(news) == sorted(set(news)) == sorted(loop.crash_buckets)
    # a crash wrote its file, found the name held, or failed to save
    assert (stats.crash_files_written + stats.crash_saves_skipped
            == stats.crashes - failed)
    assert len(loop.events.of("error")) == failed


def test_without_a_crashes_dir_nothing_is_written_or_counted(writes):
    loop = _loop(None)
    _harvest(loop, ("crash-a", b"first"), ("crash-a", b"second"))
    assert writes == [] and len(loop.events.of("crash")) == 2
    assert loop.stats.crashes == 2
    dump = loop.registry.dump()
    assert "campaign.crash_files_written" not in dump
    assert "campaign.crash_saves_skipped" not in dump


def _store_loop(tmp_path):
    from wtf_tpu.fleet.store import FleetStore
    from wtf_tpu.telemetry import Registry

    registry = Registry()
    store = FleetStore(tmp_path / "store", registry=registry)
    return _loop(tmp_path / "crashes", store=store), store, registry


def test_a_store_links_a_held_name_once(tmp_path, writes):
    """`--store`: crashes/<name> is a view of the journaled blob; a
    second crash under the name and the bucket journals and links
    nothing."""
    loop, store, _ = _store_loop(tmp_path)
    _harvest(loop, ("crash-a", b"first"), ("crash-a", b"second"))
    _harvest(loop, ("crash-a", b"third"))
    assert [rec["name"] for rec in store.records("crash")] == ["crash-a"]
    assert (tmp_path / "crashes" / "crash-a").read_bytes() == b"first"
    assert len(loop.events.of("crash")) == loop.stats.crashes == 3
    assert loop.stats.crash_files_written == 1
    assert loop.stats.crash_saves_skipped == 2


def test_a_store_journals_every_new_bucket_under_a_held_name(tmp_path):
    """One name can cover several triage buckets (two bugs faulting on
    the same wild address): every crash reaches the store, which keeps a
    blob and a journal record per NOVEL bucket, whether or not a file
    stands under the name.  Only the file under the name is made once."""
    loop, store, registry = _store_loop(tmp_path)

    def crash(name, data, bucket):
        loop._save_crash(data, Crash(name), bucket=bucket)

    crash("crash-a", b"first", "write|0x1000|aa")
    crash("crash-a", b"second", "write|0x2000|bb")   # new bug, held name
    crash("crash-a", b"third", "write|0x1000|aa")    # nothing new
    crash("crash-b", b"fourth", "write|0x2000|bb")   # known bug, new name
    records = list(store.records("crash"))
    assert [(r["name"], r["bucket"]) for r in records] == [
        ("crash-a", "write|0x1000|aa"), ("crash-a", "write|0x2000|bb")]
    assert [store.get(r["digest"]) for r in records] == [b"first", b"second"]
    counters = registry.dump()
    assert counters["fleet.store_puts"] == 2
    assert counters["fleet.bucket_dedup"] == 2
    # crashes/ holds the first testcase seen under the name; the store
    # kept no blob for crash-b, so no file stands under it and its crash
    # counts as neither written nor skipped (it is in fleet.bucket_dedup)
    assert _listing(tmp_path / "crashes") == {"crash-a": b"first"}
    assert [e["new"] for e in loop.events.of("crash")] == [
        True, True, False, False]
    assert loop.stats.crash_files_written == 1
    assert loop.stats.crash_saves_skipped == 2


# ---------------------------------------------------------------------------
# demo_tlv campaigns: a kill at a batch boundary, and a resume
# ---------------------------------------------------------------------------

# test_resume's shapes: the compile cache is shared across the suite
BUILD = dict(n_lanes=8, limit=20_000, chunk_steps=128, overlay_slots=16)
# a type-3 record that copies 32 bytes over an 8-byte stack buffer: its
# mutants `ret` into many wild addresses, and into the same one often
CRASHER = (b"\x01\x02XY" + bytes([3, 32]) + bytes(range(65, 89))
           + b"\x41" * 8 + b"\x01\x03ZZZ")
BATCHES = 6


def _campaign(crashes_dir, checkpoint_dir=None, checkpoint_every=0):
    loop = build_tlv_campaign(mutator="mangle", seed=0xAB, **BUILD)
    loop.corpus.add(CRASHER)
    loop.crashes_dir = crashes_dir
    crashes_dir.mkdir(exist_ok=True)
    loop.checkpoint_dir, loop.checkpoint_every = (checkpoint_dir,
                                                  checkpoint_every)
    loop.events = _Events()
    return loop


def _listing(crashes_dir):
    return {p.name: p.read_bytes() for p in crashes_dir.iterdir()}


@pytest.fixture(scope="module")
def uninterrupted(tmp_path_factory):
    loop = _campaign(tmp_path_factory.mktemp("ref") / "crashes")
    loop.fuzz(BUILD["n_lanes"] * BATCHES)
    assert loop.stats.crash_saves_skipped > 5     # names do repeat
    assert loop.stats.crash_files_written == len(loop.crash_names) > 5
    return loop


@pytest.mark.parametrize("kill_at", [1, 3, 5])
def test_every_name_in_a_crash_event_stands_on_disk_at_a_kill(
        kill_at, tmp_path, uninterrupted):
    """The file is made (tmp + fsync + rename) before its event: after a
    kill every announced name is there, whole, and no tmp file is."""
    victim = _campaign(tmp_path / "crashes")
    fuzz_until_killed(victim, BUILD["n_lanes"] * BATCHES, kill_at_batch=kill_at)
    events = victim.events.of("crash")
    assert events == uninterrupted.events.of("crash")[:len(events)]
    first_size = {}
    for event in events:
        first_size.setdefault(event["name"], event["size"])
    listing = _listing(tmp_path / "crashes")
    assert {n: len(d) for n, d in listing.items()} == first_size
    ref = _listing(uninterrupted.crashes_dir)
    assert all(ref[name] == data for name, data in listing.items())


def test_a_resumed_campaign_writes_only_the_names_it_does_not_find(
        tmp_path, uninterrupted, writes):
    """Checkpoint at batch 2, kill at 3: the resumed loop replays batch 3
    over the files the killed one left and writes none of them again;
    it ends on the uninterrupted campaign's crashes/ to the byte."""
    crashes_dir, ckpt = tmp_path / "crashes", tmp_path / "ck"
    victim = _campaign(crashes_dir, ckpt, checkpoint_every=2)
    fuzz_until_killed(victim, BUILD["n_lanes"] * BATCHES, kill_at_batch=3)
    left = _listing(crashes_dir)
    state, _ = load_campaign(ckpt)
    assert state["batches"] == 2 and set(state["crash_names"]) <= set(left)
    del writes[:]

    resumed = _campaign(crashes_dir, ckpt, checkpoint_every=2)
    restore_campaign(resumed, state, ckpt)
    resumed.fuzz(BUILD["n_lanes"] * BATCHES)
    ref = _listing(uninterrupted.crashes_dir)
    assert _listing(crashes_dir) == ref
    fresh = [name for name in writes if name.startswith("crash-")]
    assert sorted(fresh) == sorted(set(ref) - set(left))
    # the events of batches 3.. are the uninterrupted campaign's
    replayed = resumed.events.of("crash")
    assert replayed == uninterrupted.events.of("crash")[-len(replayed):]
    stats = resumed.stats
    assert stats.crashes == uninterrupted.stats.crashes
    assert (stats.crash_files_written + stats.crash_saves_skipped
            == stats.crashes)
