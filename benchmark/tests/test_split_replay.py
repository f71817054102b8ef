"""The replay over the host's cores on real finds: a 16-lane campaign of
demo_tlv on this machine's CPU, its crashes/ cut into P parts that a
process each replays at once.  Whatever P is, the merged listing and the
numbers compared are the one-process replay's; a wrong crash name is
caught in whichever part holds it; a part that dies fails the run; the
control is not correct.  The same campaign's last heartbeat holds the
closure that the retired `device_step_spanned_share` read."""

import os
import shutil
import subprocess
import sys

import pytest

import run as bench_run
from harness import cells, oracle, window

ROOT = cells.ROOT
CELL = cells.load_cell("tlv-default")
LIMIT = CELL.config["limit"]


@pytest.fixture(scope="module")
def campaign(tmp_path_factory):
    """{work, final, cov}: the finds of `tlv-default`'s campaign at 16
    lanes, 20 batches, and its last heartbeat."""
    work = tmp_path_factory.mktemp("campaign")
    (work / "inputs").mkdir()
    flags = CELL.campaign_flags()
    flags[flags.index("--lanes") + 1] = "16"
    proc = subprocess.run(
        [sys.executable, "-m", "wtf_tpu", "campaign", *flags,
         "--seed", str(CELL.window["campaign_seed"]), "--runs", "320",
         "--target", str(work), "--telemetry-dir", str(work / "telem")],
        env=bench_run.child_env(ROOT, "cpu"), cwd=str(ROOT),
        capture_output=True, text=True, timeout=900)
    # a campaign that found a crash exits 2, as `run` does
    assert proc.returncode in bench_run.RC_RUN_OK, proc.stderr[-2000:]
    final = bench_run.last_heartbeat(work / "telem" / "events.jsonl")
    crashes = list((work / "crashes").iterdir())
    assert len(crashes) >= 5, "the parts below need five crashes to deal"
    return {"work": work, "final": final, "cov": oracle.campaign_cov(final),
            "crashes": sorted(p.name for p in crashes)}


def replayed(campaign, dest, n_parts, limit=LIMIT, work=None):
    """The finds laid out in `n_parts` parts (0: one directory, one
    process, the replay as it was), replayed and compared."""
    manifest = oracle.gather(work or campaign["work"], dest,
                             campaign["final"]["ts"],
                             (lambda n: n_parts) if n_parts else None)
    with bench_run.Replay(CELL, oracle.part_dirs(manifest, dest), limit,
                          ROOT) as replaying:
        listing = replaying.listing()
    return manifest, listing, oracle.compare(manifest, listing,
                                             campaign["cov"])


@pytest.fixture(scope="module")
def one_process(campaign, tmp_path_factory):
    """The replay as it was: every find in one directory, one process."""
    manifest, listing, verdict = replayed(
        campaign, tmp_path_factory.mktemp("one") / "g", 0)
    assert verdict["correct"], verdict["differences"]
    assert verdict["compared"]["oracle_cov"] == campaign["cov"] > 0
    return manifest, listing, verdict


@pytest.mark.parametrize("n_parts", [1, 2, 5])
def test_the_split_replay_gives_the_one_process_replays_listing(
        campaign, one_process, tmp_path, n_parts):
    manifest, listing, verdict = replayed(campaign, tmp_path / "g", n_parts)
    assert [{k: v for k, v in m.items() if k != "dir"}
            for m in manifest] == [
        {k: v for k, v in m.items() if k != "dir"} for m in one_process[0]]
    # a crash's line is its name and the coverage new to its own process:
    # the name is what is compared, so hold the names and the outputs'
    # lines whole
    outputs = {m["file"] for m in manifest if m["origin"] == "outputs"}
    assert {f: line for f, line in listing.items() if f in outputs} == {
        f: line for f, line in one_process[1].items() if f in outputs}
    assert {f: line[0] for f, line in listing.items()} == {
        f: line[0] for f, line in one_process[1].items()}
    assert verdict["compared"] == one_process[2]["compared"]
    assert verdict["correct"]
    parts = sorted(p.name for p in (tmp_path / "g").iterdir() if p.is_dir())
    assert parts == [f"crashes-{k:02d}" for k in range(n_parts)] + [
        "outputs"]


@pytest.mark.parametrize("which", [0, 2, -1])
def test_a_wrong_crash_name_is_caught_in_whichever_part_holds_it(
        campaign, tmp_path, which):
    work = tmp_path / "work"
    shutil.copytree(campaign["work"] / "outputs", work / "outputs",
                    copy_function=shutil.copy2)
    shutil.copytree(campaign["work"] / "crashes", work / "crashes")
    name = campaign["crashes"][which]
    os.replace(work / "crashes" / name, work / "crashes" / (name + "-not"))
    manifest, _, verdict = replayed(campaign, tmp_path / "g", 5, work=work)
    assert not verdict["correct"]
    assert verdict["compared"]["crash_names_not_reproduced"] == {
        "value": 1, "limit": 0}
    planted = next(m for m in manifest if m["name"] == name + "-not")
    assert any(line.startswith(planted["file"])
               for line in verdict["differences"])
    # it lies in the part its place in crashes/ deals it to
    place = sorted(p.name for p in (work / "crashes").iterdir()).index(
        name + "-not")
    assert (tmp_path / "g" / f"crashes-{place % 5:02d}"
            / planted["file"]).is_file()


def test_a_part_that_dies_fails_the_run(campaign, tmp_path):
    manifest = oracle.gather(campaign["work"], tmp_path / "g",
                             campaign["final"]["ts"], lambda n: 2)
    dirs = oracle.part_dirs(manifest, tmp_path / "g")
    shutil.rmtree(dirs[2])                 # `run` finds no such input
    with pytest.raises(bench_run.RunFailed, match="crashes-01/ exited"):
        with bench_run.Replay(CELL, dirs, LIMIT, ROOT) as replaying:
            replaying.listing()
    assert all(proc.poll() is not None for proc, *_ in replaying.parts)


def test_the_control_goes_the_same_way_and_is_not_correct(campaign,
                                                          tmp_path):
    _, _, verdict = replayed(campaign, tmp_path / "g", 2,
                             limit=oracle.CONTROL_LIMIT)
    assert not verdict["correct"]
    assert verdict["compared"]["crash_names_not_reproduced"]["value"] >= 1
    assert verdict["compared"]["coverage_gap"]["value"] >= 1
    assert verdict["compared"]["listing_lines_missing"]["value"] == 0


def test_dispatch_and_wait_close_the_device_step_span(campaign):
    """What `device_step_spanned_share` read (99.99 in every line of the
    ledger) before PR 42 retired it: the span's two children sum to it."""
    first = {"ts": 0.0, "metrics": {}}
    closure = window.evaluate(
        {"scale": 100,
         "numerator": ["phase.seconds.execute/device-step/dispatch",
                       "phase.seconds.execute/device-step/wait"],
         "denominator": ["phase.seconds.execute/device-step"]},
        first, campaign["final"])
    assert 99.0 <= closure <= 100.0 + 1e-9
