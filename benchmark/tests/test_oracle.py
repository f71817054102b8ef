"""The comparison that decides `correct`, and the gathering of the finds."""

import os

import pytest

from harness import oracle

LISTING = """\
000000.bin: ok (|cov| = 27)
000001.bin: crash(crash-av-write-0x41) (|cov| = 4)
000002.bin: ok (|cov| = 0)
000003.bin: crash(crash-av-write-0x41) (|cov| = 0)
000004.bin: crash(crash-execute-0x7) (|cov| = 2)
some other line
"""
MANIFEST = [
    {"file": "000000.bin", "origin": "outputs", "name": "ab12"},
    {"file": "000001.bin", "origin": "outputs", "name": "cd34"},
    {"file": "000002.bin", "origin": "outputs", "name": "ef56"},
    {"file": "000003.bin", "origin": "crashes", "name": "crash-av-write-0x41"},
    {"file": "000004.bin", "origin": "crashes", "name": "crash-execute-0x7"},
]


def test_a_campaign_the_oracle_reproduces_is_correct():
    ref = oracle.parse_listing(LISTING)
    assert ref["000001.bin"] == ("crash(crash-av-write-0x41)", 4)
    v = oracle.compare(MANIFEST, ref, cov=31)
    assert v["correct"] and not v["differences"]
    assert v["compared"]["oracle_cov"] == 31      # outputs/ only: 27 + 4
    assert all(c["value"] == 0 == c["limit"]
               for c in v["compared"].values() if isinstance(c, dict))


@pytest.mark.parametrize("edit, cov, mismatches, counter, value", [
    # one answer changed where it is produced: a crash saved under
    # another name than the oracle gives it
    (("000003.bin: crash(crash-av-write-0x41)",
      "000003.bin: crash(crash-av-read-0x41)"), 31, 0,
     "crash_names_not_reproduced", 1),
    (("000004.bin: crash(crash-execute-0x7)", "000004.bin: ok"), 31, 0,
     "crash_names_not_reproduced", 1),
    # the device missed a crash the oracle sees in a saved output
    (("000002.bin: ok", "000002.bin: crash(crash-int3-0x9)"), 31, 0,
     "oracle_crashes_not_saved", 1),
    # one coverage bit more or less than the oracle counts
    (("(|cov| = 27)", "(|cov| = 28)"), 31, 0, "coverage_gap", 1),
    (("", ""), 33, 0, "coverage_gap", 2),
    # a listing shorter than the finds
    (("000002.bin: ok (|cov| = 0)\n", ""), 31, 0,
     "listing_lines_missing", 1),
    # the campaign's own cross-check of the device decoder
    (("", ""), 31, 58, "devdec_crosscheck_mismatches", 58),
])
def test_one_thing_off_is_not_correct(edit, cov, mismatches, counter, value):
    ref = oracle.parse_listing(LISTING.replace(*edit))
    v = oracle.compare(MANIFEST, ref, cov, mismatches)
    assert not v["correct"]
    assert v["compared"][counter]["value"] == value and v["differences"]


def test_no_cov_in_the_heartbeat_or_no_find_is_not_correct():
    ref = oracle.parse_listing(LISTING)
    assert not oracle.compare(MANIFEST, ref, cov=None)["correct"]
    assert not oracle.compare([], {}, cov=0)["correct"]


def test_cov_is_read_from_the_heartbeats_line():
    line = "#15360 cov: 51 corp: 1960 exec/s: 131.1 crash: 12833 timeout: 0"
    assert oracle.campaign_cov({"line": line}) == 51
    assert oracle.campaign_cov({"line": "no such field"}) is None
    assert oracle.campaign_cov({}) is None


def test_gather_takes_every_find_outputs_first_up_to_the_heartbeat(tmp_path):
    for sub, names in (("outputs", ["d0", "d1", "late", ".d2.tmp"]),
                       ("crashes", ["crash-a", "crash-late"])):
        (tmp_path / sub).mkdir()
        for i, name in enumerate(names):
            (tmp_path / sub / name).write_bytes(bytes([i]) * (i + 1))
    for late in ("outputs/late", "crashes/crash-late"):
        os.utime(tmp_path / late, (2000.0, 2000.0))
    for early in ("outputs/d0", "outputs/d1", "crashes/crash-a"):
        os.utime(tmp_path / early, (1000.0, 1000.0))
    got = oracle.gather(tmp_path, tmp_path / "g", last_heartbeat_ts=1500.0)
    assert [(m["origin"], m["name"]) for m in got] == [
        ("outputs", "d0"), ("outputs", "d1"),
        ("crashes", "crash-a"), ("crashes", "crash-late")]
    files = sorted(p.name for p in (tmp_path / "g").iterdir())
    assert files == [m["file"] for m in got]          # `run` sorts by name
    assert (tmp_path / "g" / got[1]["file"]).read_bytes() == b"\x01\x01"
    # without `parts` everything lies in the one directory
    assert oracle.part_dirs(got, tmp_path / "g") == [tmp_path / "g"]
    # a find is a link to the campaign's own file, not a second copy
    assert (tmp_path / "g" / got[0]["file"]).samefile(
        tmp_path / "outputs" / "d0")


def test_gather_of_a_campaign_that_wrote_nothing_is_empty(tmp_path):
    assert oracle.gather(tmp_path, tmp_path / "g", 1.0) == []


GIB = 1 << 30
SMALL = {"image_pages": 32}
DUMP = {"image_bytes": 4 * GIB}


@pytest.mark.parametrize("config, crashes, cpus, free, want", [
    (SMALL, 3000, 13, 36 * GIB, 12),     # a core stays with outputs/
    (SMALL, 3000, 30, 130 * GIB, 16),    # never more than MAX_PARTS
    (SMALL, 200, 13, 36 * GIB, 3),       # nor than 64 crashes a part give
    (SMALL, 5, 13, 36 * GIB, 1),
    (SMALL, 0, 13, 36 * GIB, 1),
    (SMALL, 3000, 1, 36 * GIB, 1),
    # a process over a dump builds 4 GiB before its first testcase: it is
    # worth 64 + 512 crashes
    (DUMP, 3000, 13, 36 * GIB, 5),
    (DUMP, 1610, 13, 36 * GIB, 2),
    (DUMP, 230, 13, 36 * GIB, 1),
    # and holds them beside PROCESS_BYTES: 36 GiB hold seven processes,
    # one of them the outputs'
    (DUMP, 30000, 13, 36 * GIB, 6),
    (DUMP, 30000, 30, 130 * GIB, 16),
    (DUMP, 30000, 13, 6 * GIB, 1),
])
def test_parts_follow_from_the_hosts_cores_and_memory(config, crashes, cpus,
                                                      free, want):
    assert oracle.parts(config, crashes, cpus, free) == want


def test_cores_and_free_memory_are_the_hosts_own_when_not_given():
    free = oracle.free_memory_bytes()
    assert free is None or free > 0
    assert oracle.parts(SMALL, 3000) == oracle.parts(
        SMALL, 3000, os.cpu_count(), free)


def test_gather_keeps_outputs_whole_and_deals_crashes_to_disjoint_parts(
        tmp_path):
    work = tmp_path / "work"
    for sub, names in (("outputs", ["o0", "o1", "o2"]),
                       ("crashes", [f"crash-{i}" for i in range(7)])):
        (work / sub).mkdir(parents=True)
        for name in names:
            (work / sub / name).write_text(name)
    dest = tmp_path / "g"
    asked = []
    manifest = oracle.gather(work, dest, 2e9,
                             lambda n: asked.append(n) or 3)
    assert asked == [7]                    # the number of crashes
    dirs = oracle.part_dirs(manifest, dest)
    assert [d.name for d in dirs] == ["outputs", "crashes-00", "crashes-01",
                                      "crashes-02"]
    held = {d.name: sorted(p.name for p in d.iterdir()) for d in dirs}
    # outputs/ in `run`'s order, in one directory
    assert held["outputs"] == ["000000.bin", "000001.bin", "000002.bin"]
    # crashes dealt one by one: 3 + 2 + 2, every file in exactly one part
    assert [len(held[f"crashes-0{k}"]) for k in range(3)] == [3, 2, 2]
    everywhere = sorted(f for files in held.values() for f in files)
    assert everywhere == [m["file"] for m in manifest]
    assert held["crashes-00"] == ["000003.bin", "000006.bin", "000009.bin"]
    assert [m["dir"] for m in manifest[:4]] == ["outputs"] * 3 + [
        "crashes-00"]
    assert not [p for p in dest.iterdir() if p.is_file()]
    assert (dest / "crashes-01" / "000004.bin").read_text() == "crash-1"


def test_a_campaign_without_crashes_is_laid_out_as_its_outputs_alone(
        tmp_path):
    (tmp_path / "work" / "outputs").mkdir(parents=True)
    (tmp_path / "work" / "outputs" / "o0").write_text("x")
    manifest = oracle.gather(tmp_path / "work", tmp_path / "g", 2e9,
                             lambda n: max(1, n))
    assert [d.name for d in oracle.part_dirs(manifest, tmp_path / "g")] == [
        "outputs"]
