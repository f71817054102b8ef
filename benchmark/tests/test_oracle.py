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


def test_gather_of_a_campaign_that_wrote_nothing_is_empty(tmp_path):
    assert oracle.gather(tmp_path, tmp_path / "g", 1.0) == []
