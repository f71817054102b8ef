"""What decides `correct`: everything the timed campaign itself wrote is
held to the EmuCpu oracle (the plain reference: wtf_tpu/cpu/emu.py shares
no code with the device step).

The campaign's answers are its finds: crashes/<name>, one file per crash
name the device reported, and outputs/, every testcase the device said
brought new coverage, which together carry the coverage count `cov` of its
heartbeat line.  All of them, not a sample, are replayed once through
`wtf_tpu run --backend emu` after the campaign has stopped:

- every crashes/ file has to crash on the oracle under the name the
  device saved it as;
- every outputs/ file the oracle crashes has to have its crash name in
  crashes/ (the device saw that crash too);
- the oracle's coverage of outputs/, as written up to the campaign's last
  heartbeat, has to count exactly the `cov` of that heartbeat.  (`run`
  prints per file the coverage new to its own aggregate, so the sum over
  the listing is the size of the union.)
- the campaign's own cross-check of the device decoder against the host
  decoder (`devdec.crosscheck_mismatches`) has to read 0 or be absent.

Every comparison is exact: limit 0.
"""

from __future__ import annotations

import re
import shutil
from pathlib import Path
from typing import Optional

LISTING_RE = re.compile(r"^(\S+): (.*) \(\|cov\| = (\d+)\)$")
COV_RE = re.compile(r"\bcov: (\d+)\b")
CRASH_RE = re.compile(r"^crash\((.*)\)$")
# the oracle's instruction limit in the control: a testcase is cut off
# after 8 instructions where every configuration states 100000
CONTROL_LIMIT = 8


def campaign_cov(heartbeat: dict) -> Optional[int]:
    """`cov` of a heartbeat's status line, `#N cov: C corp: ..`."""
    m = COV_RE.search(heartbeat.get("line", ""))
    return int(m.group(1)) if m else None


def _finds(directory: Path, not_after: Optional[float] = None) -> list:
    if not directory.is_dir():
        return []
    return [p for p in sorted(directory.iterdir())
            if p.is_file() and not p.name.startswith(".")
            and not p.name.endswith(".tmp")
            and (not_after is None or p.stat().st_mtime <= not_after)]


def gather(work: Path, dest: Path, last_heartbeat_ts: float) -> list:
    """Copies the campaign's finds into `dest` and returns the manifest,
    one `{"file", "origin", "name"}` per testcase.  outputs/ come first in
    `run`'s order (it sorts by name), and only those written up to the
    last heartbeat: the batches after it have no `cov` to be held to.
    crashes/ are all in: whenever it was saved, a crash has to reproduce."""
    shutil.rmtree(dest, ignore_errors=True)
    dest.mkdir(parents=True)
    manifest = []
    for origin, paths in (
            ("outputs", _finds(work / "outputs", last_heartbeat_ts)),
            ("crashes", _finds(work / "crashes"))):
        for path in paths:
            file = f"{len(manifest):06d}.bin"
            shutil.copyfile(path, dest / file)
            manifest.append({"file": file, "origin": origin,
                             "name": path.name})
    return manifest


def parse_listing(stdout: str) -> dict:
    """`<file>: <result> (|cov| = N)` lines of `wtf_tpu run`, by file."""
    out = {}
    for line in stdout.splitlines():
        m = LISTING_RE.match(line.strip())
        if m:
            out[m.group(1)] = (m.group(2), int(m.group(3)))
    return out


def compare(manifest: list, reference: dict, cov: Optional[int],
            devdec_mismatches: float = 0) -> dict:
    """The numbers compared, each against its limit of 0, and the lines
    that say what differed.  `reference` is the oracle's listing of the
    manifest's files, `cov` the campaign's own count at its last
    heartbeat.  `correct` needs every number at 0, a listing as long as
    the manifest, and a campaign that wrote something."""
    missing = names = unsaved = union = 0
    crash_names = {m["name"] for m in manifest if m["origin"] == "crashes"}
    lines = []
    for item in manifest:
        file = item["file"]
        ref = reference.get(file)
        if ref is None:
            missing += 1
            lines.append(f"{file} ({item['origin']}/{item['name']}): no "
                         "line from the oracle")
            continue
        crashed = CRASH_RE.match(ref[0])
        if item["origin"] == "crashes":
            # saved under the device result's name in the campaign: the
            # oracle has to give that testcase the same name
            if ref[0] != f"crash({item['name']})":
                names += 1
                lines.append(f"{file}: saved as crashes/{item['name']}, "
                             f"oracle says {ref[0]}")
        else:
            union += ref[1]
            if crashed and crashed.group(1) not in crash_names:
                unsaved += 1
                lines.append(f"{file} (outputs/{item['name']}): oracle says "
                             f"{ref[0]}, the campaign saved no such crash")
    gap = None if cov is None else abs(cov - union)
    if gap is None:
        lines.append("the campaign's last heartbeat has no `cov`")
    elif gap:
        lines.append(f"the campaign counts cov {cov}, the oracle covers "
                     f"{union} on its outputs/")
    if devdec_mismatches:
        lines.append("devdec.crosscheck_mismatches = "
                     f"{devdec_mismatches:g}: the device decoder published "
                     "entries the host decoder disagrees with")
    compared = {
        "testcases": len(manifest),
        "campaign_cov": cov,
        "oracle_cov": union,
        "listing_lines_missing": {"value": missing, "limit": 0},
        "crash_names_not_reproduced": {"value": names, "limit": 0},
        "oracle_crashes_not_saved": {"value": unsaved, "limit": 0},
        "coverage_gap": {"value": gap, "limit": 0},
        "devdec_crosscheck_mismatches": {"value": devdec_mismatches,
                                         "limit": 0},
    }
    ok = (bool(manifest) and gap == 0
          and not (missing or names or unsaved or devdec_mismatches))
    return {"correct": ok, "compared": compared, "differences": lines}
