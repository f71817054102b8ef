"""What decides `correct`: everything the timed campaign itself wrote is
held to the EmuCpu oracle (the plain reference: wtf_tpu/cpu/emu.py shares
no code with the device step).

The campaign's answers are its finds: crashes/<name>, one file per crash
name the device reported, and outputs/, every testcase the device said
brought new coverage, which together carry the coverage count `cov` of its
heartbeat line.  All of them, not a sample, are replayed once through
`wtf_tpu run --backend emu` after the campaign has stopped: outputs/ by
ONE process in `run`'s order (the coverage union is that process's own
aggregate), crashes/, whose lines are compared by name alone, dealt into
disjoint directories that a process each replays at the same time
(`parts`, `gather`; the listings are merged by file):

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

import os
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
MAX_PARTS = 16
# a process is not started for fewer crashes than replay in the time it
# takes to start and to build the configuration's image: 64, and one more
# for every 8 MiB of image (an oracle process over the 4-GiB dump builds it
# for 11-16 s on the chip machine's host and replays a testcase in 3-25 ms)
MIN_CRASHES_A_PART = 64
IMAGE_BYTES_A_CRASH = 8 << 20
# room for what an oracle process holds besides the image's host rows
# (its peak is 0.16 GB on the 128-KiB image and 4.28 GB over the 4-GiB
# one: the sandbox's CPU, PR 42)
PROCESS_BYTES = 1 << 30


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


def gather(work: Path, dest: Path, last_heartbeat_ts: float,
           parts=None) -> list:
    """Lays the campaign's finds out for the replay and returns the
    manifest, one `{"file", "origin", "name", "dir"}` per testcase.
    outputs/ come first in `run`'s order (it sorts by name), and only those
    written up to the last heartbeat: the batches after it have no `cov` to
    be held to.  crashes/ are all in: whenever it was saved, a crash has to
    reproduce.  With `parts` None everything lies in `dest` itself;
    otherwise `parts(number of crashes)` says into how many directories
    `dest/crashes-NN` the crashes are dealt, one by one, so that the names
    the campaign saved side by side do not land in one part, and the
    outputs lie whole in `dest/outputs`.  A file is a hard link to the
    campaign's own (a copy where the filesystem refuses: a find costs the
    chip machine's checkout 1.7 ms to copy, and a run has 25,000)."""
    shutil.rmtree(dest, ignore_errors=True)
    dest.mkdir(parents=True)
    outputs = _finds(work / "outputs", last_heartbeat_ts)
    crashes = _finds(work / "crashes")
    n_parts = parts(len(crashes)) if parts else 0
    placed = ([("outputs", p, "outputs" if n_parts else ".")
               for p in outputs]
              + [("crashes", p, f"crashes-{k % n_parts:02d}" if n_parts
                  else ".") for k, p in enumerate(crashes)])
    for sub in dict.fromkeys(sub for _, _, sub in placed):
        (dest / sub).mkdir(exist_ok=True)
    manifest = []
    for i, (origin, path, sub) in enumerate(placed):
        file = f"{i:06d}.bin"
        try:
            os.link(path, dest / sub / file)
        except OSError:
            shutil.copyfile(path, dest / sub / file)
        manifest.append({"file": file, "origin": origin, "name": path.name,
                         "dir": sub})
    return manifest


def part_dirs(manifest: list, dest: Path) -> list:
    """The directories a replay process each reads, outputs' first."""
    return [dest / sub for sub in dict.fromkeys(m["dir"] for m in manifest)]


def free_memory_bytes() -> "int | None":
    """MemAvailable of /proc/meminfo; None where there is no such file."""
    try:
        with open("/proc/meminfo") as fh:
            for line in fh:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def parts(config: dict, n_crashes: int, cpus: "int | None" = None,
          free_bytes: "int | None" = None) -> int:
    """Into how many parts crashes/ is cut: what the host has (its cores
    and free memory, read here when not given), never the cell's name.
    One core and one image stay with the outputs' process; every process
    holds the configuration's image as host rows beside its own
    PROCESS_BYTES, and is worth starting for MIN_CRASHES_A_PART crashes and
    one more for every IMAGE_BYTES_A_CRASH of image."""
    cpus = cpus or os.cpu_count() or 1
    if free_bytes is None:
        free_bytes = free_memory_bytes()
    image = int(config.get("image_bytes")
                or config.get("image_pages", 0) * 4096)
    by_memory = MAX_PARTS
    if free_bytes is not None:
        by_memory = free_bytes // (image + PROCESS_BYTES) - 1
    worth = MIN_CRASHES_A_PART + image // IMAGE_BYTES_A_CRASH
    return max(1, min(MAX_PARTS, cpus - 1, by_memory, n_crashes // worth))


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
