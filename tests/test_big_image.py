"""The image path at a deployment's size, at a small size on the CPU: the
array-based PhysMem builder against a naive packer, the host readers that
never touch the device, the dump parsers as arrays, and demo_tlv inside a
Windows-shaped full dump (harness/demo_tlv_dump.py, here 64 MiB where the
`big-image` cell holds 4 GiB): its page tables against a naive walker, the
device against the EmuCpu oracle, and one campaign on both targets."""

import contextlib
import dataclasses
import hashlib
import io
import random
import struct
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from test_tlv_default import (SMASHES, device_batch, mutator_batch,
                              oracle_run, same, smashing_record)
from wtf_tpu.backend import create_backend
from wtf_tpu.core.results import Crash
from wtf_tpu.harness import demo_tlv, demo_tlv_dump
from wtf_tpu.harness.targets import Targets
from wtf_tpu.mem import physmem
from wtf_tpu.mem.overlay import overlay_init
from wtf_tpu.mem.paging import translate_vec
from wtf_tpu.mem.physmem import PAGE_WORDS, ROW_GRANULE, PhysMem
from wtf_tpu.snapshot import kdmp
from wtf_tpu.snapshot.loader import Snapshot, load_snapshot
from wtf_tpu.snapshot.synthetic import KERNEL_BASE, RamLayout
from wtf_tpu.telemetry import Registry
from wtf_tpu.telemetry.events import read_events

PAGE = 4096
SMALL_RAM = 64 << 20
LANES = 32
LIMIT = 100_000


# -- (a) the array builder against a naive packer --------------------------

def naive_pack(pages: dict):
    """{pfn: bytes} -> (rows u8[n + 1, 4096] with row 0 zero, {pfn: row}):
    the packing rule, a page at a time."""
    rows = [bytes(PAGE)]
    where = {}
    for pfn in sorted(pages):
        where[pfn] = len(rows)
        rows.append(pages[pfn])
    return np.frombuffer(b"".join(rows), dtype=np.uint8).reshape(-1, PAGE), \
        where


def naive_read(pages: dict, gpa: int, size: int) -> bytes:
    return bytes(pages.get((gpa + i) >> 12, bytes(PAGE))[(gpa + i) & 0xFFF]
                 for i in range(size))


def frame_set(kind: str, seed: int):
    """(pfns in the order given to the builder, pages u8[n, 4096])."""
    rng = np.random.default_rng(seed)
    if kind == "sparse":
        pfns = rng.choice(1 << 20, size=37, replace=False)
    elif kind == "dense":
        pfns = np.arange(200, 200 + 300)
    elif kind == "dense-with-hole":
        pfns = np.concatenate([np.arange(64), np.arange(96, 160)])
    elif kind == "unordered":
        pfns = rng.permutation(np.arange(1000, 1100))
    elif kind == "above-a-granule":
        pfns = np.sort(rng.choice(3 * ROW_GRANULE, size=ROW_GRANULE + 70,
                                  replace=False))
    else:
        raise AssertionError(kind)
    pages = rng.integers(0, 256, size=(len(pfns), PAGE), dtype=np.uint8)
    return pfns.astype(np.int64), pages


FRAME_SETS = [("sparse", 1), ("sparse", 2), ("dense", 3),
              ("dense-with-hole", 4), ("unordered", 5),
              ("above-a-granule", 6)]


@pytest.fixture(scope="module", params=FRAME_SETS,
                ids=[f"{k}-{s}" for k, s in FRAME_SETS])
def packed(request):
    pfns, pages = frame_set(*request.param)
    as_dict = {int(p): pages[i].tobytes() for i, p in enumerate(pfns)}
    return PhysMem.from_arrays(pfns, pages), as_dict


def test_rows_and_frame_table_equal_the_naive_packer(packed):
    mem, pages = packed
    rows, where = naive_pack(pages)
    n = len(pages)
    assert np.array_equal(mem.rows.view(np.uint8)[:n + 1], rows)
    assert not mem.rows[n + 1:].any(), "padding rows read as zeros"
    table = mem.table
    assert {int(p): int(table[p]) for p in np.nonzero(table)[0]} == where
    assert mem.frames_present == n
    assert list(mem.present_pfns()) == sorted(pages)


def test_rows_are_padded_to_a_granule_and_never_doubled(packed):
    mem, pages = packed
    n_rows, need = len(mem.rows), len(pages) + 1
    assert n_rows >= need
    if need <= ROW_GRANULE:
        assert n_rows == physmem._next_pow2(need)
    else:
        assert n_rows - need < ROW_GRANULE and n_rows % ROW_GRANULE == 0
    assert len(mem.table) > max(pages)
    assert mem.nbytes == n_rows * PAGE + len(mem.table) * 4


def test_host_read_across_pages_and_holes_bit_for_bit(packed):
    mem, pages = packed
    rng = random.Random(7)
    pfns = sorted(pages)
    spots = [p << 12 for p in pfns[:3] + pfns[-3:]]
    spots += [(rng.choice(pfns) << 12) + rng.randrange(PAGE)
              for _ in range(40)]
    for gpa in spots:
        for start, size in ((gpa - 3, 11), (gpa + PAGE - 5, 9),
                            (gpa, 2 * PAGE + 1)):
            start = max(start, 0)
            assert mem.host_read(start, size) == \
                naive_read(pages, start, size), hex(start)
    beyond = len(mem.table) << 12
    assert mem.host_read(beyond - 2, 8) == naive_read(pages, beyond - 2, 8)


def test_the_dict_and_the_u64_builders_give_the_same_image(packed):
    mem, pages = packed
    again = PhysMem.from_pages(pages)
    assert np.array_equal(again.rows, mem.rows)
    assert np.array_equal(again.table, mem.table)
    pfns = np.array(sorted(pages), dtype=np.int64)
    words = np.frombuffer(b"".join(pages[int(p)] for p in pfns),
                          dtype=np.uint64).reshape(-1, PAGE_WORDS)
    third = PhysMem.from_arrays(pfns, words)
    assert np.array_equal(third.rows, mem.rows)


def test_the_device_image_is_the_host_rows_as_limb_pairs(packed):
    mem, _ = packed
    assert not mem.on_device
    image = mem.image
    assert mem.on_device and image.pages.dtype == jnp.uint32
    assert image.pages.shape == (len(mem.rows), 2 * PAGE_WORDS)
    assert np.asarray(image.pages).tobytes() == mem.rows.tobytes()
    assert np.array_equal(np.asarray(image.frame_table)[0], mem.table)


@pytest.mark.parametrize("bad", ["size", "duplicate", "dtype", "negative"])
def test_the_builders_refuse_what_is_not_a_frame_set(bad):
    pfns, pages = frame_set("dense", 9)
    with pytest.raises(ValueError):
        if bad == "size":
            PhysMem.from_pages({1: b"\0" * 100})
        elif bad == "duplicate":
            pfns[1] = pfns[0]
            PhysMem.from_arrays(pfns, pages)
        elif bad == "dtype":
            PhysMem.from_arrays(pfns, pages.astype(np.uint16))
        else:
            pfns[0] = -1
            PhysMem.from_arrays(pfns, pages)


# -- the small dump ---------------------------------------------------------

@pytest.fixture(scope="module")
def dump() -> Snapshot:
    return demo_tlv_dump.build_snapshot(SMALL_RAM)


@pytest.fixture(scope="module")
def layout():
    return RamLayout.of_bytes(SMALL_RAM)


def test_the_dump_is_windows_shaped(dump, layout):
    mem = dump.physmem
    assert layout.frames == 16384 and layout.span == 16384 + 512
    assert mem.frames_present == layout.frames
    assert list(mem.present_pfns()) == list(layout.present_pfns())
    body = mem.rows[1:layout.frames + 1]
    # but for the guest's own input, scratch and untouched stack pages,
    # no frame is a zero page and no two are equal
    assert (~body.any(axis=1)).sum() <= 12
    digests = {hashlib.sha256(row.tobytes()).digest() for row in body}
    assert len(digests) >= layout.frames - 12
    assert mem.nbytes <= 1.26 * layout.frames * PAGE   # 1.005 at 4 GiB


def test_the_dump_is_the_same_dump_every_time(dump):
    again = demo_tlv_dump.build_snapshot(SMALL_RAM)
    assert again.physmem.rows.tobytes() == dump.physmem.rows.tobytes()
    assert again.cpu.cr3 == dump.cpu.cr3
    other = demo_tlv_dump.build_snapshot(SMALL_RAM, seed=1)
    assert other.cpu.cr3 != dump.cpu.cr3


def test_the_configurations_size_is_a_full_4_gib(layout):
    full = RamLayout.of_bytes(demo_tlv_dump.RAM_BYTES)
    assert (full.frames, full.lo, full.span) == (1 << 20, 0xF8000, 0x108000)
    rows = physmem.padded(full.frames + 1, ROW_GRANULE)
    span = physmem.padded(full.span, physmem.SPAN_GRANULE)
    assert rows * PAGE + span * 4 <= 1.07 * full.frames * PAGE
    with pytest.raises(ValueError):
        RamLayout.of_bytes(48 << 20)


# -- (b) host readers never touch the device --------------------------------

def test_a_device_only_physmem_counts_its_pull():
    """The one path that moves image bytes device-to-host, so that the
    counter counts something: a PhysMem around a device image alone."""
    pfns, pages = frame_set("dense", 11)
    built = PhysMem.from_arrays(pfns, pages)
    counter = Registry().counter("physmem.device_pulls")
    bare = PhysMem(image=built.image)
    bare.pull_counter = counter
    assert bare.device_pulls == 0
    assert bare.host_read(200 << 12, 16) == built.host_read(200 << 12, 16)
    assert bare.host_read(201 << 12, 16) == built.host_read(201 << 12, 16)
    assert bare.device_pulls == 1 and counter.value == 1
    assert built.device_pulls == 0


def _small_target(name):
    if name not in Targets.instance().names():
        dataclasses.replace(
            demo_tlv_dump.TARGET, name=name,
            snapshot=lambda: demo_tlv_dump.build_snapshot(SMALL_RAM))
    return Targets.instance().get(name)


def test_an_emu_backend_run_builds_no_device_image(tmp_path):
    from wtf_tpu.cli import TargetPaths, _build_backend

    target = _small_target("demo_tlv_dump_64m")
    registry = Registry()
    backend = _build_backend(target, "emu", TargetPaths(target=tmp_path),
                             LIMIT, 1, registry=registry)
    target.init(backend)
    results = backend.run_batch(
        [b"\x01\x02AB", smashing_record(8, "unmapped")], target)
    assert [type(r).__name__ for r in results] == ["Ok", "Crash"]
    mem = backend.snapshot.physmem
    assert not mem.on_device, "the oracle read the host arrays"
    dumped = registry.dump()
    assert dumped["physmem.device_pulls"] == 0 and mem.device_pulls == 0
    assert dumped["image.frames_present"] == 16384
    assert dumped["image.rows"] == len(mem.rows)
    assert dumped["image.span_frames"] == len(mem.table)
    assert dumped["image.bytes"] == mem.nbytes
    seconds = dumped["phase.seconds"]
    assert "snapshot-load/synthesize" in seconds
    assert "snapshot-load/upload" not in seconds


def test_a_tpu_backend_uploads_under_its_span(tmp_path):
    from wtf_tpu.cli import TargetPaths, _build_backend

    registry = Registry()
    backend = _build_backend(demo_tlv.TARGET, "tpu",
                             TargetPaths(target=tmp_path), LIMIT, 2,
                             registry=registry)
    seconds = registry.dump()["phase.seconds"]
    assert seconds["snapshot-load"] >= (seconds["snapshot-load/synthesize"]
                                        + seconds["snapshot-load/upload"])
    assert backend.snapshot.physmem.on_device
    assert registry.dump()["physmem.device_pulls"] == 0


def test_a_state_directory_loads_under_parse_and_pack(tmp_path, dump):
    from wtf_tpu.cli import TargetPaths, _build_backend

    dump.save_raw(tmp_path / "state")
    registry = Registry()
    backend = _build_backend(
        demo_tlv.TARGET, "emu",
        TargetPaths(target=tmp_path, state=tmp_path / "state"), LIMIT, 1,
        registry=registry)
    seconds = registry.dump()["phase.seconds"]
    assert {"snapshot-load/parse", "snapshot-load/pack"} <= set(seconds)
    assert "snapshot-load/synthesize" not in seconds
    mem = backend.snapshot.physmem
    assert np.array_equal(mem.rows, dump.physmem.rows)
    assert np.array_equal(mem.table, dump.physmem.table)
    assert not mem.on_device


# -- (c) the dump through write_kdmp and both parsers, as arrays ------------

@pytest.fixture(scope="module", params=["bmp", "full"])
def written(request, dump, tmp_path_factory):
    path = tmp_path_factory.mktemp(f"dmp-{request.param}") / "mem.dmp"
    pages = dump.pages_dict()
    kdmp.write_kdmp(path, pages, dump_type=request.param,
                    dtb=dump.cpu.cr3, cpu=dump.cpu)
    return path, pages


@pytest.mark.parametrize("parser", ["native", "python"])
def test_the_array_parse_is_the_dict_parse_is_what_was_written(
        written, dump, parser, monkeypatch):
    path, pages = written
    if parser == "python":
        monkeypatch.setattr(kdmp, "_parse_native", lambda p: None)
    elif kdmp._native_lib() is None:
        pytest.skip("no native toolchain")
    pfns, data = kdmp.parse_kdmp_arrays(path)
    assert data.dtype == np.uint8 and data.shape == (len(pages), PAGE)
    assert not data.flags.owndata, "a view over the mapped file"
    assert list(pfns) == sorted(pages)
    assert data.tobytes() == b"".join(pages[p] for p in sorted(pages))
    assert kdmp.parse_kdmp(path) == pages
    mem = PhysMem.from_arrays(pfns, data)
    assert np.array_equal(mem.rows, dump.physmem.rows)
    assert np.array_equal(mem.table, dump.physmem.table)


def test_load_snapshot_takes_the_dump_file_as_arrays(written, dump):
    from wtf_tpu.snapshot.loader import dump_cpu_state_json

    path, _ = written
    (path.parent / "regs.json").write_text(dump_cpu_state_json(dump.cpu))
    loaded = load_snapshot(path.parent)
    assert np.array_equal(loaded.physmem.rows, dump.physmem.rows)
    assert loaded.cpu.cr3 == dump.cpu.cr3 and not loaded.physmem.on_device


# -- (d) the dump's page tables against a naive walker ----------------------

def naive_walk(mem: PhysMem, cr3: int, gva: int):
    """(gpa, ok, writable, user) by the architecture's rule, one entry at
    a time through host_read."""
    top = gva >> 47
    if top not in (0, 0x1FFFF):
        return 0, False, False, False
    table, writable, user = cr3 & 0x000F_FFFF_FFFF_F000, True, True
    for shift in (39, 30, 21, 12):
        entry, = struct.unpack("<Q", mem.host_read(
            table + ((gva >> shift) & 0x1FF) * 8, 8))
        if not entry & 1:
            return 0, False, False, False
        writable, user = writable and bool(entry & 2), user and bool(entry & 4)
        if shift in (30, 21) and entry & 0x80:
            mask = (1 << shift) - 1
            return (entry & 0x000F_FFFF_FFFF_F000 & ~mask) | (gva & mask), \
                True, writable, user
        table = entry & 0x000F_FFFF_FFFF_F000
    return table | (gva & 0xFFF), True, writable, user


def addresses(kind: str, layout: RamLayout):
    rng = random.Random(zlib.crc32(kind.encode()))
    large_bytes = (layout.span * PAGE // (1 << 21)) // 4 * (1 << 21)
    if kind == "user-mapped":
        bases = [demo_tlv.CODE_GVA, demo_tlv.FINISH_GVA, demo_tlv.INPUT_GVA,
                 demo_tlv.SCRATCH_GVA, demo_tlv.STACK_TOP - 0x8000,
                 demo_tlv.STACK_TOP]
        return [b + rng.randrange(PAGE) for b in bases for _ in range(4)]
    if kind == "user-unmapped":
        return [demo_tlv.CODE_GVA + 0x1000, demo_tlv.INPUT_GVA - 1,
                0x1337_dead_1eef, 0x7FFF_FFFF_FFFF] + [
            rng.randrange(1 << 47) for _ in range(20)]
    if kind == "kernel-2m":
        return [KERNEL_BASE + rng.randrange(large_bytes) for _ in range(24)]
    if kind == "kernel-4k":
        return [KERNEL_BASE + rng.randrange(large_bytes, layout.lo * PAGE)
                for _ in range(16)] + [
            KERNEL_BASE + rng.randrange(layout.frames * PAGE,
                                        layout.span * PAGE)
            for _ in range(8)]
    if kind == "kernel-hole":
        return [KERNEL_BASE + rng.randrange(layout.lo * PAGE,
                                            layout.frames * PAGE)
                for _ in range(12)] + [
            KERNEL_BASE + layout.span * PAGE + rng.randrange(1 << 30)
            for _ in range(12)]
    if kind == "non-canonical":
        return [rng.randrange(1 << 47, (1 << 64) - (1 << 47))
                for _ in range(24)]
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["user-mapped", "user-unmapped",
                                  "kernel-2m", "kernel-4k", "kernel-hole",
                                  "non-canonical"])
def test_page_tables_against_a_naive_walker_and_the_device(dump, layout,
                                                           kind):
    mem, cr3 = dump.physmem, dump.cpu.cr3
    gvas = addresses(kind, layout)
    walked = [naive_walk(mem, cr3, g) for g in gvas]
    oks = [w[1] for w in walked]
    assert all(oks) if kind in ("user-mapped", "kernel-2m", "kernel-4k") \
        else not any(oks) if kind != "user-unmapped" else not all(oks)
    for gva, (gpa, ok, writable, user) in zip(gvas, walked):
        if ok and kind.startswith("kernel"):
            assert gpa == gva - KERNEL_BASE and writable and not user
        if ok and kind == "user-mapped":
            assert user and (gpa >> 12) in set(layout.present_pfns())
    from test_mem import _lane

    t = translate_vec(mem.image, _lane(overlay_init(1, 4)), jnp.uint64(cr3),
                      jnp.asarray(np.array(gvas, dtype=np.uint64)))
    assert list(np.asarray(t.ok)) == oks
    for i, (gpa, ok, writable, user) in enumerate(walked):
        if ok:
            assert int(t.gpa[i]) == gpa, hex(gvas[i])
            assert bool(t.writable[i]) == writable
            assert bool(t.user[i]) == user


def test_the_kernel_half_takes_a_thousand_tables_at_4_gib():
    from wtf_tpu.snapshot.synthetic import direct_map_tables

    full = RamLayout.of_bytes(demo_tlv_dump.RAM_BYTES)
    picks = iter(full.scatter(np.random.default_rng(0), 4000).tolist())
    _, pfns, pages = direct_map_tables(full, picks.__next__)
    assert len(pfns) == len(set(pfns.tolist())) >= 1000
    large = (pages & np.uint64(0x81)) == np.uint64(0x81)
    assert large.sum() == 528, "a region of 2-MiB pages"
    assert not (pages & np.uint64(4)).any(), "supervisor-only"


# -- (e) the device against the oracle on the dump --------------------------

def _backend(name, snapshot, **kw):
    backend = create_backend(name, snapshot, limit=LIMIT, **kw)
    backend.initialize()
    demo_tlv_dump.TARGET.init(backend)
    return backend


@pytest.fixture(scope="module")
def tpu(dump):
    return _backend("tpu", dump, n_lanes=LANES)


@pytest.fixture(scope="module")
def emu(dump):
    return _backend("emu", dump)


TESTCASES = [smashing_record(k, aim) for k, aim in SMASHES] \
    + mutator_batch(34)[:LANES - len(SMASHES) % LANES]


@pytest.fixture(scope="module")
def on_device(tpu):
    seen = []
    for at in range(0, len(TESTCASES), LANES):
        seen += device_batch(tpu, TESTCASES[at:at + LANES])
    return seen


@pytest.mark.parametrize("index", range(len(TESTCASES)))
def test_device_matches_the_oracle_on_the_dump(on_device, emu, index):
    same(on_device[index], oracle_run(emu, TESTCASES[index]))


def test_the_testcases_crash_and_return_and_dirty_scattered_frames(
        on_device, dump, tpu):
    kinds = {type(s["result"]).__name__ for s in on_device}
    assert kinds >= {"Ok", "Crash"}
    names = {s["result"].name for s in on_device
             if isinstance(s["result"], Crash)}
    assert len(names) >= 8
    dirty = set().union(*(s["dirty"] for s in on_device))
    assert max(dirty) - min(dirty) > 1000, "the guest's frames are scattered"
    assert tpu.snapshot.physmem.device_pulls == 0


# -- satellite: the pair is one campaign ------------------------------------

CAMPAIGN_LANES = 16
COUNTERS = ("device.instructions", "device.lane_steps", "device.mem_faults",
            "runner.chunks", "campaign.crashes", "campaign.testcases",
            "backend.batches")


def run_campaign(name, target) -> dict:
    from wtf_tpu.cli import main

    (target / "inputs").mkdir(parents=True)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(["campaign", "--name", name, "--backend", "tpu",
                   "--target", str(target), "--lanes", str(CAMPAIGN_LANES),
                   "--runs", str(4 * CAMPAIGN_LANES), "--limit", str(LIMIT),
                   "--max_len", "256", "--seed", "3000000012",
                   "--telemetry-dir", str(target / "telem")])
    assert rc in (0, 2)
    records = list(read_events(target / "telem" / "events.jsonl"))
    final = [ln for ln in out.getvalue().splitlines() if ln.startswith("#")]
    digests = {}
    for sub in ("crashes", "outputs"):
        h = hashlib.sha256()
        for p in sorted((target / sub).iterdir()):
            h.update(p.name.encode() + b"\0" + p.read_bytes())
        digests[sub] = h.hexdigest()
    return {"final": final[-1].split(" exec/s")[0], "digests": digests,
            "metrics": [r for r in records
                        if r["type"] == "run-end"][-1]["metrics"]}


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    _small_target("demo_tlv_dump_64m")
    return (run_campaign("demo_tlv", tmp_path_factory.mktemp("pair-tlv")),
            run_campaign("demo_tlv_dump_64m",
                         tmp_path_factory.mktemp("pair-dump")))


def test_the_pair_ends_on_the_same_line_and_finds(pair):
    plain, in_dump = pair
    assert in_dump["final"] == plain["final"]
    assert in_dump["digests"] == plain["digests"]
    assert plain["metrics"]["backend.batches"] == 4
    assert plain["metrics"]["campaign.crashes"] > 0


@pytest.mark.parametrize("counter", COUNTERS)
def test_the_pair_counts_the_same(pair, counter):
    plain, in_dump = pair
    assert in_dump["metrics"][counter] == plain["metrics"][counter]


def test_the_pair_differs_in_the_image_alone(pair):
    plain, in_dump = pair
    assert plain["metrics"]["image.rows"] == 32
    assert in_dump["metrics"]["image.rows"] == 20480
    assert in_dump["metrics"]["image.frames_present"] == 16384
    for run in pair:
        assert run["metrics"]["physmem.device_pulls"] == 0
        assert run["metrics"]["runner.fallbacks"] == 0
        assert "snapshot-load/upload" in run["metrics"]["phase.seconds"]
