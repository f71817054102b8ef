"""kdmp parser tests: write_kdmp fixtures round-trip through both the
native C++ parser and the pure-Python fallback, and a .dmp-backed snapshot
actually fuzzes (VERDICT round-2 item 4's done criterion)."""

import struct

import pytest

from wtf_tpu.core.cpustate import CpuState
from wtf_tpu.snapshot import kdmp
from wtf_tpu.snapshot.loader import load_snapshot
from wtf_tpu.harness import demo_tlv


def _pages():
    # non-contiguous PFNs -> multiple runs / bitmap holes
    return {
        0x10: bytes([0x11]) * 0x1000,
        0x11: bytes([0x22]) * 0x1000,
        0x40: bytes([0x33]) * 0x1000,
        0x1000: bytes(range(256)) * 16,
    }


@pytest.mark.parametrize("dump_type", ["full", "bmp"])
def test_roundtrip_python(tmp_path, dump_type, monkeypatch):
    path = tmp_path / "mem.dmp"
    cpu = CpuState()
    cpu.rip = 0x1337
    cpu.rax = 0xAABBCCDD
    cpu.rflags = 0x246
    kdmp.write_kdmp(path, _pages(), dump_type=dump_type,
                    dtb=0x1AD000, cpu=cpu, bugcheck_code=0xDEADDEAD)
    # force the pure-python path
    monkeypatch.setattr(kdmp, "_parse_native", lambda p: None)
    info = kdmp.parse_kdmp_info(path)
    assert info.dtb == 0x1AD000
    assert info.bugcheck_code == 0xDEADDEAD
    assert info.n_pages == 4
    regs = info.context_registers()
    assert regs["rip"] == 0x1337
    assert regs["rax"] == 0xAABBCCDD
    assert regs["rflags"] == 0x246
    pages = kdmp.parse_kdmp(path)
    assert pages.keys() == _pages().keys()
    for pfn, data in _pages().items():
        assert pages[pfn] == data, hex(pfn)


@pytest.mark.parametrize("dump_type", ["full", "bmp"])
def test_roundtrip_native(tmp_path, dump_type):
    lib = kdmp._native_lib()
    if lib is None:
        pytest.skip("no native toolchain")
    path = tmp_path / "mem.dmp"
    kdmp.write_kdmp(path, _pages(), dump_type=dump_type, dtb=0x1AD000)
    info, index = kdmp._parse_native(path)
    assert info.dump_type == (1 if dump_type == "full" else 5)
    assert info.dtb == 0x1AD000
    assert {pfn for pfn, _ in index} == _pages().keys()
    pages = kdmp.parse_kdmp(path)
    for pfn, data in _pages().items():
        assert pages[pfn] == data, hex(pfn)


def test_native_and_python_agree(tmp_path):
    lib = kdmp._native_lib()
    if lib is None:
        pytest.skip("no native toolchain")
    path = tmp_path / "mem.dmp"
    kdmp.write_kdmp(path, _pages(), dump_type="bmp", dtb=0x7777000)
    native_info, native_index = kdmp._parse_native(path)
    with open(path, "rb") as f:
        py_info, py_index = kdmp._parse_python(f.read())
    assert (native_index == py_index).all()
    assert native_info.dtb == py_info.dtb
    assert native_info.context_raw == py_info.context_raw


def test_bad_signature(tmp_path):
    path = tmp_path / "mem.dmp"
    path.write_bytes(b"NOPE" * 0x1000)
    with pytest.raises(kdmp.KdmpError):
        kdmp.parse_kdmp(path)


def test_kernel_dump_rejected(tmp_path):
    path = tmp_path / "mem.dmp"
    header = bytearray(0x3000)
    struct.pack_into("<II", header, 0, kdmp.SIG_PAGE, kdmp.SIG_DU64)
    struct.pack_into("<I", header, 0xF98, kdmp.KERNEL_DUMP)
    path.write_bytes(bytes(header))
    with pytest.raises(kdmp.KdmpError, match="partial kernel"):
        kdmp.parse_kdmp(path)


def test_dmp_snapshot_fuzzes(tmp_path):
    """A demo_tlv snapshot exported as mem.dmp + regs.json loads through
    load_snapshot and reproduces the planted crash end-to-end."""
    from wtf_tpu.backend import create_backend
    from wtf_tpu.core.results import Crash, Ok
    from wtf_tpu.snapshot.loader import dump_cpu_state_json

    import numpy as np

    snap = demo_tlv.build_snapshot()
    state = tmp_path / "state"
    state.mkdir()
    # export guest memory as a BMP crash dump
    kdmp.write_kdmp(state / "mem.dmp", snap.pages_dict(), dump_type="bmp",
                    dtb=snap.cpu.cr3, cpu=snap.cpu)
    (state / "regs.json").write_text(dump_cpu_state_json(snap.cpu))

    loaded = load_snapshot(state)
    assert loaded.cpu.rip == snap.cpu.rip
    backend = create_backend("emu", loaded)
    backend.initialize()
    demo_tlv.TARGET.init(backend)
    results = backend.run_batch(
        [b"\x01\x02AB", bytes([3, 64]) + b"A" * 64], demo_tlv.TARGET)
    assert isinstance(results[0], Ok)
    assert isinstance(results[1], Crash)


# ---------------------------------------------------------------------------
# differential vs the REFERENCE kdmp-parser (VERDICT r3 item 4)
# ---------------------------------------------------------------------------

_REF_LIB = "/root/reference/src/libs/kdmp-parser/src/lib"


@pytest.fixture(scope="session")
def ref_testapp(tmp_path_factory):
    """Compile the reference header-only parser into a check binary (our
    tests/native/kdmp_ref_check.cc); skip where the reference tree or a
    C++ toolchain isn't available."""
    import shutil
    import subprocess
    from pathlib import Path as _P

    if not _P(_REF_LIB).is_dir():
        pytest.skip("reference kdmp-parser sources not available")
    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")
    src = _P(__file__).parent / "native" / "kdmp_ref_check.cc"
    out = tmp_path_factory.mktemp("kdmpref") / "kdmp_ref_check"
    proc = subprocess.run(
        ["g++", "-O1", "-std=c++20", f"-I{_REF_LIB}", str(src),
         "-o", str(out)], capture_output=True, text=True)
    if proc.returncode != 0:
        pytest.skip(f"reference parser does not build: {proc.stderr[-300:]}")
    return out


def _fnv1a_pages(pages):
    h = 0xCBF29CE484222325
    for pfn in sorted(pages):
        pa = pfn << 12
        for chunk in (pa.to_bytes(8, "little"), pages[pfn]):
            for b in chunk:
                h = ((h ^ b) * 0x100000001B3) & (1 << 64) - 1
    return h


@pytest.mark.parametrize("dump_type", ["full", "bmp"])
def test_differential_vs_reference_parser(tmp_path, dump_type, ref_testapp):
    """Break the closed writer->parser loop: the same dump must yield the
    same DTB / context / page set / page CONTENTS from the reference's
    battle-tested parser and from ours (native + pure-Python).  A shared
    misreading of the format between our writer and our parser would
    round-trip cleanly but diverge here."""
    import json
    import subprocess

    path = tmp_path / "mem.dmp"
    cpu = CpuState()
    cpu.rip = 0xFFFFF805_1087_76A0
    cpu.rsp = 0xFFFFF805_1356_84F8
    cpu.rax = 3
    cpu.rcx = 1
    cpu.r15 = 0x52
    cpu.rflags = 0x40202
    cpu.cs.selector = 0x10
    cpu.ss.selector = 0x18
    pages = _pages()
    kdmp.write_kdmp(path, pages, dump_type=dump_type, dtb=0x6D4000, cpu=cpu)

    proc = subprocess.run([str(ref_testapp), str(path)],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    ref = json.loads(proc.stdout)

    # reference enum: FullDump=1, BMPDump=5 (kdmp-parser-structs.h)
    assert ref["type"] == {"full": 1, "bmp": 5}[dump_type]
    assert ref["dtb"] == 0x6D4000
    assert ref["n_pages"] == len(pages)
    assert ref["rip"] == cpu.rip
    assert ref["rsp"] == cpu.rsp
    assert ref["rax"] == cpu.rax
    assert ref["rcx"] == cpu.rcx
    assert ref["r15"] == cpu.r15
    assert ref["eflags"] == cpu.rflags
    assert ref["seg_cs"] == 0x10 and ref["seg_ss"] == 0x18
    assert ref["first_pa"] == min(pages) << 12
    assert ref["last_pa"] == max(pages) << 12

    # now OUR parsers (both paths) must agree with the reference, page
    # contents included (same fnv1a(pa || bytes) digest formula)
    from unittest import mock

    for parser in ("native", "python"):
        if parser == "native" and kdmp._native_lib() is None:
            continue
        patch = (mock.patch.object(kdmp, "_parse_native", lambda p: None)
                 if parser == "python" else mock.patch.object(
                     kdmp, "_IGNORED_", None, create=True))
        with patch:
            info = kdmp.parse_kdmp_info(path)
            got_pages = kdmp.parse_kdmp(path)
        regs = info.context_registers()
        assert info.dtb == ref["dtb"], parser
        assert info.n_pages == ref["n_pages"], parser
        assert regs["rip"] == ref["rip"], parser
        assert regs["rsp"] == ref["rsp"], parser
        assert regs["rflags"] == ref["eflags"], parser
        assert regs["cs"] == ref["seg_cs"], parser
        assert _fnv1a_pages(got_pages) == ref["pages_digest"], parser
