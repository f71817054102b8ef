"""CLI tests: the `run` repro workflow and `campaign` driver
(reference wtf.cc:33-371 + subcommands.cc:16-101)."""

import random
from pathlib import Path

import pytest

from wtf_tpu.cli import build_parser, main
from wtf_tpu.config import TargetPaths

from test_harness import BENIGN, OVERFLOW


def test_parser_subcommands():
    parser = build_parser()
    args = parser.parse_args(
        ["run", "--name", "demo_tlv", "--input", "/tmp/x",
         "--trace-type", "cov", "--limit", "500"])
    assert args.subcommand == "run"
    assert args.trace_type == "cov"
    assert args.limit == 500
    with pytest.raises(SystemExit):
        parser.parse_args(["run"])  # --name/--input required
    with pytest.raises(SystemExit):
        parser.parse_args(["bogus"])


def test_engine_option_is_rejected_by_the_parser(capsys):
    """One device interpreter: `--fused-step` selected between two and is
    rejected by the argument parser itself (exit 2), no shim."""
    from wtf_tpu.cli import main

    with pytest.raises(SystemExit) as ei:
        main(["campaign", "--name", "demo_tlv", "--fused-step", "on"])
    assert ei.value.code == 2
    assert "--fused-step" in capsys.readouterr().err


def test_target_paths_resolve(tmp_path):
    paths = TargetPaths(target=tmp_path / "t").resolve()
    assert paths.inputs == tmp_path / "t" / "inputs"
    assert paths.outputs == tmp_path / "t" / "outputs"
    assert paths.crashes == tmp_path / "t" / "crashes"
    assert paths.state == tmp_path / "t" / "state"
    # explicit dirs win over the convention
    paths = TargetPaths(target=tmp_path, inputs=tmp_path / "else").resolve()
    assert paths.inputs == tmp_path / "else"


def test_run_repro_with_trace(tmp_path, capsys):
    """`run --input crash.bin --trace-path t.txt` reproduces the crash and
    writes the rip trace (the de-facto repro/regression workflow,
    README.md:67-79)."""
    crash_file = tmp_path / "crash.bin"
    crash_file.write_bytes(OVERFLOW)
    trace = tmp_path / "t.txt"
    rc = main(["run", "--name", "demo_tlv", "--backend", "emu",
               "--input", str(crash_file), "--trace-path", str(trace),
               "--trace-type", "rip"])
    assert rc == 2  # crash reproduced
    out = capsys.readouterr().out
    assert "crash-" in out
    lines = trace.read_text().splitlines()
    assert len(lines) > 10
    assert all(l.startswith("0x") for l in lines)
    # first rip = parser entry
    from wtf_tpu.harness import demo_tlv

    assert int(lines[0], 16) == demo_tlv.CODE_GVA


def test_run_over_directory(tmp_path, capsys):
    inputs = tmp_path / "inputs"
    inputs.mkdir()
    (inputs / "benign").write_bytes(BENIGN)
    (inputs / "boom").write_bytes(OVERFLOW)
    traces = tmp_path / "traces"
    rc = main(["run", "--name", "demo_tlv", "--backend", "emu",
               "--input", str(inputs), "--trace-path", str(traces),
               "--trace-type", "cov"])
    assert rc == 2
    out = capsys.readouterr().out
    assert "benign: ok" in out
    assert "boom: crash" in out
    assert (traces / "benign.trace").exists()
    assert (traces / "boom.trace").exists()


def test_snapshot_conversion_roundtrip(tmp_path, capsys):
    """npz -> dmp -> load -> run: the snapshot subcommand round-trips a
    working guest through the Windows crash-dump format."""
    from wtf_tpu.harness import demo_tlv

    state_npz = tmp_path / "npz"
    demo_tlv.build_snapshot().save_raw(state_npz)
    rc = main(["snapshot", "--state", str(state_npz),
               "--out", str(tmp_path / "dmp"), "--format", "dmp-bmp"])
    assert rc == 0
    assert (tmp_path / "dmp" / "mem.dmp").exists()
    crash_file = tmp_path / "crash.bin"
    crash_file.write_bytes(OVERFLOW)
    rc = main(["run", "--name", "demo_tlv", "--backend", "emu",
               "--state", str(tmp_path / "dmp"),
               "--input", str(crash_file)])
    assert rc == 2  # planted crash reproduces from the converted dump
    assert "crash-" in capsys.readouterr().out


def test_campaign_emu_finds_crash(tmp_path, capsys):
    rc = main(["campaign", "--name", "demo_tlv", "--backend", "emu",
               "--runs", "600", "--seed", "5", "--max_len", "128",
               "--crashes", str(tmp_path / "crashes"), "--stop-on-crash"])
    assert rc == 2
    assert any((tmp_path / "crashes").iterdir())


def test_campaign_minset(tmp_path, capsys):
    """--runs=0 = minset (reference server.h:552-556): replay seeds only,
    outputs/ = the coverage-minimal subset, no mutations, no seed copies."""
    inputs = tmp_path / "inputs"
    inputs.mkdir()
    # two identical-coverage seeds (type-1 only), one bigger seed covering
    # types 1+2, and one small seed reaching the type-3 path nothing else
    # covers: minset = {big, type-3 representative}
    (inputs / "a").write_bytes(b"\x01\x02XY")
    (inputs / "b").write_bytes(b"\x01\x02ZW")
    (inputs / "c").write_bytes(b"\x01\x02AA\x02\x08BBBBBBBB")
    (inputs / "d").write_bytes(b"\x03\x02ok")
    rc = main(["campaign", "--name", "demo_tlv", "--backend", "tpu",
               "--lanes", "4", "--target", str(tmp_path), "--runs", "0",
               "--limit", "100000"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "minset: kept" in out
    kept = list((tmp_path / "outputs").glob("*"))
    # the two identical-coverage seeds collapse to one representative
    assert len(kept) == 2, [p.name for p in kept]

    # re-minimizing with a stale subsumed find in outputs/ prunes it:
    # outputs is always exactly the measured minimal subset
    from wtf_tpu.utils.hashing import hex_digest

    stale = b"\x01\x02QQ"  # type-1 only: subsumed by the big seed
    (tmp_path / "outputs" / hex_digest(stale)).write_bytes(stale)
    rc = main(["campaign", "--name", "demo_tlv", "--backend", "tpu",
               "--lanes", "4", "--target", str(tmp_path), "--runs", "0",
               "--limit", "100000"])
    assert rc == 0
    kept2 = sorted(p.name for p in (tmp_path / "outputs").glob("*"))
    assert kept2 == sorted(p.name for p in kept), kept2
