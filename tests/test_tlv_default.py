"""The `tlv-default` cell's deployment (demo_tlv on the batch loop with its
own structure mutator) held to the EmuCpu oracle on the CPU: the finds of
a seeded campaign, the target's ordinary crash (a smashed return address
that lands in wild code), one testcase as deep as `max_len` allows, and
the `device.lockstep_floor_steps` counter.  The oracle (wtf_tpu/cpu/emu.py)
shares no code with the device step."""

import contextlib
import io
import random
import re
import types

import numpy as np
import pytest

from wtf_tpu.backend import create_backend
from wtf_tpu.core.results import Crash, Ok
from wtf_tpu.fuzz.mutator import TlvStructureMutator
from wtf_tpu.harness import demo_tlv
from wtf_tpu.telemetry.events import read_events

LANES = 32
LIMIT = 100_000
MAX_LEN = 1024


def _backend(name, **kw):
    backend = create_backend(name, demo_tlv.build_snapshot(), limit=LIMIT,
                             **kw)
    backend.initialize()
    demo_tlv.TARGET.init(backend)
    return backend


@pytest.fixture(scope="module")
def tpu():
    return _backend("tpu", n_lanes=LANES)


@pytest.fixture(scope="module")
def emu():
    return _backend("emu")


def oracle_run(emu, data: bytes) -> dict:
    """One testcase on the oracle, read before the restore."""
    demo_tlv.TARGET.insert_testcase(emu, data)
    result = emu.run()
    cpu = emu.cpu
    seen = {"result": result, "rip": cpu.rip, "gpr": list(cpu.gpr),
            "icount": cpu.icount, "cov": set(emu._run_cov),
            "dirty": {pfn: bytes(page)
                      for pfn, page in cpu.mem.overlay.items()}}
    emu.restore()
    return seen


def device_batch(tpu, testcases: list) -> list:
    """One batch on the device, every lane read before the restore; the
    registry's `device.*` deltas of the batch ride on lane 0's entry."""
    reg = tpu.registry
    before = {k: reg.counter(k).value for k in (
        "device.lockstep_floor_steps", "device.lane_steps",
        "device.instructions")}
    results = tpu.run_batch(testcases, demo_tlv.TARGET)
    view = tpu.runner.view()
    icount = np.asarray(tpu.runner.machine.icount)
    seen = []
    for lane, result in enumerate(results):
        pfns = sorted(int(p) for p in view._ov_pfn[lane] if p >= 0)
        seen.append({"result": result, "rip": view.get_rip(lane),
                     "gpr": [view.get_reg(lane, i) for i in range(16)],
                     "icount": int(icount[lane]),
                     "cov": tpu.lane_coverage(lane),
                     "dirty": {pfn: view.page(lane, pfn) for pfn in pfns}})
    seen[0]["counters"] = {k: reg.counter(k).value - v
                           for k, v in before.items()}
    demo_tlv.TARGET.restore()
    tpu.restore()
    return seen


def same(device: dict, oracle: dict) -> None:
    assert type(device["result"]) is type(oracle["result"]), (device, oracle)
    if isinstance(oracle["result"], Crash):
        assert device["result"].name == oracle["result"].name
    assert device["rip"] == oracle["rip"]
    assert device["gpr"] == oracle["gpr"]
    assert device["icount"] == oracle["icount"]
    assert device["cov"] == oracle["cov"]
    assert sorted(device["dirty"]) == sorted(oracle["dirty"])
    for pfn, page in oracle["dirty"].items():
        assert device["dirty"][pfn] == page, f"pfn {pfn:#x}"


# -- (b) the ordinary crash: a partly overwritten return address -----------

# parse()'s frame: 16 bytes from the copy buffer [rbp-0x10] to the saved
# rbp, 8 more to the return address (FINISH_GVA).  A type-3 record of 24+k
# bytes overwrites the k low bytes of it.
RET_OFFSET = 24
RECORD_LEN = RET_OFFSET + 8 + 2
SHELLCODE = bytes.fromhex("4889f0")        # mov rax, rsi; the page's zeros
#                                            then `add [rax], al` their way
#                                            to the end of the input page
AIMS = {
    # off an instruction boundary of parse(): +2 is the `89 e5` inside
    # `mov rbp, rsp`, +0xff5 a 15-byte window that runs into the unmapped
    # page behind the code page (PERF.md section 6, fault (a))
    "code": lambda k: demo_tlv.CODE_GVA + (0xff5 if k % 2 == 0 else 2),
    # the attacker's own bytes behind the record
    "input": lambda k: demo_tlv.INPUT_GVA + RECORD_LEN,
    "unmapped": lambda k: 0x0000_1337_dead_1eef,
}


def smashing_record(k: int, aim: str) -> bytes:
    """A type-3 record that overwrites the k low bytes of the return
    address with those of the aim; the bytes it leaves decide with them
    where `ret` lands."""
    low = AIMS[aim](k).to_bytes(8, "little")[:k]
    record = bytes([3, RET_OFFSET + k]) + bytes(range(0x41, 0x41 + 16)) \
        + (demo_tlv.STACK_TOP - 0x2000).to_bytes(8, "little") + low
    return record.ljust(RECORD_LEN, b"\x00") + SHELLCODE


SMASHES = [(k, aim) for k in range(1, 9) for aim in AIMS]


@pytest.fixture(scope="module")
def smashed(tpu):
    return device_batch(tpu, [smashing_record(k, aim) for k, aim in SMASHES])


@pytest.mark.parametrize("k,aim", SMASHES)
def test_partial_return_overwrite_matches_the_oracle(smashed, emu, k, aim):
    oracle = oracle_run(emu, smashing_record(k, aim))
    landed = (demo_tlv.FINISH_GVA & ~((1 << 8 * k) - 1)) \
        | (AIMS[aim](k) & ((1 << 8 * k) - 1))
    assert landed in oracle["cov"] or (
        isinstance(oracle["result"], Crash)
        and oracle["result"].name == f"crash-execute-{landed:#x}")
    same(smashed[SMASHES.index((k, aim))], oracle)


def test_the_smashes_land_where_they_are_aimed(emu):
    """What the 24 cases cover, so that a change of the target's frame
    cannot turn them into 24 clean returns."""
    names = {(k, aim): str(oracle_run(emu, smashing_record(k, aim))["result"])
             for k, aim in SMASHES}
    assert "ok" not in names.values()
    assert names[(8, "unmapped")] == "crash(crash-execute-0x1337dead1eef)"
    # the slide through the input page ends at its last byte, where the
    # window holds one byte of a two-byte instruction
    assert names[(8, "input")] == (
        f"crash(crash-unsupported-{demo_tlv.INPUT_GVA + 0xfff:#x})")
    assert len(set(names.values())) >= 8


# -- (c) one testcase as deep as max_len allows ---------------------------

def deepest_testcase() -> bytes:
    out = b""
    while len(out) + 2 < MAX_LEN:
        n = min(255, MAX_LEN - len(out) - 2)
        out += bytes([1, n]) + bytes((7 * i + 1) & 0xFF for i in range(n))
    return out


def test_a_max_len_testcase_of_sum_records_runs_to_its_end(tpu, emu):
    data = deepest_testcase()
    assert len(data) in (MAX_LEN, MAX_LEN - 1)
    oracle = oracle_run(emu, data)
    assert isinstance(oracle["result"], Ok) and oracle["icount"] > 5000
    chunks = tpu.runner.stats["chunks"]
    device = device_batch(tpu, [data])
    # several chunks, and past the 256-step rung of the ladder: the deep
    # lanes of this target run the 4096-step program too
    assert tpu.runner.stats["chunks"] - chunks >= 3
    assert tpu.runner.stats["max_chunk_steps"] >= 4096
    same(device[0], oracle)
    assert device[0]["gpr"][3] == sum(
        b for i in range(0, len(data), 257) for b in data[i + 2:i + 257])
    assert device[0]["counters"]["device.lockstep_floor_steps"] == \
        LANES * oracle["icount"]
    assert device[0]["counters"]["device.lane_steps"] >= \
        LANES * oracle["icount"]


# -- (d) the counter on mixed batches -------------------------------------

def mutator_batch(seed: int) -> list:
    """LANES testcases as the campaign's mutator grows them from nothing."""
    mutator = TlvStructureMutator(random.Random(seed), MAX_LEN)
    out = []
    corpus = types.SimpleNamespace(pick=lambda: out[-1] if out else None)
    for _ in range(LANES):
        out.append(mutator.get_new_testcase(corpus))
    return out


@pytest.mark.parametrize("seed", [3, 28, 2026])
def test_lockstep_floor_is_lanes_times_the_deepest_lane(tpu, emu, seed):
    batch = mutator_batch(seed)
    icounts = [oracle_run(emu, data)["icount"] for data in batch]
    counters = device_batch(tpu, batch)[0]["counters"]
    assert len(set(icounts)) > 4, "a mixed batch"
    assert counters["device.instructions"] == sum(icounts)
    assert counters["device.lockstep_floor_steps"] == LANES * max(icounts)
    # floor <= lane_steps holds because these 32 lanes take no tail
    # (step.TAIL_MIN_LANES): the chunk loop sweeps every lane as long as
    # the deepest one runs.  With the tail on, lane_steps falls BELOW the
    # floor (the tail sweeps a quarter of the lanes where lockstep swept
    # all; tests/test_chunk_tail.py holds that direction), and
    # instructions <= floor is what stays true at any width.
    assert counters["device.instructions"] \
        <= counters["device.lockstep_floor_steps"] \
        <= counters["device.lane_steps"]


# -- (a) a seeded campaign's finds ----------------------------------------

def run_campaign(target) -> dict:
    from wtf_tpu.cli import main

    (target / "inputs").mkdir(parents=True)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(["campaign", "--name", "demo_tlv", "--backend", "tpu",
                   "--target", str(target), "--lanes", str(LANES),
                   "--runs", str(3 * LANES), "--limit", str(LIMIT),
                   "--max_len", str(MAX_LEN), "--seed", "2800000012",
                   "--telemetry-dir", str(target / "telem")])
    assert rc in (0, 2)
    records = list(read_events(target / "telem" / "events.jsonl"))
    beats = [r for r in records if r["type"] == "heartbeat"]
    final = [ln for ln in out.getvalue().splitlines() if ln.startswith("#")]
    return {"target": target, "heartbeats": beats, "final": final[-1],
            "metrics": [r for r in records
                        if r["type"] == "run-end"][-1]["metrics"]}


@pytest.fixture(scope="module")
def campaign(tmp_path_factory):
    return run_campaign(tmp_path_factory.mktemp("tlv-campaign"))


def _files(directory):
    return sorted(p for p in directory.iterdir() if p.is_file())


def test_campaign_runs_three_batches_on_the_batch_loop(campaign):
    metrics = campaign["metrics"]
    assert metrics["backend.batches"] == 3
    assert metrics["campaign.testcases"] == 3 * LANES
    assert "megachunk.windows" not in metrics
    # a parser's integer stores: none reaches its window's third word
    assert metrics["device.store3_steps"] == 0 < metrics["device.store_steps"]
    assert _files(campaign["target"] / "crashes")
    assert _files(campaign["target"] / "outputs")
    for beat in campaign["heartbeats"]:
        if beat["metrics"].get("backend.batches"):
            assert beat["metrics"]["device.lockstep_floor_steps"] > 0


def test_every_saved_crash_reproduces_on_the_oracle_under_its_name(
        campaign, emu):
    for path in _files(campaign["target"] / "crashes"):
        result = oracle_run(emu, path.read_bytes())["result"]
        assert isinstance(result, Crash) and result.name == path.name


def test_the_oracle_covers_what_the_campaign_counts(campaign, emu):
    union, crash_names = set(), {
        p.name for p in _files(campaign["target"] / "crashes")}
    for path in _files(campaign["target"] / "outputs"):
        seen = oracle_run(emu, path.read_bytes())
        union |= seen["cov"]
        if isinstance(seen["result"], Crash):
            assert seen["result"].name in crash_names
    cov = int(re.search(r"\bcov: (\d+)\b", campaign["final"]).group(1))
    assert len(union) == cov


def test_equal_seeds_give_equal_counters_and_finds(campaign,
                                                   tmp_path_factory):
    again = run_campaign(tmp_path_factory.mktemp("tlv-campaign-again"))
    # `#N cov: C corp: K`, without the rate and the uptime behind it
    assert again["final"].split(" exec/s")[0] \
        == campaign["final"].split(" exec/s")[0]
    for key in ("device.lockstep_floor_steps", "device.lane_steps",
                "device.instructions", "device.sweeps", "runner.chunks",
                "campaign.crashes"):
        assert again["metrics"][key] == campaign["metrics"][key], key
    for sub in ("crashes", "outputs"):
        assert [(p.name, p.read_bytes())
                for p in _files(again["target"] / sub)] == [
            (p.name, p.read_bytes())
            for p in _files(campaign["target"] / sub)]
