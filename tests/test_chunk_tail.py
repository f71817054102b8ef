"""The chunk executor's narrow sweeps (`step.make_run_chunk`: all lanes in
groups of W while more than the groups but one would hold are RUNNING,
then a group fewer, down to one group of W lanes) held to the full-width
executor on the CPU, bit for bit: every leaf of the machine but the three
counters that say how the sweeps were taken (`CTR_SWEPT`, `CTR_TAIL`,
`CTR_LEVELS`).
The default width is 0 at these lane counts (`step.TAIL_MIN_LANES`), so
the direct cases ask `make_run_chunk` for a width and the cases through
`Runner.run` lower the floor for the test.
"""

import random
import types

import jax
import numpy as np
import pytest

from wtf_tpu.backend import create_backend
from wtf_tpu.core.results import Crash, StatusCode
from wtf_tpu.fuzz.mutator import TlvStructureMutator
from wtf_tpu.harness import demo_kernel, demo_tlv
from wtf_tpu.interp import step
from wtf_tpu.interp.machine import CTR_LEVELS, CTR_SWEPT, CTR_TAIL

LANES = 16
LIMIT = 100_000
WIDTHS = [1, 2, LANES // 4, LANES]
RUNNING = int(StatusCode.RUNNING)


def tlv_batch() -> list:
    """The campaign mutator's first testcases (depths 20 to 700) and one
    as deep as `max_len` 1024 allows (5,000 instructions and more)."""
    mutator = TlvStructureMutator(random.Random(28), 1024)
    out = []
    corpus = types.SimpleNamespace(pick=lambda: out[-1] if out else None)
    for _ in range(LANES - 1):
        out.append(mutator.get_new_testcase(corpus))
    deep = b""
    while len(deep) + 2 < 1024:
        n = min(255, 1024 - len(deep) - 2)
        deep += bytes([1, n]) + bytes((7 * i + 1) & 0xFF for i in range(n))
    return out + [deep]


def kernel_batch() -> list:
    """Every command of the ioctl handler at seeded lengths (sum loops,
    bugchecks, the out-of-bounds copy, wild jumps, empty inputs) and one
    sum loop over 900 bytes."""
    rng = random.Random(31)
    out = [bytes([rng.choice((0, 1, 1, 1, 2, 3, 3, 4))])
           + rng.randbytes(rng.randrange(0, 120)) for _ in range(LANES - 2)]
    return out + [b"", b"\x01" + bytes(range(256)) * 3 + bytes(131)]


TARGETS = {"tlv": (demo_tlv, tlv_batch), "kernel": (demo_kernel,
                                                    kernel_batch)}
# every width on the user-mode parser; the ring-0 target (swapgs, the
# syscall MSRs, faults that are terminal) at the width the policy takes:
# each (target, width) is a compile of its own, about a minute on the CPU
CASES = [("tlv", w) for w in WIDTHS] + [("kernel", LANES // 4)]


def _backend(name, kind="tpu", **kw):
    module = TARGETS[name][0]
    backend = create_backend(kind, module.build_snapshot(), limit=LIMIT,
                             **kw)
    backend.initialize()
    module.TARGET.init(backend)
    if kind == "tpu":
        # one rung of the chunk ladder: every chunk is the 256-step
        # program (each rung is a compile of its own)
        backend.runner.adaptive_chunks = False
    return backend


def _host(machine) -> dict:
    """Every leaf of a machine on the host, by path."""
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(machine)}


def _same_but_sweep_counters(got: dict, want: dict, where: str) -> None:
    assert got.keys() == want.keys()
    for path, leaf in want.items():
        mine = got[path]
        if path == ".ctr":
            keep = [c for c in range(leaf.shape[1])
                    if c not in (CTR_SWEPT, CTR_TAIL, CTR_LEVELS)]
            mine, leaf = mine[:, keep], leaf[:, keep]
        np.testing.assert_array_equal(mine, leaf,
                                      err_msg=f"{where}: leaf {path}")


_STAGED = {}


def staged(name):
    """The target's batch inserted into a runner whose decode cache a
    first run of the same batch has filled (so no lane parks on a miss),
    and its head-only trajectory: the machine after each 256-step chunk
    until no lane runs, nothing serviced in between."""
    if name not in _STAGED:
        module, make = TARGETS[name]
        batch = make()
        backend = _backend(name, n_lanes=LANES)
        backend.run_batch(batch, module.TARGET)
        module.TARGET.restore()
        backend.restore()
        view = backend._ensure_view()
        for lane, data in enumerate(batch):
            with backend._bound(view, lane):
                module.TARGET.insert_testcase(backend, data)
        backend.runner.push(view)
        operands = backend.runner.executor_operands()
        n_steps = backend.runner.chunk_steps
        # the runner's own executor: no tail at LANES lanes
        assert step.tail_width(LANES) == 0
        chunk = step.make_run_chunk(n_steps, donate=False)
        machine, trajectory = operands[2], []
        while (np.asarray(machine.status) == RUNNING).any():
            machine = chunk(operands[0], operands[1], machine, operands[3])
            trajectory.append(_host(machine))
        _STAGED[name] = (operands, n_steps, trajectory)
    return _STAGED[name]


_TAILED = {}


def tailed(name, width):
    """The same chunks through the narrowing executor at `width`, each
    compared with the full-width one as it returns -> per chunk (sweeps
    of all lanes, sweeps of fewer, lanes RUNNING at the end)."""
    if (name, width) not in _TAILED:
        operands, n_steps, trajectory = staged(name)
        chunk = step.make_run_chunk(n_steps, donate=False, tail=width)
        machine, shape = operands[2], []
        swept = np.zeros(LANES, np.int64)
        tail = np.zeros(LANES, np.int64)
        levels = np.zeros(LANES, np.int64)
        for k, want in enumerate(trajectory):
            machine = chunk(operands[0], operands[1], machine, operands[3])
            got = _host(machine)
            _same_but_sweep_counters(got, want,
                                     f"{name} W={width} chunk {k}")
            d_swept = got[".ctr"][:, CTR_SWEPT] - swept
            d_tail = got[".ctr"][:, CTR_TAIL] - tail
            swept, tail = got[".ctr"][:, CTR_SWEPT], got[".ctr"][:, CTR_TAIL]
            # every lane takes the sweeps of all lanes; the narrow ones
            # are taken by whole groups of `width`, and counted per lane
            h = int(d_swept.min())
            t = int(d_swept.max()) - h
            np.testing.assert_array_equal(d_tail, d_swept - h)
            for passes in set(d_swept.tolist()):
                assert int((d_swept >= passes).sum()) % width == 0
            # the loop stops where the full-width loop stops: the lane
            # that runs longest is swept every time
            assert h + t == int(
                want[".ctr"][0, CTR_SWEPT]
                - (trajectory[k - 1][".ctr"][0, CTR_SWEPT] if k else 0))
            # CTR_LEVELS: every lane carries the chunk's levels that swept;
            # the full-width executor has no levels
            d_levels = got[".ctr"][:, CTR_LEVELS] - levels
            levels = got[".ctr"][:, CTR_LEVELS]
            assert len(set(d_levels.tolist())) == 1
            # one level for the full-width sweeps, one or more for the rest
            assert (h > 0) + (t > 0) <= d_levels[0] <= LANES // width
            assert t > 0 or d_levels[0] == (h > 0), (d_levels[0], h, t)
            assert not want[".ctr"][:, CTR_LEVELS].any()
            shape.append((h, t, int((got[".status"] == RUNNING).sum())))
        _TAILED[name, width] = (n_steps, shape)
    return _TAILED[name, width]


@pytest.mark.parametrize("name,width", CASES)
def test_narrowing_chunk_equals_the_full_width_executor(name, width):
    n_steps, shape = tailed(name, width)
    assert len(shape) >= 3, "the deep lane runs several chunks"
    if width == LANES:
        assert all(t == 0 for _, t, _ in shape), "one group: never narrow"
    else:
        assert sum(t for _, t, _ in shape) > 0, "narrow sweeps ran"


NARROW = [c for c in CASES if c[1] < LANES]


@pytest.mark.parametrize("name,width", NARROW)
def test_a_chunk_narrows_mid_way(name, width):
    _, shape = tailed(name, width)
    assert any(h > 0 and t > 0 for h, t, _ in shape), shape


@pytest.mark.parametrize("name,width", NARROW)
def test_a_chunk_starts_narrow(name, width):
    _, shape = tailed(name, width)
    assert any(h == 0 and t > 0 for h, t, _ in shape), shape


@pytest.mark.parametrize("name,width", NARROW)
def test_a_chunk_ends_by_n_steps_while_narrow(name, width):
    n_steps, shape = tailed(name, width)
    assert any(t > 0 and h + t == n_steps and live > 0
               for h, t, live in shape), shape


def test_the_default_width():
    assert step.tail_width(4096) == 256 and step.tail_width(1024) == 64
    assert step.tail_width(1023) == 0 and step.tail_width(4) == 0


# -- through Runner.run: decode misses and breakpoints serviced ------------

@pytest.fixture
def tail_on(monkeypatch):
    """`Runner.run`'s executors take groups of LANES / 4 lanes for one
    test: the floor and the number of groups lowered, the memoized
    executors of the default width set aside."""
    monkeypatch.setattr(step, "TAIL_MIN_LANES", LANES)
    monkeypatch.setattr(step, "TAIL_SHARE", 4)
    monkeypatch.setattr(step, "_CHUNK_CACHE", {})
    assert step.tail_width(LANES) == LANES // 4


_COLD = {}


def _run_cold(name):
    """The batch on a fresh backend (empty decode cache: every new rip is
    a decode miss the host services between chunks; the finish breakpoint
    and the crash hooks are serviced too) -> what it left.  One run for
    each width `Runner.run` takes."""
    key = (name, step.tail_width(LANES))
    if key in _COLD:
        return _COLD[key]
    module, make = TARGETS[name]
    backend = _backend(name, n_lanes=LANES)
    results = backend.run_batch(make(), module.TARGET)
    counters = {k: backend.registry.counter(k).value for k in (
        "device.instructions", "device.lane_steps", "device.tail_lane_steps",
        "device.lockstep_floor_steps", "device.decode_misses",
        "device.mem_faults", "runner.chunks", "device.levels_used",
        "device.store_steps", "device.vector_steps",
        "device.store3_steps")}
    _COLD[key] = {"machine": _host(backend.runner.machine),
                  "counters": counters,
                  "results": [str(r) for r in results]}
    module.TARGET.restore()
    return _COLD[key]


@pytest.mark.parametrize("name", TARGETS)
def test_runner_batch_resumed_after_servicing_equals_head_only(
        name, request):
    plain = _run_cold(name)
    request.getfixturevalue("tail_on")
    tail = _run_cold(name)
    assert plain["counters"]["device.decode_misses"] > 0
    assert plain["counters"]["runner.chunks"] >= 4
    assert plain["counters"]["device.tail_lane_steps"] == 0
    _same_but_sweep_counters(tail["machine"], plain["machine"], name)
    assert tail["results"] == plain["results"]
    for key in ("device.instructions", "device.decode_misses",
                "device.mem_faults", "device.lockstep_floor_steps",
                "runner.chunks", "device.store_steps",
                "device.vector_steps"):
        assert tail["counters"][key] == plain["counters"][key], key
    # the fold hands `device.levels_used` out per lane, as it does sweeps:
    # a chunk's levels that swept, at least one a chunk and more than one
    # in a chunk that narrowed; full-width chunks have no levels
    # neither target stores more than eight bytes at once
    assert plain["counters"]["device.store3_steps"] \
        == tail["counters"]["device.store3_steps"] == 0
    assert plain["counters"]["device.levels_used"] == 0
    assert tail["counters"]["device.levels_used"] == \
        tail["machine"][".ctr"][:, CTR_LEVELS].sum() / LANES
    assert tail["counters"]["runner.chunks"] \
        < tail["counters"]["device.levels_used"] \
        <= 4 * tail["counters"]["runner.chunks"]
    assert 0 < tail["counters"]["device.tail_lane_steps"] \
        <= tail["counters"]["device.lane_steps"] \
        < plain["counters"]["device.lane_steps"]
    # narrow sweeps are taken by whole groups of W lanes
    width = LANES // 4
    assert tail["counters"]["device.lane_steps"] % width == 0
    assert tail["counters"]["device.tail_lane_steps"] % width == 0


@pytest.mark.parametrize("name", TARGETS)
def test_instructions_equal_the_oracle_and_the_floor_is_beaten(
        name, tail_on):
    """With the tail on, every lane ends as the oracle ends its testcase,
    `device.instructions` is the oracle's summed icount and stays under
    the lockstep floor; `lane_steps` no longer has to reach the floor
    (lanes x the deepest lane): the tail sweeps W lanes where lockstep
    swept all, which is what it is for."""
    module, make = TARGETS[name]
    emu = _backend(name, kind="emu")
    icounts, results = [], []
    for data in make():
        module.TARGET.insert_testcase(emu, data)
        results.append(emu.run())
        icounts.append(emu.cpu.icount)
        emu.restore()
    seen = _run_cold(name)
    assert seen["results"] == [str(r) for r in results]
    assert name != "kernel" or any(isinstance(r, Crash) for r in results)
    counters = seen["counters"]
    assert counters["device.instructions"] == sum(icounts)
    assert counters["device.lockstep_floor_steps"] == LANES * max(icounts)
    assert counters["device.instructions"] \
        <= counters["device.lockstep_floor_steps"]
    assert counters["device.instructions"] <= counters["device.lane_steps"] \
        < counters["device.lockstep_floor_steps"]
