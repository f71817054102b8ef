"""The trace arithmetic on rows written by hand, and the loader on a trace
taken without a chip."""

import pytest

from harness import trace
from harness.trace import Row

P0, P1 = "/device:TPU:0", "/device:TPU:1"


def op(plane, name, start, dur, line=trace.OPS_LINE):
    return Row(plane, line, name, float(start), float(dur))


@pytest.mark.parametrize("intervals, want", [
    ([], 0),
    ([(0, 10)], 10),
    ([(0, 10), (20, 30)], 20),                 # apart
    ([(0, 10), (5, 15)], 15),                  # overlapping
    ([(0, 100), (10, 20), (30, 40)], 100),     # nested
    ([(30, 40), (0, 10), (10, 20)], 30),       # touching, out of order
    ([(0, 10), (0, 10)], 10),                  # twice the same
])
def test_union(intervals, want):
    assert trace.union_ns(intervals) == want


ROWS = [
    op(P0, "while", 0, 1200),                  # holds the next two
    op(P0, "fusion.1", 100, 300),
    op(P0, "gather.2", 500, 400),
    op(P0, "fusion.1", 1500, 500),             # after a gap of 300
    op(P0, "%copy.3 = u32[1024]{0:T(1024)} copy(u32[1024]{0} %p)", 2000, 1000),
    op(P0, "module", 0, 3000, line="XLA Modules"),   # not an operation row
    op("/host:CPU", "python", 0, 99999, line="python"),
]


def test_leaves_busy_top_and_gaps_on_one_chip():
    chips = trace.device_op_rows(ROWS)
    # the while holds two operations and is left out; so are other lines
    assert sorted(chips) == [0]
    assert [r.name.split(" = ")[0] for r in chips[0]] == [
        "fusion.1", "gather.2", "fusion.1", "%copy.3"]
    busy, extent = trace.busy_and_extent(chips)
    assert busy == pytest.approx(2200e-9)       # 300 + 400 + 500 + 1000
    assert extent == pytest.approx(2900e-9)     # from 100 to 3000
    got = trace.reduce_slices([(chips, 3000e-9)])
    assert got["busy_s"] == pytest.approx(2200e-9)
    assert got["window_s"] == pytest.approx(3000e-9)   # the host's sleep
    top = got["breakdown"]["device_ops"]
    assert top[0] == ["%copy.3", pytest.approx(1000e-9)]   # cut to its name
    assert top[1] == ["fusion.1", pytest.approx(800e-9)]
    gaps = got["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == [pytest.approx(600e-9),
                                    pytest.approx(100e-9)]
    assert "gather.2" in gaps[0][0] and "fusion.1" in gaps[0][0]


@pytest.mark.parametrize("rows, leaves", [
    ([("a", 0, 10), ("b", 10, 10)], ["a", "b"]),               # apart
    ([("p", 0, 100), ("c", 0, 50), ("d", 50, 50)], ["c", "d"]),  # same start
    ([("p", 0, 100), ("q", 10, 80), ("c", 20, 10)], ["c"]),    # two deep
    ([("a", 0, 60), ("b", 50, 60)], ["a", "b"]),       # overlapping: both
    ([("p", 0, 100), ("c", 90, 10), ("e", 100, 5)], ["c", "e"]),
])
def test_leaves(rows, leaves):
    got = trace.leaf_rows(op(P0, n, s, d) for n, s, d in rows)
    assert [r.name for r in got] == leaves


def test_slices_sum_and_a_slice_with_no_operation_counts_as_idle():
    busy_slice = trace.device_op_rows(ROWS)
    got = trace.reduce_slices([(busy_slice, 2000e-9), ({}, 2500e-9),
                               ({0: []}, 500e-9)])
    assert got["busy_s"] == pytest.approx(2200e-9)
    # the first slice's operations span more than the host slept
    assert got["window_s"] == pytest.approx((2900 + 2500 + 500) * 1e-9)
    assert got["breakdown"]["idle_gaps"][0] == [
        "unattributed: slice 1, no operation", pytest.approx(2500e-9)]


def test_busy_is_averaged_over_the_chips_and_the_extent_spans_them():
    rows = ROWS + [op(P1, "fusion.1", 1000, 500)]
    busy, extent = trace.busy_and_extent(trace.device_op_rows(rows))
    assert busy == pytest.approx((2200 + 500) / 2 * 1e-9)
    assert extent == pytest.approx(2900e-9)


def test_no_operation_in_any_slice_is_an_error_not_an_idle_chip():
    no_chip = trace.device_op_rows([r for r in ROWS if r.plane != P0])
    assert no_chip == {}
    with pytest.raises(trace.TraceError, match="no slice"):
        trace.reduce_slices([(no_chip, 1.0), ({}, 1.0)])


def test_loader_finds_no_chip_in_a_trace_taken_without_one(tmp_path):
    """A real (tiny) trace from this machine's CPU: planes and rows load,
    and the device loader finds no TPU plane in it."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.arange(1024.0).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(tmp_path)
    rows = trace.load_rows(path)
    assert rows and all(r.duration_ns >= 0 for r in rows)
    assert any(r.plane.startswith("/host:") for r in rows)
    if jax.default_backend() == "tpu":
        pytest.skip("a chip is attached: its planes are there")
    assert trace.load_device_rows(path) == []
    with pytest.raises(trace.TraceError, match="no slice"):
        trace.reduce_slices([(trace.device_op_rows([]), 1.0)])


def test_no_trace_written_is_an_error(tmp_path):
    with pytest.raises(trace.TraceError, match="no .xplane.pb"):
        trace.find_xplane(tmp_path)


def traced_doc(taken):
    """A child's `.traced`: slice i was due at i seconds; the taken ones
    slept 0.05 s and cost 0.05 s to start and `taken[i]` s to stop."""
    return {"opened_ts": 100.0, "budget_s": 45, "slices": [
        {"dir": f"{i:02d}", "due_s": float(i), "taken": True,
         "start_ts": 100.0 + i, "stop_ts": 100.05 + i,
         "start_took_s": 0.05, "stop_took_s": cost}
        if cost is not None else
        {"dir": None, "due_s": float(i), "taken": False}
        for i, cost in enumerate(taken)]}


def test_a_traced_document_with_skipped_slices_reduces_the_ones_taken():
    """Six due, the budget cut the last two: busy and traced time are
    sums over the four taken, and the result says how many were due."""
    doc = traced_doc([4.0, 0.4, 3.5, 0.4, None, None])
    loaded = []

    def load(directory):
        loaded.append(directory)
        # slices 00 and 02 met the chip running, 01 and 03 met it waiting
        return ROWS if directory in ("00", "02") else []

    got = trace.reduce_traced(doc, load, min_taken=4)
    assert loaded == ["00", "01", "02", "03"]
    assert (got["slices_due"], got["slices_taken"]) == (6, 4)
    assert got["busy_s"] == pytest.approx(2 * 2200e-9)
    assert got["window_s"] == pytest.approx(4 * 0.05)
    assert got["writeout_s"] == pytest.approx(4 * 0.05 + 4.0 + 0.4 + 3.5 + 0.4)
    assert [s["dir"] for s in got["per_slice"]] == ["00", "01", "02", "03"]
    assert got["per_slice"][0]["busy_s"] == pytest.approx(2200e-9)
    assert got["per_slice"][0]["cost_s"] == pytest.approx(4.05)
    assert got["per_slice"][1]["busy_s"] == 0.0
    # a gap is named after the slice's directory, not its place in a list
    gaps = [g[0] for g in got["breakdown"]["idle_gaps"]]
    assert "unattributed: slice 03, no operation" in gaps
    assert any(g.startswith("unattributed: slice 02, after") for g in gaps)
    assert ("/device:TPU:0", trace.OPS_LINE) in got["lines"]


def test_fewer_slices_than_the_least_is_an_error_that_names_the_budget():
    doc = traced_doc([50.0, None, None, None, None, None, None, None])
    with pytest.raises(trace.TraceError, match="1 of 8 due slices.*45"):
        trace.reduce_traced(doc, lambda d: ROWS, min_taken=4)


def test_taken_slices_without_an_operation_are_still_no_chip():
    doc = traced_doc([0.4] * 4)
    with pytest.raises(trace.TraceError, match="no slice"):
        trace.reduce_traced(doc, lambda d: [], min_taken=4)
