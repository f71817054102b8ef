"""The child's slice loop against a profiler whose stop costs scripted
seconds: no real clock, no JAX."""

import pytest

from harness import child


class FakeProfiler:
    """start_trace costs `start_s`; the n-th stop_trace costs
    `stops[n]` seconds of the clock it shares with the loop."""

    def __init__(self, clock, stops, start_s=0.05):
        self.clock, self.stops, self.start_s = clock, list(stops), start_s
        self.dirs = []

    def start_trace(self, directory, profiler_options=None):
        self.dirs.append(directory)
        self.clock.now += self.start_s

    def stop_trace(self):
        self.clock.now += self.stops[len(self.dirs) - 1]


class Clock:
    def __init__(self, now=1000.0):
        self.now = now

    def time(self):
        return self.now

    def sleep(self, seconds):
        assert seconds >= 0
        self.now += seconds


def run(stops, offsets, budget_s, least=1, slice_s=0.05):
    clock = Clock()
    profiler = FakeProfiler(clock, stops)
    slices = child.take_slices(profiler, "X", slice_s, budget_s, least,
                               offsets, opened=1000.0, clock=clock.time,
                               sleep=clock.sleep)
    return slices, profiler, clock


def test_cheap_slices_are_all_taken_each_at_its_own_moment():
    offsets = [1.0, 4.0, 7.0, 10.0]
    slices, profiler, _ = run([0.4] * 4, offsets, budget_s=45)
    assert [s["taken"] for s in slices] == [True] * 4
    assert [s["dir"] for s in slices] == ["00", "01", "02", "03"]
    assert profiler.dirs == ["X/00", "X/01", "X/02", "X/03"]
    for s, due in zip(slices, offsets):
        assert s["due_s"] == due
        # started when due: start_ts is after start_trace returned
        assert s["start_ts"] == pytest.approx(1000.0 + due + 0.05)
        assert s["stop_ts"] - s["start_ts"] == pytest.approx(0.05)
        assert s["start_took_s"] == pytest.approx(0.05)
        assert s["stop_took_s"] == pytest.approx(0.4)


def test_no_slice_is_started_once_the_write_out_has_cost_the_budget():
    offsets = [1.0, 4.0, 7.0, 10.0, 13.0, 16.0]
    slices, profiler, clock = run([4.0, 3.0, 0.4, 3.0, 9.9, 9.9], offsets,
                                  budget_s=10)
    # 4.05 + 3.05 + 0.45 = 7.55 < 10: the fourth is taken and passes it
    assert [s["taken"] for s in slices] == [True] * 4 + [False] * 2
    assert len(profiler.dirs) == 4
    spent = sum(s["start_took_s"] + s["stop_took_s"]
                for s in slices if s["taken"])
    assert spent == pytest.approx(10.6)
    # at most the budget plus one slice's cost
    assert spent <= 10 + max(s["start_took_s"] + s["stop_took_s"]
                             for s in slices if s["taken"])
    # what it skipped is listed, with the moment it was due
    assert [(s["dir"], s["due_s"]) for s in slices[4:]] == [
        (None, 13.0), (None, 16.0)]
    # and nothing is waited for on their account
    assert clock.now == pytest.approx(slices[3]["stop_ts"] + 3.0)


def test_a_slice_whose_turn_passed_while_another_was_written_starts_at_once():
    slices, _, _ = run([6.0, 0.4, 0.4], [1.0, 2.0, 9.0], budget_s=45)
    first, late, on_time = slices
    end_of_first = first["stop_ts"] + first["stop_took_s"]
    assert end_of_first == pytest.approx(1000.0 + 1.0 + 0.05 + 0.05 + 6.0)
    assert late["start_ts"] - late["start_took_s"] == pytest.approx(
        end_of_first)                       # due at 2.0: no wait
    assert on_time["start_ts"] - on_time["start_took_s"] == pytest.approx(
        1000.0 + 9.0)


def test_a_first_stop_that_spends_the_budget_alone_still_leaves_the_least():
    """The profiler's first busy stop in a process costs tens of seconds
    more than a later one: the run still gets the slices a reading needs,
    and the budget cuts the rest."""
    stops, offsets = [50.0] + [4.0] * 7, [float(i) for i in range(8)]
    slices, profiler, _ = run(stops, offsets, budget_s=45, least=4)
    assert [s["taken"] for s in slices] == [True] * 4 + [False] * 4
    assert profiler.dirs == ["X/00", "X/01", "X/02", "X/03"]
    slices, _, _ = run(stops, offsets, budget_s=45, least=1)
    assert [s["taken"] for s in slices] == [True] + [False] * 7
