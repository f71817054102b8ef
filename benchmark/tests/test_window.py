"""The window picker and the heartbeat arithmetic, on recorded streams."""

import json

import pytest

from harness import window


def hb(ts, batches, testcases=None, instructions=None, **phase):
    return {"type": "heartbeat", "ts": float(ts), "metrics": {
        "backend.batches": batches,
        "campaign.testcases": 1024 * batches if testcases is None
        else testcases,
        "backend.instructions": 40000 * batches if instructions is None
        else instructions,
        "phase.seconds": dict(phase)}}


COMPILE = {"type": "compile", "ts": 0.0, "kind": "megachunk"}


def pick(records, seconds=51, warm=8):
    p = window.WindowPicker(seconds, warm)
    for r in records:
        p.feed(r)
    return p


def test_window_waits_for_the_warm_batches():
    p = pick([COMPILE, hb(100, 2), hb(130, 8), hb(160, 16), hb(190, 24),
              hb(220, 32)])
    assert p.first["ts"] == 130 and p.last["ts"] == 190
    assert p.restarts == 0 and p.heartbeats == 3


def test_window_ends_at_the_first_heartbeat_late_enough_and_divides_by_it():
    # one iteration of overshoot: the rate divides by the real 60 s
    p = pick([hb(0, 8), hb(30, 16), hb(60, 24), hb(90, 32)])
    assert p.done and p.last["ts"] == 60
    rate = window.evaluate({"numerator": ["campaign.testcases"],
                            "denominator": ["window_s"]}, p.first, p.last)
    assert rate == pytest.approx(16 * 1024 / 60)


def test_records_after_the_window_change_nothing():
    p = pick([hb(0, 8), hb(60, 24), COMPILE, COMPILE, hb(200, 99)])
    assert p.last["ts"] == 60 and p.restarts == 0


def test_a_compile_inside_the_window_restarts_it():
    p = pick([hb(0, 8), hb(30, 16), COMPILE, hb(70, 24), hb(100, 32),
              hb(125, 40)])
    assert p.restarts == 1
    assert p.first["ts"] == 70 and p.last["ts"] == 125


def test_a_window_that_keeps_compiling_is_an_error():
    stream = [hb(0, 8)]
    for i in range(1, 4):
        stream += [COMPILE, hb(40 * i, 8 + 8 * i)]
    assert pick(stream).restarts == 3
    with pytest.raises(window.WindowError, match="compile number 4"):
        pick(stream + [COMPILE])


def test_compiles_before_the_window_are_warm_up():
    p = pick([COMPILE, hb(10, 1), COMPILE, hb(20, 2), hb(80, 9)], warm=2)
    assert p.restarts == 0 and p.first["ts"] == 20 and p.last["ts"] == 80


def test_a_stream_that_ends_early_leaves_the_window_open():
    assert not pick([hb(0, 8), hb(30, 16)]).done


def test_follow_reads_appended_records_and_ends_with_the_child(tmp_path):
    path = tmp_path / "events.jsonl"
    state = {"alive": True, "polls": 0}
    path.write_text(json.dumps(hb(0, 8)) + "\n" + '{"type": "heart')
    seen = []
    for rec in window.follow(path, alive=lambda: state["alive"], poll=0.01):
        if rec is None:
            state["polls"] += 1
            if state["polls"] == 1:      # the torn line is completed
                with open(path, "a") as fh:
                    fh.write('beat", "ts": 5.0, "metrics": {}}\n')
            else:
                state["alive"] = False   # child death: the stream ends
            continue
        seen.append(rec)
    assert [r["ts"] for r in seen] == [0.0, 5.0]


def test_follow_waits_for_a_file_that_is_not_there_yet(tmp_path):
    got = list(window.follow(tmp_path / "none.jsonl", alive=lambda: False,
                             poll=0.01))
    assert got == []


A = hb(100, 8, execute=10.0, harvest=1.0, **{"execute/insert": 0.5})
B = hb(160, 24, execute=60.0, harvest=4.0, **{"execute/insert": 2.5})
B["metrics"]["runner.fallbacks_by_opclass"] = {"sse": 3, "x87": 2}


@pytest.mark.parametrize("source, want", [
    ({"numerator": ["campaign.testcases"], "denominator": ["window_s"]},
     16 * 1024 / 60),
    ({"numerator": ["phase.seconds.harvest", "phase.seconds.mutate"],
      "denominator": ["window_s"], "scale": 100}, 5.0),   # absent reads 0
    ({"numerator": ["phase.seconds.execute/insert"],
      "denominator": ["backend.batches"]}, 0.125),
    ({"numerator": ["runner.chunks"], "denominator": ["backend.batches"]},
     0.0),
    ({"numerator": ["devdec.zero_host_windows"],
      "denominator": ["megachunk.windows"]}, None),       # nothing to read
    ({"numerator": ["backend.batches"],
      "denominator": ["phase.seconds.mutate"]}, None),
    # a labelled counter reads as its total; only the last record has it
    ({"numerator": ["runner.fallbacks_by_opclass"],
      "denominator": ["backend.batches"], "scale": 16}, 5.0),
])
def test_heartbeat_delta_arithmetic(source, want):
    got = window.evaluate(source, A, B)
    assert got is None if want is None else got == pytest.approx(want)


def test_a_denominator_that_did_not_move_makes_the_metric_absent():
    assert window.evaluate({"numerator": ["campaign.testcases"],
                            "denominator": ["backend.batches"]}, A, A) is None
