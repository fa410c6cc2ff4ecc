"""The trace -> metrics reduction on small traces: one written by hand, whose
answers can be worked out on paper, and one recorded on the chip."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def _hand_trace():
    # window 0..100 ms; two programs; host spans "plan" (10..30) inside "query:collect" (5..95)
    return {
        "device": {
            "/device:TPU:0": {
                tr.MODULE_LINE: [[tr.START_MARK + "(1)", -1 * MS, 1 * MS], ["jit__probe(3)", 20 * MS, 10 * MS],
                                  ["jit_group_ids(7)", 50 * MS, 20 * MS], ["jit__probe(4)", 90 * MS, 20 * MS],
                                  [tr.END_MARK + "(2)", 100 * MS, 1 * MS]],
                tr.OP_LINE: [["fusion.1", 20 * MS, 10 * MS], ["sort.2", 50 * MS, 20 * MS], ["fusion.3", 90 * MS, 20 * MS]],
            }
        },
        "host": {
            "python": [["query:collect", 5 * MS, 90 * MS], ["plan", 10 * MS, 20 * MS]],
        },
    }


def test_hand_trace():
    red = tr.reduce(_hand_trace())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.04)  # 10 + 20 + the 10 ms of the last op inside the window
    assert red["idle_share"] == pytest.approx(0.6)
    assert red["program_s"] == pytest.approx({"jit__probe": 0.02, "jit_group_ids": 0.02})
    gaps = dict(red["idle_gaps"])
    # idle 0-20 (mid 10: plan opens at 10), 30-50 (mid 40: query:collect), 70-90 (mid 80: query:collect)
    assert gaps == pytest.approx({"plan": 0.02, "query:collect": 0.04})
    assert [k for k, _ in red["device_ops"]] in (["jit__probe", "jit_group_ids"], ["jit_group_ids", "jit__probe"])


def test_program_time_inside_own_spans():
    # the program's own spans are on the wall clock; t_mark puts them on the trace's
    spans = [{"name": "build:stages", "start_s": 100.015, "duration_s": 0.02},
             {"name": "build:stages", "start_s": 100.085, "duration_s": 0.01}]
    red = tr.reduce(_hand_trace(), spans=spans, t_mark=100.0)
    # jit__probe(3) runs 20-30 ms (middle inside the first span); jit__probe(4)
    # runs 90-110 ms, 10 ms of it in the window, its middle (100) outside the
    # second span; jit_group_ids runs outside both
    assert list(red["program_s_in_span"]) == ["build:stages"]
    assert red["program_s_in_span"]["build:stages"] == pytest.approx({"jit__probe": 0.01})
    assert "plan" not in red["program_s_in_span"]  # the trace's own host events are not asked


def test_no_window_or_device_is_an_error():
    t = _hand_trace()
    lines = t["device"]["/device:TPU:0"]
    no_end = dict(lines, **{tr.MODULE_LINE: lines[tr.MODULE_LINE][:-1]})
    with pytest.raises(ValueError):
        tr.reduce({"device": {"/device:TPU:0": no_end}, "host": t["host"]})
    with pytest.raises(ValueError):
        tr.reduce({"device": {}, "host": t["host"]})


def test_recorded_trace():
    """A 10 s build window recorded on a v5e (my chip run, PR 22)."""
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        rec = json.load(f)
    red = tr.reduce(rec["extract"])
    for key, want in rec["expect"].items():
        assert red[key] == pytest.approx(want), key
    assert 0 < red["busy_s"] < red["window_s"]
    # the sort's device time, summed here by hand from its module events
    w0, w1 = tr.window(rec["extract"])
    modules = rec["extract"]["device"]["/device:TPU:0"][tr.MODULE_LINE]
    sort_s = sum(d for n, s, d in modules if tr.program_name(n) == "jit_impl" and w0 <= s and s + d <= w1) / 1e9
    assert red["program_s"]["jit_impl"] == pytest.approx(sort_s)
