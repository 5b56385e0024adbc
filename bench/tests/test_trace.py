"""The reduction from a profiler trace to the per-layer numbers.

    python -m pytest -q bench/tests/test_trace.py
"""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import tracereduce  # noqa: E402

FIXTURE = BENCH / "tests" / "fixtures" / "v5e.xplane.pb"
MS = 1_000_000  # ns


def test_reduce_spans_by_hand():
    ops = [("fusion.1", 0 * MS, 4 * MS), ("fusion.2", 3 * MS, 6 * MS),
           ("all-reduce.3", 8 * MS, 9 * MS), ("fusion.1", 12 * MS, 20 * MS),
           ("fusion.9", 25 * MS, 30 * MS)]
    modules = [("jit_round_fn(7)", 0, 9 * MS), ("jit_round_fn(7)", 12 * MS,
                                                20 * MS)]
    spans = [("bench.window", 2 * MS, 22 * MS),
             ("bench.data", 5 * MS, 12 * MS), ("bench.dispatch", 9 * MS,
                                               10 * MS)]
    r = tracereduce.reduce_spans([{"ops": ops, "modules": modules}], spans, 1)
    # inside [2, 22] ms: busy [2,6] + [8,9] + [12,20] = 13 ms
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.013)
    assert r["collective_s"] == pytest.approx(0.001)
    assert r["programs"]["jit_round_fn"] == (pytest.approx(0.015), 2)
    assert r["top_ops"][0] == ("fusion.1", pytest.approx(0.010))
    # gaps [6,8] (data), [9,12] (data 2 ms vs dispatch 1 ms), [20,22]
    assert r["idle_gaps"] == [["bench.data", pytest.approx(0.003)],
                              ["bench.data", pytest.approx(0.002)],
                              ["other", pytest.approx(0.002)]]


def test_two_chips_average():
    spans = [("bench.window", 0, 10 * MS)]
    devs = [{"ops": [("a", 0, 10 * MS)], "modules": []},
            {"ops": [("a", 0, 4 * MS)], "modules": []}]
    r = tracereduce.reduce_spans(devs, spans, 2)
    assert r["busy_s"] == pytest.approx(0.007)


def test_no_device_op_inside_the_window_is_an_error():
    spans = [("bench.window", 50 * MS, 60 * MS)]
    devs = [{"ops": [("a", 0, 10 * MS)], "modules": []}]
    with pytest.raises(ValueError, match="inside the bench.window"):
        tracereduce.reduce_spans(devs, spans, 1)


def test_recorded_v5e_trace():
    """A traced run of mamba2-130m.train.m8-s512 on a TPU v5 lite, cut to
    the first 0.5 s of its window (the window span cut to match, op names
    cut to 120 characters, event stats and other planes dropped)."""
    r = tracereduce.reduce_file(str(FIXTURE), 1)
    assert r["window_s"] == pytest.approx(0.5)
    # device-bound: the round program runs back to back
    assert 0.95 * r["window_s"] < r["busy_s"] <= r["window_s"]
    assert r["programs"]["jit_round_fn"][1] == 2
    assert r["collective_s"] == 0.0
    names = [n for n, _ in r["top_ops"]]
    assert names[0] == "while.292" and not any(" = " in n for n in names)
    assert r["idle_gaps"] and r["ops"] > 10_000
