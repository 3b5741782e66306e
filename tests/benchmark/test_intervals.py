"""The trace reduction's interval arithmetic on made-up intervals, and the
readers of the trace's metrics."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402


def test_union_clip_subtract_and_gaps():
    cover = xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert cover == [(0, 3), (5, 7)]
    assert xplane.total(cover) == 5
    assert xplane.clip(cover, 2, 6) == [(2, 3), (5, 6)]
    assert xplane.gaps(cover, -1, 8) == [(-1, 0), (3, 5), (7, 8)]
    assert xplane.subtract([(0, 10)], [(2, 3), (4, 12)]) == [(0, 2), (3, 4)]
    assert xplane.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]


def test_self_times_of_nested_events():
    ev = [("while", 0.0, 10.0), ("a", 1.0, 4.0), ("b", 5.0, 9.0),
          ("b.inner", 6.0, 7.0), ("after", 11.0, 12.0)]
    st = {n: s for n, _, _, s in xplane.self_times(ev)}
    assert st == {"while": 3.0, "a": 3.0, "b": 3.0, "b.inner": 1.0,
                  "after": 1.0}


def test_gap_attribution_takes_the_innermost_span():
    spans = [("iteration", 0.0, 10.0), ("eval", 4.0, 6.0)]
    assert xplane.attribute((4.5, 5.5), spans) == "eval"
    assert xplane.attribute((7.0, 8.0), spans) == "iteration"
    assert xplane.attribute((11.0, 12.0), spans) == "outside the program's spans"


def test_device_metric_readers_divide_by_the_traced_rounds():
    import importlib
    trace = {"window_s": 2.0, "busy_s": 1.5, "rounds": 10,
             "module_s": {"jit__train_round(1)": 0.5,
                          "jit__train_iteration_eval(2)": 0.3,
                          "jit_acc_matrix(3)": 9.0}}
    r = lambda n: importlib.import_module(f"benchmark.metrics.{n}").read  # noqa: E731
    read = r("train_program_device_ms")
    assert read({"round_program": "train_round"}, trace, {"chips": 1}) \
        == pytest.approx(50.0)
    assert read({"round_program": "train_iteration_eval"}, trace,
                {"chips": 1}) == pytest.approx(30.0)
    assert read({"round_program": "megastep"}, trace, {"chips": 1}) is None
    assert r("device_idle_share")({}, trace, {"chips": 1}) == pytest.approx(25.0)
