"""The trace reduction: on a small trace recorded on a v5e (two
``bench_time_step`` windows, each two runs of a four-step scan of 256x256
matmuls and a 20 ms sleep). ``test_intervals.py`` has the arithmetic on
made-up intervals."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402

FIX = os.path.join(ROOT, "benchmark", "fixtures")


@pytest.fixture(scope="module")
def raw():
    return xplane.load(os.path.join(FIX, "v5e_small.xplane.pb"))


@pytest.fixture(scope="module")
def reduced(raw):
    with open(os.path.join(FIX, "v5e_small.host.json")) as f:
        host = json.load(f)
    return xplane.reduce(raw, sync_wall=host["sync_wall"],
                         host_spans=[tuple(s) for s in host["spans"]],
                         rounds=4)


def test_fixture_holds_one_chip_and_the_benchmarks_annotations(raw):
    assert sorted(raw["devices"]) == [0]
    names = [n for n, _, _ in raw["host"]]
    assert names.count("bench_time_step") == 2 and "bench_sync" in names
    assert len(raw["devices"][0]["modules"]) == 4
    assert all(n.startswith("jit_fixture_step")
               for n, _, _ in raw["devices"][0]["modules"])


def test_window_runs_from_the_first_annotation_to_the_last(reduced):
    # 45.607629 ms .. 90.562258 ms on the trace's clock
    assert reduced["window_s"] == pytest.approx(0.044954629, abs=1e-9)


def test_module_time_is_that_of_the_programs_inside_the_window(reduced):
    # the chip's clock runs 0.9 ms ahead of the host's here, so the first
    # window's two programs (at 44.68 and 45.32 ms) fall before it; the two
    # of the second window last 2358 ns and 2232 ns
    (name, s), = reduced["module_s"].items()
    assert name.startswith("jit_fixture_step")
    assert s == pytest.approx(4.590e-6, abs=2e-9)


def test_busy_is_the_union_of_ops_and_idle_is_the_rest(reduced):
    assert 4.5e-6 < reduced["busy_s"] <= 4.590e-6
    assert reduced["busy_s_per_device"] == {0: reduced["busy_s"]}
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.9999, abs=1e-4)


def test_top_ops_are_self_times_under_short_names(reduced):
    ops = dict(reduced["breakdown"]["device_ops"])
    # four fusions of ~291 ns in each of the two programs
    assert ops["fusion.8 bf16[256,256]"] == pytest.approx(8 * 291e-9, rel=0.02)
    # the while spans its body: its self time is what the body leaves
    assert ops["while (s32[], bf16[256,256], s32[])"] < 2e-7
    assert len(reduced["breakdown"]["device_ops"]) <= 10
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=0.02)


def test_longest_gaps_are_attributed_to_the_host_span_they_fall_in(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    assert gaps[0][0] == "sleep" and gaps[0][1] == pytest.approx(0.0229, abs=1e-3)
    assert gaps[1][0] == "sleep" and gaps[1][1] == pytest.approx(0.0212, abs=1e-3)


def test_a_trace_without_the_annotation_is_refused(raw):
    with pytest.raises(ValueError):
        xplane.reduce({"devices": raw["devices"], "host": []})
