"""The harness on the CPU: the readers' arithmetic and the control flow of
the command."""

import importlib
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_manifest()
CELLS = [c["name"] for c in MANIFEST["workloads"]]


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = {"time_steps": [], "window_s": 1.0, "compiles": 0,
             "tracked_compiles": 0}
    for m in MANIFEST["per_layer"]:
        if m["name"] == "steady_compiles":
            continue
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        assert reader.read(empty, None, {"chips": 1}) is None, m["name"]


def test_peaks_table_is_keyed_by_device_kind_with_its_source():
    peaks = bench.load_json("peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert all("source" in row for row in peaks.values())
    records = {"time_steps": [{"active_pairs": 1, "rounds": 1}],
               "window_s": 1.0, "chips": 1, "local_steps": 1, "batch": 1,
               "participants": 1, "clients": 1,
               "train_flops_per_example": 1, "peaks": peaks,
               "device_kind": "some other chip"}
    mfu = importlib.import_module("benchmark.metrics.train_step_mfu")
    with pytest.raises(KeyError):
        mfu.read(records, None, {"chips": 1})


def test_mfu_arithmetic_counts_active_pairs_of_the_participants_only():
    peaks = {"k": {"bf16_flops_per_s": 1e12}}
    records = {"time_steps": [{"active_pairs": 2, "rounds": 5}] * 2,
               "window_s": 4.0, "chips": 2, "local_steps": 5, "batch": 64,
               "participants": 4, "clients": 4,
               "train_flops_per_example": 1e9, "peaks": peaks,
               "device_kind": "k"}
    mfu = importlib.import_module("benchmark.metrics.train_step_mfu")
    useful = 2 * 5 * 2 * 5 * 64 * 1e9
    assert mfu.read(records, None, {}) == pytest.approx(
        100 * useful / (4.0 * 2 * 1e12))
    assert mfu.read(dict(records, participants=2), None, {}) == pytest.approx(
        50 * useful / (4.0 * 2 * 1e12))


def test_memory_readers_keep_the_allocators_peak_and_the_reserve_apart():
    records = {"peak_bytes": 2.5e9, "peak_reserved_bytes": 10e9}
    r = lambda n: importlib.import_module(f"benchmark.metrics.{n}").read  # noqa: E731
    assert r("peak_hbm_gb")(records, None, {}) == pytest.approx(2.5)
    assert r("peak_hbm_reserved_gb")(records, None, {}) == pytest.approx(10.0)
    assert r("peak_hbm_reserved_gb")({"peak_reserved_bytes": 0}, None, {}) \
        is None


def test_the_job_is_made_from_the_files_groups_the_cells_last():
    from benchmark.drivers.train import change_point_literal, experiment_config
    assert change_point_literal([[0, 1], [1, 0]], 5) == "0 1 0 1 0;1 0 1 0 1"
    assert bench.overlay({"a": {"x": 1, "y": 2}, "b": 3}, {"a": {"y": 5}}) \
        == {"a": {"x": 1, "y": 5}, "b": 3}
    _cell, config, traffic, sizes = bench.load_cell(MANIFEST, CELLS[0],
                                                    rehearse=True)
    cfg = experiment_config(config, traffic, sizes, 7, 4)
    assert (cfg.client_num_in_total, cfg.client_num_per_round) == (4, 4)
    assert cfg.train_iterations == sizes["program"]["train_iterations"]
    # a file may have a round sample its participants
    part = bench.overlay(sizes, {"program": {"client_num_per_round": 2}})
    assert experiment_config(config, traffic, part, 7, 4) \
        .client_num_per_round == 2


def _command(*extra, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *extra],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=900)


def test_without_a_tpu_the_command_prints_no_result_and_fails():
    p = _command("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_rehearsal_drives_a_whole_run_and_prints_no_device_number():
    p = _command("--workload", "resnet20.ifca_perround", "--seed",
                 str(2 ** 31 + 77), "--seconds", "1", "--trace", "0",
                 "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "rehearsal done: correct=True" in p.stderr
    assert "examples/s" not in p.stderr and "mfu" not in p.stderr
    # traced or not, a run names the segments of its longest time step
    assert "longest time step of the window: t=" in p.stderr
