"""The plain reference with no program involved: put in the program's place
it agrees with itself and reads each planted fault and the lower precision
apart. ``test_compare.py`` has the comparison's arithmetic."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_driving import files as _files  # noqa: E402

from benchmark import weights  # noqa: E402
from benchmark.drivers import train  # noqa: E402


# ----------------------------------------------------------------------
# the reference put in the program's place (no program involved)
@pytest.fixture(scope="module")
def tiny_job():
    _cell, config, traffic, _sizes = _files("resnet20.ifca_perround")
    rng = np.random.default_rng(0)
    C, T1, N, M = 4, 3, 16, traffic["program"]["concept_num"]
    x = rng.normal(0.5, 0.8, size=(C, T1, N, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=(C, T1, N)).astype(np.int32)
    flat = {k: np.asarray(v) for k, v in
            weights.make_weights(config["arch"], 11, M).items()}
    init = [{k: v[m] for k, v in flat.items()} for m in range(M)]
    hyper = dict(config["optimizer"], lr=0.01, wd=0.001)
    job = {"seed": 11, "batch": 8, "local_steps": 2}
    return config["arch"], hyper, init, x, y, job, traffic


def _numbers(tiny_job, **planted):
    arch, hyper, init, x, y, job, traffic = tiny_job
    seen = train.reference_as_program(arch, hyper, init, x, y, job, traffic,
                                      **planted)
    return train.check(arch, hyper, init, x, y, job, traffic, seen)


def test_reference_agrees_with_itself(tiny_job):
    numbers = _numbers(tiny_job)
    assert numbers["change_gap"] < 1e-5 and numbers["moment_gap"] < 1e-5
    assert numbers["train_loss_gap"] < 1e-5 and numbers["assign_regret"] == 0
    assert numbers["test_loss_gap"] < 1e-5
    assert numbers["param_store_gap"] < 1e-5
    assert numbers["moment_store_gap"] < 1e-5


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_planted_fault_reads_far_from_the_sound_reference(tiny_job, fault):
    numbers = _numbers(tiny_job, fault=fault)
    assert max(numbers["change_gap"], numbers["moment_gap"]) > 0.1, numbers


def test_an_altered_assignment_reads_as_accuracy_given_away(tiny_job):
    numbers = _numbers(tiny_job, fault="assign_altered")
    assert numbers["assign_regret"] > 0.1, numbers
    assert numbers["change_gap"] < 1e-5       # nothing else is touched


def test_convolutions_in_bfloat16_alone_read_between_sound_and_control(tiny_job):
    """The look of PERF.md section 2: the configuration's compute precision
    by itself moves the numbers, and keeps float32 parameters."""
    numbers = _numbers(tiny_job, compute_dtype="bfloat16")
    assert 1e-4 < numbers["change_gap"] < 0.5, numbers
    assert numbers["param_store_gap"] < 0.3, numbers


def test_lower_precision_reference_reads_apart(tiny_job):
    numbers = _numbers(tiny_job, lower=True)
    assert numbers["param_store_gap"] == pytest.approx(1.0)
    assert numbers["moment_store_gap"] == pytest.approx(1.0)
    assert max(numbers["change_gap"], numbers["moment_gap"]) > 0.02, numbers
