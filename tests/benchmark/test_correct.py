"""The comparison that decides ``correct``, at a tiny size on the CPU, through
everything in a run but the look for a chip and by the cell's own limits: it
passes the program as the configuration states it, also where a round
samples its participants, and fails it one precision step down.
(``test_planted_faults.py`` plants the faults; the two files share the work
between two test workers.)"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_driving import MANIFEST, CELL, drive as _drive, failed as _failed  # noqa: E402


def test_program_as_the_configuration_states_it_is_correct():
    result = _drive()
    assert result["correct"], result["check"]
    from benchmark import run as bench
    limits = bench.load_json("cells", f"{CELL}.json")["limits"]
    assert set(result["check"]) == set(limits)
    assert all(c["limit"] == limits[k] for k, c in result["check"].items())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert MANIFEST["workloads"]


def test_a_round_that_samples_its_participants_is_followed():
    """One of the two clients per round: the reference follows the time
    weights as the program masked them."""
    result = _drive(program={"client_num_per_round": 1})
    assert result["correct"], result["check"]


@pytest.mark.parametrize("precision", ["bf16_mixed", "bf16_pure"])
def test_one_precision_step_down_is_not_correct(precision):
    """The program's own lower-precision paths are the control: bfloat16
    parameters and moments, with float32 or bfloat16 aggregation."""
    result = _drive(program={"precision": precision})
    assert not result["correct"]
    assert {"param_store_gap", "moment_store_gap"} <= set(_failed(result))
    assert result["check"]["param_store_gap"]["value"] == pytest.approx(1.0)
