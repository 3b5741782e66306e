"""Faults planted under the timed path of a whole rehearsal run of cell
``kanana2.ifca_perround`` (``bench_driving.plant`` has them; the faults of
``test_planted_faults.py`` that ``sgd`` admits): ``correct``, by the cell's
own limits, comes out false for a state left unchanged, for half a batch
left out and for an altered assignment. Under ``sgd`` there is no moment to
read, so each fault has to show in the change of the parameters or in the
assignment."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_driving import drive, failed, plant  # noqa: E402

CELL = "kanana2.ifca_perround"


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    plant(monkeypatch, "state_unchanged")
    result = drive(CELL)
    assert not result["correct"]
    assert "change_gap_median" in failed(result)
    assert result["check"]["change_gap_median"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    """Half a batch is one sequence of two. The change of the parameters
    holds it, by the median leaf and by the worst one: on the chip a sound
    run reads 1.1e-4 and 0.0027 there at most, the fault 0.009-0.03 and
    0.1 (PERF.md section 2); in float32 at the rehearsal's size 0.07 and
    more."""
    plant(monkeypatch, "half_batch")
    result = drive(CELL)
    assert not result["correct"]
    assert {"change_gap_median", "change_gap"} <= set(failed(result))
    assert result["numbers"]["change_gap_median"] > 0.02
    assert result["numbers"]["change_gap"] > 0.1


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    plant(monkeypatch, "assign_altered")
    result = drive(CELL)
    assert not result["correct"]
    assert "assign_regret" in failed(result)
