"""Faults planted under the timed path of a whole rehearsal run of cell
``sdar.ifca_perround``: ``correct``, by the cell's own limits, comes out
false for a state left unchanged, for half of each batch left out and for
an altered assignment. Under ``sgd`` there is no moment to read, so each
fault has to show in the change of the parameters or in the assignment. A
local step of this cell is ONE sequence, so half a batch is half of its
noisy positions (the objective over the first half of them, the mean over
those), planted in the model's own objective, which the round programs
call in place of a cross-entropy a label."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_driving import drive, failed, plant  # noqa: E402

CELL = "sdar.ifca_perround"


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    plant(monkeypatch, "state_unchanged")
    result = drive(CELL)
    assert not result["correct"]
    assert "change_gap_median" in failed(result)
    assert result["check"]["change_gap_median"]["value"] == pytest.approx(1.0)


def test_half_of_each_batch_left_out_is_not_correct(monkeypatch):
    from feddrift_tpu.models import sdar_moe
    real = sdar_moe.diffusion_loss

    def half(logits, y):
        n = y.shape[-1] // 2
        return real(logits[..., :n, :], y[..., :n])
    monkeypatch.setattr(sdar_moe, "diffusion_loss", half)
    result = drive(CELL)
    assert not result["correct"]
    assert "change_gap_median" in failed(result)


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    plant(monkeypatch, "assign_altered")
    result = drive(CELL)
    assert not result["correct"]
    assert "assign_regret" in failed(result)
