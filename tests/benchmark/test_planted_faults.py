"""Faults planted under the timed path of a whole rehearsal run, once for each
that a one-chip training cell can have (``bench_driving.plant`` has them):
``correct``, by the cell's own limits, has to come out false for each."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_driving import drive as _drive, failed as _failed, plant  # noqa: E402


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    plant(monkeypatch, "state_unchanged")
    result = _drive()
    assert not result["correct"]
    assert {"change_gap_median", "first_grad_gap_median"} <= set(_failed(result))
    assert result["check"]["change_gap_median"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    """The loss and its gradient taken over the first half of every batch,
    the mean over the rest."""
    plant(monkeypatch, "half_batch")
    result = _drive()
    assert not result["correct"]
    assert {"first_grad_gap_median", "train_loss_gap"} <= set(_failed(result))


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The accuracy matrix that the host's re-assignment reads comes back
    negated, so every client goes to its worst model."""
    plant(monkeypatch, "assign_altered")
    result = _drive()
    assert not result["correct"]
    assert "assign_regret" in _failed(result)
