"""Faults planted under the timed path of a whole rehearsal run, once for each
that a one-chip training cell can have: ``correct``, by the cell's own
limits, has to come out false for each."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_driving import drive as _drive, failed as _failed  # noqa: E402


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import functools
    import jax
    from feddrift_tpu.core.step import TrainStep
    real = TrainStep.train_round

    @functools.wraps(real)
    def unchanged(self, params, opt_states, *a, **kw):
        keep = jax.tree_util.tree_map(lambda l: l.copy(), (params, opt_states))
        out = real(self, params, opt_states, *a, **kw)
        return keep + tuple(out[2:])

    monkeypatch.setattr(TrainStep, "train_round", unchanged)
    result = _drive()
    assert not result["correct"]
    assert {"change_gap_median", "first_grad_gap_median"} <= set(_failed(result))
    assert result["check"]["change_gap_median"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    """The loss and its gradient taken over the first half of every batch,
    the mean over the rest."""
    from feddrift_tpu.core import step
    real = step.cross_entropy
    monkeypatch.setattr(
        step, "cross_entropy",
        lambda logits, labels: real(logits[: logits.shape[0] // 2],
                                    labels[: labels.shape[0] // 2]))
    result = _drive()
    assert not result["correct"]
    assert {"first_grad_gap_median", "train_loss_gap"} <= set(_failed(result))


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The accuracy matrix that the host's re-assignment reads comes back
    negated, so every client goes to its worst model."""
    from feddrift_tpu.core.step import TrainStep
    real = TrainStep.acc_matrix

    def negated(self, *a, **kw):
        correct, loss, total = real(self, *a, **kw)
        return -correct, loss, total

    monkeypatch.setattr(TrainStep, "acc_matrix", negated)
    result = _drive()
    assert not result["correct"]
    assert "assign_regret" in _failed(result)
