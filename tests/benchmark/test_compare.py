"""The comparison's arithmetic, with no program and no reference run."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402
from benchmark.drivers import train  # noqa: E402


def test_norm_gap_is_taken_by_the_worst_leaf_against_the_median():
    ref = {"a": 1.0, "b": 1.0, "tiny": 1e-6}
    prog = {"a": 1.1, "b": 1.0, "tiny": 3e-6}
    gap, at = reference.worst_norm_gap(prog, ref)
    assert at == "a" and gap == pytest.approx(0.1)
    # a leaf that did not move on one side reads 1
    gap, at = reference.worst_norm_gap({"a": 0.0, "b": 1.0, "tiny": 1e-6}, ref)
    assert at == "a" and gap == pytest.approx(1.0)
    gap, _ = reference.worst_norm_gap(prog, ref, skip={"a"})
    assert gap < 0.1


def test_leaves_with_no_gradient_are_left_out_by_rule_not_by_name():
    norms = {"w1": 1.0, "w2": 0.5, "w3": 2.0, "dead": 1e-5}
    assert reference.flat_gradient_leaves(norms) == {"dead"}


def test_compare_holds_each_number_to_its_own_limit():
    out = reference.compare({"x": 0.2, "y": float("nan"), "z": 1.0},
                            {"x": 0.3, "y": 0.3, "w": 0.1})
    assert out["x"]["ok"] and not out["y"]["ok"] and "z" not in out
    assert not out["w"]["ok"]        # a limit whose number never came


def test_batch_indices_follow_the_jobs_draw():
    """A time step by weight, then one contiguous batch within it."""
    import jax
    w = np.array([0.0, 1.0, 0.0], np.float32)
    idx = np.asarray(reference.batch_indices(
        jax.random.PRNGKey(3), w, n_per_step=32, batch=8, num_steps=4))
    assert idx.shape == (4, 8)
    assert ((idx >= 32) & (idx < 64)).all()          # only time step 1
    assert (np.diff(idx, axis=1) == 1).all() and (idx[:, 0] % 8 == 0).all()


def test_time_weights_are_masked_by_the_rounds_participants():
    """As the program does it: ``time_w * client_mask[None, :, None]``."""
    tw = np.ones((2, 3, 4), np.float32)
    assert len(train.masked_time_weights(tw, None)) == 1
    (one,) = train.masked_time_weights(tw, np.array([1.0, 0.0, 1.0]))
    assert one[:, 1].sum() == 0 and one[:, 0].sum() == 8
    fused = train.masked_time_weights(tw, np.array([[1.0, 0, 0], [0, 1, 1]]))
    assert [float(w.sum()) for w in fused] == [8.0, 16.0]
