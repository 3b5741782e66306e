"""The time window of a round's data (ISSUE 36): where the algorithm declares
that only W trailing time steps can carry weight, ``train_round`` hands the
round program ``x[:, lo:lo+W]``, ``y[:, lo:lo+W]`` and ``time_w[..., lo:lo+W]``
and nothing else. The same results as from the whole axis, one program for
every time step, an invariant where the algorithm syncs its weights, and the
program it has always been where no window is declared; CPU, float32."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from feddrift_tpu import obs
from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.core.step import StackOperands, TrainStep, make_optimizer
from feddrift_tpu.models.mlp import FeedForwardNN

M, C, T1, N, F = 3, 4, 5, 20, 3
STEPS = (0, 2, T1 - 1)                     # the first, a middle one, the last


def _job(axis, weighted_sampling=False):
    module = FeedForwardNN(num_classes=2, hidden_dim=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (C, T1, N, F))
    y = (x.sum(-1) > 0).astype(jnp.int32)
    one = module.init(jax.random.PRNGKey(1), x[0, 0])["params"]
    params = jax.tree_util.tree_map(
        lambda l: jnp.stack([l * (1 + 0.1 * m) for m in range(M)]), one)
    step = TrainStep(
        apply_fn=lambda p, xb: module.apply({"params": p}, xb),
        # the scanned body keeps an optimizer without state alone
        optimizer=make_optimizer("sgd" if axis == "scan" else "adam",
                                 0.05, 0.001),
        batch_size=5, num_steps=3, num_classes=2, client_axis=axis,
        weighted_sampling=weighted_sampling, cost_capture="off")
    return step, params, x, y


def _hard_weights(t):
    """IFCA's at time step t: one model a client; client 3 sits the round
    out, so every model has pairs without weight."""
    tw = np.zeros((M, C, T1), np.float32)
    for c, m in enumerate((0, 1, 2)):
        tw[m, c, t] = 1.0
    return jnp.asarray(tw)


def _round(step, params, x, y, tw, sample_w=None, **kw):
    opt = step.init_opt_states(params, M, C)
    # the scanned round is given the pool to write over: hand it a copy
    params = jax.tree_util.tree_map(jnp.copy, params)
    sw = jnp.ones((M, C, N)) if sample_w is None else sample_w
    return step.train_round(
        params, opt, jax.random.PRNGKey(5), x, y, tw, sw, jnp.ones((M, F)),
        jnp.float32(1.0), keep_client_params=False, with_agg_stats=True, **kw)


def _assert_same_round(whole, windowed):
    """Every output bit for bit: the pool, the optimizer state, n, the
    aggregation's stats, the scanned round's counts, and the losses of the
    pairs that trained. A pair without weight (n = 0) reports the mean loss
    of batches drawn by a uniform law over the time steps it is handed (its
    parameters, state and n are masked, the loss is not), so with T1 steps
    and with one it names other batches; the divergence guard, its only
    reader, skips n = 0 (resilience/divergence.py::check). The scanned body
    skips such a pair and writes 0; the compact body runs it only where it
    fills a client's slot (client 3's here)."""
    assert len(whole) == len(windowed)
    for i in (0, 1, 3, 5, *range(7, len(whole))):
        a = jax.tree_util.tree_leaves(whole[i])
        b = jax.tree_util.tree_leaves(windowed[i])
        assert len(a) == len(b)
        for u, v in zip(a, b):
            np.testing.assert_array_equal(v, u)
    assert windowed[2] is None and windowed[6] is None
    trained = np.asarray(whole[3]) > 0
    assert trained.sum() == 3
    np.testing.assert_array_equal(np.asarray(windowed[4])[trained],
                                  np.asarray(whole[4])[trained])
    return trained


@pytest.mark.parametrize("t", STEPS)
@pytest.mark.parametrize("body,axis,K", [
    ("dense", "vmap", None), ("compact", "vmap", 1), ("scanned", "scan", None)])
def test_a_window_of_one_step_trains_what_the_whole_axis_trains(
        body, axis, K, t):
    step, params, x, y = _job(axis)
    tw = _hard_weights(t)
    whole = _round(step, params, x, y, tw, models_per_client=K)
    windowed = _round(step, params, x, y, tw, models_per_client=K,
                      time_window=(t, 1))
    trained = _assert_same_round(whole, windowed)
    if body == "scanned":
        # a pair that did not run: loss 0 on both sides, so all of it
        np.testing.assert_array_equal(windowed[4], whole[4])
        assert (np.asarray(windowed[4])[~trained] == 0).all()
    # it trained: the pool moved
    assert not np.array_equal(
        jax.tree_util.tree_leaves(windowed[0])[0],
        jax.tree_util.tree_leaves(params)[0])
    # two programs, the whole axis's and the window's, each compiled once
    assert len(step._signatures["train_round"]) == 2


@pytest.mark.parametrize("t", STEPS)
def test_weighted_sampling_draws_the_same_samples_from_the_window(t):
    """KUE's batches: B inverse-CDF draws over the flat [T1 * N] axis with
    p[t, n] ~ w_t[t] * s_n[n]. The window's CDF is the whole axis's without
    its stretches of probability 0, ahead of the step and behind it. EQUAL
    DRAWS, not only an equal law: the sample weights are Poisson counts and
    the window's time weight is 1, so every partial sum is a whole number
    under 2**24, exact in float32 in whatever order `cumsum` adds, and the
    total that divides them is the same; `searchsorted(side="right")` over
    the same uniforms then names index lo * N + i where the window's names
    i. (Weights that are no whole numbers could part in a sum's last bit
    and flip a draw that falls on an edge: the same law, not the same
    draws. No algorithm hands such weights to a windowed round: KUE
    declares no window.)"""
    step, params, x, y = _job("vmap", weighted_sampling=True)
    tw = _hard_weights(t)
    counts = jnp.asarray(np.random.default_rng(3).poisson(
        1.0, size=(M, C, N)).astype(np.float32))
    whole = _round(step, params, x, y, tw, sample_w=counts)
    windowed = _round(step, params, x, y, tw, sample_w=counts,
                      time_window=(t, 1))
    _assert_same_round(whole, windowed)
    # and the sample weights were read: other batches than the plain round's
    plain = _round(step, params, x, y, tw, time_window=(t, 1))
    assert not np.array_equal(jax.tree_util.tree_leaves(plain[0])[0],
                              jax.tree_util.tree_leaves(windowed[0])[0])


def test_a_window_of_two_steps_holds_inside_the_axis():
    """``lo`` is clipped so that the width is W at every t: the program
    reads what `dynamic_slice` reads, and the weights of both steps."""
    step, params, x, y = _job("vmap")
    tw = np.zeros((M, C, T1), np.float32)
    tw[0, :, T1 - 2:] = 1.0
    out = _round(step, params, x, y, jnp.asarray(tw), time_window=(T1 - 2, 2))
    np.testing.assert_array_equal(np.asarray(out[3])[0], 2.0 * N)
    assert (np.asarray(out[3])[1:] == 0).all()


def _experiment(algo="softclusterwin-1", arg="hard-r", **kw):
    from feddrift_tpu.simulation.runner import Experiment
    return Experiment(ExperimentConfig(**{**dict(
        model="fnn", dataset="sea", lr=0.05, concept_drift_algo=algo,
        concept_drift_algo_arg=arg, concept_num=3, comm_round=3,
        frequency_of_the_test=1, train_iterations=4, sample_num=40,
        batch_size=10, epochs=2, cost_model="off",
        checkpoint_every_iteration=False), **kw}))


@pytest.mark.parametrize("axis", ["vmap", "scan"])
def test_four_time_steps_of_an_ifca_rehearsal_meet_one_round_program(axis):
    kw = {} if axis == "vmap" else {"client_axis": "scan",
                                    "client_optimizer": "sgd"}
    exp = _experiment(**kw)
    assert exp.algo.train_window == 1
    before = obs.registry().snapshot()
    for t in range(4):
        assert exp.algo.time_window(t) == (t, 1)
        exp.run_iteration(t)
    after = obs.registry().snapshot()

    def rose(name):
        return after.get(name, 0) - before.get(name, 0)
    said = [s["args"] for s in exp.spans.spans("dispatch")
            if s["args"].get("fn") == "train_round"]
    assert len(said) == 4 * 3
    assert all(a["time_steps"] == 1 for a in said)
    assert rose("train_round_time_steps") == len(said)        # 1 a round
    assert len(exp.step._signatures["train_round"]) == 1
    assert rose('jit_recompiles{fn="train_round"}') == 0
    assert rose('jit_compiles{fn="train_round"}') == 1


def test_an_experiment_logs_and_decides_the_same_with_and_without_the_window(
        monkeypatch):
    from feddrift_tpu.algorithms.base import DriftAlgorithm
    keys = ("Train/Acc", "Train/Loss", "Test/Acc", "Test/Loss")

    def run():
        exp = _experiment()
        for t in range(4):
            exp.run_iteration(t)
        steps = {s["args"]["time_steps"]
                 for s in exp.spans.spans("dispatch")
                 if s["args"].get("fn") == "train_round"}
        return exp, steps, {k: exp.logger.series(k) for k in keys}
    windowed, steps, got = run()
    assert steps == {1}
    monkeypatch.setattr(DriftAlgorithm, "time_window", lambda self, t: None)
    whole, steps, want = run()
    assert steps == {whole.ds.num_steps + 1}
    np.testing.assert_array_equal(windowed.algo.weights, whole.algo.weights)
    assert got == want                                   # to the last digit
    for a, b in zip(jax.tree_util.tree_leaves(windowed.pool.params),
                    jax.tree_util.tree_leaves(whole.pool.params)):
        np.testing.assert_array_equal(a, b)


def test_weights_that_leave_the_declared_window_raise_at_the_sync():
    exp = _experiment()
    exp.run_iteration(0)
    exp.algo.begin_iteration(1)
    exp.algo.weights[0, 0, 0] = 1.0          # a step the window has dropped
    with pytest.raises(ValueError, match=r"train_window=1 .*time step\(s\) "
                                         r"\[0\]"):
        exp.algo._sync_device_weights(1)
    exp.algo.weights[0] = 0.0
    exp.algo.weights[3, 0, 0] = 1.0          # a step not assigned yet
    with pytest.raises(ValueError, match=r"time step\(s\) \[3\]"):
        exp.algo._sync_device_weights(1)


@pytest.mark.parametrize("algo,arg,window", [
    ("softclusterwin-1", "hard-r", 1), ("softclusterwin-1", "cfl_0.1_win-1", 1),
    # CFL's retrain "all" copies a split back over the earlier steps
    ("softclusterwin-1", "cfl_0.1_all", None),
    ("softcluster", "hard-r", None), ("win-1", "", 1), ("all", "", None),
    ("window", "", 1), ("exp", "", None), ("kue", "", None)])
def test_who_declares_a_window(algo, arg, window):
    """From the algorithm's own definition, no config field: the window of
    one step where every step before t is zeroed and none after t is
    assigned; None (the whole axis) for whoever weights its history."""
    exp = _experiment(algo, arg)
    assert exp.algo.train_window == window
    assert exp.algo.time_window(2) == (None if window is None else (2, 1))
    exp.algo.begin_iteration(0)              # its weights keep the promise


def test_no_window_lowers_the_round_program_it_has_always_been():
    """``time_window=None``: no operand, no static value, and the module
    text of a `_train_round_jit` written out as it stood before the window
    came (the parent's signature and body), character for character; the
    window's program differs from it."""
    step, params, x, y = _job("vmap")
    opt = step.init_opt_states(params, M, C)
    nine = (params, opt, jax.random.PRNGKey(5), x, y, _hard_weights(1),
            jnp.ones((M, C, N)), jnp.ones((M, F)), jnp.float32(1.0))

    @partial(jax.jit, static_argnums=0,
             static_argnames=("keep_client_params", "models_per_client"))
    def _train_round_jit(self, params, opt_states, key, x, y, time_w,
                         sample_w, feat_mask, lr_scale, client_mask=None,
                         operands=StackOperands(), *,
                         keep_client_params: bool = True,
                         models_per_client: int | None = None):
        out = self._round_body(params, opt_states, key, x, y, time_w,
                               sample_w, feat_mask, lr_scale, client_mask,
                               operands, models_per_client)
        return out if keep_client_params else (*out[:2], None, *out[3:])

    for kw in ({"keep_client_params": False},
               {"keep_client_params": False, "models_per_client": 1}):
        before = _train_round_jit.lower(step, *nine, **kw).as_text()
        now = TrainStep._train_round_jit.lower(step, *nine, **kw).as_text()
        assert now == before
        windowed = TrainStep._train_round_jit.lower(
            step, *nine, None, StackOperands(), np.int32(1), time_steps=1,
            **kw).as_text()
        assert windowed != before
        assert (windowed.count("stablehlo.dynamic_slice")
                == before.count("stablehlo.dynamic_slice") + 3)  # x, y, time_w
