"""The client-scanned round body (``client_axis="scan"``) against the vmap
body on ``fnn`` and on the tiny decoder, per-token labels through
``acc_matrix`` and ``_local_sgd``, and what refuses the scanned body; CPU,
float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.core.step import StackOperands, TrainStep, make_optimizer
from feddrift_tpu.models.mlp import FeedForwardNN

M, C, T1, N, F = 3, 4, 3, 20, 3


def _fnn_job(optimizer="sgd"):
    module = FeedForwardNN(num_classes=2, hidden_dim=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (C, T1, N, F))
    y = (x.sum(-1) > 0).astype(jnp.int32)
    one = module.init(jax.random.PRNGKey(1), x[0, 0])["params"]
    params = jax.tree_util.tree_map(
        lambda l: jnp.stack([l * (1 + 0.1 * m) for m in range(M)]), one)

    def step(axis):
        return TrainStep(
            apply_fn=lambda p, xb: module.apply({"params": p}, xb),
            optimizer=make_optimizer(optimizer, 0.05, 0.001), batch_size=5,
            num_steps=3, num_classes=2, client_axis=axis, cost_capture="off")
    return step, params, x, y, jnp.ones((M, F))


def _decoder_job(optimizer="sgd"):
    from feddrift_tpu.models.mla_moe import MLAMoEDecoder
    module = MLAMoEDecoder(preset="mla_moe_tiny", remat=True)
    x = jax.random.randint(jax.random.PRNGKey(0), (C, T1, 4, 9), 0, 64)
    x, y = x[..., :-1], x[..., 1:]
    one = module.init(jax.random.PRNGKey(1), x[0, 0])["params"]
    params = jax.tree_util.tree_map(
        lambda l: jnp.stack([l * (1 + 0.1 * m) for m in range(M)]), one)

    def step(axis):
        return TrainStep(
            apply_fn=lambda p, xb: module.apply({"params": p}, xb),
            stats_fn=lambda p, xb: module.apply({"params": p}, xb,
                                                return_stats=True),
            optimizer=make_optimizer(optimizer, 0.05, 0.0), batch_size=2,
            num_steps=2, num_classes=64, client_axis=axis,
            cost_capture="off")
    return step, params, x, y, jnp.ones((M, 1))


def _weights(kind):
    tw = np.zeros((M, C, T1), np.float32)
    if kind == "full":          # every client trains one model, all in use
        for c, m in enumerate((0, 1, 2, 0)):
            tw[m, c, 0] = 1.0
    else:                       # model 2: nobody; client 3: nowhere; client
        tw[0, 0, 0] = tw[0, 1, 0] = tw[0, 1, 1] = tw[1, 2, 1] = 1.0   # 1: two
    return jnp.asarray(tw)


def _round(step, params, x, y, tw, fm, **kw):
    opt = step.init_opt_states(params, M, C)
    # the scanned round is given the pool to write over: hand it a copy
    params = jax.tree_util.tree_map(jnp.copy, params)
    return step.train_round(
        params, opt, jax.random.PRNGKey(5), x, y, tw,
        jnp.ones((M, C, x.shape[2])), fm, jnp.float32(1.0),
        keep_client_params=False, with_agg_stats=True, **kw)


@pytest.mark.parametrize("job", [_fnn_job, _decoder_job])
@pytest.mark.parametrize("weights,mask,pairs", [
    ("full", None, 4), ("full", [1.0, 0.0, 1.0, 1.0], 3),
    ("empty_cluster", None, 3)])
def test_scanned_body_agrees_with_the_vmap_body(job, weights, mask, pairs):
    """Same keys, same batches, the same weighted mean term by term: the
    new pool, n and the losses to float32 rounding of the sum's order,
    under full and partial participation and with an empty cluster."""
    make, params, x, y, fm = job()
    kw = {} if mask is None else {"client_mask": jnp.asarray(mask)}
    a, b = (_round(make(axis), params, x, y, _weights(weights), fm, **kw)
            for axis in ("vmap", "scan"))
    assert len(a) == 7 and len(b) == 8
    for u, v in zip(jax.tree_util.tree_leaves(a[0]),
                    jax.tree_util.tree_leaves(b[0])):
        np.testing.assert_allclose(v, u, rtol=2e-5, atol=1e-6)
    assert b[2] is None and b[6] is None
    np.testing.assert_array_equal(b[3], a[3])                    # n
    trained = np.asarray(a[3]) > 0
    np.testing.assert_allclose(np.asarray(b[4])[trained],
                               np.asarray(a[4])[trained], rtol=1e-5)
    assert (np.asarray(b[4])[~trained] == 0).all()
    np.testing.assert_array_equal(b[5], a[5])                    # agg stats
    assert int(b[7]["pairs_trained"]) == int(trained.sum()) == pairs
    if job is _decoder_job:
        # 2 expert layers x 2 local steps x 2 sequences x 8 tokens a pair
        assert int(b[7]["expert_tokens"]) == pairs * 2 * 2 * 2 * 8
        assert b[7]["expert_load"].shape == (4,)


def test_an_idle_model_and_a_model_with_a_loss_not_finite_keep_their_parameters():
    make, params, x, y, fm = _fnn_job()
    out = _round(make("scan"), params, x, y, _weights("empty_cluster"), fm)
    n, losses = np.asarray(out[3]), np.asarray(out[4])
    assert n[0, 3] == 0 and losses[0, 3] == 0 and (n[2] == 0).all()
    for new, old in zip(jax.tree_util.tree_leaves(out[0]),
                        jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(new[2], old[2])    # model 2: nobody
        assert not np.array_equal(new[0], old[0])
    # the pool is donated, so the round keeps what the guard would restore:
    # client 2's data is not finite, model 1 (its only trainer) stays
    bad = x.at[2].set(jnp.nan)
    out = _round(make("scan"), params, bad, y, _weights("empty_cluster"), fm)
    assert not np.isfinite(np.asarray(out[4])[1, 2])
    for new, old in zip(jax.tree_util.tree_leaves(out[0]),
                        jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(new[1], old[1])
        assert np.isfinite(np.asarray(new)).all()
        assert not np.array_equal(new[0], old[0])


def test_the_scanned_round_is_given_the_pool_to_write_over():
    make, params, x, y, fm = _fnn_job()
    step = make("scan")
    mine = jax.tree_util.tree_map(jnp.copy, params)
    step.train_round(mine, step.init_opt_states(params, M, C),
                     jax.random.PRNGKey(5), x, y, _weights("full"),
                     jnp.ones((M, C, N)), fm, jnp.float32(1.0),
                     keep_client_params=False)
    assert all(l.is_deleted() for l in jax.tree_util.tree_leaves(mine))


@pytest.mark.parametrize("axis", ["vmap", "scan"])
def test_acc_matrix_counts_tokens_where_a_label_is_per_token(axis):
    """y [C, N, L]: hits and summed loss over every token against a numpy
    loop over models, clients, sequences and positions; ``total`` is the
    tokens of a client."""
    Lq, V = 6, 5
    table = jax.random.normal(jax.random.PRNGKey(0), (M, V, V))
    x = jax.random.randint(jax.random.PRNGKey(1), (C, N, Lq), 0, V)
    y = jax.random.randint(jax.random.PRNGKey(2), (C, N, Lq), 0, V)
    step = TrainStep(apply_fn=lambda p, xb: p["table"][xb],
                     optimizer=make_optimizer("sgd", 0.1, 0), batch_size=5,
                     num_steps=1, num_classes=V, client_axis=axis,
                     cost_capture="off")
    correct, loss, total = step.acc_matrix({"table": table}, x, y,
                                           jnp.ones((M, 1)))
    want_c, want_l = np.zeros((M, C), np.int64), np.zeros((M, C))
    t, xs, ys = np.asarray(table, np.float64), np.asarray(x), np.asarray(y)
    for m in range(M):
        for c in range(C):
            for s in range(N):
                for i in range(Lq):
                    row = t[m, xs[c, s, i]]
                    want_c[m, c] += int(row.argmax() == ys[c, s, i])
                    want_l[m, c] += np.log(np.exp(row).sum()) - row[ys[c, s, i]]
    np.testing.assert_array_equal(correct, want_c)
    np.testing.assert_allclose(loss, want_l, rtol=1e-5)
    assert list(np.asarray(total)) == [N * Lq] * C


@pytest.mark.parametrize("axis", ["vmap", "scan"])
def test_local_sgd_takes_the_mean_loss_over_every_token(axis):
    """One local step of sgd on per-token labels moves the table by the
    gradient of the mean next-token loss over batch and positions."""
    Lq, V, lr = 4, 5, 0.5
    table = jax.random.normal(jax.random.PRNGKey(0), (1, V, V))
    x = jax.random.randint(jax.random.PRNGKey(1), (1, 1, 2, Lq), 0, V)
    y = jax.random.randint(jax.random.PRNGKey(2), (1, 1, 2, Lq), 0, V)
    step = TrainStep(apply_fn=lambda p, xb: p["table"][xb],
                     optimizer=make_optimizer("sgd", lr, 0), batch_size=2,
                     num_steps=1, num_classes=V, client_axis=axis,
                     cost_capture="off")
    out = step.train_round(
        {"table": jnp.copy(table)},
        step.init_opt_states({"table": table}, 1, 1), jax.random.PRNGKey(0),
        x, y, jnp.ones((1, 1, 1)), jnp.ones((1, 1, 2)), jnp.ones((1, 1)),
        jnp.float32(1.0), keep_client_params=False)

    def loss(t):
        logp = jax.nn.log_softmax(t[x[0, 0]])
        return -jnp.take_along_axis(logp, y[0, 0][..., None], -1).mean()
    np.testing.assert_allclose(out[0]["table"][0],
                               table[0] - lr * jax.grad(loss)(table[0]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("field,value,named", [
    ("client_optimizer", "adam", "client_optimizer='adam'"),
    ("robust_agg", "median", "robust_agg='median'"),
    ("robust_agg", "krum", "robust_agg='krum'"),
    ("robust_agg", "trimmed_mean", "robust_agg='trimmed_mean'"),
    ("byzantine_clients", "0,1", "byzantine_clients"),
    ("compress_codec", "int8", "compress_codec='int8'"),
    ("hierarchy_edges", 2, "hierarchy_edges"),
    ("concept_drift_algo_arg", "cfl_0.1_win-1", "CFL"),
    ("megastep_k", 2, "megastep_k"),
])
def test_what_needs_the_stack_refuses_the_scanned_body_by_name(field, value,
                                                              named):
    base = {"client_optimizer": "sgd"}
    with pytest.raises(ValueError, match="client_axis='scan'") as e:
        ExperimentConfig(client_axis="scan", **{**base, field: value})
    assert named in str(e.value) and "client_axis='vmap'" in str(e.value)
    ExperimentConfig(client_axis="vmap", **{**base, field: value})
    with pytest.raises(ValueError, match="unknown client_axis"):
        ExperimentConfig(client_axis="loop")


@pytest.mark.parametrize("how,named", [
    ("adam", "optimizer with state"), ("keep", "keep_client_params=True"),
    ("median", "robust_agg='median'"), ("codec", "codec='int8'"),
    ("byz", "byz_modes")])
def test_the_round_program_refuses_what_reads_a_stack_under_scan(how, named):
    make, params, x, y, fm = _fnn_job("adam" if how == "adam" else "sgd")
    step = make("scan")
    if how == "median":
        step.robust_agg = "median"
    if how == "codec":
        step.codec = "int8"
    kw = {"operands": StackOperands(byz_modes=jnp.zeros((C,), jnp.int32))
          } if how == "byz" else {}
    with pytest.raises(ValueError, match="client_axis='scan'") as e:
        step.train_round(params, step.init_opt_states(params, M, C),
                         jax.random.PRNGKey(5), x, y, _weights("full"),
                         jnp.ones((M, C, N)), fm, jnp.float32(1.0),
                         keep_client_params=(how == "keep"), **kw)
    assert named in str(e.value)


def test_an_experiment_on_sea_logs_the_same_series_under_either_body():
    """``fnn`` on ``sea`` under IFCA's per-round path, ``sgd``: the runner
    hands the scanned round the pool to write over, passes no old pool to
    ``after_round`` and feeds the round's count at the guard's fetch; what
    it logs is what the vmap body's run logs."""
    from feddrift_tpu.simulation.runner import Experiment
    series = {}
    for axis in ("vmap", "scan"):
        exp = Experiment(ExperimentConfig(
            model="fnn", dataset="sea", client_optimizer="sgd", lr=0.05,
            client_axis=axis, concept_drift_algo="softclusterwin-1",
            concept_drift_algo_arg="hard-r", concept_num=3, comm_round=3,
            frequency_of_the_test=1, train_iterations=3, sample_num=40,
            batch_size=10, epochs=2, cost_model="off",
            checkpoint_every_iteration=False))
        for t in range(3):
            exp.run_iteration(t)
        series[axis] = {k: exp.logger.series(k)
                        for k in ("Train/Loss", "Test/Acc", "Test/Loss")}
        guards = [s["args"] for s in exp.spans.spans("guard")
                  if "pairs_trained" in s.get("args", {})]
        assert len(guards) == (9 if axis == "scan" else 0)
        assert all(g["pairs_trained"] == 10 for g in guards)
    for k, want in series["vmap"].items():
        got = series["scan"][k]
        assert [r for r, _ in got] == [r for r, _ in want]
        np.testing.assert_allclose([v for _, v in got], [v for _, v in want],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_the_span_recorder_does_not_keep_a_finished_experiment_alive():
    """The process-wide span recorder outlives an experiment and calls its
    completion hook; it holds the hook weakly, so an experiment that its
    owner lets go of is collected with its pool (the benchmark frees the
    program's device state before its float32 reference runs: a pool of
    gigabytes that stayed would not leave it room)."""
    import gc
    import weakref
    from feddrift_tpu.obs import spans
    from feddrift_tpu.simulation.runner import Experiment
    exp = Experiment(ExperimentConfig(
        model="fnn", dataset="sea", train_iterations=2, comm_round=1,
        sample_num=20, batch_size=10, epochs=1, cost_model="off",
        checkpoint_every_iteration=False))
    exp.run_iteration(0)
    assert exp.last_round_breakdown["segments"]["dispatch"] > 0   # hooked
    alive = weakref.ref(exp)
    del exp
    gc.collect()
    assert alive() is None
    with spans.span("dispatch", cat="round"):     # a dead hook is no error
        pass
    seen = []
    rec = spans.get_recorder()
    rec.set_hook(seen.append)         # a builtin's bound method: held as is
    assert rec._local.hook == seen.append
    rec.set_hook(None)
