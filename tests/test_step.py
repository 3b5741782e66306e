"""TrainStep unit tests: masking, aggregation math, batched eval."""

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.core.pool import ModelPool
from feddrift_tpu.core.step import StackOperands, TrainStep, make_optimizer
from feddrift_tpu.data.registry import make_dataset
from feddrift_tpu.models import create_model


def _setup(M=3, C=4, T=3, N=40, B=20):
    cfg = ExperimentConfig(dataset="sine", train_iterations=T, sample_num=N,
                           batch_size=B, epochs=4, client_num_in_total=C,
                           client_num_per_round=C, lr=0.05)
    ds = make_dataset(cfg)
    mod = create_model("fnn", ds, cfg)
    pool = ModelPool.create(mod, jnp.zeros((2, 2)), M, seed=1)
    step = TrainStep(pool.apply, make_optimizer("adam", cfg.lr, cfg.wd),
                     B, cfg.epochs, ds.num_classes)
    x, y = jnp.asarray(ds.x), jnp.asarray(ds.y)
    opt = step.init_opt_states(pool.params, M, C)
    sw = jnp.ones((M, C, N), jnp.float32)
    fm = jnp.ones((M, 2), jnp.float32)
    return cfg, ds, pool, step, x, y, opt, sw, fm


def _leafdiff(a, b):
    return sum(float(jnp.abs(la - lb).sum())
               for la, lb in zip(jax.tree_util.tree_leaves(a),
                                 jax.tree_util.tree_leaves(b)))


@pytest.mark.slow
class TestTrainRound:
    def test_unused_models_untouched(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        tw = np.zeros((3, 4, 4), np.float32)
        tw[0, :, 0] = 1.0          # only model 0 trains
        newp, _, _, n, _ = step.train_round(
            pool.params, opt, jax.random.PRNGKey(0), x, y,
            jnp.asarray(tw), sw, fm, jnp.float32(1.0))
        n = np.asarray(n)
        assert (n[0] == 40).all() and (n[1:] == 0).all()
        assert _leafdiff(jax.tree_util.tree_map(lambda p: p[0], newp),
                         jax.tree_util.tree_map(lambda p: p[0], pool.params)) > 0
        for m in (1, 2):
            assert _leafdiff(jax.tree_util.tree_map(lambda p: p[m], newp),
                             jax.tree_util.tree_map(lambda p: p[m], pool.params)) == 0

    def test_per_client_zero_weight_masked(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        tw = np.zeros((3, 4, 4), np.float32)
        tw[0, :2, 0] = 1.0         # model 0: only clients 0, 1 participate
        newp, _, client_params, n, _ = step.train_round(
            pool.params, opt, jax.random.PRNGKey(0), x, y,
            jnp.asarray(tw), sw, fm, jnp.float32(1.0))
        n = np.asarray(n)
        assert (n[0, :2] == 40).all() and (n[0, 2:] == 0).all()
        # non-participating clients' local params remain the broadcast globals
        cp0 = jax.tree_util.tree_leaves(client_params)[0]
        p0 = jax.tree_util.tree_leaves(pool.params)[0]
        assert np.allclose(cp0[0, 2], p0[0]) and np.allclose(cp0[0, 3], p0[0])

    def test_aggregation_is_weighted_mean(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        tw = np.zeros((3, 4, 4), np.float32)
        tw[0, 0, :2] = 1.0         # client 0 trains on steps 0+1 (n=80)
        tw[0, 1, 0] = 1.0          # client 1 trains on step 0    (n=40)
        newp, _, client_params, n, _ = step.train_round(
            pool.params, opt, jax.random.PRNGKey(1), x, y,
            jnp.asarray(tw), sw, fm, jnp.float32(1.0))
        n = np.asarray(n)
        assert n[0, 0] == 80 and n[0, 1] == 40
        for la, lc in zip(jax.tree_util.tree_leaves(newp),
                          jax.tree_util.tree_leaves(client_params)):
            manual = (lc[0, 0] * 80 + lc[0, 1] * 40) / 120
            assert np.allclose(la[0], manual, atol=1e-5)

    def test_determinism(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        tw = jnp.ones((3, 4, 4), jnp.float32)
        a = step.train_round(pool.params, opt, jax.random.PRNGKey(3), x, y,
                             tw, sw, fm, jnp.float32(1.0))[0]
        b = step.train_round(pool.params, opt, jax.random.PRNGKey(3), x, y,
                             tw, sw, fm, jnp.float32(1.0))[0]
        assert _leafdiff(a, b) == 0

    def test_lr_scale_zero_freezes(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        tw = jnp.ones((3, 4, 4), jnp.float32)
        newp, _, _, _, _ = step.train_round(
            pool.params, opt, jax.random.PRNGKey(0), x, y, tw, sw, fm,
            jnp.float32(0.0))
        assert _leafdiff(newp, pool.params) == 0

    def test_feature_mask_blocks_features(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        # masking all features: inputs become 0; training still runs
        fm0 = jnp.zeros((3, 2), jnp.float32)
        tw = jnp.ones((3, 4, 4), jnp.float32)
        newp, *_ = step.train_round(pool.params, opt, jax.random.PRNGKey(0),
                                    x, y, tw, sw, fm0, jnp.float32(1.0))
        assert np.isfinite(jax.tree_util.tree_leaves(newp)[0]).all()


class TestEval:
    def test_acc_matrix_matches_manual(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        correct, loss_sum, total = step.acc_matrix(pool.params, x[:, 0], y[:, 0], fm)
        m0 = pool.slot(0)
        logits = pool.apply(m0, x[0, 0])
        manual = int((jnp.argmax(logits, -1) == y[0, 0]).sum())
        assert int(correct[0, 0]) == manual
        assert int(total[0]) == 40

    def test_ensemble_hard_single_model_equals_plain(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        w = jnp.asarray([1.0, 0.0, 0.0])
        ec, et, el = step.ensemble_eval(pool.params, x[:, 0], y[:, 0], w, "hard")
        correct, _, _ = step.acc_matrix(pool.params, x[:, 0], y[:, 0], fm)
        assert np.array_equal(np.asarray(ec), np.asarray(correct[0]))
        assert np.isfinite(np.asarray(el)).all()

    def test_confusion_matrix_sums(self):
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup()
        cm = step.confusion_matrices(pool.params, x[:, 0], y[:, 0], fm)
        assert cm.shape == (3, 4, 2, 2)
        assert np.allclose(np.asarray(cm).sum(axis=(-1, -2)), 40)


class TestWeightedSamplingDistribution:
    def test_inverse_cdf_draw_matches_weights(self):
        """The KUE batch draw (inverse-CDF over w_t x s_n) must sample each
        (t, n) cell proportionally to its weight — the semantics of the
        reference's Poisson-bootstrap batch choice (retrain.py:65-74 +
        FedAvgEnsTrainerKue), independent of the sampler implementation."""
        import jax
        import jax.numpy as jnp
        from feddrift_tpu.core.step import inverse_cdf_draw, weight_cdf

        T1, N, B = 3, 8, 4096
        w_t = jnp.asarray([0.0, 1.0, 3.0])
        s_n = jnp.asarray([1.0, 0.0, 2.0, 1.0, 1.0, 0.0, 1.0, 2.0])
        probs = (w_t[:, None] * s_n[None, :]).reshape(-1)
        idx = inverse_cdf_draw(jax.random.PRNGKey(0), weight_cdf(probs), B)
        counts = np.bincount(np.asarray(idx), minlength=T1 * N)
        expected = np.asarray(probs / probs.sum()) * B
        # zero-weight cells must never be drawn; others within 5 sigma
        assert (counts[np.asarray(probs) == 0] == 0).all()
        nonzero = np.asarray(probs) > 0
        sigma = np.sqrt(expected[nonzero].clip(1))
        assert (np.abs(counts[nonzero] - expected[nonzero]) < 5 * sigma + 5).all()


# ----------------------------------------------------------------------
# the compact vmap body (ISSUE 30): K models a client against all M
def _tree_close(a, b, **tol):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x_, y_ in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x_), np.asarray(y_), **tol)


def _one_hot_tw(rows, M=3, T1=4, t=0):
    """[M, C, T1] weights: client c trains the models ``rows[c]`` on step t."""
    tw = np.zeros((M, len(rows), T1), np.float32)
    for c, models in enumerate(rows):
        for m in models:
            tw[m, c, t] = 1.0
    return tw


def _train_round_spans():
    from feddrift_tpu import obs
    return [s for s in obs.spans.get_recorder().spans("dispatch")
            if s["args"].get("fn") == "train_round"]


def _param_stack(pool, M=3, C=4):
    """The pool's parameters as an [M, C, ...] stack of client copies."""
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[:, None], (M, C, *l.shape[1:])),
        pool.params)


def _forbid_the_compact_body(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("the compact body was traced")
    monkeypatch.setattr(TrainStep, "_round_compact", boom)


@pytest.fixture()
def spans_in_memory():
    """The compact body's tests read ``dispatch`` spans: the process's
    recorder is on for them, in memory, and off again afterwards as the
    library leaves it."""
    from feddrift_tpu.obs import spans
    spans.configure(None)
    yield
    spans.configure(None)
    spans.get_recorder().enabled = False


@pytest.fixture(scope="module")
def warmed():
    """One TrainStep and one dense round behind it, so that every case
    starts from optimizer states that differ by pair and shares the
    compiled programs."""
    cfg, ds, pool, step, x, y, opt, sw, fm = _setup(M=3, C=4)
    tw = _one_hot_tw([(0, 1), (1,), (2,), (0, 2)])
    params, opt, *_ = step.train_round(
        pool.params, opt, jax.random.PRNGKey(5), x, y, jnp.asarray(tw), sw,
        fm, jnp.float32(1.0), keep_client_params=False)
    return step, params, opt, x, y, sw, fm


# the assignment, the round's participants, the K the host would count
COMPACT_CASES = {
    "ifca_rows": ([(0,), (2,), (2,), (0,)], None, 1),
    "one_and_two_live_models": ([(0, 2), (1,), (2,), (0, 1)], None, 2),
    "client_mask_drops_a_client": ([(0,), (2,), (1,), (0,)], [1, 0, 1, 1], 1),
    "phantom_padded_clients": ([(1,), (0,), (), ()], None, 1),
    "a_model_nobody_trains": ([(0,), (0,), (2,), (2,)], None, 1),
    "k_above_every_client's_count": ([(0,), (2,), (2,), (0,)], None, 2),
}


@pytest.mark.usefixtures("spans_in_memory")
class TestCompactRound:
    @pytest.mark.parametrize("case", COMPACT_CASES)
    def test_compact_equals_dense(self, warmed, case):
        step, params, opt, x, y, sw, fm = warmed
        rows, mask, K = COMPACT_CASES[case]
        tw = jnp.asarray(_one_hot_tw(rows))
        cm = None if mask is None else jnp.asarray(mask, jnp.float32)
        call = lambda **kw: step.train_round(          # noqa: E731
            params, opt, jax.random.PRNGKey(11), x, y, tw, sw, fm,
            jnp.float32(1.0), cm, keep_client_params=False,
            with_agg_stats=True, **kw)
        dense, compact = call(), call(models_per_client=K)
        assert _train_round_spans()[-1]["args"]["pairs_run"] == K * 4
        tol = dict(rtol=1e-5, atol=1e-7)
        _tree_close(compact[0], dense[0], **tol)         # new_params
        _tree_close(compact[1], dense[1], **tol)         # the [M, C] stack
        assert compact[2] is None and compact[6] is None
        n = np.asarray(dense[3])
        np.testing.assert_array_equal(np.asarray(compact[3]), n)
        trained = n > 0
        assert trained.sum() == sum(
            len(r) * (mask is None or mask[c]) for c, r in enumerate(rows))
        np.testing.assert_allclose(np.asarray(compact[4])[trained],
                                   np.asarray(dense[4])[trained], **tol)
        np.testing.assert_array_equal(np.asarray(compact[5]),
                                      np.asarray(dense[5]))
        # a model no pair trained keeps its parameters, bit for bit, and a
        # pair that did not train its optimizer state
        for m in np.where(~trained.any(axis=1))[0]:
            assert _leafdiff(jax.tree_util.tree_map(lambda l: l[m], compact[0]),
                             jax.tree_util.tree_map(lambda l: l[m], params)) == 0
        for got, was in zip(jax.tree_util.tree_leaves(compact[1]),
                            jax.tree_util.tree_leaves(opt)):
            np.testing.assert_array_equal(np.asarray(got)[~trained],
                                          np.asarray(was)[~trained])

    def test_same_k_other_assignment_is_one_signature(self, warmed):
        from feddrift_tpu import obs
        step, params, opt, x, y, sw, fm = warmed
        reg = obs.registry()
        key = 'jit_recompiles{fn="train_round"}'
        outs = []
        for i, rows in enumerate(([(0,), (1,), (2,), (0,)],
                                  [(2,), (2,), (0,), (1,)])):
            outs.append(step.train_round(
                params, opt, jax.random.PRNGKey(3), x, y,
                jnp.asarray(_one_hot_tw(rows)), sw, fm, jnp.float32(1.0),
                keep_client_params=False, models_per_client=1))
            if i == 0:
                sigs = len(step._signatures["train_round"])
                cached = TrainStep._train_round_jit._cache_size()
                recompiles = reg.snapshot().get(key, 0)
        assert len(step._signatures["train_round"]) == sigs
        assert TrainStep._train_round_jit._cache_size() == cached
        assert reg.snapshot().get(key, 0) == recompiles
        assert _leafdiff(outs[0][0], outs[1][0]) > 0

    def test_pairs_run_counts_k_times_c(self, warmed):
        from feddrift_tpu import obs
        step, params, opt, x, y, sw, fm = warmed
        tw = jnp.asarray(_one_hot_tw([(0, 2), (1,), (2,), (0, 1)]))
        reg = obs.registry()
        for K, pairs in ((1, 4), (2, 8), (3, 12), (None, 12)):
            before = reg.snapshot().get("pairs_run", 0)
            step.train_round(params, opt, jax.random.PRNGKey(3), x, y, tw, sw,
                             fm, jnp.float32(1.0), keep_client_params=False,
                             models_per_client=K)
            assert reg.snapshot().get("pairs_run", 0) - before == pairs
            assert _train_round_spans()[-1]["args"]["pairs_run"] == pairs
        with pytest.raises(ValueError, match="models_per_client"):
            step.train_round(params, opt, jax.random.PRNGKey(3), x, y, tw, sw,
                             fm, jnp.float32(1.0), keep_client_params=False,
                             models_per_client=0)

    # what of a call keeps the dense body: (TrainStep fields, call's
    # operands by name, keep_client_params, K)
    DENSE_CASES = {
        "k_equals_m": ({}, {}, False, 3),
        "no_k": ({}, {}, False, None),
        "keep_client_params": ({}, {}, True, 1),
        "robust_agg": ({"robust_agg": "median"}, {}, False, 1),
        "dp_noise": ({"dp": 0.01}, {}, False, 1),
        "codec": ({"codec": "int8"}, {}, False, 1),
        "hier_edges": ({"hier_edges": 2}, {}, False, 1),
        "byz_modes": ({}, {"byz_modes": "zeros"}, False, 1),
        "stale_params": ({}, {"stale_params": "stack"}, False, 1),
        "edge_operands": ({}, {"edge_ids": "zeros"}, False, 1),
        "codec_prev": ({}, {"codec_prev": "stack"}, False, 1),
        "weighted_sampling": ({"weighted_sampling": True}, {}, False, 1),
    }

    @pytest.mark.parametrize("case", DENSE_CASES)
    def test_falls_back_to_the_dense_program(self, case, monkeypatch):
        import dataclasses
        from feddrift_tpu.resilience.robust_agg import RobustAggConfig
        fields, operands, keep, K = self.DENSE_CASES[case]
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup(M=3, C=4)
        fields = dict(fields)
        if "dp" in fields:
            fields["robust_cfg"] = RobustAggConfig(dp_stddev=fields.pop("dp"))
        step = dataclasses.replace(step, _signatures={}, **fields)
        _forbid_the_compact_body(monkeypatch)
        stack = _param_stack(pool)
        given = {name: stack if kind == "stack"
                 else jnp.zeros((4,), jnp.int32)
                 for name, kind in operands.items()}
        tw = jnp.asarray(_one_hot_tw([(0,), (2,), (2,), (0,)]))
        call = lambda **kw: step.train_round(          # noqa: E731
            pool.params, opt, jax.random.PRNGKey(2), x, y, tw, sw, fm,
            jnp.float32(1.0), operands=StackOperands(**given),
            keep_client_params=keep, **kw)
        today = call()
        sigs = set(step._signatures["train_round"])
        cached = TrainStep._train_round_jit._cache_size()
        asked = call(models_per_client=K)
        # the same program: no new signature, no new entry in jit's cache
        assert step._signatures["train_round"] == sigs and len(sigs) == 1
        assert TrainStep._train_round_jit._cache_size() == cached
        assert _train_round_spans()[-1]["args"]["pairs_run"] == 3 * 4
        assert _leafdiff(today[0], asked[0]) == 0

    def test_compact_equals_dense_on_the_host_mesh(self):
        """x, y and the optimizer stack split over a ``clients`` axis of 4
        (the 8-device host platform of conftest.py), the pool replicated:
        the gathers run along the replicated model axis."""
        from feddrift_tpu.parallel.mesh import (make_mesh, replicate,
                                                shard_client_arrays)
        mesh = make_mesh(num_devices=4)
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup(M=3, C=8)
        step.mesh = mesh
        x, y = shard_client_arrays(mesh, (x, y))
        opt = shard_client_arrays(mesh, opt, client_axis=1)
        params, sw, fm = replicate(mesh, (pool.params, sw, fm))
        tw = replicate(mesh, jnp.asarray(_one_hot_tw(
            [(0, 2), (1,), (2,), (0,), (1,), (1, 2), (), (0,)])))
        call = lambda **kw: step.train_round(          # noqa: E731
            params, opt, jax.random.PRNGKey(4), x, y, tw, sw, fm,
            jnp.float32(1.0), keep_client_params=False, with_agg_stats=True,
            **kw)
        dense, compact = call(), call(models_per_client=2)
        assert _train_round_spans()[-1]["args"]["pairs_run"] == 16
        tol = dict(rtol=1e-5, atol=1e-7)
        _tree_close(compact[0], dense[0], **tol)
        _tree_close(compact[1], dense[1], **tol)
        np.testing.assert_array_equal(np.asarray(compact[3]),
                                      np.asarray(dense[3]))
        np.testing.assert_array_equal(np.asarray(compact[5]),
                                      np.asarray(dense[5]))
        # the stack comes back split over the clients as it went in
        for got, was in zip(jax.tree_util.tree_leaves(compact[1]),
                            jax.tree_util.tree_leaves(dense[1])):
            assert got.sharding == was.sharding


# ----------------------------------------------------------------------
# the seam (ISSUE 31): what a round is given (`StackOperands`), and what
# TrainStep makes of it (`_round_program`, `donates_pool`, `fuses_rounds`)
@pytest.mark.usefixtures("spans_in_memory")
class TestRoundSeam:
    @pytest.mark.parametrize("field", StackOperands._fields)
    def test_a_field_set_alone_keeps_the_stack(self, field, monkeypatch):
        """Named by `_stack_users`, sent to the dense program, refused under
        ``client_axis="scan"`` by its name."""
        import dataclasses
        cfg, ds, pool, step, x, y, opt, sw, fm = _setup(M=3, C=4)
        if field in ("stale_params", "codec_prev"):
            value = _param_stack(pool)
        else:
            value = jnp.zeros((2,) if field in ("edge_mask", "edge_modes")
                              else (4,), jnp.int32)
        none, alone = StackOperands(), StackOperands(**{field: value})
        assert step._stack_users(False, none) == []
        assert step._stack_users(False, alone) == [f"the operand {field}"]
        assert step._round_program(3, 1, False, none) == (
            TrainStep._train_round_jit, 1)
        assert step._round_program(3, 1, False, alone) == (
            TrainStep._train_round_jit, None)
        _forbid_the_compact_body(monkeypatch)
        tw = jnp.asarray(_one_hot_tw([(0,), (2,), (2,), (0,)]))
        step.train_round(pool.params, opt, jax.random.PRNGKey(2), x, y, tw,
                         sw, fm, jnp.float32(1.0), None, alone,
                         keep_client_params=False, models_per_client=1)
        assert _train_round_spans()[-1]["args"]["pairs_run"] == 3 * 4

        scanned = dataclasses.replace(
            step, client_axis="scan", _signatures={},
            optimizer=make_optimizer("sgd", cfg.lr, cfg.wd))
        assert scanned.donates_pool and not scanned.fuses_rounds
        assert step.fuses_rounds and not step.donates_pool
        assert scanned._round_program(3, 1, False, none) == (
            TrainStep._train_round_scan_jit, None)
        with pytest.raises(ValueError, match="client_axis='scan'") as e:
            scanned.train_round(
                jax.tree_util.tree_map(jnp.copy, pool.params),
                scanned.init_opt_states(pool.params, 3, 4),
                jax.random.PRNGKey(2), x, y, tw, sw, fm, jnp.float32(1.0),
                None, alone, keep_client_params=False)
        assert f"the operand {field} needs" in str(e.value)

    def test_the_entries_keep_what_the_benchmark_binds(self):
        """`benchmark/drivers/train.py::Recorder` binds ``train_round`` /
        ``train_iteration_eval`` and reads ``time_w`` and ``client_mask`` /
        ``client_masks`` by name; `benchmark/sizing.py` and `sizing_scan.py`
        lower the three jits on their positional prefix alone, with
        ``keep_client_params=False``; the device-trace readers find the
        programs by the jitted functions' names."""
        import inspect

        def names(fn):
            return list(inspect.signature(fn).parameters)
        a_round = ["self", "params", "opt_states", "key", "x", "y", "time_w",
                   "sample_w", "feat_mask", "lr_scale"]
        a_step = a_round[:3] + ["iter_key"] + a_round[4:] + ["R", "freq", "t"]
        assert names(TrainStep.train_round)[:11] == a_round + ["client_mask"]
        assert names(TrainStep.train_iteration_eval)[:14] == (
            a_step + ["client_masks"])
        for name in ("_acc_matrix_jit", "init_opt_states",
                     "fresh_opt_states"):
            assert callable(getattr(TrainStep, name))

        cfg, ds, pool, step, x, y, opt, sw, fm = _setup(M=3, C=4)
        tw = jnp.ones((3, 4, 4), jnp.float32)
        nine = (pool.params, opt, jax.random.PRNGKey(0), x, y, tw, sw, fm,
                jnp.float32(1.0))
        scanned = TrainStep(pool.apply, make_optimizer("sgd", cfg.lr, 0.0),
                            20, cfg.epochs, ds.num_classes, client_axis="scan")
        for jit, prefix, lowered in (
                (TrainStep._train_round_jit, a_round,
                 lambda j: j.lower(step, *nine, keep_client_params=False)),
                (TrainStep._train_round_scan_jit, a_round,
                 lambda j: j.lower(
                     scanned, pool.params,
                     scanned.init_opt_states(pool.params, 3, 4), *nine[2:],
                     keep_client_params=False)),
                (TrainStep._train_iteration_eval_jit, a_step,
                 lambda j: j.lower(step, *nine, 2, 1, jnp.int32(0)))):
            params = inspect.signature(jit).parameters
            assert list(params)[:len(prefix)] == prefix
            assert all(p.default is not p.empty
                       for p in list(params.values())[len(prefix):])
            assert lowered(jit).as_text().startswith(
                f"module @jit_{jit.__name__} ")
