"""One evaluation of each (pool, time step) pair (ISSUE 32): the drift
decision, the per-round re-assignment and the runner's evaluation read their
accuracy counts through one store in front of ``TrainStep.acc_matrix``
(``DriftAlgorithm.acc_counts_at``), keyed on the identity of the pool. Under
IFCA ``hard-r`` on the per-round path 3 of a time step's 10 requests are of
a pair the host already holds; what a run logs and decides is bit for bit
what it logs and decides with the store defeated."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from feddrift_tpu import obs
from feddrift_tpu.algorithms.base import DriftAlgorithm
from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.core.step import TrainStep

T, ROUNDS = 3, 5
SERIES = ("Train/Acc", "Train/Loss", "Test/Acc", "Test/Loss")
IFCA = dict(concept_drift_algo="softclusterwin-1",
            concept_drift_algo_arg="hard-r")
AXES = {"vmap": dict(client_axis="vmap"),
        "scan": dict(client_axis="scan", client_optimizer="sgd")}


def _cfg(**kw):
    base = dict(
        model="fnn", dataset="sea", lr=0.05, concept_num=3, epochs=2,
        comm_round=ROUNDS, frequency_of_the_test=ROUNDS, train_iterations=T,
        sample_num=40, batch_size=10, client_num_in_total=4,
        client_num_per_round=4, chunk_rounds=False, report_client=0,
        cost_model="off", checkpoint_every_iteration=False, **IFCA)
    base.update(kw)
    return ExperimentConfig(**base)


def defeat(monkeypatch):
    """A store that holds nothing: every lookup misses, every write is
    lost."""
    monkeypatch.setattr(DriftAlgorithm, "_acc_entries",
                        lambda self, params: {})


@pytest.fixture()
def calls(monkeypatch):
    """Counts the calls of ``TrainStep.acc_matrix``, the method the
    benchmark's ``assign_altered`` fault patches: the store sits above it."""
    seen, real = [], TrainStep.acc_matrix

    def counting(self, *a, **kw):
        seen.append(1)
        return real(self, *a, **kw)
    monkeypatch.setattr(TrainStep, "acc_matrix", counting)
    return seen


def _run(cfg, calls=()):
    """The time steps one at a time, as the benchmark's window drives them;
    the calls of ``acc_matrix`` in each."""
    from feddrift_tpu.simulation.runner import Experiment
    exp = Experiment(cfg)
    per_step = []
    for t in range(cfg.train_iterations):
        before = len(calls)
        exp.run_iteration(t)
        per_step.append(len(calls) - before)
    return exp, per_step


def _assert_same_run(a, b):
    for k in SERIES:
        assert len(a.logger.series(k)) >= T, k
        assert a.logger.series(k) == b.logger.series(k), k
    assert a.algo.weights.tobytes() == b.algo.weights.tobytes()
    for la, lb in zip(jax.tree_util.tree_leaves(a.pool.params),
                      jax.tree_util.tree_leaves(b.pool.params)):
        assert np.asarray(la).tobytes() == np.asarray(lb).tobytes()


# ----------------------------------------------------------------------
# (a) the cells' traffic: IFCA hard-r, 5 rounds, an evaluation behind
# rounds 0 and 4
@pytest.mark.parametrize("axis", sorted(AXES))
def test_ifca_dispatches_seven_of_ten_and_logs_the_same(axis, calls,
                                                        monkeypatch):
    kept, with_store = _run(_cfg(**AXES[axis]), calls)
    # time step 0 re-draws the models and evaluates them once more
    assert with_store == [8] + [7] * (T - 1)
    defeat(monkeypatch)
    bare, without = _run(_cfg(**AXES[axis]), calls)
    assert without == [11] + [10] * (T - 1)
    _assert_same_run(kept, bare)


# (b) an algorithm that does not re-assign every round (its one duplicate
# is the drift decision's matrix, the last evaluation's test half), on the
# per-round path and on the fused one, whose final eval slot is written
# into the same store
@pytest.mark.parametrize("chunk_rounds", [False, True],
                         ids=["per_round", "fused"])
def test_a_feddrift_run_logs_the_same(chunk_rounds, calls, monkeypatch):
    kw = dict(concept_drift_algo="softcluster",
              concept_drift_algo_arg="H_A_C_1_10_0",
              chunk_rounds=chunk_rounds)
    kept, with_store = _run(_cfg(**kw), calls)
    defeat(monkeypatch)
    bare, without = _run(_cfg(**kw), calls)
    assert with_store[0] == without[0]
    assert all(w < wo for w, wo in zip(with_store[1:], without[1:])), \
        (with_store, without)
    if chunk_rounds:        # the decision's matrix was the only dispatch
        assert with_store[1:] == [0] * (T - 1)
    _assert_same_run(kept, bare)


# (d) population mode swaps x under an unchanged pool: rebind_data clears
# the store, or the decision would read the last cohort's accuracies
def test_a_population_run_logs_the_same(calls, monkeypatch):
    kw = dict(population_size=12, cohort_size=4, seed=3)
    kept, with_store = _run(_cfg(**kw), calls)
    # the decision's matrix is of a new cohort's data: dispatched
    assert with_store == [8] * T
    defeat(monkeypatch)
    bare, without = _run(_cfg(**kw), calls)
    assert without == [11] + [10] * (T - 1)
    members = [r["members"] for r in kept.events.events("cluster_assign")]
    assert len({tuple(m) for m in members}) > 1     # the cohorts did differ
    _assert_same_run(kept, bare)


# ----------------------------------------------------------------------
# (c), (e) where a request must miss, and what a hit hands out
@pytest.fixture()
def warm(calls):
    """An experiment after one time step: the store holds the final pool's
    counts on steps 0 and 1."""
    exp, _ = _run(_cfg(train_iterations=1), calls)
    assert set(exp.algo._acc_store[1]) == {0, 1}
    assert exp.algo._acc_store[0] is exp.pool.params
    del calls[:]
    return exp


def test_a_hit_hands_out_the_stored_read_only_arrays(warm, calls):
    first = warm.algo.acc_counts_at([0, 1])
    again = warm.algo.acc_counts_at([1, 0, 1])
    assert calls == []
    assert [id(a) for a in again[1]] == [id(a) for a in first[0]]
    assert again[0] is again[2] and again[0] is first[1]
    for triple in first:
        correct, loss, total = triple
        assert correct.shape == loss.shape == (3, warm.C_pad)
        assert total.shape == (warm.C_pad,)
        for arr in triple:
            assert not arr.flags.writeable
            with pytest.raises(ValueError):
                arr[...] = 0
    # and they are what the program gives for that pair
    fresh = jax.device_get(warm.step.acc_matrix(
        warm.pool.params, warm.x[:, 1], warm.y[:, 1],
        warm.algo._ones_feat_mask))
    for got, want in zip(first[1], fresh):
        assert got.tobytes() == np.asarray(want).tobytes()
    ratio = warm.algo.acc_matrix_at(1)
    assert ratio.flags.writeable        # the ratio is the caller's own
    np.testing.assert_array_equal(
        ratio, first[1][0][:, :warm.C_] / first[1][2][None, :warm.C_])


def test_a_rebuilt_slot_misses(warm, calls):
    before = warm.algo.acc_matrix_at(0)
    assert calls == []
    warm.pool.reinit_slot(0)            # rebinds pool.params
    after = warm.algo.acc_matrix_at(0)
    assert len(calls) == 1
    assert not np.array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[1:], after[1:])
    # the dead pool's entries went with it
    assert warm.algo._acc_store[0] is warm.pool.params
    assert set(warm.algo._acc_store[1]) == {0}


def test_rebound_data_misses(warm, calls):
    warm.algo.rebind_data(warm.x, warm.y)
    assert warm.algo._acc_store is None
    warm.algo.acc_matrix_at(0)
    assert len(calls) == 1


def test_a_mask_of_the_callers_is_neither_served_nor_stored(warm, calls):
    held = dict(warm.algo._acc_store[1])
    ones = warm.algo._ones_feat_mask
    half = ones.at[:, 0].set(0.0)
    masked = warm.algo.acc_matrix_at(0, feat_mask=half)
    plain = warm.algo.acc_matrix_at(0, feat_mask=jnp.ones_like(ones))
    assert len(calls) == 2
    # the all-ones object that round_inputs hands out is the plain mask
    np.testing.assert_array_equal(plain,
                                  warm.algo.acc_matrix_at(0, feat_mask=ones))
    assert len(calls) == 2 and not np.array_equal(masked, plain)
    assert warm.algo._acc_store[1] == held


@pytest.mark.parametrize("axis", sorted(AXES))
def test_after_a_rollback(axis, calls, monkeypatch):
    """The guard rolls the time step's last round back. A pool that was
    not donated goes back to the object the store knows, whose entry is
    still true of it: a hit. A donated pool comes back from the round as
    a new object: a miss."""
    real, rounds = TrainStep.train_round, []

    def poisoned_last(self, *a, **kw):
        out = real(self, *a, **kw)
        rounds.append(1)
        if len(rounds) != ROUNDS:
            return out
        p, o, cp, n, losses, *rest = out
        return (p, o, cp, n, jnp.full_like(losses, jnp.nan), *rest)
    monkeypatch.setattr(TrainStep, "train_round", poisoned_last)
    exp, per_step = _run(_cfg(train_iterations=1, divergence_guard=True,
                              divergence_warmup_rounds=0, **AXES[axis]),
                         calls)
    assert len(exp.events.events("divergence_detected")) == 1
    # no re-assignment and no evaluation behind the round rolled back
    assert per_step == [8 - 2]
    del calls[:]
    got = exp.algo.acc_counts_at([0])
    assert len(calls) == (1 if exp.step.donates_pool else 0)
    want = jax.device_get(exp.step.acc_matrix(
        exp.pool.params, exp.x[:, 0], exp.y[:, 0],
        exp.algo._ones_feat_mask))
    for a, b in zip(got[0], want):
        assert a.tobytes() == np.asarray(b).tobytes()


def test_streamed_data_has_no_matrix_to_count():
    from feddrift_tpu.simulation.runner import Experiment
    exp = Experiment(_cfg(concept_drift_algo="win-1",
                          concept_drift_algo_arg="", stream_data=True,
                          chunk_rounds=True))
    with pytest.raises(RuntimeError, match="stream_data"):
        exp.algo.acc_counts_at([0])
    with pytest.raises(RuntimeError, match="stream_data"):
        exp.algo.acc_matrix_at(0, feat_mask=exp.algo._ones_feat_mask)


# ----------------------------------------------------------------------
# (f) the counters, and the spans that carry them
def test_the_counters_and_the_spans_say_three_of_ten():
    before = obs.registry().snapshot()
    exp, _ = _run(_cfg())
    after = obs.registry().snapshot()
    added = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("acc_matrix_reused", "acc_matrix_computed")}
    assert added == {"acc_matrix_reused": 3 * T,
                     "acc_matrix_computed": 8 + 7 * (T - 1)}

    def tally(name, t):
        return [(s["args"]["acc_reused"], s["args"]["acc_computed"])
                for s in exp.spans.spans(name)
                if s["cat"] == "round" and s["args"]["iteration"] == t
                and "acc_reused" in s["args"]]
    for t in range(T):
        # begin_iteration; end_iteration asks for nothing
        assert tally("drift_decision", t) == [(1, 1) if t == 0 else (1, 0)]
        assert tally("writeback", t) == [(0, 1)] * ROUNDS
        assert tally("eval", t) == [(1, 1)] * 2
    # the dispatch and the wait under a request are spans of their own
    # and carry none of it
    assert not any("acc_reused" in s["args"]
                   for name in ("dispatch", "device_compute")
                   for s in exp.spans.spans(name))
