"""The token data set with drift (``data/text.py::generate_token_drift``)
and a label per token through ``DriftDataset``, the runner's rounds, its
evaluation and what reads single labels; CPU."""

import numpy as np
import pytest

from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.data.drift_dataset import DriftDataset
from feddrift_tpu.data.registry import make_dataset
from feddrift_tpu.data.text import (FOLLOW_PROB, _affine_maps,
                                    _rank_permutation, generate_text_drift,
                                    generate_word_drift)

CP = "0 0 0 0;0 1 0 1;1 1 0 1;1 1 0 0"


def _cfg(**kw):
    base = dict(model="mla_moe_tiny", dataset="token_drift", text_seq_len=16,
                token_vocab=64,
                sample_num=8, batch_size=4, epochs=2, client_optimizer="sgd",
                lr=0.05, wd=0.0, client_axis="scan", remat=True,
                concept_drift_algo="softclusterwin-1",
                concept_drift_algo_arg="hard-r", concept_num=3, comm_round=2,
                frequency_of_the_test=2, train_iterations=3,
                client_num_in_total=4, client_num_per_round=4,
                change_points=CP, cost_model="off",
                checkpoint_every_iteration=False)
    base.update(kw)
    return ExperimentConfig(**base)


def test_token_data_is_seeded_inside_the_slice_and_labelled_with_the_next_token():
    ds = make_dataset(_cfg())
    assert ds.x.shape == ds.y.shape == (4, 4, 8, 16)
    assert ds.x.dtype == ds.y.dtype == np.int32
    assert ds.num_classes == 64 and ds.is_sequence   # the tiny preset's rows
    assert ds.labels_per_sample == 16 and ds.samples_per_step == 8
    for a in (ds.x, ds.y):
        assert a.min() >= 0 and a.max() < 64
    np.testing.assert_array_equal(ds.y[..., :-1], ds.x[..., 1:])
    again = make_dataset(_cfg())
    np.testing.assert_array_equal(again.x, ds.x)
    np.testing.assert_array_equal(again.y, ds.y)
    assert not np.array_equal(make_dataset(_cfg(seed=1)).x, ds.x)
    np.testing.assert_array_equal(ds.concepts[:, 1], [0, 1, 1, 1])
    # a data set of single labels has one label a sample, as before
    assert make_dataset(_cfg(model="fnn", dataset="sea",
                             client_axis="vmap")).labels_per_sample == 1


def test_two_concepts_differ_in_their_frequent_ids_and_in_what_follows_what():
    """A concept permutes the ranks of the unigram law and has its own
    affine successor map; the data follows its own concept's map about
    half the time and the other's hardly."""
    V = 64
    a, b = _affine_maps(2, V)
    p0, p1 = _rank_permutation(0, V), _rank_permutation(1, V)
    assert p0[0] != p1[0] and sorted(p0) == sorted(p1) == list(range(V))
    ds = make_dataset(_cfg(sample_num=64))
    for t, c in ((0, 0), (1, 1)):
        k = int(ds.concepts[t, c])
        x, y = ds.x[c, t].astype(np.int64), ds.y[c, t]
        follows = ((a[k] * x + b[k]) % V == y).mean()
        assert FOLLOW_PROB - 0.05 < follows < FOLLOW_PROB + 0.12
        assert ((a[1 - k] * x + b[1 - k]) % V == y).mean() < 0.15
        # rank 1 of its own law is among its most frequent ids (the map's
        # successors of frequent ids are frequent too), the other's is not
        ids, counts = np.unique(y, return_counts=True)
        top = set(ids[np.argsort(counts)[-3:]])
        assert (p0, p1)[k][0] in top and (p0, p1)[1 - k][0] not in top


def test_the_older_generators_draw_what_they_drew():
    """``generate_word_drift`` and ``generate_text_drift`` take their
    concepts from the helpers the token data set shares with them: the same
    draws as before (digests of the parent commit's arrays)."""
    ds = generate_word_drift(np.array([[0, 0], [0, 1]]), 2, 2, 4, seed=3,
                             seq_len=5, vocab=50, data_dir="/nonexistent")
    assert ds.x.shape == (2, 3, 4, 5) and ds.y.shape == (2, 3, 4)
    assert (int(ds.x.sum()), int(ds.y.sum()), int(ds.x[1, 2, 3, 4])) \
        == (2731, 468, 1)
    ds = generate_text_drift(np.array([[0, 0], [0, 1]]), 2, 2, 4, seed=3,
                             seq_len=5, data_dir="/nonexistent")
    assert (int(ds.x.sum()), int(ds.y.sum()), int(ds.x[1, 2, 3, 4])) \
        == (5607, 1003, 38)


def test_the_vocabulary_is_a_field_and_the_model_refuses_another_number():
    """The data layer knows no model: ``token_vocab`` says how many ids
    there are, and a model that holds another number of rows refuses."""
    assert make_dataset(_cfg(model="fnn", client_axis="vmap",
                             token_vocab=50)).num_classes == 50
    from feddrift_tpu.models import create_model
    cfg = _cfg(token_vocab=50)
    with pytest.raises(ValueError, match="holds 64 rows"):
        create_model(cfg.model, make_dataset(cfg), cfg)
    cfg = _cfg(dataset="shakespeare", client_axis="vmap")
    with pytest.raises(ValueError, match="a label per token"):
        create_model(cfg.model, make_dataset(cfg), cfg)


def test_a_data_set_takes_labels_with_trailing_axes_and_refuses_a_mismatch():
    x = np.zeros((2, 3, 4, 5), np.int32)
    concepts = np.zeros((3, 2), np.int32)
    ds = DriftDataset(x=x, y=np.zeros((2, 3, 4, 5), np.int32), num_classes=7,
                      concepts=concepts, is_sequence=True)
    assert ds.labels_per_sample == 5
    assert ds.train_slice(0)[1].shape == ds.test_slice(1)[1].shape == (2, 4, 5)
    assert DriftDataset(x=x, y=np.zeros((2, 3, 4), np.int32), num_classes=7,
                        concepts=concepts).labels_per_sample == 1
    with pytest.raises(AssertionError):
        DriftDataset(x=x, y=np.zeros((2, 3, 5), np.int32), num_classes=7,
                     concepts=concepts)


def test_what_reads_single_labels_refuses_a_label_per_token_by_name():
    import jax
    import jax.numpy as jnp
    from feddrift_tpu.core.step import (StackOperands, TrainStep,
                                        make_optimizer)
    step = TrainStep(apply_fn=lambda p, xb: p["t"][xb],
                     optimizer=make_optimizer("sgd", 0.1, 0), batch_size=2,
                     num_steps=1, num_classes=5, cost_capture="off")
    p = {"t": jnp.zeros((1, 5, 5))}
    x = jnp.zeros((2, 2, 2, 3), jnp.int32)
    with pytest.raises(ValueError, match="label_flip.*a label per token"):
        step.train_round(p, step.init_opt_states(p, 1, 2),
                         jax.random.PRNGKey(0), x, x, jnp.ones((1, 2, 2)),
                         jnp.ones((1, 2, 2)), jnp.ones((1, 1)),
                         jnp.float32(1.0), None,
                         StackOperands(byz_modes=jnp.zeros((2,), jnp.int32)))
    with pytest.raises(ValueError, match="confusion_matrices.*per token"):
        step.confusion_matrices(p, x[:, 0], x[:, 0], jnp.ones((1, 1)))


@pytest.mark.parametrize("algo,arg", [("softclusterwin-1", "hard-r"),
                                      ("softcluster", "H_A_C_1_10_0")])
def test_the_decoder_trains_through_the_normal_path_counting_tokens(algo, arg):
    """``Experiment.run_iteration`` with the new model, data set and the
    scanned body, under IFCA and under FedDrift's softcluster: the logged
    accuracies count tokens, the loss falls, the counts come back on the
    guard's span and as counters, ``run_start`` names the round body and
    the experts held."""
    from feddrift_tpu import obs
    from feddrift_tpu.simulation.runner import Experiment
    before = obs.registry().snapshot().get("pairs_trained", 0)
    exp = Experiment(_cfg(concept_drift_algo=algo, concept_drift_algo_arg=arg))
    for t in range(2):
        exp.run_iteration(t)
    losses = [v for _, v in exp.logger.series("Train/Loss")]
    assert losses[-1] < losses[0] < np.log(64) + 0.5
    acc = exp.logger.last("Train/Acc")
    assert 0 < acc < 1 and (acc * 4 * 8 * 16) == pytest.approx(
        round(acc * 4 * 8 * 16), abs=1e-6)         # a count of tokens
    guards = [s for s in exp.spans.spans("guard")
              if "pairs_trained" in s.get("args", {})]
    assert len(guards) == 4                         # one a round
    for g in guards:
        a = g["args"]
        assert a["pairs_trained"] == 4              # each client one model
        # 4 pairs x 2 steps x 4 sequences x 16 tokens x 2 expert layers
        assert a["expert_tokens"] == 4 * 2 * 4 * 16 * 2
        assert 0 < a["expert_assignments_held"] <= 2 * a["expert_tokens"]
        assert a["expert_load_max_over_mean"] >= 1.0
        assert a["expert_blocks"] >= 0
    snap = obs.registry().snapshot()
    assert snap["pairs_trained"] - before == 16
    assert snap["expert_tokens"] > 0 and snap["expert_assignments_held"] > 0
    assert exp.module.experts_held == (0, 4)
