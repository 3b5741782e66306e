"""The compact vmap round through the runner (ISSUE 30): SoftCluster counts,
beside the time weights it hands the round, the most models any client
trains; the per-round path passes the count to ``train_round``, which runs
that many models a client. What an experiment logs and decides is what it
logs and decides with every (model, client) pair run."""

import numpy as np
import pytest

from feddrift_tpu.algorithms import softcluster
from feddrift_tpu.config import ExperimentConfig

T = 4


def _run(algo, monkeypatch, dense):
    from feddrift_tpu.simulation.runner import Experiment
    if dense:
        monkeypatch.setattr(softcluster, "live_models_per_client",
                            lambda weights: None)
    exp = Experiment(ExperimentConfig(
        model="fnn", dataset="sea", lr=0.05, concept_drift_algo=algo,
        concept_drift_algo_arg="hard-r", concept_num=3, comm_round=3,
        frequency_of_the_test=1, train_iterations=T, sample_num=40,
        batch_size=10, epochs=2, cost_model="off",
        checkpoint_every_iteration=False))
    for t in range(T):
        exp.run_iteration(t)
    pairs = [s["args"]["pairs_run"] for s in exp.spans.spans("dispatch")
             if s["args"].get("fn") == "train_round"]
    series = {k: exp.logger.series(k)
              for k in ("Train/Acc", "Train/Loss", "Test/Acc", "Test/Loss")}
    return exp, pairs, series


# IFCA with a window of one time step: one model a client in every round;
# without the window a client that changed models keeps training the old
# one on its old steps: K grows, and each K is a program of its own
@pytest.mark.parametrize("algo", ["softclusterwin-1", "softcluster"])
def test_an_ifca_experiment_logs_and_decides_the_same_compact_or_dense(
        algo, monkeypatch):
    compact, pairs, got = _run(algo, monkeypatch, dense=False)
    M, C = compact.pool.num_models, compact.C_pad
    assert len(pairs) == T * 3
    if algo == "softclusterwin-1":
        assert pairs == [C] * (T * 3)
        assert compact.algo.models_per_client == 1
        assert len(compact.step._signatures["train_round"]) == 1
    else:
        assert set(pairs) <= {k * C for k in range(1, M + 1)}
        assert pairs[0] == C and max(pairs) > C       # it engaged, and grew
    dense, pairs_dense, want = _run(algo, monkeypatch, dense=True)
    assert dense.algo.models_per_client is None
    assert pairs_dense == [M * C] * (T * 3)
    np.testing.assert_array_equal(compact.algo.weights, dense.algo.weights)
    for k, series in want.items():
        assert len(series) >= T
        assert [r for r, _ in got[k]] == [r for r, _ in series]
        np.testing.assert_allclose([v for _, v in got[k]],
                                   [v for _, v in series],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_the_count_is_taken_from_the_weights_on_the_host():
    w = np.zeros((3, 4, 5), np.float32)            # [T1, M, C]
    assert softcluster.live_models_per_client(w) == 1     # nobody trains
    w[2, 1, :] = 1.0
    assert softcluster.live_models_per_client(w) == 1
    w[0, 3, 2] = 0.5                                # client 2: a second model
    assert softcluster.live_models_per_client(w) == 2
    w[1] = 0.25                                     # soft weights: every pair
    assert softcluster.live_models_per_client(w) == 4


def test_the_resnet_cells_job_at_the_rehearsals_size_runs_one_model_a_client():
    """The benchmark's ``resnet20.ifca_perround`` files at their rehearsal
    sizes (C = 2, M = 3): every ``train_round`` dispatch of the window's
    time steps says ``pairs_run`` = K x C = 2, one signature, and the
    counter adds up to what the spans say. What a reader of a dozen lines
    (``benchmark/metrics/_round_counts.rounds_with(records, "pairs_run")``)
    would report as ``pairs_run_per_round`` 2.0 once a ``benchmark`` PR
    lists that metric (PERF.md section 7)."""
    from benchmark.drivers import train
    from benchmark.run import load_cell, load_manifest
    from feddrift_tpu import obs
    from feddrift_tpu.parallel.mesh import make_mesh
    from feddrift_tpu.simulation.runner import Experiment
    cell, config, traffic, sizes = load_cell(
        load_manifest(), "resnet20.ifca_perround", rehearse=True)
    clients = int(sizes["clients_per_chip"])
    cfg = train.experiment_config(config, traffic, sizes, 2 ** 31 + 5, clients)
    counted = obs.registry().snapshot().get("pairs_run", 0)
    exp = Experiment(cfg, mesh=make_mesh(num_devices=1))
    for t in range(3):
        exp.run_iteration(t)
    said = [s["args"] for s in exp.spans.spans("dispatch")
            if s["args"].get("fn") == "train_round"]
    assert (clients, exp.pool.num_models) == (2, 3)
    assert len(said) == 3 * cfg.comm_round
    assert all(a["pairs_run"] == 2 for a in said)
    assert obs.registry().snapshot()["pairs_run"] - counted == 2 * len(said)
    assert len(exp.step._signatures["train_round"]) == 1
