"""End-to-end simulation tests on the 8-device CPU mesh.

Mirrors the reference's reproducibility-as-testing stance (SURVEY.md §4):
fixed seeds, assert accuracy trajectories.
"""


from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.simulation.runner import run_experiment
import pytest

pytestmark = pytest.mark.slow   # heavy compiles: full-tier only


def _cfg(**kw):
    base = dict(dataset="sine", model="fnn", concept_drift_algo="win-1",
                train_iterations=2, comm_round=16, epochs=5, sample_num=100,
                batch_size=50, frequency_of_the_test=5, lr=0.05,
                client_num_in_total=10, client_num_per_round=10, seed=0)
    base.update(kw)
    return ExperimentConfig(**base)


class TestEndToEnd:
    def test_win1_learns_sine(self):
        exp = run_experiment(_cfg())
        accs = dict(exp.logger.series("Test/Acc"))
        # end of iteration 0 (round 15): model must beat chance solidly
        assert accs[15] > 0.8, accs

    def test_drift_hurts_oblivious_baseline(self):
        exp = run_experiment(_cfg(train_iterations=3, comm_round=12))
        accs = exp.logger.series("Test/Acc")
        by_round = dict(accs)
        # test at iteration 2 covers step-3 data where half the clients have
        # flipped concepts (preset A) -> win-1 single model falls toward 0.5
        assert by_round[35] < 0.75, by_round

    def test_chunked_matches_per_round(self):
        # the scanned multi-round program must reproduce the per-round host
        # loop bitwise (same fold_in key sequence)
        a = run_experiment(_cfg(chunk_rounds=True)).logger.series("Test/Acc")
        b = run_experiment(_cfg(chunk_rounds=False)).logger.series("Test/Acc")
        assert a == b, (a, b)

    def test_chunked_matches_per_round_softcluster(self):
        kw = dict(concept_drift_algo="softcluster",
                  concept_drift_algo_arg="H_A_C_1_10_0", concept_num=3,
                  train_iterations=3, comm_round=8, frequency_of_the_test=4)
        a = run_experiment(_cfg(chunk_rounds=True, **kw)).logger.series("Test/Acc")
        b = run_experiment(_cfg(chunk_rounds=False, **kw)).logger.series("Test/Acc")
        assert a == b, (a, b)

    def test_acc_matrix_ride_along_cache(self, monkeypatch):
        # The fused path stores its final eval slot as next iteration's
        # cluster-phase acc matrix (runner._run_iteration_fused ->
        # DriftAlgorithm.store_acc_counts): the store must actually hit
        # (saving one device round trip per iteration) AND the clustering
        # trajectory must be identical with the store defeated.
        from feddrift_tpu.algorithms.base import DriftAlgorithm
        from feddrift_tpu.core.step import TrainStep

        kw = dict(concept_drift_algo="softcluster",
                  concept_drift_algo_arg="H_A_C_1_10_0", concept_num=3,
                  train_iterations=3, comm_round=8, frequency_of_the_test=4)

        calls = {"n": 0}
        orig = TrainStep.acc_matrix

        def counting(self, *a, **k):
            calls["n"] += 1
            return orig(self, *a, **k)

        monkeypatch.setattr(TrainStep, "acc_matrix", counting)
        exp_a = run_experiment(_cfg(chunk_rounds=True, **kw))
        hits = calls["n"]

        # a store that holds nothing: every lookup misses, every write is
        # lost (tests/test_acc_reuse.py defeats it the same way)
        monkeypatch.setattr(DriftAlgorithm, "_acc_entries",
                            lambda self, params: {})
        calls["n"] = 0
        exp_b = run_experiment(_cfg(chunk_rounds=True, **kw))
        misses = calls["n"]

        # cache removes >= (iterations - 1) standalone acc_matrix dispatches
        assert misses - hits >= kw["train_iterations"] - 1, (hits, misses)
        # and changes nothing observable
        assert exp_a.logger.series("Test/Acc") == exp_b.logger.series("Test/Acc")
        import numpy as np
        assert np.array_equal(exp_a.algo.weights, exp_b.algo.weights)

    def test_fused_iteration_eval_cadence(self):
        # the fully-fused iteration program must log evals at the reference
        # cadence — every frequency_of_the_test rounds plus the final round
        # (AggregatorSoftCluster.py:211) — with correct global round numbers
        exp = run_experiment(_cfg(chunk_rounds=True, train_iterations=2,
                                  comm_round=13, frequency_of_the_test=5))
        rounds = [r for r, _ in exp.logger.series("Test/Acc")]
        assert rounds == [0, 5, 10, 12, 13, 18, 23, 25], rounds

    def test_client_subsampling_paths_agree(self):
        # client_num_per_round < C: round-seeded sampling masks
        # (client_sampling, AggregatorSoftCluster.py:197-205) must give
        # identical trajectories on the fused and per-round paths
        kw = dict(client_num_per_round=4, train_iterations=2, comm_round=9,
                  frequency_of_the_test=4)
        a = run_experiment(_cfg(chunk_rounds=True, **kw)).logger.series("Test/Acc")
        b = run_experiment(_cfg(chunk_rounds=False, **kw)).logger.series("Test/Acc")
        assert a == b, (a, b)
        # and subsampling must actually change the trajectory vs full clients
        c = run_experiment(_cfg(chunk_rounds=True, train_iterations=2,
                                comm_round=9,
                                frequency_of_the_test=4)).logger.series("Test/Acc")
        assert a != c

    def test_remat_identical_numerics(self):
        # jax.checkpoint rematerialization must not change trajectories
        a = run_experiment(_cfg(comm_round=6)).logger.series("Test/Acc")
        b = run_experiment(_cfg(comm_round=6, remat=True)).logger.series("Test/Acc")
        assert a == b

    def test_determinism(self):
        a = run_experiment(_cfg()).logger.series("Test/Acc")
        b = run_experiment(_cfg()).logger.series("Test/Acc")
        assert a == b

    def test_all_retrain_all_data(self):
        exp = run_experiment(_cfg(concept_drift_algo="all", comm_round=10))
        assert exp.logger.last("Test/Acc") > 0.7

    def test_recency_exp(self):
        exp = run_experiment(_cfg(concept_drift_algo="exp", comm_round=10))
        assert exp.logger.last("Test/Acc") > 0.6

    def test_metrics_names_reference_compatible(self):
        exp = run_experiment(_cfg(comm_round=6))
        rec = exp.logger.history[-1]
        for key in ("Train/Acc", "Train/Loss", "Test/Acc", "Test/Loss",
                    "Train/Acc-CL-0", "Test/Acc-CL-9", "Plurality/CL-0"):
            assert key in rec, key
