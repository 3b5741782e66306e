"""Multi-iteration megastep: K fused time steps per device dispatch.

The megastep (TrainStep.train_megastep, runner.run_megastep) scans K
whole time steps — each itself an R-round fused scan with scheduled
evals — inside ONE device program, so the host touches the device once
per K iterations instead of once per iteration. The contract under test:

- bitwise parity: the K>1 path must reproduce the K=1 driver exactly
  (params, eval series, decision trajectories) — same fold_in key
  sequence, same opt-state reinit, same eval cadence;
- validity gating: ``_megastep_span`` fuses only configurations the scan
  actually models, and ``megastep_horizon`` clamps the span at the next
  drift-decision boundary;
- compile stability: one program per K, compiled once — steady-state
  blocks must hit the jit cache (the perf win evaporates otherwise);
- the regress gate's megastep axis (rounds/s floor, absolute
  zero-recompile, host-overhead-beats-K=1).
"""

import jax
import numpy as np
import pytest

from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.simulation.runner import Experiment, run_experiment


def _cfg(**kw):
    base = dict(dataset="sea", model="lr", concept_drift_algo="oblivious",
                concept_drift_algo_arg="", concept_num=1,
                client_num_in_total=8, client_num_per_round=8,
                train_iterations=8, comm_round=5, epochs=1, batch_size=50,
                sample_num=50, frequency_of_the_test=5, lr=0.05,
                seed=7, trace_sync=True)
    base.update(kw)
    return ExperimentConfig(**base)


def _leafdiff(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(la, lb))


@pytest.mark.slow
class TestMegastepParity:
    """K=4 vs K=1 must be bitwise-identical end to end."""

    def _pair(self, **kw):
        return run_experiment(_cfg(megastep_k=1, **kw)), \
               run_experiment(_cfg(megastep_k=4, **kw))

    def test_oblivious_bitwise(self):
        e1, e4 = self._pair()
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")
        assert e1.logger.series("Train/Acc") == e4.logger.series("Train/Acc")

    def test_softcluster_cadence_bitwise(self):
        # cadence-3 softcluster: decisions at t=0,3,6 — the megastep fuses
        # the decision-free gaps and the carried-forward weight trajectory
        # must match the sequential driver exactly
        kw = dict(concept_drift_algo="softcluster",
                  concept_drift_algo_arg="H_A_C_1_10_0", concept_num=3,
                  decision_cadence=3)
        e1, e4 = self._pair(**kw)
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")
        assert np.array_equal(e1.algo.weights, e4.algo.weights)

    def test_partial_participation_bitwise(self):
        # per-round client masks ride through the scan as a [K, R, C] xs
        e1, e4 = self._pair(client_num_per_round=2)
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")

    def _assert_fused_once(self, exp):
        # non-vacuous: the K=4 run must actually have dispatched the
        # megastep program, with ONE argument signature (zero steady-state
        # recompiles — _cache_size is class-global, signatures are not)
        assert "train_megastep" in exp.step._signatures
        assert len(exp.step._signatures["train_megastep"]) == 1

    def test_population_cohorts_bitwise(self):
        # cohort gathers ride the scan as stacked [K, C, T1, ...] inputs;
        # churn + straggler chaos exercises the full registry bookkeeping
        kw = dict(population_size=40, cohort_size=8, cohort_overprovision=2,
                  straggler_prob=0.1, churn_leave_prob=0.02,
                  churn_join_prob=0.04)
        e1, e4 = self._pair(**kw)
        self._assert_fused_once(e4)
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")
        # registry bookkeeping committed at the block boundary must land
        # exactly where the per-iteration path put it
        for attr in ("active", "joined_round", "last_seen_round",
                     "last_sampled_round", "absent_streak", "reliability",
                     "cluster"):
            assert np.array_equal(getattr(e1.registry, attr),
                                  getattr(e4.registry, attr)), attr

    def test_population_resume_identical_cohorts(self, tmp_path):
        # a kill after the first fused block must resume onto the exact
        # cohort schedule the uninterrupted run draws
        import json, os
        kw = dict(population_size=40, cohort_size=8, cohort_overprovision=2,
                  straggler_prob=0.1, churn_leave_prob=0.02,
                  churn_join_prob=0.04, megastep_k=4,
                  checkpoint_every_iteration=True)

        def cohorts(d):
            out = {}
            with open(os.path.join(d, "events.jsonl")) as f:
                for line in f:
                    e = json.loads(line)
                    if e.get("kind") == "cohort_sampled":
                        out.setdefault(e["iteration"], e["members"])
            return out

        d_full = str(tmp_path / "full")
        e_full = Experiment(_cfg(**kw), out_dir=d_full)
        e_full.run()
        d_part = str(tmp_path / "part")
        e_part = Experiment(_cfg(**kw), out_dir=d_part)
        with e_part.logger, e_part.events:
            done = e_part.run_megastep(0, e_part._megastep_span(0))
        assert done == 4           # "killed" after the first block
        e_res = Experiment.resume(_cfg(**kw), d_part)
        assert e_res.start_iteration == 4
        e_res.run()
        assert cohorts(d_part) == cohorts(d_full)
        assert _leafdiff(e_full.pool.params, e_res.pool.params) == 0.0

    def test_hierarchy_e3_bitwise(self):
        e1, e4 = self._pair(hierarchy_edges=3,
                            edge_robust_agg="trimmed_mean")
        self._assert_fused_once(e4)
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")

    def test_byzantine_sign_flip_bitwise(self):
        e1, e4 = self._pair(byzantine_clients="0,3",
                            robust_agg="trimmed_mean")
        self._assert_fused_once(e4)
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")

    def test_byzantine_stale_replay_bitwise(self):
        # stale_replay threads a per-round submissions carry; the scan
        # re-seeds it per step exactly like the per-iteration reset
        e1, e4 = self._pair(byzantine_clients="0,3",
                            byzantine_mode="stale_replay",
                            robust_agg="trimmed_mean")
        self._assert_fused_once(e4)
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")

    def test_codec_int8_bitwise(self):
        e1, e4 = self._pair(compress_codec="int8")
        self._assert_fused_once(e4)
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")

    def test_codec_delta_bitwise_all_paths(self):
        # delta codec's carry re-seeds per scanned step; parity must hold
        # against BOTH K=1 drivers — the fused single-iteration program
        # and the per-round host loop
        kw = dict(compress_codec="delta")
        e1, e4 = self._pair(**kw)
        self._assert_fused_once(e4)
        er = run_experiment(_cfg(megastep_k=1, chunk_rounds=False, **kw))
        assert _leafdiff(e1.pool.params, e4.pool.params) == 0.0
        assert _leafdiff(er.pool.params, e4.pool.params) == 0.0
        assert e1.logger.series("Test/Acc") == e4.logger.series("Test/Acc")
        assert er.logger.series("Test/Acc") == e4.logger.series("Test/Acc")

    def test_single_compile_across_blocks(self):
        # 8 iterations at K=4 = two blocks; block 2's params are scan
        # outputs (committed NamedSharding) — the init-time pool placement
        # must make block 1 present the same signature, or every steady
        # block silently recompiles the whole program. (_cache_size is
        # per jit-wrapped function, shared by every TrainStep via the
        # static self argnum — so assert NO GROWTH past block 1, not an
        # absolute count.)
        exp = Experiment(_cfg(megastep_k=4))
        t = exp.run_megastep(0, exp._megastep_span(0))
        n0 = exp.step._train_megastep_jit._cache_size()
        while t < exp.cfg.train_iterations:
            t += exp.run_megastep(t, exp._megastep_span(t))
        assert exp.step._train_megastep_jit._cache_size() == n0


class TestMegastepGate:
    """_megastep_span: fuse only what the scan models, clamp at decision
    boundaries and the end of the run."""

    def test_span_and_tail_clamp(self):
        exp = Experiment(_cfg(megastep_k=4))
        assert exp._megastep_span(0) == 4
        assert exp._megastep_span(6) == 2      # train_iterations=8 tail
        assert exp._megastep_span(7) == 1

    def test_k1_and_unfusable_configs_stay_sequential(self):
        assert Experiment(_cfg(megastep_k=1))._megastep_span(0) == 1
        assert Experiment(
            _cfg(megastep_k=4, chunk_rounds=False))._megastep_span(0) == 1

    def test_feature_configs_fuse(self):
        # the per-feature capability table: codecs, Byzantine schedules,
        # hierarchy and population cohorts all ride the outer scan now
        assert Experiment(
            _cfg(megastep_k=4, compress_codec="topk"))._megastep_span(0) == 4
        assert Experiment(
            _cfg(megastep_k=4, compress_codec="delta"))._megastep_span(0) == 4
        assert Experiment(
            _cfg(megastep_k=4, byzantine_clients="0,3",
                 robust_agg="trimmed_mean"))._megastep_span(0) == 4
        assert Experiment(
            _cfg(megastep_k=4, hierarchy_edges=3))._megastep_span(0) == 4
        assert Experiment(
            _cfg(megastep_k=4, population_size=40, cohort_size=8,
                 cohort_overprovision=2))._megastep_span(0) == 4

    def test_gated_event_and_counter_name_the_reason(self):
        exp = Experiment(_cfg(megastep_k=4, chunk_rounds=False))
        assert exp._megastep_span(0) == 1
        gated = [e for e in exp.events.ring if e["kind"] == "megastep_gated"]
        assert gated and gated[-1]["reason"] == "chunk_rounds_off"
        assert gated[-1]["requested"] == 4 and gated[-1]["granted"] == 1

    def test_horizon_clamp_emits_algo_horizon(self):
        exp = Experiment(_cfg(
            megastep_k=4, concept_drift_algo="softcluster",
            concept_drift_algo_arg="H_A_C_1_10_0", concept_num=3,
            decision_cadence=3))
        assert exp._megastep_span(0) == 3
        gated = [e for e in exp.events.ring if e["kind"] == "megastep_gated"]
        assert gated and gated[-1]["reason"] == "algo_horizon"
        assert gated[-1]["granted"] == 3

    def test_k1_and_tail_clamp_stay_silent(self):
        # K=1 forfeits nothing (fusion never requested); the end-of-run
        # tail clamp is arithmetic, not a feature gate
        exp = Experiment(_cfg(megastep_k=1, chunk_rounds=False))
        assert exp._megastep_span(0) == 1
        exp2 = Experiment(_cfg(megastep_k=4))
        assert exp2._megastep_span(6) == 2
        for e in (exp, exp2):
            assert not [r for r in e.events.ring
                        if r["kind"] == "megastep_gated"]

    def test_horizon_window_stretches_full_tail(self):
        exp = Experiment(_cfg(megastep_k=4, concept_drift_algo="win-1"))
        assert exp.algo.megastep_horizon(0) == 8
        assert exp.algo.megastep_horizon(5) == 3

    def test_horizon_softcluster_cadence(self):
        exp = Experiment(_cfg(
            megastep_k=4, concept_drift_algo="softcluster",
            concept_drift_algo_arg="H_A_C_1_10_0", concept_num=3,
            decision_cadence=3))
        # step t may itself decide; the horizon reaches the NEXT boundary
        assert exp.algo.megastep_horizon(0) == 3
        assert exp.algo.megastep_horizon(1) == 2
        assert exp.algo.megastep_horizon(2) == 1
        assert exp.algo.megastep_horizon(3) == 3
        assert exp._megastep_span(0) == 3      # clamped below megastep_k
        assert exp._megastep_span(1) == 2

    def test_horizon_cadence_one_never_fuses(self):
        exp = Experiment(_cfg(
            megastep_k=4, concept_drift_algo="softcluster",
            concept_drift_algo_arg="H_A_C_1_10_0", concept_num=3))
        assert exp.algo.megastep_horizon(2) == 1
        assert exp._megastep_span(2) == 1

    def test_horizon_conservative_default(self):
        from feddrift_tpu.algorithms.base import DriftAlgorithm
        # the base contract: algorithms that don't certify decision-free
        # stretches inherit no fusion at all
        assert DriftAlgorithm.megastep_horizon.__get__(object())(5) == 1


class TestStoreAliasing:
    """The store of evaluated counts hands the SAME ndarrays to every
    consumer; the frozen-array + identity-key + rebind-invalidation trio
    keeps one consumer's mutation (or a dataset swap) from corrupting the
    rest."""

    @staticmethod
    def _counts(exp, hits):
        M, Cp = exp.pool.num_models, exp.C_pad
        return (np.full((M, Cp), hits, np.int32),
                np.zeros((M, Cp), np.float32), np.full((Cp,), 16, np.int32))

    def test_stored_counts_are_frozen(self):
        exp = Experiment(_cfg())
        exp.algo.store_acc_counts(exp.pool.params,
                                  {0: self._counts(exp, 8)})
        (correct, loss, total), = exp.algo.acc_counts_at([0])
        for arr in (correct, loss, total):
            assert not arr.flags.writeable
        with pytest.raises(ValueError):
            correct[0, 0] = 0
        # the ratio is the caller's own array, made anew from the counts
        got = exp.algo.acc_matrix_at(0)
        assert got.shape == (exp.pool.num_models, exp.algo.C)
        assert (got == 0.5).all() and got.flags.writeable

    def test_rebind_invalidates_the_store(self):
        exp = Experiment(_cfg())
        exp.algo.store_acc_counts(exp.pool.params,
                                  {0: self._counts(exp, 8)})
        exp.algo.rebind_data(exp.x, exp.y)
        assert exp.algo._acc_store is None

    def test_pool_mutation_misses_the_store(self):
        exp = Experiment(_cfg())
        exp.algo.store_acc_counts(exp.pool.params,
                                  {0: self._counts(exp, 0)})
        # any writeback rebinds pool.params to a new object: identity key
        exp.pool.params = jax.tree_util.tree_map(lambda l: l + 0,
                                                 exp.pool.params)
        fresh = exp.algo.acc_matrix_at(0)
        assert float(fresh.max()) > 0.0


class TestMegastepRegressAxis:
    def test_floor_zero_recompile_and_host_overhead_gates(self):
        from feddrift_tpu.obs.regress import compare
        base = {"megastep": [
            {"megastep_k": 1, "rounds_per_sec": 100.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.8},
            {"megastep_k": 4, "rounds_per_sec": 160.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.3}]}
        ok = compare({"megastep": [
            {"megastep_k": 1, "rounds_per_sec": 95.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.82},
            {"megastep_k": 4, "rounds_per_sec": 150.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.35}]}, base)
        ms = {r["metric"]: r for r in ok
              if r["metric"].startswith("megastep")}
        assert ms["megastep[4].rounds_per_s"]["status"] == "ok"
        assert ms["megastep[4].steady_recompiles"]["status"] == "ok"
        assert ms["megastep[4].host_overhead_frac"]["status"] == "ok"
        bad = compare({"megastep": [
            {"megastep_k": 1, "rounds_per_sec": 100.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.5},
            {"megastep_k": 4, "rounds_per_sec": 50.0,
             "steady_recompiles": 1, "host_overhead_frac": 0.6}]}, base)
        ms = {r["metric"]: r for r in bad
              if r["metric"].startswith("megastep")}
        assert ms["megastep[4].rounds_per_s"]["status"] == "regress"
        # absolute gates: any recompile, or K>1 overhead >= this run's K=1
        assert ms["megastep[4].steady_recompiles"]["status"] == "regress"
        assert ms["megastep[4].host_overhead_frac"]["status"] == "regress"

    def test_pop_hier_variant_keys_and_absolute_speedup_gate(self):
        from feddrift_tpu.obs.regress import compare
        # composed-variant rows get megastep[pop_hier:{k}] keys, their own
        # K=1 host-overhead reference, and an ABSOLUTE >= 2x speedup gate
        base = {"megastep": [
            {"variant": "pop_hier", "megastep_k": 1, "rounds_per_sec": 20.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.9,
             "speedup_vs_k1": 1.0},
            {"variant": "pop_hier", "megastep_k": 4, "rounds_per_sec": 50.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.4,
             "speedup_vs_k1": 2.5}]}
        ok = compare({"megastep": [
            {"variant": "pop_hier", "megastep_k": 1, "rounds_per_sec": 19.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.9,
             "speedup_vs_k1": 1.0},
            {"variant": "pop_hier", "megastep_k": 4, "rounds_per_sec": 48.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.45,
             "speedup_vs_k1": 2.53}]}, base)
        ms = {r["metric"]: r for r in ok
              if r["metric"].startswith("megastep")}
        assert ms["megastep[pop_hier:4].rounds_per_s"]["status"] == "ok"
        assert ms["megastep[pop_hier:4].speedup_vs_k1"]["status"] == "ok"
        assert ms["megastep[pop_hier:4].host_overhead_frac"]["status"] == "ok"
        bad = compare({"megastep": [
            {"variant": "pop_hier", "megastep_k": 1, "rounds_per_sec": 20.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.9,
             "speedup_vs_k1": 1.0},
            {"variant": "pop_hier", "megastep_k": 4, "rounds_per_sec": 36.0,
             "steady_recompiles": 0, "host_overhead_frac": 0.5,
             "speedup_vs_k1": 1.8}]}, base)
        ms = {r["metric"]: r for r in bad
              if r["metric"].startswith("megastep")}
        # absolute: below 2x fails even though the baseline's 2.5 would
        # tolerate it under a relative check
        assert ms["megastep[pop_hier:4].speedup_vs_k1"]["status"] == "regress"

    def test_variantless_baseline_is_dense_backcompat(self):
        from feddrift_tpu.obs.regress import compare
        # MEGASTEP_r10 rows carry no "variant": they must keep matching
        # bare-keyed dense candidate rows, and a pop_hier candidate row
        # must NOT silently match a dense baseline K point
        base = {"megastep": [
            {"megastep_k": 4, "rounds_per_sec": 160.0,
             "steady_recompiles": 0}]}
        rows = compare({"megastep": [
            {"variant": "dense", "megastep_k": 4, "rounds_per_sec": 155.0,
             "steady_recompiles": 0},
            {"variant": "pop_hier", "megastep_k": 4, "rounds_per_sec": 50.0,
             "steady_recompiles": 0, "speedup_vs_k1": 2.4}]}, base)
        ms = {r["metric"]: r for r in rows
              if r["metric"].startswith("megastep")}
        assert ms["megastep[4].rounds_per_s"]["status"] == "ok"
        assert ms["megastep[pop_hier:4]"]["status"] == "skip"

    def test_baseline_without_axis_skips(self):
        from feddrift_tpu.obs.regress import compare
        rows = compare({"value": 1.0}, {"value": 1.0, "megastep": [
            {"megastep_k": 1, "rounds_per_sec": 100.0}]})
        skips = [r for r in rows if r["metric"] == "megastep"]
        assert skips and skips[0]["status"] == "skip"
