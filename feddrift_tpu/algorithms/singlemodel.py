"""Single-model continual baselines: oblivious windows and recency weighting.

Covers the reference's ``fedavg_cont_one`` pipeline (win-N / all / weight-*
via --retrain_data, fedml_experiments/distributed/fedavg_cont_one/) and the
``exp`` / ``lin`` recency-weighted trainers of the ensemble pipeline
(FedAvgEnsTrainerExp.py:66 weight 2^t, FedAvgEnsTrainerLin.py:66 weight t+1,
with the Vanilla single-model aggregator FedAvgEnsAggregatorVanilla.py:14).
"""

from __future__ import annotations

import jax.numpy as jnp

from feddrift_tpu.algorithms.base import DriftAlgorithm, register_algorithm
from feddrift_tpu.data.retrain import time_weights


@register_algorithm("win-1", "all", "oblivious", "window")
class WindowBaseline(DriftAlgorithm):
    """One model trained on a retrain-window of past steps. The window spec
    comes from cfg.retrain_data ('win-N', 'all', 'weight-exp', ...) as in the
    cont_one shell arg 19 (run_fedavg_distributed_pytorch.sh:21)."""

    name = "window"
    # Single shared model, no per-client state: the base cohort bridge
    # (slot->member mapping only) is sufficient for population mode, and
    # each sampled member trains on its OWN gathered past-step data.
    supports_cohort = True

    def __init__(self, cfg, ds, pool, step) -> None:
        super().__init__(cfg, ds, pool, step)
        spec = cfg.retrain_data
        if cfg.concept_drift_algo in ("win-1", "all"):
            spec = cfg.concept_drift_algo
        elif cfg.concept_drift_algo == "oblivious":
            # the paper's drift-oblivious baseline: ONE model on ALL data
            # (cont_one with retrain_data=all); without this it would fall
            # back to cfg.retrain_data's win-1 default and silently equal
            # the win-1 baseline
            spec = "all"
        self.spec = spec
        self._tw = None
        # win-1 trains on the current step only -> streamable, and the
        # round program needs that step's data alone
        self.supports_streaming = spec == "win-1"
        self.train_window = 1 if spec == "win-1" else None

    def begin_iteration(self, t: int) -> None:
        w = time_weights(self.spec, self.C, t, self.T1)      # [C, T1]
        self._check_time_window(t, w.T)
        self._tw = jnp.asarray(w[None], jnp.float32)          # [1, C, T1]

    def round_inputs(self, t: int, r: int):
        return self._tw, self._ones_sample_w, self._ones_feat_mask, jnp.float32(1.0)

    def chunkable(self, t: int) -> bool:
        return True

    def megastep_horizon(self, t: int) -> int:
        # No drift decisions ever: every remaining step's time weights are
        # a pure function of t, so the whole tail is fusable.
        return max(1, self.cfg.train_iterations - t)


@register_algorithm("exp", "lin")
class RecencyWeighted(DriftAlgorithm):
    """Exponential / linear recency sampling over all past steps
    (FedAvgEnsTrainer{Exp,Lin}.py:66)."""

    name = "recency"
    supports_cohort = True          # stateless per client, like window

    def begin_iteration(self, t: int) -> None:
        kind = "weight-exp" if self.cfg.concept_drift_algo == "exp" else "weight-linear"
        w = time_weights(kind, self.C, t, self.T1)
        self._tw = jnp.asarray(w[None], jnp.float32)

    def round_inputs(self, t: int, r: int):
        return self._tw, self._ones_sample_w, self._ones_feat_mask, jnp.float32(1.0)

    def chunkable(self, t: int) -> bool:
        return True

    def megastep_horizon(self, t: int) -> int:
        return max(1, self.cfg.train_iterations - t)
