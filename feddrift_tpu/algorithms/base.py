"""Drift-adaptation algorithm interface.

An algorithm owns the host-side state machine (the reference's pickled
SoftClusterState / DriftSurfState / AdaState / KueState / MultiModelAccState,
FedAvgEnsDataLoader.py) and steers the device program through four hooks:

- ``begin_iteration(t)``: start-of-time-step clustering / drift detection
  (reference: aggregator ctor ``init_sc_state`` and the *_data_loader
  functions, SURVEY.md §3.3-3.4). May mutate the model pool.
- ``round_inputs(t, r)``: the [M, C, T1] time-weight tensor plus per-sample
  weights / feature masks / LR scale consumed by ``TrainStep.train_round``.
- ``after_round(...)``: post-aggregation work — CFL split checks
  (AggregatorSoftCluster.py:140-146), IFCA hard-r re-clustering (:187-191),
  Ada per-round LR statistics. Returns the params the pool should adopt.
- ``end_iteration(t)``: state persistence / weight updates done near run end
  (e.g. AUE ensemble-weight update, sc_state pickling).

Evaluation routing mirrors ``test_on_all_clients``
(AggregatorSoftCluster.py:210-285): either a per-client model index or an
ensemble vote spec.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax.numpy as jnp
import numpy as np

from feddrift_tpu import obs
from feddrift_tpu.comm import multihost

_REGISTRY: dict[str, Callable[..., "DriftAlgorithm"]] = {}


def register_algorithm(*names: str):
    def deco(cls):
        for n in names:
            _REGISTRY[n] = cls
        return cls
    return deco


def available_algorithms() -> list[str]:
    return sorted(_REGISTRY)


def make_algorithm(cfg, ds, pool, step) -> "DriftAlgorithm":
    name = cfg.concept_drift_algo
    if name not in _REGISTRY:
        raise KeyError(f"unknown concept_drift_algo {name!r}; "
                       f"available: {available_algorithms()}")
    return _REGISTRY[name](cfg, ds, pool, step)


def algorithm_class(name: str) -> type:
    """Registered class without instantiation (the runner needs class-level
    traits like ``uses_sample_weights`` before the algorithm exists)."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown concept_drift_algo {name!r}; "
                       f"available: {available_algorithms()}")
    return _REGISTRY[name]


@dataclass
class EnsembleSpec:
    """Ensemble-vote evaluation (AUE hard vote / KUE soft vote)."""
    mode: str                      # 'hard' | 'soft'
    weights: np.ndarray            # [M] or [M, C]
    model_mask: Optional[np.ndarray] = None   # [M] 1=include


class DriftAlgorithm:
    name = "base"
    # Class trait: True if round_inputs returns non-unit per-sample weights
    # (KUE's Poisson bootstrap). Compiled statically into TrainStep — an
    # algorithm that sets sample_w without this trait would have it ignored.
    uses_sample_weights = False
    # True if after_round consumes the per-client [M, C, ...] parameter
    # output (CFL-family gradient clustering); everyone else lets the round
    # program drop that buffer (TrainStep.train_round keep_client_params).
    needs_client_params = False
    # True if the algorithm's training window is exactly the current time
    # step (time_w zero elsewhere) and it never reads the bound full dataset
    # (acc_matrix_at / acc_cells_upto) — the precondition for cfg.stream_data
    # host-streaming execution. Instance attribute where spec-dependent.
    supports_streaming = False
    # True if the algorithm can run cohort-sampled population rounds
    # (cfg.population_size > 0): its per-client state must be expressible
    # as (cluster assignment history, drift-detector arm) so the runner
    # can reload it from the ClientRegistry for whichever members are
    # sampled this iteration. Stateless algorithms get this for free via
    # the base load_cohort_state; instance attribute where kind-dependent.
    supports_cohort = False
    # The most models with weight in ``round_inputs``' time weights that any
    # client has, where the algorithm counted them on the host as it made
    # those weights: the round program then runs that many models a client
    # and not M (TrainStep.train_round ``models_per_client``). None says
    # nothing, and every (model, client) pair runs.
    models_per_client: int | None = None
    # The number of trailing time steps that can carry weight in
    # ``round_inputs``' time weights, where the algorithm's own definition
    # bounds it (a window of one step: 1): the round program is then handed
    # those steps of the data and not all T1 (`time_window`,
    # TrainStep.train_round ``time_window``). None: the whole axis.
    train_window: int | None = None

    def __init__(self, cfg, ds, pool, step) -> None:
        self.cfg = cfg
        self.ds = ds
        self.pool = pool
        self.step = step
        self.M = pool.num_models
        # Device-visible client-axis size: the cohort slots in population
        # mode (cfg.population_size > 0), every client in dense mode.
        self.C = cfg.device_clients
        self.T1 = ds.num_steps + 1
        self.N = ds.samples_per_step
        # default device-side constants
        self._ones_sample_w = jnp.ones((self.M, self.C, self.N), jnp.float32)
        self._ones_feat_mask = jnp.ones((self.M, *ds.feature_shape), jnp.float32) \
            if not ds.is_sequence else jnp.ones((self.M, 1), jnp.float32)
        # Per-client accuracy-entry ages (rounds since last observed
        # participation) + the failure detector's suspect set, pushed by
        # the runner before each begin_iteration. Drives stale_clients.
        self._client_ages = np.zeros(self.C, dtype=np.int64)
        self._suspected_clients: tuple[int, ...] = ()
        # Population mode: the member id behind each cohort slot this
        # iteration (None in legacy dense mode, where slot == client id),
        # and the slots with no member behind them (active pop < slots).
        self._cohort_members: np.ndarray | None = None
        self._invalid_slots: np.ndarray | None = None

    def time_window(self, t: int) -> tuple[int, int] | None:
        """``(lo, W)``: the W = ``train_window`` time steps that can carry
        weight at time step ``t``, the trailing ones, held inside the axis
        so that W is the same at every ``t``; None where the algorithm
        declares no window (or one as long as the axis)."""
        W = self.train_window
        if W is None or W >= self.T1:
            return None
        return min(max(t + 1 - W, 0), self.T1 - W), W

    def _check_time_window(self, t: int, weights: np.ndarray) -> None:
        """Raise where host weights ``[T1, ...]`` carry weight outside
        `time_window`: the round program would not see those steps."""
        window = self.time_window(t)
        if window is None:
            return
        lo, W = window
        weighted = (weights.reshape(self.T1, -1) != 0).any(axis=1)
        weighted[lo:lo + W] = False
        steps = np.nonzero(weighted)[0]
        if steps.size:
            raise ValueError(
                f"{type(self).__name__} declares train_window={W} (time "
                f"steps {lo}..{lo + W - 1} at t={t}) but gives weight to "
                f"time step(s) {steps.tolist()}")

    # -- runtime binding ------------------------------------------------
    def bind(self, x, y, logger, c_pad: int) -> None:
        """Called by the runner after construction: device-resident dataset
        (client axis padded to c_pad), and the metrics logger. Algorithms
        slice device results back to [:C] before host-side decisions."""
        self.x = x
        self.y = y
        self.logger = logger
        self.C_pad = c_pad
        # the store of evaluated counts is keyed on the pool object alone:
        # what it holds was counted on the data bound before
        self._acc_store = None

    def rebind_data(self, x, y) -> None:
        """Population mode: swap in this iteration's gathered cohort shard
        (same shapes as the previous one — XLA never recompiles). Clears
        the store of evaluated counts: under an unchanged pool a hit would
        serve the previous cohort's."""
        self.x = x
        self.y = y
        self._acc_store = None

    # -- cohort state bridge (population mode) --------------------------
    def load_cohort_state(self, t: int, members: np.ndarray,
                          assign_hist: np.ndarray, arm_acc: np.ndarray,
                          reserved_models=None) -> None:
        """Install the sampled members' per-client state for iteration t.

        ``members`` [C] ids (< 0 = phantom slot), ``assign_hist`` [C, T1]
        each member's own past cluster assignments (-1 = unknown: not
        sampled that step), ``arm_acc`` [C] drift-detector arms (NaN =
        never observed), ``reserved_models`` model ids some ACTIVE member
        outside the cohort is still registered to (slot allocators must
        not clobber them). The base implementation records the
        slot->member mapping — sufficient for algorithms without
        per-client state; stateful algorithms override AND call super()."""
        self._cohort_members = np.asarray(members, dtype=np.int64)
        self._invalid_slots = self._cohort_members < 0

    def save_cohort_state(self, t: int) -> None:
        """Hook before the runner's registry writeback: sync any
        slot-keyed internal state back to member-keyed storage."""

    def cohort_arm_acc(self, t: int) -> "np.ndarray | None":
        """[C] per-slot drift-detector arm accuracies to persist per
        member (None = algorithm has no drift detector)."""
        return None

    def set_client_staleness(self, ages, suspected=()) -> None:
        """Runner hook: per-client absence ages ([C] rounds since the last
        observed participation, ``FailureDetector.absent_streak``) and the
        detector's current suspect set. Read back through
        ``stale_clients`` by the clustering decision layers."""
        self._client_ages = np.asarray(ages, dtype=np.int64)[: self.C]
        self._suspected_clients = tuple(int(c) for c in suspected)

    @property
    def stale_clients(self) -> np.ndarray:
        """[C] bool — clients whose accuracy-matrix entries are too stale to
        drive clustering decisions: absent >= ``cfg.acc_staleness_limit``
        rounds or currently failure-suspected. All-False when the limit is
        0 (feature off — historical trusting behavior)."""
        out = np.zeros(self.C, dtype=bool)
        limit = getattr(self.cfg, "acc_staleness_limit", 0)
        if limit > 0:
            ages = np.zeros(self.C, dtype=np.int64)
            ages[: len(self._client_ages)] = self._client_ages[: self.C]
            out |= ages >= limit
            sus = [c for c in self._suspected_clients if c < self.C]
            out[sus] = True
        # Phantom cohort slots (population mode, active pop < slots) hold
        # copies of another member's data: never let them steer decisions.
        if self._invalid_slots is not None:
            out |= self._invalid_slots
        return out

    def _resident_data(self):
        if self.x is None:
            raise RuntimeError(
                "full-dataset eval is unavailable under cfg.stream_data")
        return self.x, self.y

    # -- the store of evaluated counts ----------------------------------
    # One (pool, time step) pair is evaluated once: the drift decision, the
    # per-round re-assignment and the runner's evaluation ask for the same
    # accuracy matrices (IFCA on the per-round path: 3 of a time step's 10).
    # The store sits above ``TrainStep.acc_matrix``, so whatever that method
    # returns, a test's patch included, is what every reader sees.
    def _acc_entries(self, params) -> dict:
        """{t: (correct, loss_sum, total)} as the store holds them for the
        pool object ``params``. The key is identity: JAX arrays are
        immutable and every writer of ``pool.params`` rebinds it (a round, a
        donated round too, ``set_slot``, a restore, a rollback), so the same
        object holds the same values, and a rollback to an object the store
        still knows finds its entries true. Entries of any other object are
        dropped first: the store never keeps a dead pool alive."""
        if self._acc_store is None or self._acc_store[0] is not params:
            self._acc_store = (params, {})
        return self._acc_store[1]

    def store_acc_counts(self, params, counts: "dict[int, tuple]") -> dict:
        """Keep ``{t: (correct [M, C_pad], loss_sum [M, C_pad], total
        [C_pad])}``, host arrays as ``step.acc_matrix(params, x[:, t],
        y[:, t], all-ones mask)`` gives them, under the pool object
        ``params``: the EVALUATED object (a fused program's output), not
        ``pool.params`` after an ``after_round`` that may have transformed
        it. The fused drivers hand in their final eval slot this way; the
        arrays are made read-only because a hit hands the same array to
        every consumer. Returns what it kept."""
        def frozen(arr):
            arr = np.asarray(arr)
            arr.setflags(write=False)
            return arr
        kept = {t: tuple(frozen(a) for a in triple)
                for t, triple in counts.items()}
        self._acc_entries(params).update(kept)
        return kept

    def acc_counts_at(self, ts, feat_mask=None) -> list:
        """For each time step of ``ts`` the triple (correct [M, C_pad],
        loss_sum [M, C_pad], total [C_pad]) of ``pool.params`` on that
        step's data, read-only host arrays. Under the plain mask (None, or
        the all-ones object ``round_inputs`` hands out) what the store holds
        for this pool object is served from it; the steps that miss are
        dispatched in order and fetched together, one host sync however
        many, and stored. Under a feature mask of the caller's nothing is
        served or stored. Counted per requested step in the counters
        ``acc_matrix_reused`` / ``acc_matrix_computed`` and as
        ``acc_reused`` / ``acc_computed`` on the span the caller has open
        (``drift_decision``, ``writeback``, ``eval``)."""
        x, y = self._resident_data()
        params = self.pool.params
        plain = feat_mask is None or feat_mask is self._ones_feat_mask
        held = self._acc_entries(params) if plain else {}
        found = {t: held[t] for t in ts if t in held}
        missing = [t for t in dict.fromkeys(ts) if t not in found]
        if missing:
            fm = self._ones_feat_mask if plain else feat_mask
            fetched = dict(zip(missing, multihost.fetch([
                self.step.acc_matrix(params, x[:, t], y[:, t], fm)
                for t in missing])))
            found.update(self.store_acc_counts(params, fetched) if plain
                         else fetched)
        reused, computed = len(ts) - len(missing), len(missing)
        reg = obs.registry()
        reg.counter("acc_matrix_reused").inc(reused)
        reg.counter("acc_matrix_computed").inc(computed)
        obs.spans.innermost().add(acc_reused=reused, acc_computed=computed)
        return [found[t] for t in ts]

    def acc_matrix_at(self, t: int, feat_mask=None) -> np.ndarray:
        """[M, C] accuracy of every model on every client's step-t data
        (reference train_acc_matrix, FedAvgEnsDataLoader.py:1074-1085)."""
        (correct, _, total), = self.acc_counts_at([t], feat_mask)
        return np.asarray(correct)[:, :self.C] / np.asarray(total)[None, :self.C]

    def acc_cells_upto(self, t: int, feat_mask=None) -> np.ndarray:
        """[M, C, t+1] correct counts per (model, client, step<=t).

        Evaluates the full [T1] axis (static shape -> one compile) and slices
        on host; the extra cells are cheap relative to a recompilation per t.
        """
        x, y = self._resident_data()
        fm = feat_mask if feat_mask is not None else self._ones_feat_mask
        correct = self.step.acc_cells(self.pool.params, x, y, fm)
        return np.asarray(multihost.fetch(correct))[:, :self.C, : t + 1]

    # -- hooks ----------------------------------------------------------
    def begin_iteration(self, t: int) -> None:
        raise NotImplementedError

    def round_inputs(self, t: int, r: int):
        """-> (time_w [M,C,T1] jnp, sample_w [M,C,N], feat_mask, lr_scale)."""
        raise NotImplementedError

    def after_round(self, t: int, r: int, prev_params, agg_params,
                    client_params, n) -> Any:
        """Return the params the pool adopts for the next round.

        In chunked execution (``chunkable``) this is only called at chunk
        boundaries with ``prev_params=None, client_params=None`` — an
        algorithm that needs either every round must keep chunkable False.
        """
        return agg_params

    def chunkable(self, t: int) -> bool:
        """True if rounds of time step t may run as one device program
        (TrainStep.train_iteration_eval): round_inputs must be round-invariant and
        after_round must not need per-round host work. Default conservative."""
        return False

    def megastep_horizon(self, t: int) -> int:
        """How many upcoming iterations starting AT ``t`` are
        drift-decision-free, i.e. fusable into one multi-iteration device
        program (TrainStep.train_megastep).

        The contract: for every step t+1 .. t+h-1 inside the returned
        horizon h, ``begin_iteration`` must not read any training result
        produced inside the block (accuracy matrices, losses, aggregated
        params) — its ``round_inputs`` must be computable host-side from t
        alone before the block dispatches. Step t itself MAY decide: its
        begin_iteration runs on pre-block state exactly as in sequential
        execution. Oblivious/window/recency stretches return the full
        remaining run; decision algorithms return the distance to their
        next cadence boundary; the conservative default is 1 (no fusion),
        which every algorithm that also keeps ``chunkable`` False should
        inherit."""
        return 1

    def end_iteration(self, t: int) -> None:
        pass

    # -- evaluation routing --------------------------------------------
    def test_model_idx(self, t: int) -> np.ndarray:
        """[C] model index per client for test-data eval."""
        return np.zeros((self.C,), dtype=np.int64)

    def train_model_idx(self, t: int) -> np.ndarray:
        """[C] model index per client for train-data eval. Defaults to the
        test index (SoftCluster/DriftSurf); AUE/KUE pin it to model 0 and
        mmgeniex trains/tests different models."""
        return self.test_model_idx(t)

    def ensemble_spec(self, t: int) -> Optional[EnsembleSpec]:
        return None

    # -- checkpointing --------------------------------------------------
    def state_dict(self) -> dict:
        return {}

    def load_state_dict(self, d: dict) -> None:
        pass

    # -- helpers --------------------------------------------------------
    def emit_assignment(self, t: int) -> None:
        """Emit the per-iteration ``cluster_assign`` event: the dense
        client -> model vector (the EM view's E-step state,
        arXiv:2111.10192) plus per-model client counts, and — when the
        dataset carries ground-truth concepts — the live oracle ARI /
        purity of this iteration's clustering (obs/lineage.py scores the
        whole timeline offline from these same events)."""
        assign = np.asarray(self.test_model_idx(t), dtype=np.int64)
        members = self._cohort_members
        scored = assign
        concepts = getattr(self.ds, "concepts", None)
        truth = None
        if members is not None:
            # population mode: slots are cohort positions; score valid
            # slots against THEIR members' ground-truth concepts and ship
            # the member ids so offline consumers can resolve the mapping
            valid = members >= 0
            scored = assign[valid]
            if concepts is not None and t < concepts.shape[0] and valid.any():
                truth = np.asarray(concepts)[t, members[valid]]
        elif concepts is not None and t < concepts.shape[0]:
            truth = np.asarray(concepts)[t, : self.C]
        counts = np.bincount(scored, minlength=self.M)
        fields: dict = {
            "assignment": assign.tolist(),
            "model_clients": {int(m): int(counts[m])
                              for m in np.nonzero(counts)[0]},
        }
        if members is not None:
            fields["members"] = members[members >= 0].tolist()
        if truth is not None and len(scored):
            fields["oracle_ari"] = round(
                obs.lineage.adjusted_rand_index(truth, scored), 4)
            fields["oracle_purity"] = round(
                obs.lineage.cluster_purity(truth, scored), 4)
        obs.emit("cluster_assign", **fields)

    def feature_mask_for(self, mask_flat: np.ndarray) -> jnp.ndarray:
        """Reshape [M, F_flat] masks to the dataset's feature shape (KUE
        reshapes masks to the sample shape, FedAvgEnsTrainerKue.py:68-71)."""
        if self.ds.is_sequence:
            return jnp.ones((self.M, 1), jnp.float32)
        return jnp.asarray(mask_flat, jnp.float32).reshape(
            (self.M, *self.ds.feature_shape))
