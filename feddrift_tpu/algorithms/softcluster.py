"""The SoftCluster family: FedDrift, FedDrift-Eager, IFCA, CFL, GMM, softmax,
oracle — the reference's multi-model clustering heart.

Re-design of ``SoftClusterState`` + ``FedAvgEnsAggregatorSoftCluster``
(fedml_api/distributed/fedavg_ens/FedAvgEnsDataLoader.py:581-1341,
FedAvgEnsAggregatorSoftCluster.py). The time-indexed weight dict
``{t -> M x C}`` becomes a dense ``[T1, M, C]`` float tensor; device work
(accuracy matrices/cells) is batched XLA; the clustering decisions
(drift detection, LRU model pool, hierarchical merge, CFL bipartition) stay
host-side numpy/scipy on O(M^2) matrices — exactly the split SURVEY.md §7
prescribes.

Variant dispatch mirrors the reference (AggregatorSoftCluster.init_sc_state
:46-118 + SoftClusterState.cluster :640-658):

  cluster_alg 'H_*'     -> FedDrift hierarchical (cluster_hierarchical :840-978)
  'mmacc*'              -> FedDrift-Eager (cluster_mmacc2 :796-837)
  'hard' / 'hard-r'     -> IFCA; '-r' re-clusters every round (:187-191)
  'softmax_{alpha}'     -> softmax weights over accuracies (:680-682)
  'gmm'                 -> 2-component GaussianMixture (:782-794)
  'geni'                -> change-point oracle (:1141-1146)
  'cfl_{gamma}_{rt}'    -> clustered-FL gradient bipartition (:1159-1249)

concept_drift_algo variants: 'softclusterwin-1' zeroes weights of past steps
(:102-104, :1263-1265); 'softclusterreset' deletes non-competitive models
(:85-97).
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
import scipy.cluster.hierarchy as sch
from scipy.spatial.distance import squareform
from scipy.special import softmax as sp_softmax

from feddrift_tpu import obs
from feddrift_tpu.algorithms.base import DriftAlgorithm, register_algorithm
from feddrift_tpu.comm import multihost

log = logging.getLogger("feddrift_tpu.softcluster")


def live_models_per_client(weights: np.ndarray) -> int:
    """The most models any client trains under the [T1, M, C] weights: a
    (model, client) pair trains where its weights over time sum above 0
    (core/step.py::_local_sgd). 1 under a hard assignment with a window of
    one step, M under soft weights; at least 1, so that a round in which
    nobody trains still has a program to run."""
    return max(1, int((weights.sum(axis=0) > 0).sum(axis=0).max()))


@register_algorithm("softcluster", "softclusterwin-1", "softclusterreset")
class SoftCluster(DriftAlgorithm):
    name = "softcluster"

    def __init__(self, cfg, ds, pool, step) -> None:
        super().__init__(cfg, ds, pool, step)
        p = cfg.algo_params()
        self.kind = p["kind"]
        self.p = p
        # dense [T1, M, C] replaces the reference's {t -> M x C} dict (:589)
        self.weights = np.zeros((self.T1, self.M, self.C), dtype=np.float32)
        self.mmacc_acc = np.zeros(self.C)           # per-client last best acc
        self.mmacc_delta = p.get("mmacc_delta", p.get("h_delta", 0.1))
        # FedDrift hierarchical state (:598-606)
        self.h_delta = p.get("h_delta", 0.1)
        self.h_deltap = p.get("h_deltap", 0.1)
        self.h_w = p.get("h_w", 1)
        self.h_distance = p.get("h_distance", "A")
        self.h_cluster = p.get("h_cluster", "C")
        self.h_marked: dict[int, tuple[int, int]] = {}   # client -> (model, unmark t)
        self.h_next_free = 1
        # CFL state (:608-612)
        self.cfl_gamma = p.get("cfl_gamma", 0.1)
        self.cfl_retrain = p.get("cfl_retrain", "win-1")
        self.cfl_norm = 0.0
        self.cfl_eps1 = 0.0
        self.cfl_eps2 = 1e4
        # geni oracle: the dataset's own ground-truth concept matrix (already
        # time-stretch dilated), so the oracle can never diverge from the
        # generated drift — incl. change_points='rand'
        if self.kind == "geni":
            self.geni_concepts = ds.concepts[:, : self.C]
        self.rng = np.random.default_rng(cfg.seed + 1009)
        # Cumulative drift-machinery event counters. The scaling bench reads
        # these per iteration so throughput cliffs at particular client
        # counts can be attributed to actual spawn/merge activity (the
        # host-side work that fires data-dependently) instead of inferred
        # from phase timings alone (SCALING_r04 weak point).
        self.event_counts = {"spawns": 0, "merges": 0, "linkage_calls": 0}
        self._tw = None
        # win-1 zeroes every step before t (`begin_iteration`) and no step
        # after t is assigned yet: one time step carries weight, unless
        # CFL's retrain "all" copies a split back over the earlier steps
        if cfg.concept_drift_algo == "softclusterwin-1" and not (
                self.kind == "cfl" and self.cfl_retrain == "all"):
            self.train_window = 1
        # only the CFL variant reads per-client deltas in after_round
        self.needs_client_params = self.kind == "cfl"
        # Population mode (cfg.population_size > 0): the hard-assignment
        # variants can reload their per-client state from the registry's
        # (assignment history, detector arm) columns. Fractional-weight
        # variants (softmax, gmm) and CFL's per-round gradient clustering
        # cannot round-trip through an argmax writeback.
        self.supports_cohort = self.kind in (
            "hierarchical", "mmacc", "hard", "hard-r", "geni")
        # member-keyed isolation marks + pending registry remaps
        # (population mode only; see load/save_cohort_state)
        self._h_marked_members: dict[int, tuple[int, int]] = {}
        self._model_remaps: list[tuple[str, int, int]] = []
        self._reserved_models: set[int] = set()

    # ------------------------------------------------------------------
    # plumbing
    def _models_in_use_before(self, t: int, exclude_marked: bool = False) -> list[int]:
        """Models with any weight before step t (reference :686-690, :855-859).

        Population mode: the slot-local weight tensor only carries the
        sampled members' history, so models serving only UNSAMPLED members
        would look unused — union the registry-known reserved set (models
        any active member is registered to), excluding out-of-cohort
        isolation models like the in-cohort ones."""
        marked = {m for (m, _) in self.h_marked.values()} if exclude_marked else set()
        if exclude_marked and self._cohort_members is not None:
            marked |= {m for (m, _) in self._h_marked_members.values()}
        used = {m for m in range(self.M)
                if (self.weights[:t, m, :] > 0).any()}
        if self._cohort_members is not None and t > 0:
            used |= {m for m in self._reserved_models if 0 <= m < self.M}
            if not (used - marked):
                used.add(0)     # degenerate fresh population: model 0
        return [m for m in sorted(used) if m not in marked]

    def _sync_device_weights(self, t: int) -> None:
        self._check_time_window(t, self.weights)
        # [T1, M, C] -> [M, C, T1] for the train step
        self._tw = jnp.asarray(np.transpose(self.weights, (1, 2, 0)))
        self.models_per_client = live_models_per_client(self.weights)

    def round_inputs(self, t: int, r: int):
        return self._tw, self._ones_sample_w, self._ones_feat_mask, jnp.float32(1.0)

    def chunkable(self, t: int) -> bool:
        # cfl needs per-round split checks on client updates; hard-r
        # re-clusters every round (after_round above) — both steer per round
        return self.kind not in ("cfl", "hard-r")

    def _is_decision_step(self, t: int) -> bool:
        """Clustering/drift decisions run only at cadence boundaries
        (cfg.decision_cadence); off-boundary steps carry the previous
        assignment forward unchanged — the property ``megastep_horizon``
        certifies. Per-round deciders (cfl, hard-r) ignore the cadence:
        their decision lives in after_round, not here."""
        d = self.cfg.decision_cadence
        return (t == 0 or d <= 1 or t % d == 0
                or self.kind in ("cfl", "hard-r"))

    def megastep_horizon(self, t: int) -> int:
        d = self.cfg.decision_cadence
        if d <= 1 or not self.chunkable(t):
            return 1
        # Step t may itself decide (its begin_iteration runs on pre-block
        # state); only t+1 .. t+h-1 must be decision-free, so the horizon
        # reaches exactly to the next cadence boundary after t.
        return max(1, ((t // d) + 1) * d - t)

    def test_model_idx(self, t: int) -> np.ndarray:
        return np.argmax(self.weights[t], axis=0)        # (:1257-1258)

    # ------------------------------------------------------------------
    # life cycle
    def begin_iteration(self, t: int) -> None:
        if t == 0:
            self._cluster_init()
            if self.kind in ("hard", "hard-r"):
                # IFCA symmetry breaking: distinct random models at t=0
                # (AggregatorSoftCluster.py:64-71)
                for m in range(self.M):
                    self.pool.distinct_reinit_slot(m, seed=self.cfg.seed + 7700 + m)
                self._cluster(self.acc_matrix_at(0), 0, round_idx=0)
        elif not self._is_decision_step(t):
            # cadence carry-forward: the last decision's assignment extends
            # to this step's data — no accuracy matrix, no cluster pass, no
            # host<->device traffic, which is what lets the runner fuse
            # these steps into one megastep.
            self.weights[t] = self.weights[t - 1]
        else:
            if self.kind == "hierarchical":
                self._cluster_hierarchical(t)
            elif self.kind == "mmacc":
                self._cluster_mmacc2(t)
            elif self.kind == "cfl":
                self._cluster_cfl_init(t)
            elif self.kind in ("hard", "hard-r"):
                # reference 'hard' branch: cluster only, never combined with
                # the reset variant (AggregatorSoftCluster.py:64-71)
                self._cluster(self.acc_matrix_at(t), t, round_idx=0)
            else:
                # the reference's final else branch (:78-100): reset variant
                # applies only here
                if self.cfg.concept_drift_algo == "softclusterreset":
                    self._reset_noncompetitive(t)
                self._cluster(self.acc_matrix_at(t), t, round_idx=0)

        if self.cfg.concept_drift_algo == "softclusterwin-1":
            self.weights[:t] = 0.0                       # (:1263-1265)

        if t == 0:
            # arm the drift detector with initial accuracies (:106-116);
            # where IFCA asked for them above, the store has them
            acc = self.acc_matrix_at(0)
            idx = self.test_model_idx(0)
            for c in range(self.C):
                self.mmacc_acc[c] = acc[idx[c], c]
        self._log_models(t)
        if self.cfg.debug_checks and self.kind not in ("softmax", "gmm"):
            # hard-assignment variants: per-client weights at t must be a
            # one-hot partition (softmax/gmm produce fractional assignments
            # validated by their own normalization)
            from feddrift_tpu.utils.invariants import check_weight_partition
            check_weight_partition(self.weights, t)
        self._sync_device_weights(t)

    def after_round(self, t: int, r: int, prev_params, agg_params,
                    client_params, n):
        if self.kind == "cfl":
            did_split = self._cluster_cfl_round(t, r + 1, prev_params,
                                                client_params, n)
            if did_split:
                # skip this round's aggregation: local updates correspond to
                # an outdated model assignment (AggregatorSoftCluster.py:140-146)
                self._sync_device_weights(t)
                return self.pool.params
        self.pool.params = agg_params
        if self.kind == "hard-r":
            # re-cluster every round (:187-191)
            self._cluster(self.acc_matrix_at(t), t, round_idx=r + 1)
            self._sync_device_weights(t)
        return self.pool.params

    # ------------------------------------------------------------------
    # cohort state bridge (population mode)
    def load_cohort_state(self, t: int, members, assign_hist, arm_acc,
                          reserved_models=None) -> None:
        """Rebuild the slot-indexed state for this iteration's cohort from
        each member's OWN registry columns: past-step training weights
        from its assignment history (-1 = not sampled then = no weight —
        unknown is not evidence), the drift-detector arm from its last
        observed accuracy (NaN = unarmed: a trigger can never fire off a
        baseline nobody measured)."""
        super().load_cohort_state(t, members, assign_hist, arm_acc)
        hist = np.asarray(assign_hist)
        self.weights[:] = 0.0
        for tt in range(min(t, hist.shape[1])):
            known = np.where(hist[:, tt] >= 0)[0]
            self.weights[tt, hist[known, tt], known] = 1.0
        arm = np.asarray(arm_acc, dtype=np.float64)
        self.mmacc_acc = np.where(np.isnan(arm), -np.inf, arm)
        self._reserved_models = set(reserved_models or ())
        if self.kind == "geni":
            # oracle concepts re-sliced to the sampled members (phantom
            # slots borrow member 0's column; they are stale-masked anyway)
            m = np.where(self._cohort_members >= 0, self._cohort_members, 0)
            self.geni_concepts = self.ds.concepts[:, m]
        # isolation marks: member-keyed -> slot-keyed for this cohort;
        # marks whose unmark time has passed expire even if the member
        # was never resampled in between
        self._h_marked_members = {
            mem: mk for mem, mk in self._h_marked_members.items()
            if mk[1] > t}
        slot_of = {int(mem): s for s, mem in enumerate(self._cohort_members)
                   if mem >= 0}
        self.h_marked = {slot_of[mem]: mk
                         for mem, mk in self._h_marked_members.items()
                         if mem in slot_of}

    def save_cohort_state(self, t: int) -> None:
        """Sync slot-keyed isolation marks back to member-keyed storage
        (members outside this cohort keep theirs)."""
        if self._cohort_members is None:
            return
        sampled = {int(m) for m in self._cohort_members if m >= 0}
        keep = {mem: mk for mem, mk in self._h_marked_members.items()
                if mem not in sampled}
        for slot, mk in self.h_marked.items():
            mem = int(self._cohort_members[slot])
            if mem >= 0:
                keep[mem] = mk
        self._h_marked_members = keep

    def cohort_arm_acc(self, t: int) -> np.ndarray:
        """Persist the detector arm per member; -inf (never armed this
        life) round-trips as NaN = still unarmed."""
        return np.where(np.isfinite(self.mmacc_acc), self.mmacc_acc, np.nan)

    def drain_model_remaps(self) -> list[tuple[str, int, int]]:
        """Pool-structure changes (merges, slot reuse/deletes) recorded
        this iteration, for the runner to replay onto the registry so
        unsampled members' stored assignments follow their model."""
        out, self._model_remaps = self._model_remaps, []
        return out

    # ------------------------------------------------------------------
    # clustering variants
    def _cluster_init(self) -> None:
        """Everyone on model 0 — or one model per client for FedDrift-F
        (cluster_init, :616-638)."""
        self.weights[0] = 0.0
        if self.h_cluster == "F" and self.kind == "hierarchical":
            if self.M < self.C:
                raise ValueError(
                    f"h_cluster='F' needs concept_num >= clients ({self.M} < {self.C})")
            for c in range(self.C):
                self.weights[0, c, c] = 1.0
            self.h_next_free = self.C
        else:
            self.weights[0, 0, :] = 1.0

    def _cluster(self, acc: np.ndarray, t: int, round_idx: int) -> None:
        """Per-round-capable variants (SoftClusterState.cluster, :640-658)."""
        if self.kind in ("hard", "hard-r"):
            self.weights[t] = 0.0
            best = np.argmax(acc, axis=0)
            self.weights[t, best, np.arange(self.C)] = 1.0
        elif self.kind == "softmax":
            alpha = self.p.get("softmax_alpha", 0)
            self.weights[t] = sp_softmax(acc * (2**alpha), axis=0)
        elif self.kind == "gmm":
            self._cluster_gmm(acc, t)
        elif self.kind == "geni":
            if round_idx == 0:
                self.weights[t] = 0.0
                best = self.geni_concepts[t] % self.M
                self.weights[t, best, np.arange(self.C)] = 1.0
        else:
            raise NameError(self.kind)

    def _cluster_gmm(self, acc: np.ndarray, t: int) -> None:
        from sklearn.mixture import GaussianMixture       # (:782-794)
        self.weights[t] = 0.0
        gm = GaussianMixture(n_components=2, random_state=0).fit(acc.T)
        probs = gm.predict_proba(acc.T).T
        if gm.means_[0][0] > gm.means_[0][1]:
            self.weights[t, 0], self.weights[t, 1] = probs[0], probs[1]
        else:
            self.weights[t, 0], self.weights[t, 1] = probs[1], probs[0]

    # -- staleness-aware decision inputs --------------------------------
    def _carry_stale_assignments(self, t: int, stale: np.ndarray) -> None:
        """Stale clients keep their step-(t-1) cluster assignment instead of
        being re-assigned (and possibly spawning models) from an accuracy
        column no live client vouches for. Falls back to the fresh
        assignment when the previous model was merged/reset away."""
        for c in np.nonzero(stale)[0]:
            if t > 0 and (self.weights[t - 1, :, c] > 0).any():
                self.weights[t, :, c] = self.weights[t - 1, :, c]

    def _emit_stale_drift_exclusions(self, stale: np.ndarray, acc, best,
                                     delta: float) -> None:
        """acc_stale_excluded for the drift-trigger decision; ``changed``
        is True when an excluded client's stale accuracy WOULD have fired
        the trigger (i.e. the exclusion altered a create decision)."""
        idx = np.nonzero(stale)[0]
        if idx.size == 0:
            return
        changed = bool(any(
            self.mmacc_acc[c] - acc[best[c], c] > delta for c in idx))
        obs.emit("acc_stale_excluded", clients=idx.tolist(),
                 decision="drift_trigger", changed=changed)
        obs.registry().counter("acc_stale_exclusions").inc(int(idx.size))

    # -- FedDrift-Eager -------------------------------------------------
    def _cluster_mmacc2(self, t: int) -> None:
        """Drift detect + at most one new model per step, no merge
        (cluster_mmacc2, :796-837)."""
        acc = self.acc_matrix_at(t)
        in_use = self._models_in_use_before(t)
        stale = self.stale_clients
        self.weights[t] = 0.0
        best_rows = np.argmax(acc[in_use], axis=0)
        best = np.asarray(in_use)[best_rows]
        self.weights[t, best, np.arange(self.C)] = 1.0
        self._carry_stale_assignments(t, stale)
        self._emit_stale_drift_exclusions(stale, acc, best, self.mmacc_delta)

        next_free = -42
        for c in range(self.C):
            if stale[c]:        # absent too long: no trigger, keep detector
                continue        # armed at its last live accuracy
            newest_acc = acc[best[c], c]
            if self.mmacc_acc[c] - newest_acc > self.mmacc_delta:
                obs.emit("drift_detected", client=c,
                         acc_drop=round(float(self.mmacc_acc[c] - newest_acc), 4),
                         threshold=self.mmacc_delta,
                         best_model=int(best[c]))
                if next_free == -42:
                    next_free = self._find_unused_model_lru(
                        t, original_model=best[c], client=c)
                if next_free != -1:
                    self.event_counts["spawns"] += 1
                    self.weights[t, :, c] = 0.0
                    self.weights[t, next_free, c] = 1.0
            self.mmacc_acc[c] = newest_acc

    # -- FedDrift (hierarchical) ---------------------------------------
    def _cluster_hierarchical(self, t: int) -> None:
        """The FedDrift algorithm (cluster_hierarchical, :840-978)."""
        # FedDrift-C: keep only one of the models created last step (:842-849)
        if self.h_cluster == "E":
            marked_models = [m for (m, _) in self.h_marked.values()]
            if marked_models:
                keep = self.rng.choice(marked_models)
                for mm in marked_models:
                    if mm != keep:
                        self.pool.reinit_slot(mm)
                        self.weights[:, mm, :] = 0.0
                        if self._cohort_members is not None:
                            self._model_remaps.append(("clear", mm, -1))
                        obs.emit("cluster_delete", model=int(mm),
                                 reason="feddrift_c_keep_one")

        # clients leave isolation (:852, :1038-1046)
        self.h_marked = {c: (m, tt) for c, (m, tt) in self.h_marked.items()
                         if tt != t}

        in_use = self._models_in_use_before(t, exclude_marked=True)
        acc = self.acc_matrix_at(t)                       # device: [M, C]
        stale = self.stale_clients

        self.weights[t] = 0.0
        for c, (m, _) in self.h_marked.items():           # marked stay local (:868)
            self.weights[t, m, c] = 1.0

        # everyone else on their best in-use model (:872-876); stale clients
        # then keep their previous assignment instead of chasing a dead
        # column (the fresh best remains as fallback when that model is gone)
        for c in range(self.C):
            if c not in self.h_marked:
                best = in_use[int(np.argmax(acc[in_use, c]))]
                self.weights[t, best, c] = 1.0
        self._carry_stale_assignments(t, stale)
        hbest = np.asarray([in_use[int(np.argmax(acc[in_use, c]))]
                            for c in range(self.C)])
        self._emit_stale_drift_exclusions(stale, acc, hbest, self.h_delta)

        # drift detection -> isolate on a fresh model (:879-897)
        for c in range(self.C):
            if c in self.h_marked or stale[c]:
                continue
            best = in_use[int(np.argmax(acc[in_use, c]))]
            newest_acc = acc[best, c]
            if self.mmacc_acc[c] - newest_acc > self.h_delta:
                obs.emit("drift_detected", client=c,
                         acc_drop=round(float(self.mmacc_acc[c] - newest_acc), 4),
                         threshold=self.h_delta,
                         best_model=int(best))
                next_free = self._find_unused_model_lru(
                    t, original_model=best, client=c)
                if next_free != -1:
                    self.event_counts["spawns"] += 1
                    self.h_marked[c] = (next_free, t + self.h_w)
                    self.weights[t, :, c] = 0.0
                    self.weights[t, next_free, c] = 1.0
            self.mmacc_acc[c] = newest_acc

        if len(in_use) > 1:
            self._hierarchical_merge(t, in_use, stale)

    def _hierarchical_merge(self, t: int, in_use: list[int],
                            stale: np.ndarray | None = None) -> None:
        """Cluster-accuracy matrix -> distance -> linkage -> merge
        (:899-972). The M x M accuracies come from full per-cell correct
        counts (one XLA call) instead of the reference's 20-batch subsample.

        ``stale`` [C] bool excludes those clients' accuracy cells from the
        cluster-distance matrix: a client absent past the staleness limit
        contributes no evidence for (or against) merging."""
        cells = self.acc_cells_upto(t)                    # [M, C, t+1] correct
        w = np.transpose(self.weights[: t + 1], (1, 2, 0))  # [M, C, t+1]
        assigned = (w == 1.0).astype(np.float64)
        if stale is not None and stale.any():
            excluded_cells = float(assigned[:, stale, :].sum())
            assigned[:, stale, :] = 0.0
            obs.emit("acc_stale_excluded",
                     clients=np.nonzero(stale)[0].tolist(),
                     decision="merge_matrix", changed=excluded_cells > 0)
            obs.registry().counter("acc_stale_exclusions").inc(
                int(stale.sum()))
        k = len(in_use)
        cluster_acc = np.zeros((k, k))
        for j_pos, j in enumerate(in_use):
            vol = assigned[j].sum() * self.N * self.ds.labels_per_sample
            if vol == 0:
                continue
            for i_pos, i in enumerate(in_use):
                cluster_acc[i_pos, j_pos] = (cells[i] * assigned[j]).sum() / vol

        dist = np.zeros((k, k))
        for i in range(k):
            for j in range(k):
                if self.h_distance == "A":                # (:937-940)
                    dist[i, j] = max(cluster_acc[i, i] - cluster_acc[i, j],
                                     cluster_acc[j, j] - cluster_acc[j, i], 0.0)
                elif self.h_distance == "B":              # (:941-944)
                    dist[i, j] = max(cluster_acc[i, i] - cluster_acc[j, i],
                                     cluster_acc[j, j] - cluster_acc[i, j], 0.0)
        np.fill_diagonal(dist, 0.0)

        method = "average" if self.h_cluster == "D" else "complete"  # (:947-950)
        self.event_counts["linkage_calls"] += 1
        Z = sch.linkage(squareform(dist, checks=False), method=method)
        T = sch.fcluster(Z, t=self.h_deltap, criterion="distance")

        clusters: dict[int, list[int]] = {}
        for pos, cid in enumerate(T):
            clusters.setdefault(cid, []).append(in_use[pos])

        merged_log = []
        for group in clusters.values():
            if len(group) > 1:
                merged_log.append("(" + ", ".join(str(m) for m in group) + ")")
            base = group[0]
            base_pos = in_use.index(base)
            for second in group[1:]:
                # The decision's evidence rides on the event: the winning
                # pairwise distance (vs. the merge threshold Δ') and the
                # merged model's full distance row over every in-use model,
                # so a lineage replay can show WHY this pair merged and how
                # close the runners-up were.
                second_pos = in_use.index(second)
                self._merge(t, base, second, evidence={
                    "distance": round(float(dist[base_pos, second_pos]), 4),
                    "threshold": self.h_deltap,
                    "in_use": [int(m) for m in in_use],
                    "distance_row": [round(float(d), 4)
                                     for d in dist[second_pos]],
                })
        if merged_log and self.logger:
            self.logger.set_summary("Merge", ", ".join(merged_log))

    def _merge(self, t: int, base: int, second: int,
               evidence: dict | None = None) -> None:
        """Weighted param average + weight union (merge, :1048-1072)."""
        self.event_counts["merges"] += 1
        if self._cohort_members is not None:
            self._model_remaps.append(("merge", base, second))
        obs.emit("cluster_merge", base=int(base), merged=int(second),
                 **(evidence or {}))
        w1 = float(self.weights[: t + 1, base, :].sum())
        w2 = float(self.weights[: t + 1, second, :].sum())
        s = w1 + w2
        self.pool.merge_slots(base, second, w1 / s, w2 / s)
        self.weights[: t + 1, base, :] += self.weights[: t + 1, second, :]
        self.weights[:, second, :] = 0.0

    def _find_unused_model_lru(self, t: int, original_model: int,
                               client: int | None = None) -> int:
        """LRU slot allocation (find_unused_model_lru, :1011-1036).

        ``client`` is the drift-trigger client — recorded on the
        cluster_create event so the lineage layer can attribute each
        spawned model to the client set that demanded it."""
        if self.h_next_free < self.M:
            nxt = self.h_next_free
            self.h_next_free += 1
        else:
            last_used = -1 * np.ones(self.M)
            for tt in range(t + 1):
                for m in range(self.M):
                    if (self.weights[tt, m] > 0).any():
                        last_used[m] = tt
            # Population mode: a model can look LRU-free here only because
            # its clients were not sampled this iteration — protect any
            # model some active member is still registered to.
            for m in self._reserved_models:
                last_used[m] = max(last_used[m], t - 1)
            lru = np.where(last_used == last_used.min())[0]
            nxt = int(self.rng.choice(lru))
            if last_used[nxt] == t:
                return -1
            self.weights[:, nxt, :] = 0.0
            if self._cohort_members is not None:
                self._model_remaps.append(("clear", nxt, -1))
        # initialise from the drifted client's previous model (:1031-1033)
        self.pool.copy_slot(nxt, original_model)
        obs.emit("cluster_create", model=int(nxt),
                 init_from=int(original_model),
                 client=None if client is None else int(client))
        return nxt

    # -- softclusterreset ----------------------------------------------
    def _reset_noncompetitive(self, t: int) -> None:
        """Delete models not epsilon-better than the rest
        (AggregatorSoftCluster.py:85-97)."""
        acc = self.acc_matrix_at(t)
        deleted: list[int] = []
        for m in reversed(range(self.M)):
            rest = np.delete(acc, deleted + [m], axis=0)
            if rest.shape[0] > 0 and (acc[m] < np.max(rest, axis=0) + 0.01).all():
                deleted.append(m)
                if self.logger:
                    self.logger.set_summary(f"Reset-{m}", 1)
                self.weights[:, m, :] = 0.0
                self.pool.reinit_slot(m)
                obs.emit("cluster_delete", model=int(m),
                         reason="noncompetitive_reset")

    # -- CFL ------------------------------------------------------------
    def _cluster_cfl_init(self, t: int) -> None:
        """Copy assignment forward at step start (cluster_cfl_init, :1150-1157)."""
        self.weights[t] = self.weights[t - 1].copy()
        if self.cfl_retrain == "win-1":
            self.weights[:t] = 0.0

    def _cluster_cfl_round(self, t: int, round_idx: int, prev_params,
                           client_params, n) -> bool:
        """Gradient-norm gated bipartition (cluster_cfl, :1159-1223)."""
        did_split = False
        in_use = [m for m in range(self.M) if (self.weights[t, m] > 0).any()]

        # flatten per-client updates: [C_pad, P] per model
        def flat_updates(m):
            rows = []
            for cp_leaf, pv_leaf in zip(jax.tree_util.tree_leaves(client_params),
                                        jax.tree_util.tree_leaves(prev_params)):
                delta = cp_leaf[m] - pv_leaf[m][None]      # [C_pad, ...]
                rows.append(delta.reshape(delta.shape[0], -1))
            return jnp.concatenate(rows, axis=1)

        # ONE fetch for n + every model's update matrix: on DCN links the
        # per-collective round-trip dominates, so batch them.
        n_np, updates = multihost.fetch(
            (n, {m: flat_updates(m) for m in in_use}))
        n_np = np.asarray(n_np)[:, :self.C]

        for m in in_use:
            clients = np.nonzero(self.weights[t, m])[0]
            participating = [c for c in clients if n_np[m, c] > 0]
            if not participating:
                continue
            dW = np.asarray(updates[m])[participating]
            norms = np.linalg.norm(dW, axis=1)
            max_norm = float(norms.max())
            mean_norm = float(np.linalg.norm(dW.mean(axis=0)))

            if mean_norm > self.cfl_norm:                     # (:1191-1194)
                self.cfl_norm = mean_norm
                self.cfl_eps1 = self.cfl_norm / 10.0
                self.cfl_eps2 = 6 * self.cfl_eps1
            elif mean_norm < self.cfl_eps1 and max_norm > self.cfl_eps2:
                S = (dW @ dW.T) / (np.outer(norms, norms) + 1e-12)
                cl1, cl2 = self._bipartition(S)
                alpha_cross = max(S[i, j] for i in cl1 for j in cl2)
                if ((1 - alpha_cross) / 2.0) ** 0.5 > self.cfl_gamma:
                    nxt = self._find_unused_model_capped()
                    if nxt != -1:
                        did_split = True
                        self.pool.reinit_slot(m)              # (:1205)
                        self.weights[t, m, :] = 0.0
                        for i in cl1:
                            self.weights[t, m, participating[i]] = 1.0
                        for i in cl2:
                            self.weights[t, nxt, participating[i]] = 1.0
                        obs.emit(
                            "cluster_split", model=int(m), new_model=int(nxt),
                            clients_kept=[int(participating[i]) for i in cl1],
                            clients_moved=[int(participating[i]) for i in cl2],
                            alpha_cross=round(float(alpha_cross), 4),
                            gamma=self.cfl_gamma,
                            mean_norm=round(mean_norm, 6),
                            max_norm=round(max_norm, 6))

        if did_split and self.cfl_retrain == "all":           # (:1219-1221)
            for tt in range(t):
                self.weights[tt] = self.weights[t].copy()
        return did_split

    def _find_unused_model_capped(self) -> int:
        """Give up when the pool cap is reached (:982-987)."""
        if self.h_next_free < self.M:
            nxt = self.h_next_free
            self.h_next_free += 1
            return nxt
        return -1

    @staticmethod
    def _bipartition(S: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Complete-linkage bipartition on similarity (cfl_util_bipartition,
        :1245-1249). d = 1 - S is a strictly monotone transform of the
        reference's -S, and complete linkage is invariant under monotone
        distance transforms, so the 2-way cut is identical."""
        # clip: float error can push a cosine similarity past 1.0, which
        # would hand scipy a negative distance
        d = 1.0 - np.clip(S, -1.0, 1.0)
        np.fill_diagonal(d, 0.0)
        d = (d + d.T) / 2.0     # numerical symmetry for squareform
        Z = sch.linkage(squareform(d, checks=False), method="complete")
        labels = sch.fcluster(Z, t=2, criterion="maxclust")
        cl1 = np.where(labels == labels[0])[0]
        cl2 = np.where(labels != labels[0])[0]
        return cl1, cl2

    # ------------------------------------------------------------------
    # logging (log_models, :723-764)
    def _log_models(self, t: int) -> None:
        if not getattr(self, "logger", None):
            return
        if self.h_cluster == "E":
            num_models = len(self._models_in_use_before(t))
            if self.h_marked:
                num_models += 1
        else:
            num_models = sum(1 for m in range(self.M)
                             if (self.weights[: t + 1, m, :] > 0).any())
        self.logger.set_summary("num_models", num_models)
        # The paper's key hidden state, now first-class telemetry: one
        # cluster_state event per iteration plus a live gauge, and the
        # dense assignment vector (cluster_assign) with live oracle
        # ARI/purity when ground truth exists.
        assign = self.test_model_idx(t)
        counts = np.bincount(assign, minlength=self.M)
        obs.registry().gauge("num_models").set(num_models)
        obs.emit("cluster_state", num_models=int(num_models),
                 spawns=self.event_counts["spawns"],
                 merges=self.event_counts["merges"],
                 model_clients={int(m): int(counts[m])
                                for m in np.nonzero(counts)[0]})
        self.emit_assignment(t)

        trained_by = {m: set(np.nonzero(self.weights[: t + 1, m, :].sum(0))[0])
                      for m in range(self.M)}
        local_models = sum(1 for m, cs in trained_by.items() if len(cs) == 1)
        self.logger.set_summary("local_models", local_models)
        shared = {m: cs for m, cs in trained_by.items() if len(cs) > 1}
        for c in range(self.C):
            self.logger.set_summary(
                f"Contribute/CL-{c}",
                sum(1 for cs in shared.values() if c in cs))

    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        return {
            "weights": self.weights,
            "mmacc_acc": self.mmacc_acc,
            "h_marked": dict(self.h_marked),
            "h_marked_members": dict(self._h_marked_members),
            "h_next_free": self.h_next_free,
            "cfl_norm": self.cfl_norm,
            "cfl_eps1": self.cfl_eps1,
            "cfl_eps2": self.cfl_eps2,
            # rng state so a resumed run replays the same stochastic slot
            # choices (LRU ties, FedDrift-C keep-one) as a continuous one
            "rng_state": self.rng.bit_generator.state,
        }

    def load_state_dict(self, d: dict) -> None:
        self.weights = np.asarray(d["weights"], dtype=np.float32)
        self.mmacc_acc = np.asarray(d["mmacc_acc"])
        self.h_marked = {int(k): tuple(v) for k, v in d["h_marked"].items()}
        self._h_marked_members = {
            int(k): tuple(v)
            for k, v in d.get("h_marked_members", {}).items()}
        self.h_next_free = int(d["h_next_free"])
        self.cfl_norm = float(d["cfl_norm"])
        self.cfl_eps1 = float(d["cfl_eps1"])
        self.cfl_eps2 = float(d["cfl_eps2"])
        if "rng_state" in d:
            self.rng.bit_generator.state = d["rng_state"]
