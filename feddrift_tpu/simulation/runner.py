"""The experiment driver: the whole time x round loop in one process.

Replaces the reference's shell loop that re-executes an MPI job per time step
(run_fedavg_distributed_pytorch.sh:49-84, forced by MPI_Abort termination)
and its server/client manager message loop (SURVEY.md §3.1-3.2). State that
the reference persists in CWD files between processes (model_params.pt,
sc_state.pkl, ...) simply lives in memory here; checkpoints are optional
rather than load-bearing.

Round structure parity:
  for t in time steps:                  # one reference mpirun
      algo.begin_iteration(t)           # clustering / drift detection
      reset per-(m, c) optimizer states # fresh client processes
      for r in rounds:                  # comm_round
          train_round (vmap M x C local SGD -> masked weighted FedAvg)
          algo.after_round              # CFL split / hard-r / Ada LR
          eval every frequency_of_the_test rounds + last round
      algo.end_iteration(t)
"""

from __future__ import annotations

import contextlib
import logging
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from feddrift_tpu import obs
from feddrift_tpu.algorithms import algorithm_class, make_algorithm
from feddrift_tpu.comm import multihost
from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.core.pool import ModelPool
from feddrift_tpu.core.precision import resolve_precision
from feddrift_tpu.core.step import StackOperands, TrainStep, make_optimizer
from feddrift_tpu.data.registry import make_dataset
from feddrift_tpu.models import create_model
from feddrift_tpu.parallel.mesh import (
    make_mesh,
    replicate,
    shard_client_arrays,
)
from feddrift_tpu.utils.metrics import MetricsLogger
from feddrift_tpu.utils.prng import experiment_key, iteration_key, round_key
from feddrift_tpu.utils.tracing import PhaseTracer

log = logging.getLogger("feddrift_tpu")


def _sample_input(ds) -> jnp.ndarray:
    x0 = ds.x[0, 0, :2]
    return jnp.asarray(x0)


@partial(jax.jit, static_argnums=(1,))
def _unstack_steps(ps, K: int):
    """All K per-step param slices of the megastep's stacked [K, M, ...]
    output in ONE device program. The replay loop used to gather each
    step's params eagerly (K x leaves dispatches per block); slicing is
    value-identical either way, and the jitted outputs keep the stacked
    tree's committed sharding, so the next block's input signature is
    unchanged (steady_recompiles stays 0 — bench-gated)."""
    return tuple(jax.tree_util.tree_map(lambda l, _k=k: l[_k], ps)
                 for k in range(K))


class Experiment:
    """Holds the compiled programs + state for one configured run."""

    def __init__(self, cfg: ExperimentConfig, mesh=None,
                 use_wandb: bool = False, out_dir: Optional[str] = None) -> None:
        self.cfg = cfg
        self.ds = make_dataset(cfg)
        self.module = create_model(cfg.model, self.ds, cfg)
        # cfg.mesh_shape (e.g. {"models": 2, "clients": 4}) selects the 2-D
        # layout; empty dict = legacy 1-D clients mesh over all devices.
        self.mesh = mesh if mesh is not None \
            else make_mesh(shape=cfg.mesh_shape or None)
        # End-to-end precision policy (core/precision.py): resolved ONCE
        # here — "auto" reproduces the legacy dtype/compute_dtype behavior
        # (bf16 apply boundary on TPU only), explicit presets apply on any
        # backend. The pool is created AT param_dtype, so a bf16 policy is
        # bf16 from the first stored leaf (optimizer moments follow).
        self.precision = resolve_precision(cfg)
        self.pool = ModelPool.create(self.module, _sample_input(self.ds),
                                     cfg.num_models, seed=cfg.seed + 42,
                                     param_dtype=self.precision.param_dtype)
        # Commit the pool to the mesh (replicated) up front: every jitted
        # step consumes COMMITTED x/y (shard_client_arrays), so its param
        # outputs come back committed to a NamedSharding — if the t=0
        # params were left uncommitted, t=1 would present a new sharding
        # signature and silently recompile the whole iteration program.
        self.pool.params = replicate(self.mesh, self.pool.params)
        from feddrift_tpu.resilience.robust_agg import RobustAggConfig
        self.step = TrainStep(
            apply_fn=self._make_apply(),
            optimizer=make_optimizer(cfg.client_optimizer, cfg.lr, cfg.wd),
            batch_size=cfg.batch_size,
            num_steps=cfg.epochs,
            num_classes=self.ds.num_classes,
            # Static: algorithms declare the Poisson-bootstrap trait
            # (Kue.uses_sample_weights); everyone else skips the expensive
            # flattened-categorical batch draw entirely.
            weighted_sampling=algorithm_class(
                cfg.concept_drift_algo).uses_sample_weights,
            # Static: the per-cluster aggregation strategy closing every
            # round (resilience/robust_agg.py; "mean" = historical FedAvg).
            robust_agg=cfg.robust_agg,
            robust_cfg=RobustAggConfig(
                trim_frac=cfg.robust_trim_frac, krum_f=cfg.robust_krum_f,
                clip_norm=cfg.robust_clip_norm,
                dp_stddev=cfg.robust_dp_stddev),
            byz_scale=cfg.byzantine_scale,
            byz_std=cfg.byzantine_std,
            # Static: two-tier hierarchical aggregation + in-program wire
            # codec simulation (platform/hierarchical.py, comm/compress.py).
            hier_edges=cfg.hierarchy_edges,
            edge_agg=cfg.edge_robust_agg,
            server_agg=cfg.server_robust_agg,
            codec=cfg.compress_codec,
            codec_topk_frac=cfg.compress_topk_frac,
            # Static: the resolved precision policy — drives the in-program
            # aggregation boundary (agg_dtype) and eval-buffer dtypes.
            precision=self.precision,
            # Static: XLA cost-capture level (obs/costmodel.py) — each
            # tracked program's first compile also harvests cost_analysis
            # (and memory_analysis under "compiled") into program_cost
            # events + gauges.
            cost_capture=cfg.cost_model,
            # Static: vmap over all (model, client) pairs, or the pairs one
            # at a time (core/step.py::_round_body_scan); the forward that
            # also returns the model's own counts, where it gives any
            client_axis=cfg.client_axis,
            stats_fn=self._make_apply(stats=True),
            # The megastep program annotates its [M, C, ...] stacks with
            # with_sharding_constraint over this mesh (no-op on 1-D/1-device
            # meshes — parallel/mesh.py::constrain_pool).
            mesh=self.mesh,
        )
        # Device-resident dataset, client axis sharded over the mesh. The
        # client axis is padded to a multiple of the mesh size with phantom
        # clients whose time weights stay zero — they train masked and
        # contribute n=0 to aggregation, so results are identical.
        # Population mode flips the residency story: the dataset covers the
        # whole registered population HOST-side, and only the sampled
        # cohort's shard is staged into the fixed-shape [C_pad, T1, N, ...]
        # device stacks each iteration (_prepare_cohort) — XLA program
        # shapes depend on the cohort, never on the population.
        self.population_mode = cfg.population_size > 0
        # Pad the client axis to the CLIENTS mesh-axis size: on a 2-D
        # (models, clients) mesh only the clients dimension shards data.
        n_dev = dict(self.mesh.shape).get("clients", self.mesh.devices.size)
        C = cfg.device_clients
        self.C_pad = ((C + n_dev - 1) // n_dev) * n_dev
        pad = self.C_pad - C
        x_np, y_np = self.ds.x, self.ds.y
        if self.population_mode:
            self._x_pop, self._y_pop = x_np, y_np
            self.x = self.y = None
        elif cfg.stream_data:
            if pad:
                x_np = np.concatenate([x_np, np.repeat(x_np[:1], pad, 0)],
                                      axis=0)
                y_np = np.concatenate([y_np, np.repeat(y_np[:1], pad, 0)],
                                      axis=0)
            # host-resident: only a [C, 2, N, ...] window (current + next
            # step) is staged into HBM per iteration (data/prefetch.py)
            self._x_host, self._y_host = x_np, y_np
            self.x = self.y = None
            self._view_iter = None
            self._view_next_t = -1
        else:
            if pad:
                x_np = np.concatenate([x_np, np.repeat(x_np[:1], pad, 0)],
                                      axis=0)
                y_np = np.concatenate([y_np, np.repeat(y_np[:1], pad, 0)],
                                      axis=0)
            self.x = shard_client_arrays(self.mesh, jnp.asarray(x_np))
            self.y = shard_client_arrays(self.mesh, jnp.asarray(y_np))
        self.algo = make_algorithm(cfg, self.ds, self.pool, self.step)
        if self.population_mode and not getattr(self.algo, "supports_cohort",
                                                False):
            raise ValueError(
                f"population_size > 0 needs a cohort-capable algorithm "
                f"(per-client state expressible as registry columns); "
                f"{cfg.concept_drift_algo!r}/{cfg.concept_drift_algo_arg!r} "
                f"is not")
        if cfg.stream_data and not self.algo.supports_streaming:
            raise ValueError(
                f"stream_data requires a current-step-window algorithm "
                f"(supports_streaming); {cfg.concept_drift_algo!r} trains on "
                f"past steps or reads the full dataset")
        # Multi-controller runs: every process computes metrics (host logic
        # must stay in lockstep) but only the coordinator touches disk/wandb.
        self.is_coordinator = multihost.is_coordinator()
        self.logger = MetricsLogger(out_dir if self.is_coordinator else None,
                                    use_wandb and self.is_coordinator)
        # Structured event bus: events.jsonl next to metrics.jsonl. The bus
        # is process-local so comm-broker threads and the fault injector
        # reach it without a handle on this object.
        import os
        obs_cap = int(cfg.obs_max_file_mb * (1 << 20))   # 0 = unbounded
        self.events = obs.configure(
            os.path.join(out_dir, "events.jsonl")
            if (out_dir and self.is_coordinator) else None,
            max_bytes=obs_cap)
        # Span recorder: wall-clock intervals (phases, iterations, comm
        # publishes) next to the event stream; `report <run_dir> --trace`
        # folds both into one Perfetto-loadable trace.json. Every process
        # records (pid = its lane in the merged timeline); only the
        # coordinator gets a file sink, like the event bus.
        # Every span also enters a TraceAnnotation of its name, so a
        # profiler capture (xla_trace, the benchmark's) shows the runner's
        # segments in its host plane; obs/spans.py itself stays JAX-free.
        self.spans = obs.spans.configure(
            os.path.join(out_dir, "spans.jsonl")
            if (out_dir and self.is_coordinator) else None,
            pid=jax.process_index(), max_bytes=obs_cap,
            annotate=jax.profiler.TraceAnnotation)
        # Host-plane observatory (obs/hostprof.py): the per-subsystem
        # host-seconds/bytes ledger finalized at each iteration tail, and
        # the optional sampling stack profiler (cfg.hostprof_hz > 0) whose
        # slices land in hostprof.jsonl (merged into report --trace) and
        # whose folded stacks are written at run() exit. configure_profiler
        # stops any sampler left by a previous Experiment in this process.
        self._ledger = obs.hostprof.ledger()
        self._ledger.reset()
        self.hostprof = obs.hostprof.configure_profiler(
            cfg.hostprof_hz,
            path=os.path.join(out_dir, "hostprof.jsonl")
            if (out_dir and self.is_coordinator) else None,
            pid=jax.process_index())
        # Live health monitor (obs/alerts.py): a bus tap evaluating the
        # declarative rule set over every emitted event; fired alerts are
        # re-emitted as alert_raised AND appended to alerts.jsonl so a
        # crashed run keeps its alert trail.
        self.alerts = None
        if cfg.alerts:
            self.alerts = obs.alerts.AlertMonitor(
                rules=obs.alerts.default_rules(
                    churn_threshold=cfg.alert_churn_threshold,
                    churn_window=cfg.alert_window),
                path=os.path.join(out_dir, "alerts.jsonl")
                if (out_dir and self.is_coordinator) else None,
                max_bytes=obs_cap,
            ).attach(self.events)
        # Live ops plane (obs/live.py): SLO burn-rate engine on the event
        # tap, plus the /metrics + /healthz + /status HTTP server when
        # cfg.ops_port enables it (0 = fully off: no tap, no thread, no
        # per-iteration work beyond the two sketch observes that also
        # feed bench p99 fields).
        self.slo = self.ops = None
        self._ops_active = cfg.ops_port != 0
        slo_thresholds = dict(
            rounds_per_s=cfg.slo_rounds_per_s,
            host_overhead=cfg.slo_host_overhead,
            p99_round_wall_s=cfg.slo_p99_round_wall_s,
            eval_gap=cfg.slo_eval_gap,
            model_accuracy=cfg.slo_model_accuracy)
        if self._ops_active or any(v > 0 for v in slo_thresholds.values()):
            self.slo = obs.live.SLOEngine(
                objectives=obs.live.default_slos(**slo_thresholds),
                path=os.path.join(out_dir, "alerts.jsonl")
                if (out_dir and self.is_coordinator) else None,
                max_bytes=obs_cap,
            ).attach(self.events)
        if self._ops_active:
            obs.live.status_board().reset()
            obs.live.StatusTap().attach(self.events)
            self.ops = obs.live.OpsServer(
                port=max(cfg.ops_port, 0),   # -1 -> ephemeral bind
                slo=self.slo).start()
        # Incident plane (obs/blackbox.py, obs/incident.py): always-on
        # flight recorder over the event stream + debounced bundle
        # capture on the trigger set (crit alerts, SLO burns, replica
        # deaths, secure degradation, preemption, divergence aborts via
        # run()'s exception guard). Every process records; only the
        # coordinator writes bundles, like every other sink here.
        self.flight = self.incidents = None
        if cfg.incident_capture:
            self.flight = obs.blackbox.configure(
                capacity=cfg.incident_ring).attach(self.events)
            self.incidents = obs.incident.IncidentManager(
                run_dir=out_dir if (out_dir and self.is_coordinator)
                else None,
                recorder=self.flight,
                debounce_s=cfg.incident_debounce_s,
                max_bundles=cfg.incident_max_bundles,
                config_json=cfg.to_json(),
                ckpt_path=os.path.join(out_dir, "ckpt") if out_dir
                else None,
            ).attach(self.events)
        self.algo.bind(self.x, self.y, self.logger, self.C_pad)
        # Population-scale participation (platform/registry.py,
        # resilience/participation.py): host-side registry of every
        # registered client, a seeded per-iteration cohort sampler, and a
        # deadline+quorum closing rule; straggler/churn injectors are the
        # chaos for this layer. cfg forbids the dense-pool fault/byzantine
        # injectors here — their client indices mean device slots.
        self.registry = self.sampler = None
        self.straggler = self.churn = self.participation = None
        self._cohort_members = None
        self._slot_valid = None
        self._stager = None
        if self.population_mode:
            from feddrift_tpu.platform.faults import (ChurnSchedule,
                                                      StragglerInjector)
            from feddrift_tpu.platform.registry import (ClientRegistry,
                                                        CohortSampler)
            from feddrift_tpu.resilience.participation import \
                ParticipationPolicy
            P = cfg.population_size
            self.registry = ClientRegistry(P, num_steps=self.ds.num_steps + 1)
            self.sampler = CohortSampler(self.registry, cfg.cohort_slots,
                                         seed=cfg.cohort_seed)
            if cfg.straggler_prob > 0 or cfg.straggler_slow_frac > 0:
                self.straggler = StragglerInjector(
                    P, cfg.straggler_prob, cfg.straggler_slow_frac,
                    deadline=cfg.round_deadline, seed=cfg.straggler_seed)
            if cfg.churn_leave_prob > 0 or cfg.churn_join_prob > 0:
                self.churn = ChurnSchedule(P, cfg.churn_leave_prob,
                                           cfg.churn_join_prob,
                                           seed=cfg.churn_seed)
            self.participation = ParticipationPolicy(
                cfg.round_deadline, cfg.quorum_frac,
                cfg.cohort_size or cfg.client_num_in_total)
            self._slot_valid = np.ones(self.C_pad, dtype=bool)
            self._slot_valid[self.C_:] = False
            # Pipelined cohort staging: iteration t's tail kicks off the
            # t+1 gather + device_put on a background thread so the next
            # _prepare_cohort finds its shard already staged
            # (data/prefetch.py::AsyncStager; bitwise-identical — only the
            # copy timing moves). Megastep blocks keep up to K gathers in
            # flight (each plan step submits the next step's shard), hence
            # the K-deep pipeline.
            from feddrift_tpu.data.prefetch import AsyncStager
            self._stager = AsyncStager(depth=max(1, cfg.megastep_k))
        from feddrift_tpu.platform.faults import (ByzantineInjector,
                                                  FailureDetector,
                                                  FaultInjector)
        self.fault_injector = (
            FaultInjector(self.C_, cfg.fault_dropout_prob, cfg.fault_seed)
            if (cfg.fault_dropout_prob > 0 or cfg.fault_enabled) else None)
        self.failure_detector = (
            FailureDetector(self.C_, cfg.failure_patience)
            if self.fault_injector is not None else None)
        byz_clients = cfg.byzantine_client_list
        self.byzantine = (
            ByzantineInjector(self.C_, byz_clients, mode=cfg.byzantine_mode,
                              prob=cfg.byzantine_prob,
                              seed=cfg.byzantine_seed)
            if byz_clients else None)
        # Two-tier hierarchy (platform/hierarchical.py): a host-side edge
        # map over the padded client axis, an edge-level fault injector
        # (crash/stall/corrupt + scheduled kill), and the same deadline +
        # quorum closing rule as population rounds applied at edge
        # granularity.
        self.hierarchy = cfg.hierarchy_edges > 0
        self.edge_map = self.edge_fault = self.edge_participation = None
        if self.hierarchy:
            from feddrift_tpu.platform.faults import EdgeFaultInjector
            from feddrift_tpu.platform.hierarchical import EdgeMap
            from feddrift_tpu.resilience.participation import \
                ParticipationPolicy
            E = cfg.hierarchy_edges
            self.edge_map = EdgeMap(self.C_pad, E, assign=cfg.hierarchy_assign)
            if (cfg.edge_crash_prob > 0 or cfg.edge_stall_prob > 0
                    or cfg.edge_corrupt_prob > 0 or cfg.edge_kill_round >= 0):
                self.edge_fault = EdgeFaultInjector(
                    E, cfg.edge_crash_prob, cfg.edge_stall_prob,
                    cfg.edge_corrupt_prob, deadline=cfg.round_deadline,
                    seed=cfg.edge_fault_seed)
                self.edge_participation = ParticipationPolicy(
                    cfg.round_deadline, cfg.edge_quorum_frac, E)
        # Secure aggregation (resilience/secure_round.py): the cohort's
        # clients double as share-holders; the per-round path recomputes
        # the flat weighted mean through the masked protocol and a
        # degraded round keeps prev params (config validation pins the
        # flat mean/megastep_k=1 path this substitution is exact for).
        self.secure_driver = None
        if cfg.secure_agg != "off":
            from feddrift_tpu.resilience.secure_round import \
                SecureRoundDriver
            self.secure_driver = SecureRoundDriver(
                cfg.secure_agg, num_clients=self.C_,
                threshold=cfg.secure_threshold_t,
                scale_bits=cfg.secure_scale_bits,
                seed=cfg.secure_fault_seed, deadline=cfg.round_deadline,
                drop_prob=cfg.secure_drop_prob,
                delay_prob=cfg.secure_delay_prob,
                corrupt_prob=cfg.secure_corrupt_prob,
                holder_stall_prob=cfg.secure_holder_stall_prob,
                group_size=cfg.secure_group_size or None,
                strict=cfg.sanitize)
        # robust_agg_applied events only when a defense is actually on —
        # plain "mean" runs keep their historical event stream.
        self._robust_active = (
            cfg.robust_agg != "mean" or cfg.robust_dp_stddev > 0
            or (self.hierarchy and (cfg.edge_robust_agg != "mean"
                                    or cfg.server_robust_agg != "mean")))
        self._byz_stale = None   # last round's client submissions (stale_replay)
        self._codec_prev = None  # delta codec: last round's decoded diffs
        self.key = experiment_key(cfg.seed)
        self.global_round = 0
        self.start_iteration = 0
        self.out_dir = out_dir
        self.preempted = False
        from feddrift_tpu.resilience.divergence import DivergenceGuard
        self.divergence_guard = (
            DivergenceGuard(spike_factor=cfg.divergence_spike_factor,
                            max_rollbacks=cfg.divergence_max_rollbacks,
                            warmup=cfg.divergence_warmup_rounds)
            if cfg.divergence_guard else None)
        self.tracer = PhaseTracer(registry=obs.registry(), spans=self.spans)
        # Round-breakdown accounting: a segment is the summed SELF time of
        # the cat="round" spans of that name, recorded where the work
        # happens (here, core/step.py's dispatch wrapper, multihost.fetch);
        # whatever no span claims is the dispatch gap — unclaimed host
        # time. Finalized into one round_breakdown event +
        # host_overhead_frac gauge per iteration.
        self._segs: dict[str, float] = {}
        self._seg_owner: "str | None" = None
        self.last_round_breakdown: "dict | None" = None
        # The ground-truth concept matrix rides along in run_start for
        # synthetic datasets: obs/lineage.py scores the recorded
        # cluster_assign timeline against it (oracle ARI/purity) without
        # re-materializing the dataset. Size-gated so a thousand-client
        # scaling run does not bloat its first event line.
        concepts = getattr(self.ds, "concepts", None)
        # In population mode the first C_ concept columns are NOT the
        # cohort slots' clients (slots are re-sampled per iteration), so
        # no dense concept matrix is recorded; the per-iteration
        # cluster_assign events carry the member ids + live oracle scores.
        concept_matrix = (concepts[:, : self.C_].tolist()
                          if concepts is not None and not self.population_mode
                          and concepts[:, : self.C_].size <= 20000 else None)
        device = obs.costmodel.device_info()
        self.events.emit(
            "run_start", dataset=cfg.dataset, model=cfg.model,
            algo=cfg.concept_drift_algo, algo_arg=cfg.concept_drift_algo_arg,
            clients=self.C_, num_models=self.pool.num_models,
            comm_round=cfg.comm_round, train_iterations=cfg.train_iterations,
            backend=device["platform"], device_kind=device["device_kind"],
            device_count=device["device_count"], mesh=dict(self.mesh.shape),
            # the policy as RESOLVED on this backend: "auto" computes at
            # cfg.compute_dtype on a TPU and at cfg.dtype elsewhere, so the
            # same command is a different program per platform
            precision=self.precision.name,
            compute_dtype=self.precision.compute_dtype,
            param_dtype=self.precision.param_dtype,
            # which attention path "auto" became here (None: the model has
            # no attention); see models/transformer.py
            attention_impl=self._attention_impl(),
            # which round body runs the (model, client) pairs, and the
            # routed experts this process holds of each expert layer
            # ([first, end) of the router's outputs; None: no expert layer)
            client_axis=cfg.client_axis,
            experts_held=(list(self.module.experts_held)
                          if hasattr(self.module, "experts_held") else None),
            seed=cfg.seed, concept_matrix=concept_matrix,
            population=cfg.population_size or None)
        if cfg.debug_checks:
            from feddrift_tpu.utils.invariants import enable_nan_debugging
            enable_nan_debugging()
        self.sanitizer = None
        if cfg.sanitize:
            from feddrift_tpu.analysis.sanitize import Sanitizer
            self.sanitizer = Sanitizer(cfg, bus=self.events)

    def _attention_impl(self) -> Optional[str]:
        impl = getattr(self.module, "attention_impl", None)
        if impl is None:
            return None
        from feddrift_tpu.models.transformer import resolve_attention_impl
        return resolve_attention_impl(impl)

    def _make_apply(self, stats: bool = False):
        """Forward fn honoring the resolved precision policy.

        ``stats=True`` is the forward that also returns the module's own
        counts of the call (``return_stats``; models/mla_moe.py), cast at
        the same boundary, or None for a module that counts nothing.

        When the policy's compute dtype differs from the stored leaves,
        params and float inputs are cast at the call boundary so
        matmuls/convs run at compute_dtype (the MXU rate lever on TPU),
        and logits are cast back to f32 for the loss — gradients arrive
        through the cast ops at the PARAM dtype, the standard mixed
        recipe. When param == compute == float32 (the f32 policy, and
        "auto" off-TPU) the forward is the bare module apply, bit-for-bit
        the historical program. Explicit bf16 presets run on every
        backend; CPUs emulate bf16 slowly — a documented caveat
        (docs/PERFORMANCE.md), not a hard-coded gate.

        cfg.remat additionally wraps the forward in jax.checkpoint so
        activations are rematerialized in the backward pass — trades FLOPs
        for HBM, which is what lets deep models (resnet56/110, densenet)
        keep the [M, C] pool axes resident on one chip.
        """
        module = self.module
        pol = self.precision
        if stats and not getattr(module, "returns_stats", False):
            return None
        kw = {"return_stats": True} if stats else {}
        if pol.param_dtype == "float32" and pol.compute_dtype == "float32":
            def apply_fn(p, x):
                return module.apply({"params": p}, x, **kw)
        else:
            compute_dt = pol.compute_jnp

            def apply_fn(p, x):
                pc = jax.tree_util.tree_map(
                    lambda l: l.astype(compute_dt)
                    if jnp.issubdtype(l.dtype, jnp.floating)
                    and l.dtype != compute_dt else l, p)
                if jnp.issubdtype(x.dtype, jnp.floating) \
                        and x.dtype != compute_dt:
                    x = x.astype(compute_dt)
                out = module.apply({"params": pc}, x, **kw)
                if stats:
                    return out[0].astype(jnp.float32), out[1]
                return out.astype(jnp.float32)
        # a module that remats its own blocks (``remat_blocks``) is not
        # wrapped again: a checkpoint around checkpoints runs the forward
        # a third time
        if self.cfg.remat and not getattr(module, "remat_blocks", False):
            apply_fn = jax.checkpoint(apply_fn)
        return apply_fn

    # ------------------------------------------------------------------
    def evaluate(self, t: int, round_idx: int) -> dict:
        """Reference ``test_on_all_clients`` (AggregatorSoftCluster.py:210-285):
        per-client train acc on step t with that client's plurality model, and
        test acc on step t+1 data (temporal holdout); AUE/KUE use ensemble
        votes instead (FedAvgEnsAggregatorAue.py:256-283, Kue:234-262).

        The train half on step t and the test half on step t+1 (not asked
        for where an ensemble votes on the test data) come in one fetch,
        through the algorithm's store of evaluated counts: under its plain
        mask a per-round re-assignment has evaluated this pool on step t
        already.
        """
        C = self.C_
        fm = self.algo.round_inputs(t, round_idx)[2]
        spec = self.algo.ensemble_spec(t)
        fetched = self.algo.acc_counts_at(
            [t, t + 1] if spec is None else [t], fm)
        correct, loss_sum, total = fetched[0]
        if spec is None:
            corr_te, loss_te, _ = fetched[1]
        correct = correct[:, :C]
        loss_sum = loss_sum[:, :C]
        total = total[:C]

        if spec is None:
            return self._log_eval(t, correct, loss_sum,
                                  corr_te[:, :C], loss_te[:, :C], total)

        tidx = self.algo.train_model_idx(t)                    # [C]
        idx = self.algo.test_model_idx(t)                      # [C]
        cr = np.arange(self.C_)
        train_correct = correct[tidx, cr]
        train_loss = loss_sum[tidx, cr]

        ew = jnp.asarray(spec.weights, jnp.float32)
        if ew.ndim == 2:      # per-client weights (AUE-PC): pad phantom clients
            ew = self._pad_clients(ew)
        ec, et, el = self.step.ensemble_eval(
            self.pool.params, self.x[:, t + 1], self.y[:, t + 1], ew,
            spec.mode,
            None if spec.model_mask is None
            else jnp.asarray(spec.model_mask, jnp.float32),
            fm)
        ec, et, el = multihost.fetch((ec, et, el))
        return self._log_metrics(t, idx, train_correct, train_loss, total,
                                 ec[:C], el[:C], et[:C])

    def _log_eval(self, t: int, correct, loss_sum, corr_te, loss_te,
                  total) -> dict:
        """Log one eval point from host-side [M, C]/[C] numpy matrices
        (the non-ensemble test path shared by every execution mode)."""
        tidx = self.algo.train_model_idx(t)                    # [C]
        idx = self.algo.test_model_idx(t)                      # [C]
        cr = np.arange(self.C_)
        return self._log_metrics(t, idx, correct[tidx, cr], loss_sum[tidx, cr],
                                 total, corr_te[idx, cr], loss_te[idx, cr],
                                 total)

    def _log_metrics(self, t: int, idx, train_correct, train_loss, total,
                     tcorrect, tloss, ttotal) -> dict:
        """Assemble + log the reference's metric schema from per-client
        vectors (Train/Test Acc+Loss, per-client series, Plurality).

        Population mode: phantom cohort slots (no member behind them) hold
        copies of another member's data and are masked out of every
        aggregate — the reported numbers are cohort metrics, a sampled
        estimate of the population's."""
        v = getattr(self, "_slot_valid", None)
        if v is not None and not v[: self.C_].all():
            vv = v[: self.C_]
            train_correct = np.where(vv, train_correct, 0)
            train_loss = np.where(vv, train_loss, 0.0)
            tcorrect = np.where(vv, tcorrect, 0)
            tloss = np.where(vv, tloss, 0.0)
            total = np.where(vv, np.asarray(total), 0)
            ttotal = np.where(vv, np.asarray(ttotal), 0)
        tot = max(float(np.asarray(total).sum()), 1.0)
        ttot = max(float(np.asarray(ttotal).sum()), 1.0)
        metrics = {
            "round": self.global_round,
            "iteration": t,
            "Train/Acc": float(train_correct.sum() / tot),
            "Train/Loss": float(train_loss.sum() / tot),
            "Test/Acc": float(tcorrect.sum() / ttot),
            "Test/Loss": float(tloss.sum() / ttot),
        }
        if self.cfg.report_client:
            for c in range(self.C_):
                if v is not None and not v[c]:
                    continue        # phantom slot: no client behind it
                metrics[f"Train/Acc-CL-{c}"] = float(train_correct[c] / total[c])
                metrics[f"Test/Acc-CL-{c}"] = float(tcorrect[c] / ttotal[c])
                metrics[f"Plurality/CL-{c}"] = int(idx[c])
        self.logger.log(metrics)
        self.events.emit("eval", round=self.global_round,
                         test_acc=metrics["Test/Acc"],
                         train_acc=metrics["Train/Acc"],
                         test_loss=metrics["Test/Loss"])
        return metrics

    @property
    def C_(self) -> int:
        """Device-visible client-axis size: the sampled cohort in
        population mode, every client in legacy dense mode."""
        return self.cfg.device_clients

    def _pad_clients(self, arr: jnp.ndarray, axis: int = 1,
                     value: float = 0.0) -> jnp.ndarray:
        """Pad a client-indexed array up to C_pad along ``axis``."""
        pad = self.C_pad - arr.shape[axis]
        if pad == 0:
            return arr
        widths = [(0, 0)] * arr.ndim
        widths[axis] = (0, pad)
        return jnp.pad(arr, widths, constant_values=value)

    # ------------------------------------------------------------------
    # population mode: cohort lifecycle (one cohort per iteration — the
    # boundary where data windows and optimizer states change anyway)
    def _cohort_gather_index(self, members: np.ndarray) -> np.ndarray:
        """[C_pad] population row per cohort slot: phantom slots (inactive
        population shortfall + mesh padding) borrow member 0's rows — they
        train masked, are stale-excluded from decisions and metrics-masked."""
        valid = members >= 0
        idx = np.zeros(self.C_pad, dtype=np.int64)
        idx[: self.C_] = np.where(valid, members, 0)
        return idx

    def _stage_cohort(self, t: int) -> None:
        """Kick off iteration t's cohort staging at the END of iteration
        t-1: the registry mutation (churn) and the seeded cohort draw run
        on the MAIN thread — after iteration t-1's checkpoint is on disk,
        so a resume replays them identically — and only the pure
        [C_pad, T1, N, ...] gather + device_put goes to the stager thread,
        overlapping the host-side iteration tail. Bitwise-identical to
        inline staging; only the copy timing moves off the measured
        cohort_prep/h2d path."""
        if t >= self.cfg.train_iterations or self._stager is None:
            return
        # Defer the churn/draw events to consumption time (_prepare_cohort):
        # staged-but-never-consumed draws (a kill between staging and the
        # next iteration) must leave no trace in events.jsonl, or the
        # resumed run — which re-draws identically from the checkpointed
        # registry — would duplicate them.
        plan0 = time.perf_counter()
        with obs.capture() as deferred:
            if self.churn is not None:
                joins, leaves = self.churn.events(t, self.registry.active)
                self.registry.apply_churn(joins, leaves, t)
            members = self.sampler.sample(t)
        idx = self._cohort_gather_index(members)
        self._ledger.add_seconds("cohort_plan", time.perf_counter() - plan0)

        def gather():
            return (shard_client_arrays(self.mesh,
                                        jnp.asarray(self._x_pop[idx])),
                    shard_client_arrays(self.mesh,
                                        jnp.asarray(self._y_pop[idx])))
        self._stager.submit(t, gather, meta=(members, deferred))

    def _prepare_cohort(self, t: int) -> None:
        """Churn the registry, draw the seeded cohort, stage its shard
        into the fixed-shape device stacks, and reload the algorithm's
        per-slot state from the members' registry columns. Consumes the
        background-staged shard when iteration t-1 pre-staged it
        (_stage_cohort); falls back to inline staging otherwise (first
        iteration, resume)."""
        cfg = self.cfg
        staged = self._stager.take(t) if self._stager is not None else None
        if staged is None:
            if self.churn is not None:
                joins, leaves = self.churn.events(t, self.registry.active)
                self.registry.apply_churn(joins, leaves, t)
            members = self.sampler.sample(t)
        else:
            members, deferred = staged.meta
            # replay the draw's deferred events under THIS iteration's
            # context — the stream is then byte-identical to inline staging
            for kind, fields in deferred:
                self.events.emit(kind, **fields)
        self._cohort_members = members
        valid = members >= 0
        self._slot_valid = np.zeros(self.C_pad, dtype=bool)
        self._slot_valid[: self.C_] = valid
        with self._seg("h2d", iteration=t):
            if staged is None:
                idx = self._cohort_gather_index(members)
                self.x = shard_client_arrays(self.mesh,
                                             jnp.asarray(self._x_pop[idx]))
                self.y = shard_client_arrays(self.mesh,
                                             jnp.asarray(self._y_pop[idx]))
            else:
                self.x, self.y = staged.value
        self.algo.rebind_data(self.x, self.y)
        hist, arm = self.registry.cohort_view(members)
        self.algo.load_cohort_state(
            t, members, hist, arm,
            reserved_models=self.registry.reserved_models())
        # Staleness evidence for the clustering layer: consecutive
        # sampled-but-silent rounds per member (an unsampled member never
        # accrued any — unknown, not absent), suspicion past the same
        # patience the dense-mode FailureDetector uses.
        ages = np.zeros(self.C_, dtype=np.int64)
        ages[valid] = self.registry.absent_streak[members[valid]]
        self.algo.set_client_staleness(
            ages, tuple(np.where(ages >= cfg.failure_patience)[0].tolist()))

    def _population_masks(self, t: int, rounds) -> "np.ndarray | None":
        """Per-round participation over the cohort axis: the deadline+
        quorum closing rule over injected straggler latencies. Returns
        None — the legacy maskless program signature — when no straggler
        or churn chaos is configured (full cohort participation), which is
        what keeps the full-participation path bitwise-identical to the
        dense mode."""
        cfg = self.cfg
        members = self._cohort_members
        valid = members >= 0
        if self.straggler is None and self.churn is None:
            for r in rounds:
                self.registry.record_round(members, valid,
                                           t * cfg.comm_round + int(r))
            return None
        masks = np.zeros((len(rounds), self.C_pad), dtype=np.float32)
        for i, r in enumerate(rounds):
            gr = t * cfg.comm_round + int(r)
            lat = None
            if self.straggler is not None:
                # cohort-sliced draw: latencies(gr)[members] without
                # materializing the population-sized latency arithmetic
                coh_lat = self.straggler.latencies(
                    gr, np.where(valid, members, 0))
                lat = np.where(valid, coh_lat, np.inf)
            outcome = self.participation.close_round(members, lat, gr)
            self.registry.record_round(members, outcome.on_time, gr)
            if not outcome.degraded:
                masks[i, : self.C_] = outcome.on_time.astype(np.float32)
            # degraded: the all-zero row makes the round a no-op that
            # still advances the RNG/eval cadence — every aggregator of
            # resilience/robust_agg.py keeps prev params for n == 0 rows
        return masks

    def _cohort_writeback(self, t: int) -> None:
        """After end_iteration: persist the cohort's clustering outcome
        per member, replaying pool-structure changes (merges, slot reuse)
        onto members outside the cohort first."""
        self.algo.save_cohort_state(t)
        drain = getattr(self.algo, "drain_model_remaps", None)
        if drain is not None:
            for op, a, b in drain():
                self.registry.remap_model(op, a, b)
        assign = np.asarray(self.algo.test_model_idx(t))
        self.registry.writeback(t, self._cohort_members, assign,
                                self.algo.cohort_arm_acc(t))
        cb = self.registry.column_bytes()
        self._ledger.set_bytes("assign_hist", cb.get("assign_hist", 0))
        self._ledger.set_bytes(
            "registry_columns",
            sum(v for k, v in cb.items() if k != "assign_hist"))
        if self.logger:
            self.logger.set_summary("Population", self.registry.summary())

    # ------------------------------------------------------------------
    # round_breakdown segments that are HOST control-plane work double-
    # book into the hostprof ledger (device_compute/h2d/dispatch do not);
    # _on_span is the single accumulation point for both the iteration
    # and the megastep path, so this map covers both.
    _LEDGER_SEGS = {"cohort_prep": "cohort_plan",
                    "writeback": "registry_writeback",
                    "drift_decision": "drift_decision"}

    # ... and the cat="round" spans that cover a whole PhaseTracer phase
    # feed its totals: one span per interval, two accountings of it
    _PHASE_OF = {"cohort_prep": "cohort", "drift_decision": "cluster",
                 "eval": "eval"}

    def _on_span(self, name: str, cat: str, dur: float,
                 self_s: float) -> None:
        """This thread's completion hook on the recorder: a closed
        cat="round" span adds its self time to the segment of its name,
        whichever layer recorded it. Inside the drift decision it goes to
        that segment instead: drift_decision stays everything the algorithm
        does at the time-step boundary, the dispatches and device waits it
        asks for included (their spans are recorded and counted all the
        same)."""
        if cat != "round":
            return
        phase = self._PHASE_OF.get(name)
        if phase is not None:
            self.tracer.add(phase, dur)
        name = self._seg_owner or name
        self._segs[name] = self._segs.get(name, 0.0) + self_s
        sub = self._LEDGER_SEGS.get(name)
        if sub is not None:
            self._ledger.add_seconds(sub, self_s)

    def _claim_spans(self) -> None:
        """A time step starts: core/step.py and multihost.fetch record on
        the process-wide recorder, so the experiment that runs the step
        records there too (a later Experiment may have installed a new one)
        and takes this thread's completion hook."""
        self.spans = self.tracer.spans = obs.spans.get_recorder()
        self.spans.set_hook(self._on_span)
        self._segs = {}

    def _seg(self, name: str, **args):
        """Sub-span of the iteration (cat="round"); its self time lands in
        the round_breakdown segment of the same name (_on_span)."""
        return self.spans.span(name, cat="round", **args)

    @contextlib.contextmanager
    def _drift_decision(self):
        """begin_iteration / end_iteration: the algorithm's layer (the
        tracer's "cluster" phase)."""
        with self._seg("drift_decision"):
            self._seg_owner = "drift_decision"
            try:
                yield
            finally:
                self._seg_owner = None

    def _set_context(self, **ctx) -> None:
        """The ambient iteration/round of the events and of the spans,
        which core/step.py and comm/multihost.py record without knowing
        the time step."""
        self.events.set_context(**ctx)
        self.spans.set_context(**ctx)

    def _block(self, tree) -> None:
        """Wait for a dispatched program where no fetch would: the wait is
        a device_compute span."""
        with self._seg("device_compute"):
            # lint: r2-ok (the measured wait itself)
            jax.block_until_ready(tree)

    # ------------------------------------------------------------------
    def run_iteration(self, t: int) -> None:
        cfg = self.cfg
        p0 = time.perf_counter()
        self._claim_spans()
        self._set_context(iteration=t, round=self.global_round)
        self.events.emit("iteration_start")
        if self.population_mode:
            with self._seg("cohort_prep"):
                self._prepare_cohort(t)
        if self.divergence_guard is not None:
            # the time step changes the training window/concept: losses
            # legitimately re-spike, so the spike baseline starts fresh
            self.divergence_guard.new_window()
        # stale_replay attacks replay submissions WITHIN a time step; the
        # iteration boundary (fresh optimizers, possibly re-clustered pool)
        # resets the replay buffer like it resets the optimizer states
        self._byz_stale = None
        self._codec_prev = None  # delta baseline resets with the round state
        if self.failure_detector is not None:
            # Hand the clustering layer each client's absence age + the
            # current suspect set BEFORE its create/merge decisions, so
            # stale accuracy entries can be excluded (cfg.acc_staleness_limit)
            self.algo.set_client_staleness(
                self.failure_detector.absent_streak,
                self.failure_detector.suspected)
        with self._drift_decision():
            # drift detection / clustering
            self.algo.begin_iteration(t)
        if cfg.debug_checks:
            from feddrift_tpu.utils.invariants import check_round_inputs
            tw, sw, fm, _ = self.algo.round_inputs(t, 0)
            check_round_inputs(
                tw, sw, fm, num_models=self.pool.num_models,
                num_clients=self.C_, num_steps_p1=self.ds.num_steps + 1,
                sample_num=self.ds.samples_per_step)
        with self._seg("opt_init"):
            opt_states = self.step.fresh_opt_states(
                self.pool.params, self.C_pad)

        if cfg.stream_data:
            if not (self.algo.chunkable(t)
                    and self.algo.ensemble_spec(t) is None):
                raise ValueError("stream_data requires a chunkable algorithm "
                                 "with a non-ensemble test path")
            self._run_iteration_fused(t, opt_states, stream=True)
        elif (cfg.chunk_rounds and self.secure_driver is None
                and self.step.fuses_rounds
                and self.algo.chunkable(t)
                and self.algo.ensemble_spec(t) is None):
            self._run_iteration_fused(t, opt_states)
        else:
            # secure_agg always lands here: the protocol needs the
            # per-round client stack on host, so rounds cannot fuse
            self._run_rounds(t, opt_states)

        with self._drift_decision():
            self.algo.end_iteration(t)
        if self.population_mode:
            with self._seg("writeback"):
                self._cohort_writeback(t)
        if self.cfg.checkpoint_every_iteration and self.out_dir:
            with self._seg("writeback"):
                self.save_checkpoint(t)
            self.events.emit("checkpoint_save", path=self.ckpt_path())
        if self.population_mode:
            # pre-stage t+1's cohort shard on the stager thread; must run
            # AFTER this iteration's checkpoint so the churned registry the
            # draw commits is never ahead of the state a resume reloads
            self._stage_cohort(t + 1)
        wall = time.perf_counter() - p0
        log.info("iteration %d done in %.1fs (Test/Acc=%.4f)", t,
                 wall, self.logger.last("Test/Acc", -1))
        self.tracer.log_summary(prefix=f"iter {t}: ")
        self.last_phase_summary = self.tracer.summary()
        self.tracer.reset()   # per-iteration deltas, not cumulative totals
        # Round throughput in examples/s: every comm round each sampled
        # client runs `epochs` local steps on one `batch_size` batch —
        # client-examples, the FL-semantics unit (multiply by models for
        # device examples: the pool trains M x C pairs).
        B = min(cfg.batch_size, self.ds.samples_per_step)
        participants = ((cfg.cohort_size or cfg.client_num_in_total)
                        if self.population_mode
                        else min(cfg.client_num_per_round, self.C_))
        examples = cfg.comm_round * cfg.epochs * B * participants
        self.events.emit(
            "iteration_end", wall_s=round(wall, 4), rounds=cfg.comm_round,
            examples=examples,
            examples_per_s=round(examples / max(wall, 1e-9), 1),
            rounds_per_s=round(cfg.comm_round / max(wall, 1e-9), 3),
            test_acc=self.logger.last("Test/Acc"),
            phases={k: {"total_s": round(v["total_s"], 4),
                        "count": v["count"]}
                    for k, v in self.last_phase_summary.items()})
        # One trace lane entry spanning the whole time step, and a live
        # HBM watermark per iteration (silently a no-op on backends
        # without memory_stats — CPU).
        self.spans.record("iteration", self.spans.wall(p0), wall,
                          cat="runner", iteration=t)
        # Critical-path breakdown: the segments (self times of the
        # cat="round" spans) partition the iteration wall; the residual is
        # the dispatch gap: host time no span claimed. device_compute is
        # the time the host was blocked on the device (wait plus copy), so
        # host_overhead_frac = 1 - device_compute/wall is the share of the
        # wall in which the host was NOT waiting for the device;
        # `critical_path <run_dir>` and the regress host-overhead ceiling
        # both consume this event.
        gap = max(wall - sum(self._segs.values()), 0.0)
        dev = self._segs.get("device_compute", 0.0)
        host_frac = min(max(1.0 - dev / max(wall, 1e-9), 0.0), 1.0)
        segments = {k: round(v, 6) for k, v in sorted(self._segs.items())}
        segments["dispatch_gap"] = round(gap, 6)
        self.last_round_breakdown = {
            "iteration": t, "wall_s": round(wall, 6),
            "rounds": cfg.comm_round,
            # every round's device wait is measured (kept for consumers)
            "profiled_rounds": cfg.comm_round,
            "segments": segments, "dispatch_gap_s": round(gap, 6),
            "host_overhead_frac": round(host_frac, 6)}
        self.events.emit("round_breakdown", **self.last_round_breakdown)
        reg = obs.registry()
        reg.gauge("host_overhead_frac").set(round(host_frac, 6))
        reg.histogram("round_wall_seconds").observe(
            wall / max(cfg.comm_round, 1))
        # Streaming P² digests next to the histogram: live p50/p95/p99
        # for the ops plane (/metrics summary lines) and bench p99 fields.
        reg.quantile_sketch("round_wall_seconds_q").observe(
            wall / max(cfg.comm_round, 1))
        reg.quantile_sketch("dispatch_gap_seconds_q").observe(gap)
        self._ledger.finalize(iteration=t, rounds=cfg.comm_round)
        if self.flight is not None:
            # ring one instrument snapshot per iteration: the black box
            # keeps recent metric state, not just the event stream
            self.flight.snapshot_instruments()
        obs.costmodel.record_hbm_watermark(iteration=t)
        if self._ops_active and t % cfg.ops_snapshot_every == 0:
            obs.live.emit_snapshot("runner", seq=t, slo=self.slo)
        if self.out_dir and self.is_coordinator:
            # Prometheus textfile-collector snapshot, refreshed per
            # iteration (atomic replace; scrape-safe).
            import os
            obs.registry().write_textfile(
                os.path.join(self.out_dir, "metrics.prom"))

    def _client_masks(self, t: int, rounds) -> "np.ndarray | None":
        """[len(rounds), C_pad] 0/1 participation masks, or None when every
        client participates every round.

        Combines (a) the reference's round-seeded client sampling without
        replacement (client_sampling, AggregatorSoftCluster.py:197-205:
        np.random.seed(round_idx) + choice) and (b) injected faults
        (platform/faults.py), whose stream is indexed by the global
        (t, round) pair. Realized participation feeds the failure detector.
        """
        cfg = self.cfg
        if self.population_mode:
            # the cohort IS the round's sample; participation is governed
            # by the deadline/quorum policy, not dense-pool subsampling
            with self._ledger.timed("cohort_plan"):
                return self._population_masks(t, rounds)
        sampling = cfg.client_num_per_round < self.C_
        if not sampling and self.fault_injector is None:
            return None
        masks = np.zeros((len(rounds), self.C_pad), dtype=np.float32)
        for i, r in enumerate(rounds):
            if sampling:
                sel = np.random.RandomState(int(r)).choice(
                    self.C_, cfg.client_num_per_round, replace=False)
                masks[i, sel] = 1.0
            else:
                sel = np.arange(self.C_)
                masks[i, : self.C_] = 1.0
            if self.fault_injector is not None:
                fault_round = t * cfg.comm_round + int(r)
                fault_mask = self.fault_injector.mask(fault_round)
                masks[i, : self.C_] *= fault_mask
                # The detector sees GENUINE liveness — the pre-quorum-floor
                # mask — and only *failures*, not non-selection: sampled
                # clients give a liveness signal, unsampled clients keep
                # their streak unchanged. A quorum revival below is a
                # liveness lie (the client was revived BECAUSE everything
                # dropped), so it must not reset a real outage streak.
                if self.failure_detector is not None:
                    observed = np.zeros(self.C_, dtype=bool)
                    observed[sel] = True
                    self.failure_detector.observe(
                        masks[i, : self.C_] > 0, observed)
                    # Suspected-dead clients carry zero aggregation weight
                    # when configured; genuine liveness above still clears
                    # the suspicion the round a client actually returns.
                    if cfg.exclude_suspected_from_agg:
                        masks[i, self.failure_detector.suspected] = 0.0
                # Quorum floor on the COMPOSED mask (faults.py kills are
                # exempt): if every sampled client dropped, revive the
                # lowest-index sampled live client so the round is not a
                # silent no-op that still advances the RNG/eval cadence.
                if masks[i].sum() == 0:
                    alive = sel[~self.fault_injector.dead[sel]]
                    if len(alive):
                        masks[i, alive[0]] = 1.0
                        self.events.emit("quorum_revive",
                                         fault_round=fault_round,
                                         client=int(alive[0]))
                        obs.registry().counter("quorum_revives").inc()
        if self.failure_detector is not None:
            self.logger.set_summary("Failures/suspected",
                                    self.failure_detector.suspected.tolist())
        return masks

    def _check_divergence(self, losses, n, fetched: bool = False,
                          counts=None) -> bool:
        """Guard one round's losses; True = diverged (caller rolls back).
        The fetch goes through multihost so every process of a
        multi-controller run sees identical arrays and stays in lockstep;
        ``fetched`` says the caller hands in host arrays it fetched that
        way already (the megastep replay). ``counts`` (the scanned round's
        eighth output) comes back in the same fetch and is recorded as
        counters and on the ``guard`` span; with no guard there is no such
        fetch and they are not read."""
        if self.divergence_guard is None:
            return False
        with self._seg("guard") as sp:
            if not fetched:
                losses, n, counts = multihost.fetch((losses, n, counts))
            if counts is not None:
                self._record_round_counts(sp, counts)
            diverged, reason, observed = self.divergence_guard.check(
                np.asarray(losses), np.asarray(n))
        if not diverged:
            return False
        if reason == "loss_spike" and self.step.donates_pool:
            from feddrift_tpu.resilience.divergence import DivergenceError
            raise DivergenceError(
                f"loss spike ({observed:.4g}) at round {self.global_round} "
                "under client_axis='scan': the round wrote the new pool "
                "over the old one (donated), so there is nothing to roll "
                "back to; aborting the run")
        g = self.divergence_guard
        self.events.emit(
            "divergence_detected", reason=reason,
            observed_loss=(round(observed, 6) if np.isfinite(observed)
                           else None),
            baseline=(round(g.baseline, 6) if g.baseline is not None
                      else None),
            consecutive=g.consecutive_rollbacks + 1)
        obs.registry().counter("divergence_rollbacks").inc()
        log.warning("divergence (%s) at round %d: rolling back pool params",
                    reason, self.global_round)
        return True

    @staticmethod
    def _record_round_counts(span, counts: dict) -> None:
        """What the scanned round counted, on the span that fetched it and
        as counters: the pairs whose local loop ran and, from a model with
        an expert layer, over the expert layers of the round's training
        steps: the tokens that went through, the (token, expert)
        assignments that fell on held experts, the blocks of rows that went
        through them (what the routed part cost: it follows the router),
        and the fullest held expert's load over the mean held expert's (a
        gauge)."""
        reg = obs.registry()
        args = {"pairs_trained": int(counts["pairs_trained"])}
        reg.counter("pairs_trained").inc(args["pairs_trained"])
        if "expert_tokens" in counts:
            loads = np.asarray(counts["expert_load"], np.float64)
            args.update(
                expert_tokens=int(counts["expert_tokens"]),
                expert_assignments_held=int(loads.sum()))
            reg.counter("expert_tokens").inc(args["expert_tokens"])
            reg.counter("expert_assignments_held").inc(
                args["expert_assignments_held"])
            args["expert_blocks"] = int(counts["expert_blocks"])
            reg.counter("expert_blocks").inc(
                args["expert_blocks"])
            if loads.sum() > 0:
                args["expert_load_max_over_mean"] = round(
                    float(loads.max() / loads.mean()), 4)
                reg.gauge("expert_load_max_over_mean").set(
                    args["expert_load_max_over_mean"])
        span.set(**args)

    def _byz_modes(self, rounds, t: int) -> "np.ndarray | None":
        """[len(rounds), C_pad] int32 attack schedule (phantom clients are
        honest), or None without an adversary."""
        if self.byzantine is None:
            return None
        sched = self.byzantine.schedule(
            [t * self.cfg.comm_round + int(r) for r in rounds])
        out = np.zeros((len(rounds), self.C_pad), dtype=np.int32)
        out[:, : self.C_] = sched
        return out

    def _emit_robust_stats(self, agg_stats, round_idx: int) -> None:
        """One robust_agg_applied event per round from the device's [M, 3]
        (active, rejected, clipped) stats. Hierarchical rounds hand a
        [1+E, M, 3] tier stack (server tier row 0, one row per edge):
        those emit edge_aggregated with the per-tier evidence, then fall
        through with the server row and the server-tier strategy."""
        s = np.asarray(agg_stats)
        strategy = self.cfg.robust_agg
        if s.ndim == 3:
            server, edges = s[0], s[1:]
            self.events.emit(
                "edge_aggregated", round=round_idx,
                edge_strategy=self.cfg.edge_robust_agg,
                server_strategy=self.cfg.server_robust_agg,
                edge_active=edges[:, :, 0].sum(axis=1).astype(int).tolist(),
                edge_rejected=int(edges[:, :, 1].sum()),
                server_active=server[:, 0].astype(int).tolist(),
                server_rejected=int(server[:, 1].sum()))
            obs.registry().counter("edge_aggregations").inc(len(edges))
            if not self._robust_active:
                return
            s, strategy = server, self.cfg.server_robust_agg
        rejected, clipped = int(s[:, 1].sum()), int(s[:, 2].sum())
        self.events.emit(
            "robust_agg_applied", round=round_idx,
            strategy=strategy,
            active=s[:, 0].astype(int).tolist(),
            rejected=rejected, clipped=clipped)
        reg = obs.registry()
        reg.counter("robust_rejected_updates", strategy=strategy).inc(rejected)
        reg.counter("robust_clipped_updates", strategy=strategy).inc(clipped)

    def _edge_state(self, t: int, rounds):
        """Host-side edge plan for ``rounds`` of step ``t``: the per-round
        client->edge assignment [R, C_pad], the edge participation mask
        [R, E] (None without an injector), and the edge corruption modes
        [R, E] (None when nothing corrupts).

        Ordering per round: a scheduled kill lands first (edge_failed,
        reason "killed"), this round runs with the CURRENT assignment and
        the dead/crashed/stalled edges masked (below edge quorum the whole
        mask row zeroes — every aggregator keeps previous params on an
        all-masked tier), and only then are the dead edge's clients
        re-homed, so they contribute through surviving edges from the NEXT
        round — matching how a real orchestrator learns of the loss."""
        cfg = self.cfg
        E = cfg.hierarchy_edges
        R = len(rounds)
        ids = np.zeros((R, self.C_pad), dtype=np.int32)
        inj = self.edge_fault
        masks = np.ones((R, E), dtype=np.float32) if inj is not None else None
        byz = None
        for i, r in enumerate(rounds):
            gr = t * cfg.comm_round + int(r)
            if inj is not None and cfg.edge_kill_round >= 0 \
                    and gr >= cfg.edge_kill_round:
                inj.kill(cfg.edge_kill_edge, gr)   # idempotent past the round
            ids[i] = self.edge_map.ids
            if inj is None:
                continue
            crash = inj.crashes(gr)
            members = np.where(crash, -1, np.arange(E))
            outcome = self.edge_participation.close_round(
                members, inj.latencies(gr), gr, entity="edge")
            masks[i] = (np.zeros(E, dtype=np.float32) if outcome.degraded
                        else outcome.on_time.astype(np.float32))
            modes = inj.corrupt_modes(gr)
            if modes.any():
                if byz is None:
                    byz = np.zeros((R, E), dtype=np.int32)
                byz[i] = modes
            self.edge_map.rehome(inj.dead, gr)   # effective next round
        return ids, masks, byz

    def _round_rows(self, t: int, rounds):
        """What the host makes for ``rounds`` of step ``t``, as host arrays
        with a leading [len(rounds)] axis: the client masks and the
        `StackOperands` (a field None where its feature is off; the two
        carries are not rows). The one order in which the three drivers
        draw them: masks, attack schedule, edge plan."""
        cms = self._client_masks(t, rounds)
        bms = self._byz_modes(rounds, t)
        eids = emasks = ebyz = None
        if self.hierarchy:
            eids, emasks, ebyz = self._edge_state(t, rounds)
        return cms, StackOperands(byz_modes=bms, edge_ids=eids,
                                  edge_mask=emasks, edge_modes=ebyz)

    @property
    def _keeps_stale(self) -> bool:
        return self.byzantine is not None and self.byzantine.has_stale

    def _seed_round_carries(self) -> None:
        """The per-round driver's two carries (the fused programs keep
        theirs inside the scan), seeded so that a time step's first round
        meets the later rounds' jit signature: "no update" submissions for
        stale_replay, zero baseline diffs for the delta codec."""
        C = self.C_pad
        if self._keeps_stale and self._byz_stale is None:
            self._byz_stale = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(
                    l[:, None], (l.shape[0], C, *l.shape[1:])),
                self.pool.params)
        if self.step.codec == "delta" and self._codec_prev is None:
            self._codec_prev = jax.tree_util.tree_map(
                lambda l: jnp.zeros((l.shape[0], C, *l.shape[1:]), l.dtype),
                self.pool.params)

    def _keep_round_carries(self, client_params, codec_prev) -> None:
        if self._keeps_stale:
            self._byz_stale = client_params
        if self.step.codec == "delta":
            self._codec_prev = codec_prev

    def _run_rounds(self, t: int, opt_states) -> None:
        """Per-round host loop: algorithms that steer every round."""
        cfg = self.cfg
        self._seed_round_carries()
        keep_cp = (self.algo.needs_client_params or self._keeps_stale
                   or self.secure_driver is not None)
        # the scanned round is given the pool to write over (donated):
        # after the call nothing of ``prev_params`` may be read
        pool_donated = self.step.donates_pool
        # lint: hot-path-begin (per-round dispatch loop — every host sync
        # here serializes all comm_round dispatches)
        for r in range(cfg.comm_round):
            self._set_context(round=self.global_round)
            with self._seg("round_prep"):
                tw, sw, fm, lr_scale = self.algo.round_inputs(t, r)
                tw = self._pad_clients(tw)              # phantom clients: w=0
                sw = self._pad_clients(sw, value=1.0)
                # the round's key and device copies of its host-made rows
                # (eager dispatches, each tens of microseconds)
                rkey = round_key(self.key, t, r)
                cm, operands = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a[0]), self._round_rows(t, [r]))
                operands = operands._replace(stale_params=self._byz_stale,
                                             codec_prev=self._codec_prev)
            prev_params = self.pool.params
            with self.tracer.phase("train_round"):
                (new_params, opt_states, client_params, n, losses, agg_stats,
                 codec_prev, *counts) = self.step.train_round(
                    prev_params, opt_states, rkey, self.x, self.y, tw, sw,
                    fm, lr_scale, cm, operands,
                    keep_client_params=keep_cp, with_agg_stats=True,
                    models_per_client=self.algo.models_per_client,
                    time_window=self.algo.time_window(t))
                if cfg.trace_sync:
                    # attribute the device time to this phase instead of
                    # letting async dispatch spill it into whichever call
                    # blocks next (the guard's fetch, without trace_sync)
                    self._block(new_params)
                self._keep_round_carries(client_params, codec_prev)
                if self._robust_active or self.hierarchy:
                    self._emit_robust_stats(
                        # lint: r2-ok (tiny gated [M, 3] evidence fetch)
                        multihost.fetch(agg_stats), self.global_round)
                if self._check_divergence(losses, n, counts=counts[0]
                                          if counts else None):
                    # rollback: pre-round params, fresh optimizer state (the
                    # diverged step contaminated both); skip after_round and
                    # this round's eval — its numbers would be garbage.
                    # A donated pool: the round itself kept the parameters
                    # of every model with a loss that is not finite; a
                    # spike it cannot undo (_check_divergence raised)
                    self.pool.params = new_params if pool_donated \
                        else prev_params
                    with self._seg("opt_init"):
                        opt_states = self.step.fresh_opt_states(
                            self.pool.params, self.C_pad)
                    self.divergence_guard.record_rollback()
                    self.global_round += 1
                    continue
                with self._seg("writeback"):
                    if self.secure_driver is not None:
                        new_params = self._secure_substitute(
                            prev_params, new_params, client_params, n)
                    self.pool.params = self.algo.after_round(
                        t, r, None if pool_donated else prev_params,
                        new_params, client_params, n)
            if r % cfg.frequency_of_the_test == 0 or r == cfg.comm_round - 1:
                with self._seg("eval"):
                    self.evaluate(t, r)
            self.global_round += 1
        # lint: hot-path-end

    def _secure_substitute(self, prev_params, new_params, client_params, n):
        """Replace the round's plaintext device aggregate with the masked
        secure sum (resilience/secure_round.py): the adopted params come
        only from what the protocol opened — within fixed-point
        quantization of the plaintext weighted mean on the inclusion
        mask — and a degraded round keeps the pre-round params."""
        # lint: r2-ok (secure protocol runs on host every round by design)
        host_prev, host_cp, host_n = multihost.fetch(
            (prev_params, client_params, n))
        C = self.C_   # slice off phantom padding: holders = real cohort
        host_cp = jax.tree_util.tree_map(
            lambda l: np.asarray(l)[:, :C], host_cp)
        agg, _res = self.secure_driver.aggregate_params(
            jax.tree_util.tree_map(np.asarray, host_prev), host_cp,
            np.asarray(host_n)[:, :C], self.global_round)
        if agg is None:
            return prev_params
        return jax.tree_util.tree_map(
            lambda ref, v: jax.device_put(
                jnp.asarray(v, ref.dtype), ref.sharding),
            new_params, agg)

    def _stream_view(self, t: int):
        """Device view [C_pad, 2, N, ...] of steps (t, t+1), prefetched one
        iteration ahead by a background thread while the device trains t-1."""
        from feddrift_tpu.data.prefetch import prefetch_to_device

        if self._view_iter is None or self._view_next_t != t:
            if self._view_iter is not None:
                self._view_iter.close()   # release the old producer's buffers

            def host_views(t0=t):
                for tt in range(t0, self.cfg.train_iterations):
                    # contiguous zero-copy host views; the device put copies
                    yield (self._x_host[:, tt:tt + 2],
                           self._y_host[:, tt:tt + 2])

            def place(xy):
                return (shard_client_arrays(self.mesh, jnp.asarray(xy[0])),
                        shard_client_arrays(self.mesh, jnp.asarray(xy[1])))

            # size=1: consumer holds window t while t+1 is staged (plus at
            # most one more in flight on the producer thread)
            self._view_iter = prefetch_to_device(host_views(), size=1,
                                                 place=place)
            self._view_next_t = t
        self._view_next_t += 1
        return next(self._view_iter)

    def _run_iteration_fused(self, t: int, opt_states,
                             stream: bool = False) -> None:
        """ALL rounds of the time step + every scheduled eval as ONE device
        program (TrainStep.train_iteration_eval): a single dispatch and a
        single bulk D2H fetch per time step, where `_run_rounds` makes a
        dispatch a round and one an eval. Entered only for chunkable
        algorithms with a non-ensemble test path on a step that
        ``fuses_rounds``; trajectories are bitwise-identical to the other
        two drivers' (same fold_in keys, same eval cadence).

        ``stream=True`` swaps the device-resident dataset for a [C, 2, N]
        window of steps (t, t+1): the local time axis is (current, test), so
        the program runs with t_idx 0 and a 2-slot weight tensor. Batches are
        identical to resident execution — the weighted step draw degenerates
        to the single nonzero slot and the within-step slot draw uses the
        same key — so trajectories stay bitwise-identical.
        """
        cfg = self.cfg
        R, freq = cfg.comm_round, cfg.frequency_of_the_test
        it_key = iteration_key(self.key, t)
        g0 = self.global_round
        with self._seg("round_prep"):
            tw, sw, fm, lr_scale = self.algo.round_inputs(t, 0)
            tw = self._pad_clients(tw)
            sw = self._pad_clients(sw, value=1.0)
            if stream:
                tw_np = np.asarray(tw)
                if np.delete(tw_np, t, axis=2).any():
                    raise ValueError("stream_data: algorithm weights "
                                     "reference steps other than the "
                                     "current one")
                tw2 = np.zeros((*tw_np.shape[:2], 2), dtype=tw_np.dtype)
                tw2[:, :, 0] = tw_np[:, :, t]
                tw = jnp.asarray(tw2)
                x, y = self._stream_view(t)
                t_idx = 0
            else:
                x, y = self.x, self.y
                t_idx = t
            # the whole step's rows up front: edge kills/re-homes land
            # between scanned rounds exactly as on the per-round path
            cms, operands = jax.tree_util.tree_map(
                jnp.asarray, self._round_rows(t, range(R)))
            # The fused program DONATES its params input (HBM economy), so
            # the divergence rollback target must live on host: a numpy
            # snapshot of the iteration-start pool — the same D2H the
            # default per-iteration checkpoint already pays, taken only when
            # the guard is armed. It blocks on the device like a fetch.
            host_prev = None
            if self.divergence_guard is not None:
                with self._seg("device_compute"):
                    host_prev = jax.tree_util.tree_map(np.asarray,
                                                       self.pool.params)
        # lint: hot-path-begin (fused dispatch: one program per time step)
        with self.tracer.phase("train_round"):
            new_params, opt_states, n, losses, bufs, total, agg_stats = \
                self.step.train_iteration_eval(
                    self.pool.params, opt_states, it_key, x, y,
                    tw, sw, fm, lr_scale, R, freq, jnp.int32(t_idx), cms,
                    operands, byz_stale=self._keeps_stale,
                    with_agg_stats=True)
            # One dispatch covers all R rounds, so one dispatch-to-ready
            # wait covers them too (the stats/eval fetches below would
            # block here anyway — this only attributes the wait).
            self._block(new_params)
            if self._robust_active or self.hierarchy:
                # one bulk [R, M, 3] (hierarchy: [R, 1+E, M, 3]) fetch
                # -> one event per fused round
                # lint: r2-ok (single bulk [R, M, 3] stats fetch, gated)
                for rr, row in enumerate(np.asarray(
                        # lint: r2-ok (same bulk fetch, second call site)
                        multihost.fetch(agg_stats))):
                    self._emit_robust_stats(row, g0 + rr)
            if self._check_divergence(losses, n):
                # fused granularity is the whole time step: restore the
                # iteration-start params, skip after_round and the eval
                # logging — the buffers hold diverged numbers
                self.pool.params = jax.tree_util.tree_map(jnp.asarray,
                                                          host_prev)
                self.divergence_guard.record_rollback()
                self.global_round = g0 + R
                return
            with self._seg("writeback"):
                self.pool.params = self.algo.after_round(
                    t, R - 1, None, new_params, None, n)
        with self._seg("eval"):
            C = self.C_
            # lint: r2-ok (the design point: ONE bulk D2H per time step)
            bufs, total, n = multihost.fetch((bufs, total, n))
            corr_tr, loss_tr, corr_te, loss_te = bufs
            for slot, r in enumerate(self.step.eval_rounds(R, freq)):
                self.global_round = g0 + r
                self._log_eval(t, corr_tr[slot][:, :C], loss_tr[slot][:, :C],
                               corr_te[slot][:, :C], loss_te[slot][:, :C],
                               total[:C])
        self.global_round = g0 + R
        # lint: hot-path-end
        # The final eval slot holds the counts of the final params on step
        # t and on step t+1 — store both so end_iteration consumers
        # (MultiModel selection) and the next cluster phase each skip a
        # device round trip (the store's params-identity key, taken from
        # the EVALUATED new_params, makes this a pure optimisation). Only
        # valid when the chunk ran the algorithm's plain all-ones feature
        # mask on the resident dataset.
        if not stream and fm is self.algo._ones_feat_mask:
            self.algo.store_acc_counts(
                new_params, {t: (corr_tr[-1], loss_tr[-1], total),
                             t + 1: (corr_te[-1], loss_te[-1], total)})

    # ------------------------------------------------------------------
    # multi-iteration megastep (TrainStep.train_megastep)
    def _megastep_gates(self, t: int) -> list:
        """Per-feature megastep capability table: the reasons (possibly
        several) that force the fusion span to 1 at step ``t``.

        Population cohorts, two-tier hierarchy, Byzantine schedules and
        the wire codecs all FUSE now — their per-step state (stacked
        cohort gathers, edge plans, attack masks, stale-replay / delta
        carries) rides the outer scan. What still can't:

          chunk_rounds_off     — per-round host loop explicitly requested
          stream_data          — the dataset window swaps between steps
          algo_not_chunkable   — the algorithm steers individual rounds
          ensemble_eval        — ensemble test path needs host-side eval

        (The divergence guard does NOT gate fusion: blocks whose plan
        committed non-replayable bookkeeping — population registry
        mutations, edge kills/re-homes — recover at block granularity
        instead of truncate-and-rerun; see run_megastep.)
        """
        cfg = self.cfg
        reasons = []
        if not cfg.chunk_rounds:
            reasons.append("chunk_rounds_off")
        if cfg.stream_data:
            reasons.append("stream_data")
        if not self.algo.chunkable(t):
            reasons.append("algo_not_chunkable")
        if self.algo.ensemble_spec(t) is not None:
            reasons.append("ensemble_eval")
        return reasons

    def _megastep_span(self, t: int) -> int:
        """How many whole time steps starting at ``t`` to fuse into one
        train_megastep dispatch. 1 = legacy per-iteration path (always
        bitwise-identical — K=1 never even builds the megastep program).

        The per-feature capability table (``_megastep_gates``) names every
        feature that forces K down; each forcing reason is surfaced as a
        ``megastep_gated`` event + counter so `report` can say why fusion
        was forfeited. Within the fusable configurations the algorithm's
        ``megastep_horizon`` bounds the span at its next drift-decision
        boundary (also surfaced, reason "algo_horizon"); the end-of-run
        tail clamp is not a gate and stays silent."""
        cfg = self.cfg
        if cfg.megastep_k <= 1:
            return 1     # fusion not requested — nothing was forfeited
        reasons = self._megastep_gates(t)
        if reasons:
            for reason in reasons:
                self.events.emit("megastep_gated", reason=reason,
                                 gate_iteration=t, requested=cfg.megastep_k,
                                 granted=1)
                obs.registry().counter("megastep_gated", reason=reason).inc()
            return 1
        horizon = self.algo.megastep_horizon(t)
        want = min(cfg.megastep_k, cfg.train_iterations - t)
        if horizon < want:
            self.events.emit("megastep_gated", reason="algo_horizon",
                             gate_iteration=t, requested=cfg.megastep_k,
                             granted=max(1, horizon))
            obs.registry().counter("megastep_gated",
                                   reason="algo_horizon").inc()
        return max(1, min(want, horizon))

    def run_megastep(self, t0: int, K: int) -> int:
        """Run K whole time steps as ONE device dispatch
        (TrainStep.train_megastep) and replay the buffered per-step results
        into the exact per-iteration record stream the K=1 path emits.

        Three phases:
          plan    — per step, in sequential order: events context, cohort
                    prepare (population — consumes the previous plan
                    step's staged gather), begin_iteration (host drift
                    decisions on pre-block state — legal because
                    megastep_horizon certified steps t0+1.. are
                    decision-free), round_inputs, client masks (which
                    commit registry participation bookkeeping), Byzantine
                    and edge-fault schedules, and — population — the
                    registry writeback, which commits at this (block-plan)
                    boundary instead of after the step trains: legal for
                    the same decision-free reason, since every writeback
                    input (per-step model assignment, detector arms,
                    isolation marks) is settled by begin_iteration and
                    end_iteration is a no-op for every algorithm. Each
                    population plan step then submits the NEXT step's
                    cohort gather to the K-deep AsyncStager, so H2D
                    staging pipelines against the remaining host planning.
          dispatch — one device program for all K*R rounds; per-step
                    cohort shards, attack masks and edge plans ride the
                    outer scan as stacked [K, ...] inputs.
          replay  — per step, in sequential order: robust-agg stats,
                    divergence guard (same per-iteration window/check
                    cadence), after_round, the buffered eval matrices into
                    _log_eval (under that step's cohort validity mask),
                    end_iteration.

        Returns the number of COMMITTED iterations: K normally; j+1 after
        a divergence rollback at block step j — steps past j trained on
        the diverged trajectory inside the fused program, so the driver
        loop reruns them from the restored params (their planning-phase
        events re-emit; all planning state writes are idempotent by the
        megastep contract — the capability table keeps the guard off the
        non-idempotent population/edge-fault bookkeeping)."""
        cfg = self.cfg
        R, freq = cfg.comm_round, cfg.frequency_of_the_test
        block_p0 = time.perf_counter()
        self._claim_spans()
        g0 = self.global_round
        # -- plan ------------------------------------------------------
        # lint: hot-path-begin (megastep plan: K-step cohort/fault stacking)
        tws, rows = [], []
        xs_list, ys_list, slot_valids, members_list = [], [], [], []
        sw = fm = lr_scale = None
        for j in range(K):
            t = t0 + j
            self._set_context(iteration=t, round=g0 + j * R)
            self.events.emit("iteration_start", megastep_k=K)
            if self.population_mode:
                with self._seg("cohort_prep"):
                    self._prepare_cohort(t)
            self._byz_stale = None
            self._codec_prev = None
            if self.failure_detector is not None:
                self.algo.set_client_staleness(
                    self.failure_detector.absent_streak,
                    self.failure_detector.suspected)
            with self._drift_decision():
                self.algo.begin_iteration(t)
            if cfg.debug_checks:
                from feddrift_tpu.utils.invariants import check_round_inputs
                tw_d, sw_d, fm_d, _ = self.algo.round_inputs(t, 0)
                check_round_inputs(
                    tw_d, sw_d, fm_d, num_models=self.pool.num_models,
                    num_clients=self.C_, num_steps_p1=self.ds.num_steps + 1,
                    sample_num=self.ds.samples_per_step)
            with self._seg("round_prep"):
                tw, sw, fm, lr_scale = self.algo.round_inputs(t, 0)
                if fm is not getattr(self.algo, "_ones_feat_mask", None):
                    raise RuntimeError(
                        "megastep requires the algorithm's plain all-ones "
                        "feature mask (megastep_horizon contract violated)")
                tws.append(self._pad_clients(tw))
                # sequential per-step planning: edge kills/re-homes land
                # between steps exactly as on the per-iteration path
                rows.append(self._round_rows(t, range(R)))
            if self.population_mode:
                xs_list.append(self.x)
                ys_list.append(self.y)
                slot_valids.append(self._slot_valid.copy())
                # host-resident member ids — a registry draw, never a
                # device buffer; copied so replay keeps step j's cohort
                # after later plan steps re-draw
                # lint: r2-ok (host numpy registry draw, not a device sync)
                members_list.append(np.asarray(self._cohort_members).copy())
                # block-boundary registry commit (see docstring); must
                # precede the next step's draw, whose staleness view and
                # assignment history read these columns
                with self._seg("writeback"):
                    self._cohort_writeback(t)
                if j < K - 1:
                    # pipeline the NEXT plan step's gather; the block-exit
                    # stage (t0+K) waits for the block checkpoint below so
                    # a resume never re-applies its churn
                    self._stage_cohort(t + 1)
        # what follows belongs to the block: its spans carry the block's
        # first time step (the events keep the last plan step's context)
        self.spans.set_context(iteration=t0, round=g0)
        with self._seg("round_prep"):
            sw = self._pad_clients(sw, value=1.0)
            time_ws = jnp.stack(tws)                      # [K, M, C_pad, T1]
            def steps(field):
                # one row kind over the K steps -> [K, R, ...]; None where no
                # step has it, and zeros for a step without (an edge plan
                # in which nothing corrupts) beside steps with
                have = next((a for a in field if a is not None), None)
                return None if have is None else jnp.asarray(np.stack(
                    [np.zeros_like(have) if a is None else a for a in field]))
            cms_list, operands_list = zip(*rows)
            cms = steps(cms_list)
            operands = StackOperands(*map(steps, zip(*operands_list)))
            x_steps = y_steps = None
            if self.population_mode:
                # [K, C_pad, T1, N, ...] stacked per-step cohort shards — the
                # scan's data input; built identically every block so the jit
                # signature (and therefore the compile cache) is stable
                x_steps = jnp.stack(xs_list)
                y_steps = jnp.stack(ys_list)
        # lint: hot-path-end
        # -- dispatch --------------------------------------------------
        # lint: hot-path-begin (megastep: one program per K-step block)
        with self.tracer.phase("train_round"):
            ps, ns, ls, bufs, total, agg_stats = self.step.train_megastep(
                self.pool.params, self.key,
                None if self.population_mode else self.x,
                None if self.population_mode else self.y,
                time_ws, sw, fm,
                lr_scale, jnp.int32(t0), R, freq, K, cms, operands, x_steps,
                y_steps, byz_stale=self._keeps_stale)
            # one dispatch-to-ready wait per K-step block
            self._block(ps)
        # lint: hot-path-end
        # -- replay ----------------------------------------------------
        C = self.C_
        ns_h, ls_h, bufs_h, total_h = multihost.fetch((ns, ls, bufs, total))
        ns_h, ls_h, total_h = (np.asarray(ns_h), np.asarray(ls_h),
                               np.asarray(total_h))
        corr_tr, loss_tr, corr_te, loss_te = (np.asarray(b) for b in bufs_h)
        stats_h = (np.asarray(multihost.fetch(agg_stats))
                   if (self._robust_active or self.hierarchy) else None)
        evs = self.step.eval_rounds(R, freq)
        steps_p = _unstack_steps(ps, K)
        committed = K
        final_p = None
        # Truncate-and-rerun rollback needs the driver to re-execute the
        # steps past the divergence, which re-runs their planning. That is
        # only sound when planning was pure: population registry
        # bookkeeping (churn application, record_round streak/EWMA) and
        # edge kills/re-homes are already committed for the WHOLE block
        # and are not idempotent under replay, so those blocks recover at
        # block granularity instead — restore the last clean step's params
        # and skip the poisoned remainder's adoption (bookkeeping and the
        # block checkpoint stay consistent; one rollback per block).
        replayable = not (self.population_mode
                          or self.edge_fault is not None)
        skipping = False
        for j in range(K):
            t = t0 + j
            gj = g0 + j * R
            self._set_context(iteration=t, round=gj)
            if self.population_mode:
                # metrics masking + eval logging must see THIS step's
                # cohort, not the last plan step's
                self._slot_valid = slot_valids[j]
                self._cohort_members = members_list[j]
            if stats_h is not None:
                for rr in range(R):
                    self._emit_robust_stats(stats_h[j, rr], gj + rr)
            if skipping:
                # poisoned tail of a non-replayable block: no adoption, no
                # eval logging (the buffers hold diverged numbers); the
                # round cadence and iteration lifecycle still advance
                self.global_round = gj + R
                with self._drift_decision():
                    self.algo.end_iteration(t)
                continue
            if self.divergence_guard is not None:
                self.divergence_guard.new_window()
            if self._check_divergence(ls_h[j], ns_h[j], fetched=True):
                # roll back to the end of block step j-1: the fused
                # program trained later steps on the diverged trajectory.
                # For j=0 the pool still holds the pre-block params (the
                # megastep program does not donate its input), so the
                # rollback is a no-op there.
                if j > 0:
                    self.pool.params = steps_p[j - 1]
                self.divergence_guard.record_rollback()
                self.global_round = gj + R
                if replayable:
                    committed = j + 1
                    break
                skipping = True
                with self._drift_decision():
                    self.algo.end_iteration(t)
                continue
            step_p = steps_p[j]
            with self._seg("writeback"):
                self.pool.params = self.algo.after_round(
                    t, R - 1, None, step_p, None, ns_h[j])
            with self._seg("eval"):
                for slot, r in enumerate(evs):
                    self.global_round = gj + r
                    self._log_eval(
                        t, corr_tr[j, slot][:, :C], loss_tr[j, slot][:, :C],
                        corr_te[j, slot][:, :C], loss_te[j, slot][:, :C],
                        total_h[:C])
            self.global_round = gj + R
            with self._drift_decision():
                self.algo.end_iteration(t)
            final_p = step_p
        # The final slot's counts into the store, exactly like the K=1
        # fused path — keyed to the sliced final-step params object the
        # pool now holds.
        if final_p is not None and committed == K and not skipping:
            self.algo.store_acc_counts(final_p, {
                t0 + K - 1: (corr_tr[K - 1, -1], loss_tr[K - 1, -1], total_h),
                t0 + K: (corr_te[K - 1, -1], loss_te[K - 1, -1], total_h)})
        last_t = t0 + committed - 1
        if cfg.checkpoint_every_iteration and self.out_dir:
            # one checkpoint per BLOCK (the per-iteration generations
            # between block boundaries are skipped — each would overwrite
            # the same path anyway; resume granularity becomes the block)
            with self._seg("writeback"):
                self.save_checkpoint(last_t)
            self.events.emit("checkpoint_save", path=self.ckpt_path())
        if self.population_mode:
            # pre-stage the NEXT block's first cohort — after the block
            # checkpoint for the same reason run_iteration stages after
            # its own: the churn a draw commits must never be ahead of the
            # registry state a resume reloads (ChurnSchedule events filter
            # on the live active mask, so double-application diverges)
            self._stage_cohort(t0 + committed)
        # -- per-iteration telemetry records ---------------------------
        wall = time.perf_counter() - block_p0
        log.info("megastep %d..%d (K=%d) done in %.1fs (Test/Acc=%.4f)",
                 t0, last_t, K, wall, self.logger.last("Test/Acc", -1))
        self.tracer.log_summary(prefix=f"iters {t0}..{last_t}: ")
        self.last_phase_summary = self.tracer.summary()
        self.tracer.reset()
        B = min(cfg.batch_size, self.ds.samples_per_step)
        participants = ((cfg.cohort_size or cfg.client_num_in_total)
                        if self.population_mode
                        else min(cfg.client_num_per_round, self.C_))
        examples = R * cfg.epochs * B * participants
        wall_j = wall / committed
        gap = max(wall - sum(self._segs.values()), 0.0)
        dev = self._segs.get("device_compute", 0.0)
        host_frac = min(max(1.0 - dev / max(wall, 1e-9), 0.0), 1.0)
        phases = {k: {"total_s": round(v["total_s"] / committed, 4),
                      "count": v["count"]}
                  for k, v in self.last_phase_summary.items()}
        segments = {k: round(v / committed, 6)
                    for k, v in sorted(self._segs.items())}
        segments["dispatch_gap"] = round(gap / committed, 6)
        for j in range(committed):
            t = t0 + j
            self._set_context(iteration=t, round=g0 + j * R + R - 1)
            self.events.emit(
                "iteration_end", wall_s=round(wall_j, 4), rounds=R,
                examples=examples,
                examples_per_s=round(examples / max(wall_j, 1e-9), 1),
                rounds_per_s=round(R / max(wall_j, 1e-9), 3),
                test_acc=self.logger.last("Test/Acc"),
                megastep_k=K, phases=phases)
            self.spans.record("iteration",
                              self.spans.wall(block_p0) + j * wall_j, wall_j,
                              cat="runner", iteration=t)
            self.last_round_breakdown = {
                "iteration": t, "wall_s": round(wall_j, 6), "rounds": R,
                "profiled_rounds": R, "megastep_k": K,
                "segments": segments,
                "dispatch_gap_s": round(gap / committed, 6),
                "host_overhead_frac": round(host_frac, 6)}
            self.events.emit("round_breakdown", **self.last_round_breakdown)
        reg = obs.registry()
        reg.gauge("host_overhead_frac").set(round(host_frac, 6))
        reg.histogram("round_wall_seconds").observe(wall_j / max(R, 1))
        reg.quantile_sketch("round_wall_seconds_q").observe(
            wall_j / max(R, 1))
        reg.quantile_sketch("dispatch_gap_seconds_q").observe(
            gap / committed)
        self._ledger.finalize(iteration=last_t, rounds=committed * R)
        if self.flight is not None:
            self.flight.snapshot_instruments()
        obs.costmodel.record_hbm_watermark(iteration=last_t)
        if self._ops_active and last_t % cfg.ops_snapshot_every == 0:
            obs.live.emit_snapshot("runner", seq=last_t, slo=self.slo)
        if self.out_dir and self.is_coordinator:
            import os
            obs.registry().write_textfile(
                os.path.join(self.out_dir, "metrics.prom"))
        return committed

    def run(self) -> MetricsLogger:
        # Context managers so a raising iteration cannot leak the JSONL
        # handles; the in-memory history/ring stay readable after close.
        from feddrift_tpu.resilience.preempt import PreemptionHandler
        with self.logger, self.events:
            with PreemptionHandler(enabled=self.cfg.preempt_signals) as pre:
                try:
                    t = self.start_iteration
                    while t < self.cfg.train_iterations:
                        # greedy megastep fusion: K > 1 runs whole blocks
                        # of drift-decision-free time steps as one
                        # dispatch; K = 1 is the historical
                        # per-iteration path, bit for bit
                        K = self._megastep_span(t)
                        if K > 1:
                            t += self.run_megastep(t, K)
                        else:
                            self.run_iteration(t)
                            t += 1
                        if self.sanitizer is not None:
                            # raises past the steady-state recompile
                            # budget; the first block's warm-up compiles
                            # don't count
                            self.sanitizer.check()
                            self.sanitizer.mark_steady()
                        if pre.requested:
                            # preemption: the block ending at t-1 just
                            # completed — persist it and exit cleanly;
                            # --auto_resume continues here
                            self._preempt_stop(t - 1, pre.signal_name)
                            break
                except Exception as err:
                    # abnormal termination — divergence aborts included:
                    # capture the black box while the bus and file sinks
                    # are still open, then propagate unchanged
                    if self.incidents is not None:
                        self.incidents.on_exception(err)
                    raise
            self.events.emit("run_end", global_round=self.global_round,
                             test_acc=self.logger.last("Test/Acc"),
                             preempted=self.preempted)
        if self.hostprof is not None:
            self.hostprof.stop()
            if self.out_dir and self.is_coordinator:
                import os
                self.hostprof.write_folded(
                    os.path.join(self.out_dir, "hostprof.folded"))
        return self.logger

    def _preempt_stop(self, completed_iteration: int, signal_name) -> None:
        """Checkpoint at the iteration boundary after a SIGTERM/SIGINT."""
        if self.out_dir and not self.cfg.checkpoint_every_iteration:
            # not already checkpointed by run_iteration: write one now
            self.save_checkpoint(completed_iteration)
        self.preempted = True
        self.events.emit(
            "preempt_checkpoint", iteration=completed_iteration,
            signal=signal_name,
            path=self.ckpt_path() if self.out_dir else None)
        log.warning("preempted by %s: checkpointed through iteration %d, "
                    "exiting cleanly (resume with --auto_resume)",
                    signal_name, completed_iteration)

    # ------------------------------------------------------------------
    # checkpoint / resume (iteration-granular, like the reference's CWD state
    # files but atomic and single-directory; SURVEY.md §5)
    def ckpt_path(self) -> str:
        import os
        return os.path.join(self.out_dir or self.cfg.out_dir, "ckpt")

    def save_checkpoint(self, completed_iteration: int) -> None:
        if not self.is_coordinator:
            return        # pool params are replicated; one writer suffices
        from feddrift_tpu.utils.checkpoint import save_checkpoint
        algo_state = self.algo.state_dict()
        if self.population_mode:
            # the registry rides in the algo pickle under a reserved key:
            # same atomic generation, no checkpoint format change
            algo_state = {**algo_state,
                          "__registry__": self.registry.state_dict()}
        save_checkpoint(
            self.ckpt_path(), config_json=self.cfg.to_json(),
            iteration=completed_iteration, global_round=self.global_round,
            pool_params=self.pool.params, algo_state=algo_state)

    @classmethod
    def resume(cls, cfg: ExperimentConfig, out_dir: str, mesh=None,
               use_wandb: bool = False) -> "Experiment":
        """Rebuild an Experiment and continue after the last completed
        iteration recorded in ``out_dir``'s checkpoint."""
        import os
        from feddrift_tpu.utils.checkpoint import load_checkpoint
        exp = cls(cfg, mesh=mesh, use_wandb=use_wandb, out_dir=out_dir)
        state = load_checkpoint(os.path.join(out_dir, "ckpt"), exp.pool.params)
        exp.pool.params = state["pool_params"]
        algo_state = dict(state["algo_state"])
        reg_state = algo_state.pop("__registry__", None)
        if reg_state is not None and exp.registry is not None:
            exp.registry.load_state_dict(reg_state)
        exp.algo.load_state_dict(algo_state)
        exp.global_round = state["global_round"]
        exp.start_iteration = state["iteration"] + 1
        # A crash may have logged part of iteration start_iteration AFTER
        # the last checkpoint; that iteration reruns from its start, so its
        # partial rows must be dropped or metrics.jsonl carries duplicates.
        exp.logger.truncate_from(exp.start_iteration)
        return exp


def run_experiment(cfg: ExperimentConfig, mesh=None, use_wandb: bool = False,
                   out_dir: Optional[str] = None) -> Experiment:
    exp = Experiment(cfg, mesh=mesh, use_wandb=use_wandb, out_dir=out_dir)
    exp.run()
    return exp
