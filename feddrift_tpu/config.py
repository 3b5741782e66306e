"""Typed experiment configuration.

Replaces the reference's 24 positional shell arguments + argparse
(fedml_experiments/distributed/fedavg_cont_ens/main_fedavg.py:42-139 and
run_fedavg_distributed_pytorch.sh:3-26) with one dataclass. The packed
algorithm-argument strings of the reference (e.g. FedDrift's
``H_{dist}_{cluster}_{W}_{100*delta}_{100*delta'}``, CFL's
``cfl_{gamma}_{win-1|all}``, parsed ad hoc at
fedml_api/distributed/fedavg_ens/FedAvgEnsDataLoader.py:1276-1328) are still
accepted verbatim in ``concept_drift_algo_arg`` for run-for-run comparability.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any


# Default drift-detection deltas per dataset, matching the reference tables at
# FedAvgEnsDataLoader.py:1274 (softcluster), :455 (mmacc) and :274 (driftsurf).
DEFAULT_DELTAS = {"sea": 0.04, "sine": 0.20, "circle": 0.10, "MNIST": 0.10}
DRIFTSURF_DELTAS = {"sea": 0.02, "sine": 0.10, "circle": 0.05}


@dataclass
class ExperimentConfig:
    """Full configuration of a drift-FL experiment.

    Field names deliberately mirror the reference argparse flags
    (main_fedavg.py:42-139) so reference launch commands translate 1:1.
    """

    # --- model & dataset -------------------------------------------------
    model: str = "fnn"                 # lr | fnn | cnn | resnet | rnn | ...
    dataset: str = "sea"               # sea | sine | circle | MNIST | cifar10 | femnist | shakespeare
    data_dir: str = "./data"
    client_num_in_total: int = 10
    client_num_per_round: int = 10
    batch_size: int = 500
    fnn_hidden_dim: int = 10
    fmow_image_size: int = 32          # fmow partition image resolution
    smooth_sigma: float = 3.0          # basis smoothing (px) for the
                                       # "-smooth" conv-learnable synthetic
                                       # image family (data/prototype.py)
    chunk_rounds: bool = True          # scan rounds between evals as one
                                       # device program when the algorithm
                                       # permits (bitwise-identical results)
    # Multi-iteration megastep: fuse up to K whole time steps (each an
    # R-round chunked scan) into ONE device program when the algorithm's
    # megastep_horizon(t) allows — the host touches the device once per K
    # steps. 1 = off (legacy per-iteration dispatch, bitwise-identical).
    megastep_k: int = 1
    # Drift-decision cadence for decision algorithms (softcluster family):
    # clustering decisions run only at t % decision_cadence == 0; between
    # boundaries the assignment is carried forward unchanged, which is what
    # makes those stretches megastep-fusable. 1 = decide every step
    # (historical behavior).
    decision_cadence: int = 1
    trace_sync: bool = False           # block on device inside traced phases
                                       # for exact per-phase attribution (off:
                                       # keep async dispatch for throughput)

    # --- optimization ----------------------------------------------------
    client_optimizer: str = "adam"     # adam (amsgrad, as reference FedAvgEnsTrainer.py:31-33) | sgd
    lr: float = 0.01
    wd: float = 0.001
    # NOTE reference semantics: `epochs` is the number of local SGD *steps*
    # per round, each on one randomly sampled batch (FedAvgEnsTrainer.py:66-75).
    epochs: int = 5
    comm_round: int = 200
    frequency_of_the_test: int = 5

    # --- drift simulation ------------------------------------------------
    train_iterations: int = 10         # number of simulated time steps T
    sample_num: int = 500              # samples per client per time step
    concept_drift_algo: str = "softcluster"
    concept_drift_algo_arg: str = "H_A_C_1_10_0"
    concept_num: int = 4               # model-pool size M (and #concepts)
    drift_together: int = 0
    change_points: str = "A"           # preset name, 'rand', or matrix literal
    time_stretch: int = 1
    noise_prob: float = 0.0
    ensemble_window: int = 3           # AUE window (main_fedavg.py)
    retrain_data: str = "win-1"        # for single-model continual baselines
    report_client: int = 1
    # stackoverflow_lr scale (reference: vocab 10000 / 500 tags; defaults are
    # scaled down so the dense [C, T, N, F] array stays small — data/tabular.py)
    so_vocab_size: int = 1000
    so_tag_size: int = 50
    text_seq_len: int = 80             # char-dataset sequence length
                                       # (reference LEAF shakespeare: 80 =
                                       # data/text.py SEQ_LEN; shrink for CPU
                                       # smokes — the drift semantics are
                                       # length-independent)
    token_vocab: int = 16032           # ids of dataset "token_drift": the rows
                                       # of its vocabulary that the model holds

    # --- reproducibility & numerics -------------------------------------
    seed: int = 0                      # reference --dummy_arg (main_fedavg.py:292-298)
    dtype: str = "float32"             # param dtype; compute can be bfloat16
    compute_dtype: str = "bfloat16"    # bf16 matmuls/convs on TPU (runner._make_apply)
    # End-to-end precision policy (core/precision.py; docs/PERFORMANCE.md
    # "Precision policy"): "auto" keeps the historical dtype/compute_dtype
    # behavior (bf16 apply-boundary on TPU only); "f32" / "bf16_mixed" /
    # "bf16_pure" select a preset on every backend — bf16 storage halves
    # resident HBM, streamed bytes and wire frames (CPU runs it emulated).
    precision: str = "auto"
    remat: bool = False                # jax.checkpoint the forward (HBM <-> FLOPs)
    # How the round programs run the (model, client) pairs (core/step.py):
    # "vmap" trains all M x C pairs at once and keeps [M, C, ...] stacks of
    # parameters, gradients and optimizer state; "scan" takes one pair at a
    # time, skips pairs of zero weight and adds each result into one running
    # weighted sum: for a model whose M x C copies do not fit. What reads
    # an [M, C, ...] stack refuses "scan" (below).
    client_axis: str = "vmap"          # vmap | scan

    # --- TPU execution ---------------------------------------------------
    mesh_shape: dict[str, int] = field(default_factory=dict)  # e.g. {"clients": 8}
    # Stream data from host instead of keeping the full [C, T1, N, ...]
    # simulation device-resident: a [C, 2, N, ...] window (current + next
    # step) is consumed per iteration, prefetched one iteration ahead — at
    # most ~3 such windows exist transiently in HBM (held / staged / in
    # flight; data/prefetch.py). Requires an algorithm whose training window
    # is the current step only (win-1 family, supports_streaming trait).
    stream_data: bool = False
    # XLA cost-capture level for the tracked programs (obs/costmodel.py):
    # "off" | "lowered" (cost_analysis FLOPs/bytes at first compile; cheap,
    # no second XLA compile) | "compiled" (adds memory_analysis exact HBM
    # accounting at the price of one extra compile per program — bench.py).
    cost_model: str = "lowered"
    # Debug mode: validate round-input invariants every iteration and raise
    # inside the op that produces a NaN (utils/invariants.py).
    debug_checks: bool = False
    # Sanitizer mode (analysis/sanitize.py): flips jax_check_tracer_leaks +
    # jax_debug_nans and holds steady-state jit recompiles (the compile
    # tracker's jit_recompile events, after the first iteration's warm-up)
    # to an absolute budget — the run fails loudly instead of silently
    # recompiling the round program every block (the PR 10 class).
    sanitize: bool = False
    sanitize_recompile_budget: int = 8   # 0 = no budget, flags only
    out_dir: str = "./runs"
    checkpoint_every_iteration: bool = True

    # --- fault injection / failure detection (platform/faults.py; the
    # reference has neither — a dead client hangs its barrier, SURVEY.md §5)
    fault_dropout_prob: float = 0.0    # per-round transient client failure
    fault_seed: int = 0
    failure_patience: int = 3          # rounds absent before a client is suspected
    # Enable the injector/detector even with zero transient dropout — for
    # kill()-based permanent-failure / elastic-membership experiments.
    fault_enabled: bool = False

    # --- adversary model & robust aggregation (resilience/robust_agg.py,
    # platform/faults.py::ByzantineInjector; docs/RESILIENCE.md) ----------
    # Per-cluster aggregator over the stacked client updates. "mean" is the
    # historical sample-weighted FedAvg (bitwise-identical); the robust
    # strategies tolerate corrupted submissions at the cost of statistical
    # efficiency.
    robust_agg: str = "mean"       # mean | median | trimmed_mean | krum |
                                   # multi_krum | norm_clip
    robust_trim_frac: float = 0.2  # fraction trimmed from EACH end
    robust_krum_f: int = 1         # assumed Byzantine count (krum/multi_krum)
    robust_clip_norm: float = 1.0  # L2 bound on client diffs (norm_clip)
    robust_dp_stddev: float = 0.0  # weak-DP noise on the aggregate (any agg)
    # Byzantine clients: comma-separated indices ("0,3,7"); empty = none.
    byzantine_clients: str = ""
    byzantine_mode: str = "sign_flip"  # sign_flip | scale | gauss |
                                       # stale_replay | label_flip
    byzantine_scale: float = 10.0  # λ for sign_flip / scale attacks
    byzantine_std: float = 1.0     # stddev of the gauss attack
    byzantine_prob: float = 1.0    # per-round activation probability
    byzantine_seed: int = 0
    # Staleness-aware clustering decisions: accuracy-matrix entries of
    # clients absent >= this many rounds (or FailureDetector-suspected) are
    # EXCLUDED from drift triggers / cluster-distance computations instead
    # of silently reused. 0 disables (historical behavior).
    acc_staleness_limit: int = 0
    # Zero the aggregation weight of FailureDetector-suspected clients (the
    # detector still observes genuine liveness, so a client that comes back
    # clears its suspicion and rejoins).
    exclude_suspected_from_agg: bool = False

    # --- resilience (feddrift_tpu/resilience/; docs/RESILIENCE.md) -------
    # SIGTERM/SIGINT -> checkpoint at the next iteration boundary + clean
    # exit (preemptible TPU VMs). Main-thread only; harmless elsewhere.
    preempt_signals: bool = True
    # Numeric divergence guard: NaN/Inf or loss-spike detection on the
    # fetched round losses, rollback to pre-round params, abort after
    # divergence_max_rollbacks CONSECUTIVE rollbacks. The guard never
    # alters a healthy trajectory — it only adds a small per-round host
    # fetch on the per-round execution path.
    divergence_guard: bool = True
    divergence_spike_factor: float = 10.0  # x window-peak loss that counts as a spike
    divergence_max_rollbacks: int = 3      # consecutive rollbacks before abort
    divergence_warmup_rounds: int = 5      # healthy rounds before spike arms

    # --- population-scale participation (platform/registry.py,
    # resilience/participation.py; docs/RESILIENCE.md "Participation
    # model"). population_size > 0 switches the run from the legacy dense
    # lockstep loop (every registered client in every round) to
    # cohort-sampled rounds: a host-side ClientRegistry tracks the whole
    # population, a seeded sampler draws a fixed-size cohort each
    # iteration, and the device programs only ever see the cohort axis —
    # growing the population never changes an XLA program shape.
    population_size: int = 0       # registered clients; 0 = legacy dense
    cohort_size: int = 0           # aggregation target per round
                                   # (0 -> client_num_in_total)
    cohort_overprovision: int = 0  # extra sampled clients hedging stragglers
    cohort_seed: int = 0           # cohort schedule seed (pure fn of (seed, t))
    # Deadline-based partial aggregation: the round closes at
    # round_deadline (simulated latency units); sampled clients whose
    # simulated latency exceeds it are masked out of the aggregation
    # (straggler_masked). Below quorum_frac * cohort_size on-time clients
    # the round degrades gracefully: params are kept, round_degraded is
    # emitted, and the RNG/eval cadence still advances.
    round_deadline: float = 1.0
    quorum_frac: float = 0.5
    # Seeded straggler injection (platform/faults.py::StragglerInjector):
    # each sampled client independently misses the deadline with
    # straggler_prob; a persistent straggler_slow_frac of the population
    # additionally misses it with probability ~0.9 every round.
    straggler_prob: float = 0.0
    straggler_slow_frac: float = 0.0
    straggler_seed: int = 0
    # Seeded population churn (platform/faults.py::ChurnSchedule): each
    # iteration every active member leaves with churn_leave_prob and every
    # inactive member (re)joins with churn_join_prob — join/leave/flap.
    churn_leave_prob: float = 0.0
    churn_join_prob: float = 0.0
    churn_seed: int = 0

    # --- hierarchical two-tier aggregation (platform/hierarchical.py,
    # platform/faults.py::EdgeFaultInjector; docs/RESILIENCE.md
    # "Hierarchical aggregation"). hierarchy_edges > 0 routes every round
    # through client -> edge -> server: each edge closes its round with
    # edge_robust_agg applied WITHIN its group, then the server applies
    # server_robust_agg ACROSS the edge summaries — f Byzantine clients
    # inside one edge are contained at that edge, a fully compromised edge
    # is rejected at the top tier.
    hierarchy_edges: int = 0           # E edge groups; 0 = flat legacy path
    hierarchy_assign: str = "contiguous"  # contiguous | round_robin
    edge_robust_agg: str = "mean"      # within-edge aggregator (robust_agg registry)
    server_robust_agg: str = "mean"    # cross-edge aggregator (robust_agg registry)
    edge_quorum_frac: float = 0.5      # min fraction of live edges per round
    # Seeded edge-level fault injection: transient crash, stall past the
    # round_deadline, or a corrupted (sign-flipped) summary, each drawn
    # independently per edge per round.
    edge_crash_prob: float = 0.0
    edge_stall_prob: float = 0.0
    edge_corrupt_prob: float = 0.0
    edge_fault_seed: int = 0
    # Scheduled permanent edge kill (global round index; -1 = never):
    # clients of the dead edge are deterministically re-homed to surviving
    # edges from the next round on (edge_rehomed evidence).
    edge_kill_round: int = -1
    edge_kill_edge: int = 0

    # --- wire compression (comm/compress.py; docs/RESILIENCE.md) ---------
    # Codec applied to client->edge (and edge->server) update diffs. The
    # lossy effect is simulated inside the device program (the aggregate
    # sees exactly what decode(encode(update)) would yield); real framing +
    # sha256 digests ride the broker path (bench.py --hierarchy, tests).
    compress_codec: str = "none"       # none | int8 | topk | delta
    compress_topk_frac: float = 0.4    # fraction of coordinates kept by topk

    # --- secure aggregation (resilience/secure_round.py; docs/RESILIENCE.md
    # "Secure aggregation"). secure_agg != "off" replaces the per-round
    # server aggregation with a masked secure sum: each cohort client's
    # quantized weighted delta is degree-T Shamir-shared across the cohort
    # (shamir) or pushed through the Turbo-Aggregate multi-group ring
    # (turbo); the server only ever opens the sum. A share-holder past the
    # round_deadline is masked out (>= T+1 survivors reconstruct), a
    # below-threshold round keeps prev params with secure_degraded
    # evidence. Requires the flat per-round path: robust_agg == "mean",
    # hierarchy_edges == 0, megastep_k == 1, stream_data off.
    secure_agg: str = "off"            # off | shamir | turbo
    secure_threshold_t: int = 1        # T: tolerated holder dropouts / collusion
    secure_scale_bits: int = 16        # fixed-point scale = 2**bits
    secure_group_size: int = 0         # turbo ring stage width (0 = auto)
    # Seeded per-share fault injection (platform/faults.py::ShareDropInjector):
    # drop/delay/corrupt one share, or stall a whole share-holder.
    secure_drop_prob: float = 0.0
    secure_delay_prob: float = 0.0
    secure_corrupt_prob: float = 0.0
    secure_holder_stall_prob: float = 0.0
    secure_fault_seed: int = 0

    # --- decision observability (obs/alerts.py; docs/OBSERVABILITY.md) --
    # Live rule-based health monitor tapping the event bus: cluster-count
    # churn, oracle-ARI collapse, divergence+Byzantine co-occurrence,
    # eval-gap stall, client outages -> alert_raised events + alerts.jsonl.
    alerts: bool = True
    alert_window: int = 3           # churn window (iterations)
    alert_churn_threshold: int = 4  # structural cluster events per window
    # Size cap (MiB) on events.jsonl / spans.jsonl before rotation to
    # <file>.1 with a loud obs_rotated event; 0 = unbounded (default).
    obs_max_file_mb: float = 0.0
    # Host-plane sampling profiler (obs/hostprof.py; docs/OBSERVABILITY.md
    # "Host-plane observatory"): wall-clock stack samples per second taken
    # by a daemon thread over sys._current_frames(). 0 = off (default);
    # when on, the coordinator writes hostprof.jsonl (merged into
    # report --trace) and hostprof.folded (flamegraph input) to the run
    # dir. The per-subsystem HostLedger runs regardless of this knob.
    hostprof_hz: float = 0.0
    # --- live ops plane (obs/live.py; docs/OBSERVABILITY.md) ------------
    # HTTP ops endpoint (/metrics, /healthz, /status) on a background
    # thread. 0 = disabled (default, zero hot-path work); -1 = bind an
    # ephemeral port (tests / several processes on one host — read it
    # back from Experiment.ops.port); > 0 = that port.
    ops_port: int = 0
    # Iterations between local ops_snapshot events while the ops plane
    # is enabled (the fleet publisher has its own wall-clock cadence).
    ops_snapshot_every: int = 1
    # SLO objectives (0 = that objective disabled). Any non-zero value —
    # or an enabled ops plane — attaches the SLO burn-rate engine to the
    # event tap; burns emit slo_burn events and append to alerts.jsonl.
    slo_rounds_per_s: float = 0.0       # throughput floor (rounds/s)
    slo_host_overhead: float = 0.0      # host_overhead_frac ceiling
    slo_p99_round_wall_s: float = 0.0   # per-round wall p99 ceiling (s)
    slo_eval_gap: float = 0.0           # train-test accuracy gap ceiling
    slo_model_accuracy: float = 0.0     # serving joined-label accuracy floor
    # --- model-quality plane (obs/quality.py, platform/canary.py;
    # docs/OBSERVABILITY.md "Model-quality plane") ----------------------
    # Streaming per-model quality on the serving read path: a delayed-
    # label joiner + windowed accuracy/confidence/entropy/ECE estimators.
    # quality_window = labeled requests between model_quality events
    # (0 = plane disabled); quality_ttl_s = prediction retention for the
    # request_id -> label join.
    quality_window: int = 0
    quality_ttl_s: float = 60.0
    # Lineage-aware shadow canarying of serving hot swaps: fraction of
    # affected-cluster traffic shadow-executed through the candidate
    # generation (0 = canarying off, cluster events swap immediately),
    # the labeled-comparison sample floor before a verdict, and the
    # accuracy margin the candidate may lose before rollback.
    canary_fraction: float = 0.0
    canary_min_samples: int = 32
    canary_acc_margin: float = 0.02
    # --- incident plane (obs/blackbox.py, obs/incident.py;
    # docs/OBSERVABILITY.md "Incident plane") ---------------------------
    # Always-on flight recorder (bounded in-memory rings over recent
    # events/alerts/round_breakdowns) + automatic incident bundles under
    # <run_dir>/incidents/ on crit alerts, SLO burns, replica deaths,
    # secure-agg degradation, divergence aborts, preemption, unhandled
    # exceptions and SIGQUIT. Triage: python -m feddrift_tpu incident.
    incident_capture: bool = True
    incident_ring: int = 512            # flight-recorder capacity (records)
    incident_debounce_s: float = 30.0   # min seconds between bundles
    incident_max_bundles: int = 8       # oldest bundles pruned past this

    def __post_init__(self) -> None:
        if self.population_size == 0 \
                and self.client_num_per_round > self.client_num_in_total:
            raise ValueError("client_num_per_round > client_num_in_total")
        if self.population_size < 0:
            raise ValueError("population_size must be >= 0")
        if self.population_size > 0:
            if self.population_size < self.cohort_slots:
                raise ValueError(
                    f"population_size={self.population_size} < cohort slots "
                    f"{self.cohort_slots} (cohort_size + cohort_overprovision)")
            if self.fault_dropout_prob > 0 or self.fault_enabled:
                raise ValueError(
                    "fault injection (fault_dropout_prob/fault_enabled) is a "
                    "dense-pool mechanism; with population_size > 0 use "
                    "straggler_prob / churn_*_prob instead")
            if self.byzantine_clients.strip():
                raise ValueError(
                    "byzantine_clients indexes the dense client axis and is "
                    "not yet supported with population_size > 0")
            if self.stream_data:
                raise ValueError(
                    "stream_data and population_size are mutually exclusive: "
                    "population mode already stages only the cohort's shard")
        if self.cohort_size < 0 or self.cohort_overprovision < 0:
            raise ValueError("cohort_size/cohort_overprovision must be >= 0")
        if self.hostprof_hz < 0:
            raise ValueError(
                "hostprof_hz must be >= 0 (0 disables the sampling profiler)")
        if self.round_deadline <= 0:
            raise ValueError("round_deadline must be > 0")
        if not 0.0 < self.quorum_frac <= 1.0:
            raise ValueError("quorum_frac must be in (0, 1]")
        if not 0.0 <= self.straggler_prob < 1.0:
            raise ValueError("straggler_prob must be in [0, 1)")
        if not 0.0 <= self.straggler_slow_frac <= 1.0:
            raise ValueError("straggler_slow_frac must be in [0, 1]")
        for p in (self.churn_leave_prob, self.churn_join_prob):
            if not 0.0 <= p < 1.0:
                raise ValueError("churn probabilities must be in [0, 1)")
        if self.time_stretch < 1:
            raise ValueError("time_stretch must be >= 1")
        if self.megastep_k < 1:
            raise ValueError("megastep_k must be >= 1")
        if self.sanitize_recompile_budget < 0:
            raise ValueError("sanitize_recompile_budget must be >= 0")
        if self.decision_cadence < 1:
            raise ValueError("decision_cadence must be >= 1")
        if self.divergence_spike_factor <= 1.0:
            raise ValueError("divergence_spike_factor must be > 1")
        if self.divergence_max_rollbacks < 1:
            raise ValueError("divergence_max_rollbacks must be >= 1")
        if self.robust_agg not in ("mean", "median", "trimmed_mean", "krum",
                                   "multi_krum", "norm_clip"):
            raise ValueError(f"unknown robust_agg {self.robust_agg!r}")
        if not 0.0 <= self.robust_trim_frac < 0.5:
            raise ValueError("robust_trim_frac must be in [0, 0.5)")
        if self.robust_krum_f < 0:
            raise ValueError("robust_krum_f must be >= 0")
        if not 0.0 <= self.byzantine_prob <= 1.0:
            raise ValueError("byzantine_prob must be in [0, 1]")
        if self.acc_staleness_limit < 0:
            raise ValueError("acc_staleness_limit must be >= 0")
        if self.alert_window < 1:
            raise ValueError("alert_window must be >= 1")
        if self.alert_churn_threshold < 1:
            raise ValueError("alert_churn_threshold must be >= 1")
        if self.obs_max_file_mb < 0:
            raise ValueError("obs_max_file_mb must be >= 0")
        if self.ops_port < -1 or self.ops_port > 65535:
            raise ValueError("ops_port must be -1 (ephemeral), 0 (off) "
                             "or a TCP port")
        if self.ops_snapshot_every < 1:
            raise ValueError("ops_snapshot_every must be >= 1")
        for name in ("slo_rounds_per_s", "slo_host_overhead",
                     "slo_p99_round_wall_s", "slo_eval_gap"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0 (0 disables)")
        if self.slo_host_overhead > 1.0:
            raise ValueError("slo_host_overhead is a fraction in (0, 1]")
        if not 0.0 <= self.slo_model_accuracy <= 1.0:
            raise ValueError("slo_model_accuracy must be in [0, 1] "
                             "(0 disables)")
        if self.quality_window < 0:
            raise ValueError("quality_window must be >= 0 (0 disables)")
        if self.quality_ttl_s <= 0:
            raise ValueError("quality_ttl_s must be > 0")
        if not 0.0 <= self.canary_fraction <= 1.0:
            raise ValueError("canary_fraction must be in [0, 1] "
                             "(0 disables)")
        if self.canary_min_samples < 1:
            raise ValueError("canary_min_samples must be >= 1")
        if not 0.0 <= self.canary_acc_margin <= 1.0:
            raise ValueError("canary_acc_margin must be in [0, 1]")
        if self.incident_ring < 8:
            raise ValueError("incident_ring must be >= 8 records")
        if self.incident_debounce_s < 0:
            raise ValueError("incident_debounce_s must be >= 0")
        if self.incident_max_bundles < 1:
            raise ValueError("incident_max_bundles must be >= 1")
        if self.hierarchy_edges < 0:
            raise ValueError("hierarchy_edges must be >= 0")
        if self.hierarchy_edges > 0:
            if self.hierarchy_edges > self.device_clients:
                raise ValueError(
                    f"hierarchy_edges={self.hierarchy_edges} > device client "
                    f"axis {self.device_clients}")
            if self.hierarchy_assign not in ("contiguous", "round_robin"):
                raise ValueError(
                    f"unknown hierarchy_assign {self.hierarchy_assign!r}")
            for name in (self.edge_robust_agg, self.server_robust_agg):
                if name not in ("mean", "median", "trimmed_mean", "krum",
                                "multi_krum", "norm_clip"):
                    raise ValueError(f"unknown tier aggregator {name!r}")
            if self.robust_agg != "mean":
                raise ValueError(
                    "hierarchy_edges > 0 replaces the flat aggregator with "
                    "edge_robust_agg/server_robust_agg; leave robust_agg at "
                    "'mean'")
            if not 0.0 < self.edge_quorum_frac <= 1.0:
                raise ValueError("edge_quorum_frac must be in (0, 1]")
            for p in (self.edge_crash_prob, self.edge_stall_prob,
                      self.edge_corrupt_prob):
                if not 0.0 <= p < 1.0:
                    raise ValueError("edge fault probabilities must be in [0, 1)")
            if self.edge_kill_round >= 0 \
                    and not 0 <= self.edge_kill_edge < self.hierarchy_edges:
                raise ValueError("edge_kill_edge out of range")
        if self.compress_codec not in ("none", "int8", "topk", "delta"):
            raise ValueError(f"unknown compress_codec {self.compress_codec!r}")
        if not 0.0 < self.compress_topk_frac <= 1.0:
            raise ValueError("compress_topk_frac must be in (0, 1]")
        if self.secure_agg not in ("off", "shamir", "turbo"):
            raise ValueError(f"unknown secure_agg {self.secure_agg!r}")
        if self.secure_agg != "off":
            # reconstruction-possibility bound (platform/secure_agg.py:
            # validate_threshold): N cohort share-holders tolerating T
            # dropouts need N >= 2T+1
            if self.secure_threshold_t < 1:
                raise ValueError("secure_threshold_t must be >= 1")
            if self.device_clients < 2 * self.secure_threshold_t + 1:
                raise ValueError(
                    f"secure_agg needs a cohort of >= 2T+1 = "
                    f"{2 * self.secure_threshold_t + 1} clients to tolerate "
                    f"T={self.secure_threshold_t} dropped share-holders; "
                    f"got {self.device_clients}")
            if not 1 <= self.secure_scale_bits <= 24:
                raise ValueError("secure_scale_bits must be in [1, 24]")
            for p in (self.secure_drop_prob, self.secure_delay_prob,
                      self.secure_corrupt_prob,
                      self.secure_holder_stall_prob):
                if not 0.0 <= p < 1.0:
                    raise ValueError(
                        "secure fault probabilities must be in [0, 1)")
            # the secure path recomputes the flat weighted mean from the
            # per-client stack each round; fused/hierarchical/robust
            # variants would silently bypass the protocol
            if self.robust_agg != "mean":
                raise ValueError("secure_agg requires robust_agg == 'mean'")
            if self.hierarchy_edges > 0:
                raise ValueError("secure_agg requires hierarchy_edges == 0")
            if self.megastep_k != 1:
                raise ValueError("secure_agg requires megastep_k == 1")
            if self.stream_data:
                raise ValueError("secure_agg requires stream_data off")
        if self.client_axis not in ("vmap", "scan"):
            raise ValueError(f"unknown client_axis {self.client_axis!r}")
        if self.client_axis == "scan":
            # the scanned body holds no [M, C, ...] stack of any kind
            needs_stack = [
                name for name, on in (
                    (f"client_optimizer={self.client_optimizer!r} (its state "
                     "is kept per pair; only 'sgd' has none)",
                     self.client_optimizer != "sgd"),
                    (f"robust_agg={self.robust_agg!r}",
                     self.robust_agg != "mean"),
                    ("byzantine_clients", bool(self.byzantine_clients.strip())),
                    (f"compress_codec={self.compress_codec!r}",
                     self.compress_codec != "none"),
                    ("hierarchy_edges", self.hierarchy_edges > 0),
                    (f"secure_agg={self.secure_agg!r}",
                     self.secure_agg != "off"),
                    ("CFL (concept_drift_algo_arg 'cfl_...')",
                     "cfl" in self.concept_drift_algo_arg),
                    ("megastep_k > 1", self.megastep_k > 1)) if on]
            if needs_stack:
                raise ValueError(
                    "client_axis='scan' keeps no [M, C, ...] parameter, "
                    f"gradient or optimizer stack, which "
                    f"{', '.join(needs_stack)} "
                    f"need{'s' if len(needs_stack) == 1 else ''}; use "
                    "client_axis='vmap'")
        if self.precision not in ("auto", "f32", "bf16_mixed", "bf16_pure"):
            raise ValueError(f"unknown precision {self.precision!r}")
        for name in ("dtype", "compute_dtype"):
            if getattr(self, name) not in ("float32", "bfloat16"):
                raise ValueError(f"{name} must be float32 or bfloat16")

    # ------------------------------------------------------------------
    @property
    def cohort_slots(self) -> int:
        """Device-visible client-axis size in population mode: the
        aggregation target plus the straggler hedge. XLA programs are
        shaped by THIS, never by ``population_size`` — that is the whole
        compile-count-invariance contract."""
        return (self.cohort_size or self.client_num_in_total) \
            + self.cohort_overprovision

    @property
    def device_clients(self) -> int:
        """Size of the client axis the device programs see: the sampled
        cohort in population mode, every client in the legacy dense mode."""
        return self.cohort_slots if self.population_size > 0 \
            else self.client_num_in_total

    @property
    def data_clients(self) -> int:
        """Number of clients the dataset is generated for: the whole
        registered population in population mode."""
        return self.population_size or self.client_num_in_total

    @property
    def byzantine_client_list(self) -> list[int]:
        """Parsed ``byzantine_clients`` indices (empty list = no adversary)."""
        s = self.byzantine_clients.strip()
        return [int(tok) for tok in s.split(",") if tok.strip()] if s else []

    # ------------------------------------------------------------------
    @property
    def base_dataset(self) -> str:
        """Dataset name with task-family suffixes stripped — the key for
        per-dataset tables (deltas) that are indexed by the underlying
        task, not the sampler variant ("MNIST-smooth" uses MNIST's
        deltas)."""
        return self.dataset.removesuffix("-smooth")

    @property
    def num_models(self) -> int:
        """Size M of the static model pool (reference caps at concept_num)."""
        if self.concept_drift_algo == "aue" or self.concept_drift_algo == "auepc":
            return self.ensemble_window
        if self.concept_drift_algo == "driftsurf":
            return 2  # pred + (stab|reac), DriftSurfState at FedAvgEnsDataLoader.py:151
        if self.concept_drift_algo in ("ada", "win-1", "all", "exp", "lin",
                                       "oblivious", "window"):
            return 1
        return self.concept_num

    def algo_params(self) -> dict[str, Any]:
        """Parse ``concept_drift_algo_arg`` exactly as the reference does.

        FedDrift:   "H_{distance}_{cluster}_{W}_{100*delta}_{100*delta'}"
                    (FedAvgEnsDataLoader.py:1301-1310)
        CFL:        "cfl_{gamma}_{win-1|all}"      (:1311-1313)
        mmacc:      "mmacc_{100*delta}"            (:1292-1295)
        softmax:    "softmax_{alpha}"              (:1296-1297)
        ada:        "{win-1|all}_{round|iter}"     (:137-138)
        driftsurf:  "{100*delta}"                  (:276-278)
        """
        arg = self.concept_drift_algo_arg
        out: dict[str, Any] = {"raw": arg}
        # Per-algorithm arg grammars come first: the reference parses each
        # algo's arg inside its own loader, so e.g. driftsurf's "{100*delta}"
        # must never be interpreted through softcluster's string patterns.
        if self.concept_drift_algo == "driftsurf":
            delta = 0.01 * float(arg) if arg and arg.replace(".", "").isdigit() else 0.0
            if delta == 0:
                delta = DRIFTSURF_DELTAS.get(self.base_dataset, 0.1)
            out.update(kind="driftsurf", delta=delta)
            return out
        if self.concept_drift_algo == "ada":
            parts = arg.split("_")
            out.update(kind="ada",
                       ada_retrain=parts[0] if parts[0] in ("win-1", "all") else "win-1",
                       ada_update=parts[1] if len(parts) > 1 else "round")
            return out
        if "mmacc" in arg:
            delta = 0.01 * float(arg.split("_")[-1])
            if delta == 0:
                delta = DEFAULT_DELTAS.get(self.base_dataset, 0.1)
            out.update(kind="mmacc", mmacc_delta=delta)
        elif "softmax" in arg:
            out.update(kind="softmax", softmax_alpha=int(arg.split("_")[-1]))
        elif arg == "geni":
            out.update(kind="geni")
        elif arg.startswith("H"):
            parts = arg.split("_")
            h_delta = 0.01 * float(parts[4])
            if h_delta == 0:
                h_delta = DEFAULT_DELTAS.get(self.base_dataset, 0.1)
            h_deltap = 0.01 * float(parts[5])
            if h_deltap == 0:
                h_deltap = h_delta
            out.update(
                kind="hierarchical",
                h_distance=parts[1],
                h_cluster=parts[2],
                h_w=int(parts[3]),
                h_delta=h_delta,
                h_deltap=h_deltap,
            )
        elif "cfl" in arg:
            parts = arg.split("_")
            out.update(kind="cfl", cfl_gamma=float(parts[1]), cfl_retrain=parts[2])
        elif arg in ("hard", "hard-r"):
            out.update(kind=arg)
        else:
            out.update(kind=arg or "none")
        return out

    # ------------------------------------------------------------------
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ExperimentConfig":
        d = json.loads(s)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})
