"""Benchmark: FedDrift canonical config throughput on the TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}, and
every result in it names the device it was measured on (``platform``,
``device_kind``, ``device_count``, as JAX reports them in this process).
Without a TPU the benchmark exits non-zero; ``--cpu`` is the explicit test
mode (correctness and counts only — its numbers are labelled ``cpu`` and
carry no MFU or roofline). Any config or requested profile capture that
fails fails the run.

Config: the reference's canonical run (README.md:46-50): SEA-4, 10 clients,
fnn, 200 rounds x 5 local steps per time step, batch 500, lr 0.01, 500
samples/client/step. We measure steady-state communication-round throughput
(train_round + the periodic eval), which is the quantity the reference logs
per round ("aggregate time cost", FedAvgEnsAggregatorSoftCluster.py:193-194).

Baseline: the reference publishes no numbers (BASELINE.md), so
``vs_baseline`` is measured, not assumed: before the timed run we execute
the same canonical config on THIS HOST's CPU through the per-round
dispatch path (cfg.chunk_rounds=False — one host->device dispatch and one
eval fetch per round, the closest shape to the reference's per-round
message loop) for a short sample and extrapolate rounds/s.  The reported
ratio is therefore "device fused path vs this host's CPU per-round path";
it is an intra-framework speedup, NOT a measured reference-GPU comparison.
Run with --smoke for a fast CI-sized check.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax

def _canonical_cfg(smoke: bool, **overrides):
    from feddrift_tpu.config import ExperimentConfig

    base = dict(
        dataset="sea", model="fnn", concept_drift_algo="softcluster",
        concept_drift_algo_arg="H_A_C_1_10_0", concept_num=4,
        change_points="A",
        client_num_in_total=10, client_num_per_round=10,
        train_iterations=3 if smoke else 10,
        comm_round=20 if smoke else 200,
        epochs=5, batch_size=500, sample_num=100 if smoke else 500,
        lr=0.01, frequency_of_the_test=10,
        # honest phase attribution: block on device output inside each
        # traced phase so async dispatch can't bill train time to eval
        trace_sync=True,
        # full XLA memory accounting (obs/costmodel.py): the benchmark is
        # exactly where the extra per-program compile is worth exact
        # peak-HBM numbers (and the persistent compile cache halves it)
        cost_model="compiled",
        report_client=0)
    base.update(overrides)
    return ExperimentConfig(**base)


def _json_from_subprocess(cmd: list[str], timeout: float, tag: str) -> dict:
    """Run cmd and return the last JSON line of its stdout. A baseline that
    crashes, times out or prints no result fails the run, with the child's
    stderr tail in the error."""
    import subprocess

    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(f"{tag} timed out after {timeout:.0f}s") from e
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    raise RuntimeError(f"{tag} exited {out.returncode} with no JSON result; "
                       f"stderr tail: {(out.stderr or '')[-600:]}")


# The two CPU-side baselines are backend-independent and cost tens of
# minutes on one host core, so they are cached on disk across invocations.
_BASELINE_CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               ".bench_baseline_cache.json")


def _code_version() -> str:
    """Content hash of the measured code path (the framework package plus
    this file), so cached baselines are invalidated by any perf-relevant
    change (round-3 advisor: a baseline measured before e.g. a sampler
    restructure must not skew vs_baseline after it) — but survive doc-only
    commits, which on this 1-core host would otherwise re-pay ~35 min."""
    import hashlib

    root = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    paths = [os.path.join(root, "bench.py")]
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "feddrift_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        paths.extend(os.path.join(dirpath, f)
                     for f in filenames if f.endswith((".py", ".cpp")))
    for p in sorted(paths):
        try:
            with open(p, "rb") as f:
                h.update(os.path.relpath(p, root).encode())
                h.update(f.read())
        except OSError:
            pass
    return h.hexdigest()[:12]


def _baseline_cache(key: str, measure):
    key = f"{key}@{_code_version()}"
    try:
        with open(_BASELINE_CACHE) as f:
            cache = json.load(f)
    except (OSError, json.JSONDecodeError):
        cache = {}
    if key in cache:
        return cache[key]
    val = measure()
    if val is not None:
        cache[key] = val
        # prune entries from older code versions: each is a multi-minute
        # measurement keyed by a hash that will never be looked up again,
        # so without this the cache grows one dead entry per perf-relevant
        # commit (suffix comes from the already-built key — no second
        # package-tree hash walk)
        suffix = "@" + key.rsplit("@", 1)[1]
        cache = {k: v for k, v in cache.items()
                 if k.endswith(suffix) or "@" not in k}
        try:
            with open(_BASELINE_CACHE, "w") as f:
                json.dump(cache, f)
        except OSError:
            pass
    return val


def _measure_cpu_baseline(smoke: bool) -> float:
    """Rounds/s of the canonical config on this host's CPU through the
    PER-ROUND dispatch path (chunk_rounds=False) — the measured stand-in
    for the reference's per-round message loop. Runs in a subprocess that
    pins the CPU platform before any backend exists, so it never asks for
    the chip this process holds."""
    code = (
        "import jax, json, time;"
        "jax.config.update('jax_platforms', 'cpu');"
        "import bench;"
        "from feddrift_tpu.utils.cache import enable_compile_cache;"
        "enable_compile_cache();"
        "from feddrift_tpu.simulation.runner import Experiment;"
        f"cfg = bench._canonical_cfg({smoke}, train_iterations=3, "
        "comm_round=20, chunk_rounds=False);"
        "exp = Experiment(cfg);"
        # warm-up t=0 AND t=1: t>=1 is the first trace of the acc_cells /
        # merge path (same reason the main measurement starts at t=2)
        "exp.run_iteration(0); exp.run_iteration(1);"
        "t0 = time.time(); exp.run_iteration(2);"
        "jax.block_until_ready(exp.pool.params);"
        "print(json.dumps({'rps': cfg.comm_round / (time.time() - t0)}))")
    d = _json_from_subprocess([sys.executable, "-c", code], 1200,
                              "cpu baseline")
    return float(d["rps"])


def _measure_reference_shape() -> dict:
    """Cross-framework datapoint: the reference's execution shape
    (per-model torch loops, Adam steps, pickled state_dict transport,
    weighted averaging — scripts/reference_shape_bench.py) timed on this
    host's CPU in a subprocess. Complements the intra-framework baseline:
    same canonical config, same silicon, different framework."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "scripts", "reference_shape_bench.py")
    return _json_from_subprocess([sys.executable, script], 900,
                                 "reference-shape baseline")


def _dispatch_rtt() -> dict:
    """Per-dispatch round-trip latency of a trivial compiled op: the floor
    every host->device->host dispatch pays, which bounds tiny-model configs
    from below whatever the chip's speed."""
    import jax.numpy as jnp

    f = jax.jit(lambda v: v + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    jax.block_until_ready(f(x))           # compile outside the timing
    ts = []
    for _ in range(30):
        t0 = time.time()
        jax.block_until_ready(f(x))
        ts.append(time.time() - t0)
    ts.sort()
    return {"median_ms": round(1e3 * ts[len(ts) // 2], 3),
            "p90_ms": round(1e3 * ts[int(len(ts) * 0.9)], 3),
            "n": len(ts)}


def _profile_capture(cfg, profile_dir: str) -> str:
    """Capture a jax.profiler device trace of the config's fused programs on
    a SHORT replica run (4 time steps, 20 rounds each): the same compiled
    kernels as the headline measurement (compile cache shared), but trace
    collection never pollutes the timed steady state and the canonical
    rounds count keeps its defined scale. Returns the trace dir; a capture
    that was asked for and fails raises."""
    from dataclasses import replace

    from feddrift_tpu.simulation.runner import Experiment
    from feddrift_tpu.utils.tracing import xla_trace

    short = replace(cfg, train_iterations=4, comm_round=20)
    exp = Experiment(short)
    exp.run_iteration(0)                  # warm-up / compile (see _measure)
    exp.run_iteration(1)
    jax.block_until_ready(exp.pool.params)
    with xla_trace(profile_dir):
        exp.run_iteration(2)
        exp.run_iteration(3)
        jax.block_until_ready(exp.pool.params)
    return profile_dir


def _round_wall_quantiles(instruments: dict) -> dict | None:
    """Pull the per-round wall-time quantile digest out of a registry
    snapshot. The runner feeds the ``round_wall_seconds_q`` P² sketch once
    per iteration regardless of the ops plane, so steady-state p50/p95/p99
    are always available here; None before any timed iteration landed."""
    entry = instruments.get("round_wall_seconds_q")
    if not isinstance(entry, dict):
        return None
    q = entry.get("quantiles")
    return {k: round(v, 6) for k, v in q.items() if v is not None} \
        if q else None


def _measure(cfg) -> dict:
    """Run one config to steady state and return its measured numbers,
    labelled with the device of THIS process."""
    from feddrift_tpu import obs
    from feddrift_tpu.obs import costmodel
    from feddrift_tpu.simulation.runner import Experiment

    # Per-measurement program costs: a previous config's captured round
    # program must not feed this config's MFU.
    costmodel.clear()
    exp = Experiment(cfg)

    # Warm-up: run time steps 0 AND 1 fully — t=0 takes the cluster_init
    # branch only; t>=1 is the first to trace acc_cells / the hierarchical
    # merge path, so steady-state timing must start at t=2. The cost model
    # captures each program's XLA accounting at these first compiles.
    exp.run_iteration(0)
    exp.run_iteration(1)

    # Reset instruments AFTER warm-up so the snapshot attached to the
    # result covers exactly the timed steady state: compile counts here
    # mean steady-state retraces (ideally zero), and the phase_seconds
    # histograms are per-phase latency distributions of the measured rounds.
    # The per-program cost gauges were captured during warm-up and are
    # static facts of the compiled programs, so they are re-populated.
    obs.registry().reset()
    costmodel.refresh_gauges()

    # Timed steady state: the remaining time steps. Per-iteration
    # round_breakdown records (runner critical-path accounting) are
    # collected as they are emitted — host_overhead_frac is the gated
    # signal, the full segment stats ride along for attribution.
    breakdowns = []
    t0 = time.time()
    for t in range(2, cfg.train_iterations):
        exp.run_iteration(t)
        if exp.last_round_breakdown is not None:
            breakdowns.append(exp.last_round_breakdown)
    jax.block_until_ready(exp.pool.params)
    elapsed = time.time() - t0
    rounds = cfg.comm_round * (cfg.train_iterations - 2)
    rps = rounds / elapsed

    # MFU from the cost model: FLOPs/round preferring XLA's cost_analysis
    # of the captured round program (source "cost_analysis"; analytic
    # fallback otherwise) over the published peak of the chip this process
    # runs on (costmodel.DEVICE_PEAKS, keyed by device_kind). A CPU has no
    # peak: mfu_estimate and roofline are None there — not measured.
    from feddrift_tpu.core.precision import resolve_precision
    device = costmodel.device_info()
    effective_dtype = resolve_precision(cfg).compute_dtype
    flops_round, flops_source = costmodel.round_flops(exp)
    peak, peak_source = costmodel.peak_flops(device["device_kind"],
                                             effective_dtype)
    mfu = round(flops_round * rps / peak, 6) if peak else None
    roofline = costmodel.roofline(
        flops_round * rounds,
        (costmodel.round_bytes(exp) or 0) * rounds or None,
        elapsed, device["device_kind"], effective_dtype)

    # Peak HBM: XLA's static memory_analysis of the captured programs
    # (cost_model="compiled") plus the live device watermark where the
    # backend has allocator stats (None on CPU — graceful).
    costmodel.record_hbm_watermark()
    hbm_peak = costmodel.hbm_peak_bytes()

    # Critical-path numbers over the timed iterations: mean host-overhead
    # fraction (the regress ceiling) + dispatch-gap stats. trace_sync=True
    # in the canonical config means every round is dispatch-to-ready
    # profiled, so the fraction is exact, not sampled.
    hofs = [b["host_overhead_frac"] for b in breakdowns]
    gaps = [b["dispatch_gap_s"] for b in breakdowns]
    host_overhead = (round(sum(hofs) / len(hofs), 6) if hofs else None)
    dispatch_gap = ({"mean_s": round(sum(gaps) / len(gaps), 6),
                     "max_s": round(max(gaps), 6),
                     "iterations": len(gaps)} if gaps else None)

    # Streaming tail latency: the runner feeds a P² sketch per timed
    # iteration (obs/quantiles.py), so the steady-state p50/p95/p99 of
    # per-round wall time ride the artifact without sample retention.
    instruments = obs.registry().snapshot()
    wall_q = _round_wall_quantiles(instruments)

    return {
        "value": round(rps, 3),
        "unit": "rounds/s",
        **device,
        "precision_policy": exp.precision.name,
        "final_test_acc": round(float(exp.logger.last("Test/Acc")), 4),
        "wall_s": round(elapsed, 2),
        "rounds": rounds,
        "mfu_estimate": mfu,
        "mfu": {"source": flops_source, "flops_per_round": flops_round,
                "peak_flops": peak, "peak_source": peak_source,
                "dtype": effective_dtype},
        "roofline": roofline,
        "hbm_peak_bytes": hbm_peak,
        "host_overhead_frac": host_overhead,
        "dispatch_gap": dispatch_gap,
        "round_wall_p99_s": (wall_q or {}).get("0.99"),
        "round_wall_quantiles": wall_q,
        "round_breakdown": (breakdowns[-1] if breakdowns else None),
        "program_costs": {fn: pc.to_event_fields()
                          for fn, pc in costmodel.costs().items()},
        "phases": getattr(exp, "last_phase_summary", None),
        # Cross-layer instrument snapshot for the steady state: compile /
        # recompile counts per program, phase_seconds histograms, program
        # cost + hbm_peak_bytes gauges, comm counters when a transport is
        # active (obs/instruments.py).
        "instruments": instruments,
    }


def _popscale_cfg(smoke: bool, population: int):
    """Fixed cohort, growing registered population: the population-scale
    participation axis (ISSUE 6). Straggler + churn chaos is ON so the
    measured path is the production-shaped one (masked rounds, registry
    bookkeeping), and the cohort geometry never changes — the whole point
    is that XLA programs are shaped by the cohort, not the population."""
    return _canonical_cfg(
        smoke, population_size=population, cohort_size=10,
        cohort_overprovision=2, straggler_prob=0.1,
        churn_leave_prob=0.01, churn_join_prob=0.02,
        sample_num=50, batch_size=50, train_iterations=4,
        comm_round=10 if smoke else 20,
        cost_model="lowered")     # exact-HBM capture not worth 3 extra compiles here


def _popscale_bench(smoke: bool) -> list:
    """rounds/s + steady-state recompile counts vs population size.

    The POPSCALE artifact the `regress` gate checks: throughput must hold
    within the rounds tolerance per population point and steady-state
    recompiles must stay ZERO as the population grows 10^2 -> 10^4."""
    from feddrift_tpu.obs.regress import _compile_counts
    out = []
    for population in (100, 1000) if smoke else (100, 1000, 10000):
        cfg = _popscale_cfg(smoke, population)
        r = _measure(cfg)
        _, recompiles = _compile_counts(r)
        out.append({
            "population": population,
            "cohort_slots": cfg.cohort_slots,
            "rounds_per_sec": r.get("value"),
            "final_test_acc": r.get("final_test_acc"),
            "wall_s": r.get("wall_s"),
            "steady_recompiles": recompiles,
        })
        print(json.dumps({"partial": f"popscale@{population}", **out[-1]}),
              file=sys.stderr)
    return out


def _instr_value(instruments: dict, name: str, **labels):
    """One series from a registry snapshot; keys are name{k="v"}."""
    if not labels:
        return instruments.get(name)
    key = name + "{" + ",".join(
        f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
    return instruments.get(key)


def _hostscale_cfg(smoke: bool, population: int):
    """The popscale geometry with the full host-plane observatory ON
    (sampling profiler + ledger): what we are measuring here is the HOST
    control plane's cost as the registered population grows, with the
    device program held fixed by the cohort shape."""
    cfg = _popscale_cfg(smoke, population)
    import dataclasses
    return dataclasses.replace(cfg, hostprof_hz=50.0)


def _hostscale_bench(smoke: bool) -> dict:
    """Per-subsystem host-seconds/round and host-bytes vs population P,
    with fitted log-log scaling exponents (ISSUE 19).

    The HOSTSCALE artifact the `regress` hostscale axis gates: the dense
    registry columns, assign_hist and cohort planning are O(P) by
    construction — this measures their actual exponents and bytes/client
    so the ROADMAP item-2 refactor has named numbers to beat. Seconds
    come from the host_ledger_seconds_total counters, which accumulate
    exactly the steady state because _measure resets the instrument
    registry after warm-up; bytes are the ledger's latest-value gauges."""
    from feddrift_tpu.obs.hostprof import SUBSYSTEMS, fit_scaling
    from feddrift_tpu.obs.regress import _compile_counts
    structures = ("registry_columns", "assign_hist", "routing_table",
                  "staged_shards")
    rows = []
    populations = (100, 1000) if smoke else (100, 1000, 10000, 100000)
    for population in populations:
        cfg = _hostscale_cfg(smoke, population)
        r = _measure(cfg)
        _, recompiles = _compile_counts(r)
        instr = r.get("instruments") or {}
        rounds = max(r.get("rounds") or 1, 1)
        sec = {}
        for sub in SUBSYSTEMS:
            total = _instr_value(instr, "host_ledger_seconds_total",
                                 subsystem=sub)
            sec[sub] = (round(total / rounds, 8)
                        if isinstance(total, (int, float)) else None)
        byt = {s: _instr_value(instr, "host_bytes", structure=s)
               for s in structures}
        rows.append({
            "population": population,
            "cohort_slots": cfg.cohort_slots,
            "rounds_per_sec": r.get("value"),
            "wall_s": r.get("wall_s"),
            "steady_recompiles": recompiles,
            "seconds_per_round": sec,
            "bytes": byt,
            "rss_peak_bytes": _instr_value(instr, "host_rss_peak_bytes"),
        })
        print(json.dumps({"partial": f"hostscale@{population}",
                          **rows[-1]}), file=sys.stderr)
    pops = [row["population"] for row in rows]
    exp_seconds = {
        sub: fit_scaling(pops, [(row["seconds_per_round"] or {}).get(sub)
                                for row in rows])
        for sub in SUBSYSTEMS}
    exp_bytes = {
        s: fit_scaling(pops, [(row["bytes"] or {}).get(s) for row in rows])
        for s in structures}
    top = rows[-1]
    bytes_per_client = {
        s: round(v / top["population"], 3)
        for s, v in (top["bytes"] or {}).items()
        if isinstance(v, (int, float)) and v > 0}
    return {
        "populations": pops,
        "rows": rows,
        "exp_seconds": {k: round(v, 4) if v is not None else None
                        for k, v in exp_seconds.items()},
        "exp_bytes": {k: round(v, 4) if v is not None else None
                      for k, v in exp_bytes.items()},
        "bytes_per_client": bytes_per_client,
    }


def _hierarchy_bench(smoke: bool) -> list:
    """Broker bytes/round per wire codec (ISSUE 8: verified compression on
    the update path). Backend-independent by design — the codecs are numpy
    on the wire, so the measurement is the negotiated sender/receiver pair
    over the real TCP broker, read off the broker_bytes_out counter (delta,
    not reset: the registry also carries this process's compile counters).

    The COMM artifact the `regress` gate checks: bytes/round per codec must
    not grow past the bytes tolerance, and every lossy codec must keep its
    >= 3x reduction over uncompressed."""
    import numpy as np

    from feddrift_tpu import obs
    from feddrift_tpu.comm.compress import (WIRE_CODECS, UpdateReceiver,
                                            UpdateSender)
    from feddrift_tpu.comm.netbroker import NetworkBroker, NetworkBrokerClient

    rng = np.random.RandomState(8)
    # mnist-fnn-shaped update (784 -> 128 -> 10): ~406 KB of float32 per
    # round — large enough that payload, not JSON framing, is what's timed
    shapes = [(784, 128), (128,), (128, 10), (10,)]
    layers = [rng.randn(*s).astype(np.float32) for s in shapes]
    rounds = 3 if smoke else 10

    def run(codec):
        obs.configure(None)
        ctr = obs.registry().counter("broker_bytes_out", transport="netbroker")
        before = ctr.value
        broker = NetworkBroker()
        try:
            ctx = NetworkBrokerClient(broker.host, broker.port)
            crx = NetworkBrokerClient(broker.host, broker.port)
            rx = UpdateReceiver(crx, "bench/update")
            tx = UpdateSender(ctx, "bench/update", codec=codec)
            for c in (ctx, crx):   # TCP subscribe is async: loopback sync
                q = c.subscribe("__sync__")
                c.publish("__sync__", "ready")
                assert q.get(timeout=10) == "ready"
            tx.offer()
            rx.serve_ctl(timeout=10.0)
            assert tx.wait_accept(timeout=10.0) == codec
            for r in range(rounds):
                for i, base_arr in enumerate(layers):
                    # evolving weights so the delta chain sees realistic
                    # round-over-round updates, not a constant tensor
                    arr = base_arr + 0.01 * r
                    tx.send(f"w{i}", arr)
                    assert rx.recv(timeout=10.0) is not None
            ctx.close(); crx.close()
        finally:
            broker.close()
        return ctr.value - before

    out = []
    raw = None
    for codec in WIRE_CODECS:
        total = run(codec)
        if codec == "none":
            raw = total
        out.append({
            "codec": codec,
            "rounds": rounds,
            "bytes_total": int(total),
            "bytes_per_round": round(total / rounds, 1),
            "ratio_vs_none": (round(raw / total, 2) if raw else None),
        })
        print(json.dumps({"partial": f"hierarchy@{codec}", **out[-1]}),
              file=sys.stderr)
    return out


def _secure_bench(smoke: bool) -> list:
    """Secure-aggregation axis (ISSUE 18): bytes/round + wall overhead vs
    plaintext for both masked round modes (shamir, turbo) at two cohort
    sizes, plus a short real training run per mode proving the secure
    round mode leaves the train program untouched (the share protocol is
    host-side; substitution happens after the device round).

    Per (mode, cohort) row: shamir bytes are measured over the real TCP
    NetworkBroker (share + ack + sum frames of the wire protocol, read
    off the broker_bytes_out counter delta, same idiom as the hierarchy
    axis); turbo has no wire path, so its bytes are static accounting —
    the ring's frame count (C*n contribution shares + (groups-1)*n
    handoffs + T+1 opens) times one actually-encoded frame of the same
    dim.  The plaintext baseline is one quantized frame per client over
    the same transport.  Wall overhead is the in-process engine vs a
    plain numpy sum on identical payloads.

    The SECAGG artifact the `regress` gate checks: bytes_per_round and
    engine wall/round within tolerance per point, and steady-state
    recompiles EXACTLY ZERO on the train rows — secure_agg must never
    mint a new XLA signature."""
    import threading

    import numpy as np

    from feddrift_tpu import obs
    from feddrift_tpu.comm.netbroker import NetworkBroker, NetworkBrokerClient
    from feddrift_tpu.obs.regress import _compile_counts
    from feddrift_tpu.platform.secure_agg import P_DEFAULT, quantize
    from feddrift_tpu.resilience.secure_round import (SecureAggregator,
                                                      SecureShareHolder,
                                                      encode_share_frame,
                                                      run_secure_wire_round)

    dim = 2048 if smoke else 16384
    rounds = 3 if smoke else 5
    scale = 2 ** 16

    def plain_tcp_bytes(pay):
        """One quantized upload frame per client over the real broker."""
        obs.configure(None)
        ctr = obs.registry().counter("broker_bytes_out",
                                     transport="netbroker")
        before = ctr.value
        broker = NetworkBroker()
        try:
            tx = NetworkBrokerClient(broker.host, broker.port, timeout=10.0)
            rx = NetworkBrokerClient(broker.host, broker.port, timeout=10.0)
            q = rx.subscribe("secure-bench/plain")
            s = rx.subscribe("__sync__")
            rx.publish("__sync__", "ready")      # sub-then-pub is ordered
            assert s.get(timeout=10) == "ready"
            for c in range(pay.shape[0]):
                tx.publish("secure-bench/plain", encode_share_frame(
                    quantize(pay[c], scale), sender=c))
            for _ in range(pay.shape[0]):
                assert q.get(timeout=10) is not None
            tx.close(); rx.close()
        finally:
            broker.close()
        return ctr.value - before

    def shamir_tcp_bytes(pay):
        """The full wire protocol (shares, acks, masked sums) over TCP:
        C clients x C holders, holders running in threads on their own
        broker connections."""
        obs.configure(None)
        ctr = obs.registry().counter("broker_bytes_out",
                                     transport="netbroker")
        before = ctr.value
        C = pay.shape[0]
        broker = NetworkBroker()
        try:
            clients = [NetworkBrokerClient(broker.host, broker.port,
                                           timeout=10.0) for _ in range(C)]
            holders = [SecureShareHolder(cli, h)
                       for h, cli in enumerate(clients)]
            for h, cli in enumerate(clients):
                q = cli.subscribe(f"__sync__/{h}")
                cli.publish(f"__sync__/{h}", "ready")
                assert q.get(timeout=10) == "ready"
            threads = [threading.Thread(target=hold.run,
                                        kwargs={"timeout": 60.0},
                                        daemon=True) for hold in holders]
            for t in threads:
                t.start()
            server = NetworkBrokerClient(broker.host, broker.port,
                                         timeout=10.0)
            res = run_secure_wire_round(server, pay, threshold=1,
                                        num_holders=C, deadline=30.0,
                                        scale=scale)
            assert not res.degraded, res.reason
            for t in threads:
                t.join(timeout=10)
            server.close()
            for cli in clients:
                cli.close()
        finally:
            broker.close()
        return ctr.value - before

    def turbo_frame_bytes(engine, C):
        """Static accounting: the ring's frame count times one encoded
        frame (all frames carry the same dim-D field vector)."""
        cfg = engine._ring.cfg
        frame = len(encode_share_frame(
            np.zeros(dim, np.int64), sender=0, holder=0, p=P_DEFAULT))
        n_frames = (C * cfg.group_size
                    + (cfg.num_groups - 1) * cfg.group_size
                    + cfg.privacy_t + 1)
        return n_frames * frame

    out = []
    rng = np.random.RandomState(18)
    for mode in ("shamir", "turbo"):
        for cohort in (4, 8):
            pay = rng.randn(cohort, dim).astype(np.float64)
            eng = SecureAggregator(mode, cohort, threshold=1, scale=scale,
                                   seed=18)
            obs.configure(None)
            t0 = time.time()
            for r in range(rounds):
                res = eng.secure_masked_sum(pay, round_idx=r)
                assert not res.degraded
            wall_sec = (time.time() - t0) / rounds
            t0 = time.time()
            for _ in range(rounds):
                pay.sum(axis=0)
            wall_plain = (time.time() - t0) / rounds
            plain_b = plain_tcp_bytes(pay)
            if mode == "shamir":
                sec_b, transport = shamir_tcp_bytes(pay), "tcp"
            else:
                sec_b, transport = turbo_frame_bytes(eng, cohort), "frames"
            out.append({
                "mode": mode, "point": f"c{cohort}", "cohort": cohort,
                "dim": dim, "rounds": rounds, "transport": transport,
                "bytes_per_round": int(sec_b),
                "plain_bytes_per_round": int(plain_b),
                "bytes_overhead_vs_plain": round(sec_b / plain_b, 2),
                "wall_s_secure_per_round": round(wall_sec, 5),
                "wall_s_plain_per_round": round(wall_plain, 6),
                "wall_overhead_vs_plain": round(
                    wall_sec / max(wall_plain, 1e-9), 1),
                "max_abs_err": res.max_abs_err,
            })
            print(json.dumps({"partial": f"secure@{mode}:c{cohort}",
                              **out[-1]}), file=sys.stderr)
        # Train row: the real runner with secure_agg on — the gate is
        # steady_recompiles == 0 (host-side protocol, untouched program).
        cfg = _canonical_cfg(True, secure_agg=mode, comm_round=5,
                             sample_num=50, batch_size=50,
                             cost_model="lowered")
        r = _measure(cfg, "cpu")
        _, recompiles = _compile_counts(r)
        out.append({
            "mode": mode, "point": "train",
            "rounds_per_sec": r.get("value"),
            "wall_s": r.get("wall_s"),
            "final_test_acc": r.get("final_test_acc"),
            "steady_recompiles": recompiles,
        })
        print(json.dumps({"partial": f"secure@{mode}:train", **out[-1]}),
              file=sys.stderr)
    return out


def _serve_bench(smoke: bool) -> list:
    """Serving read-path axis (ISSUE 14): requests/s + latency quantiles
    across micro-batch buckets over the canonical SEA-4 pool geometry.

    One row per max bucket size. bucket=1 is the unbatched per-request
    path (every dispatch answers one request); larger buckets coalesce the
    same closed-loop traffic through the one routed forward program. The
    SERVE artifact the `regress` gate checks: requests/s floor and p99
    ceiling per bucket, batched >= 3x unbatched, and ZERO steady-state
    recompiles under mixed-cluster traffic (the bucket ladder is compiled
    at warmup; the P2P traffic mix must never mint a new signature)."""
    import numpy as np
    import jax.numpy as jnp

    from feddrift_tpu import obs
    from feddrift_tpu.core.pool import ModelPool
    from feddrift_tpu.data.registry import make_dataset
    from feddrift_tpu.models import create_model
    from feddrift_tpu.platform.serving import (SERVE_BUCKETS,
                                               InferenceEngine,
                                               RoutingTable,
                                               TrafficGenerator)

    cfg = _canonical_cfg(True, train_iterations=1, comm_round=1)
    ds = make_dataset(cfg)
    module = create_model(cfg.model, ds, cfg)
    sample = jnp.asarray(ds.x[0, 0, :2])
    # identical=False: every cluster model answers differently, so routing
    # mistakes would be visible, not silently masked by identical params
    pool = ModelPool.create(module, sample, cfg.num_models,
                            seed=cfg.seed + 42, identical=False)
    population = 64
    rng = np.random.RandomState(14)
    routing = RoutingTable.from_assignment(
        rng.randint(0, cfg.num_models, size=population))
    requests = 600 if smoke else 3000
    concurrency = 32

    def _serve_recompiles() -> int:
        snap = obs.registry().snapshot()
        return sum(int(v) for k, v in snap.items()
                   if k.startswith('jit_recompiles{fn="serve_forward'))

    out = []
    base_rps = None
    for max_bucket in (1, 4, 8, 16, 32):
        buckets = tuple(b for b in SERVE_BUCKETS if b <= max_bucket)
        eng = InferenceEngine(pool, routing, buckets=buckets).start()
        try:
            eng.warmup()
            tg = TrafficGenerator(eng, clients=range(population), seed=14,
                                  concurrency=concurrency)
            tg.run(max(requests // 10, 50))    # closed-loop warm (threads,
            rec0 = _serve_recompiles()         # queues, branch caches)
            eng.reset_latency_stats()          # sketch covers measured
            stats = tg.run(requests)           # traffic only, not warm-up
            recompiles = _serve_recompiles() - rec0
        finally:
            eng.close()
        row = {
            "bucket": max_bucket,
            "mode": "unbatched" if max_bucket == 1 else "batched",
            "requests": stats["requests"],
            "completed": stats["completed"],
            "errors": stats["errors"],
            "concurrency": concurrency,
            "requests_per_s": stats["requests_per_s"],
            "p50_ms": stats.get("p50_ms"),
            "p95_ms": stats.get("p95_ms"),
            "p99_ms": stats.get("p99_ms"),
            "steady_recompiles": int(recompiles),
        }
        if max_bucket == 1:
            base_rps = stats["requests_per_s"]
            row["speedup_vs_unbatched"] = 1.0
        else:
            row["speedup_vs_unbatched"] = (
                round(stats["requests_per_s"] / base_rps, 2)
                if base_rps else None)
        out.append(row)
        print(json.dumps({"partial": f"serve@{max_bucket}", **row}),
              file=sys.stderr)

    # socket path (ISSUE 17): the same pool behind the deployable
    # frontend (platform/frontend.py) — 2 replicas, bounded admission,
    # traffic over real HTTP. Two measurements: a closed-loop row (the
    # gated requests/s floor + p99 ceiling, comparable across runs) and
    # an OPEN-LOOP offered-rate ladder for the saturation knee — the
    # closed loop slows down with a saturated server (coordinated
    # omission), so only the fixed-rate ladder can show where the
    # frontend starts shedding and that sub-knee traffic does NOT shed
    # (the gated shed_rate bound).
    from feddrift_tpu.platform.frontend import (AdmissionController,
                                                FrontendClient,
                                                ServingFrontend,
                                                build_replica_set)
    max_bucket = 8 if smoke else 32
    buckets = tuple(b for b in SERVE_BUCKETS if b <= max_bucket)
    socket_requests = 300 if smoke else 1500
    rs = build_replica_set(pool, routing, n=2, buckets=buckets,
                           max_queue=128)
    fe = ServingFrontend(
        rs, admission=AdmissionController(max_pending=64)).start(port=0)
    try:
        client = FrontendClient(fe.url, timeout=30.0)
        tg = TrafficGenerator(client, clients=range(population), seed=14,
                              concurrency=concurrency)
        tg.run(max(socket_requests // 10, 50))   # warm sockets + threads
        rec0 = _serve_recompiles()
        for eng in rs.engines:
            eng.reset_latency_stats()
        stats = tg.run(socket_requests)
        closed_rps = stats["requests_per_s"]
        # knee ladder: offered rates around the measured closed-loop
        # capacity, with the admit window tightened so overload actually
        # sheds instead of hiding in a worker-pool bound
        fe.admission.max_pending = 32
        open_tg = TrafficGenerator(client, clients=range(population),
                                   seed=15, concurrency=64)
        knee = []

        def _point(rate):
            n = min(socket_requests, max(int(rate * 2), 60))
            o = open_tg.run_open(n, rate, timeout=5.0)
            knee.append({"offered_rps": o["offered_rps"],
                         "achieved_rps": o["achieved_rps"],
                         "shed_rate": o["shed_rate"],
                         "p99_ms": o.get("p99_ms"),
                         "timeouts": o["timeouts"]})
            return knee[-1]

        for frac in (0.5, 1.0, 1.5, 2.0):
            _point(max(closed_rps * frac, 1.0))
        # the closed-loop number is a WORKER-pool bound, not necessarily
        # the server's: if 2x it still neither sheds nor falls behind,
        # keep doubling until the knee is actually visible (sheds, or
        # achieved falls measurably short of offered) so the artifact
        # always contains the saturation point
        rate = closed_rps * 2.0
        for _ in range(6):
            last = knee[-1]
            if (last["shed_rate"] > 0.05
                    or last["achieved_rps"] < 0.85 * last["offered_rps"]):
                break
            rate *= 2.0
            _point(rate)
        recompiles = _serve_recompiles() - rec0
    finally:
        fe.close()
    row = {
        "bucket": max_bucket,
        "mode": "socket",
        "replicas": 2,
        "requests": stats["requests"],
        "completed": stats["completed"],
        "errors": stats["errors"],
        "concurrency": concurrency,
        "requests_per_s": closed_rps,
        "p50_ms": stats.get("p50_ms"),
        "p95_ms": stats.get("p95_ms"),
        "p99_ms": stats.get("p99_ms"),
        # gated bound: the SUB-KNEE (0.5x capacity) open-loop point must
        # serve essentially everything it admits
        "shed_rate": knee[0]["shed_rate"],
        "steady_recompiles": int(recompiles),
        "knee": knee,
    }
    out.append(row)
    print(json.dumps({"partial": f"serve@socket:b{max_bucket}", **row}),
          file=sys.stderr)
    return out


def _quality_bench(smoke: bool) -> dict:
    """Model-quality plane axis (ISSUE 16): seeded drifting-traffic serve
    bench behind QUALITY_r1*.json, gated by `regress` on three absolute
    acceptance bars plus the usual relative throughput/p99 tolerances:

    - the streaming live-accuracy estimate (delayed-label join feeding
      windowed per-model accuracy) lands within --tol-quality-acc of the
      offline oracle computed client-side over the SAME labeled stream;
    - a clean merge (two slots holding bitwise-identical params) canary-
      COMMITS, and a deliberately wrong merge (survivor slot holds an
      anti-model: the classifier layer negated, so re-homed clients get
      flipped logits) canary-ROLLS-BACK — verdict events carry lineage
      ids, and no OTHER canary ever rolls back (clean_canary_rollbacks);
    - shadow duplicate-execution costs < 5% requests/s vs canary-off on
      identical traffic, at ZERO steady-state recompiles (the shadow
      forward replays the warmed bucket signatures).
    """
    import threading

    import numpy as np
    import jax
    import jax.numpy as jnp

    from feddrift_tpu import obs
    from feddrift_tpu.core.pool import ModelPool
    from feddrift_tpu.data.registry import make_dataset
    from feddrift_tpu.models import create_model
    from feddrift_tpu.platform.canary import CanaryController
    from feddrift_tpu.platform.serving import (InferenceEngine, RoutingTable,
                                               TrafficGenerator)

    cfg = _canonical_cfg(True, train_iterations=1, comm_round=1)
    ds = make_dataset(cfg)
    module = create_model(cfg.model, ds, cfg)
    sample = jnp.asarray(ds.x[0, 0, :2])
    pool = ModelPool.create(module, sample, cfg.num_models,
                            seed=cfg.seed + 42, identical=False)
    # slot 1 := slot 0 — two clusters whose models genuinely converged;
    # merging them is the GOOD swap (shadow answers match live bitwise)
    pool.copy_slot(1, 0)
    # slot 2 := slot 3 with the classifier layer negated — a corrupt
    # survivor; merging 3 into 2 is the DELIBERATELY WRONG swap (the
    # candidate generation answers re-homed clients with flipped logits)
    p3 = pool.slot(3)
    last_layer = sorted(p3.keys())[-1]
    pool.set_slot(2, {k: (jax.tree_util.tree_map(lambda a: -a, v)
                          if k == last_layer else v)
                      for k, v in p3.items()})

    population = 64
    rng = np.random.RandomState(14)
    routing = RoutingTable(rng.randint(0, cfg.num_models, size=population))
    window = 200 if smoke else 400
    eps = 0.1               # label noise: live accuracy targets ~0.9
    eng = InferenceEngine(pool, routing, quality_window=window).start()
    ctl = CanaryController(eng, fraction=1.0, min_samples=48,
                           acc_margin=0.02, seed=3, timeout_s=600.0)
    # genesis history so verdict lineage ids resolve through the DAG
    for m in range(cfg.num_models):
        ctl.note_event({"kind": "cluster_create", "model": m,
                        "iteration": 0})
    eng.attach_canary(ctl)

    def _serve_recompiles() -> int:
        snap = obs.registry().snapshot()
        return sum(int(v) for k, v in snap.items()
                   if k.startswith('jit_recompiles{fn="serve_forward'))

    num_classes = int(np.asarray(eng.step.forward(
        eng._gen.params,
        jnp.zeros((1,) + eng._example_shape, dtype=eng._example_dtype),
        jnp.zeros((1,), dtype=jnp.int32))).shape[-1])

    lock = threading.Lock()
    oracle: list = []        # (model, correct) from the client's own view

    def labeled_run(n: int, seed: int, concurrency: int = 8,
                    record: bool = False) -> None:
        """Closed-loop labeled traffic: submit, then close the delayed-
        label loop with y = served prediction flipped with prob eps —
        the client-side (pred == y) log IS the offline oracle."""
        per = [n // concurrency] * concurrency
        for i in range(n % concurrency):
            per[i] += 1

        def worker(w: int) -> None:
            wr = np.random.RandomState(
                (seed * 1_000_003 + w * 7_919 + 1) % (2**31 - 1))
            recs = []
            for _ in range(per[w]):
                c = int(wr.randint(population))
                x = wr.standard_normal(eng._example_shape).astype(
                    eng._example_dtype, copy=False)
                try:
                    res = eng.submit(c, x, timeout=30.0)
                except Exception:   # noqa: BLE001 — keep the loop closed
                    continue
                pred = int(np.argmax(res.logits))
                y = pred if wr.uniform() >= eps else \
                    int((pred + 1 + wr.randint(num_classes - 1))
                        % num_classes)
                eng.observe_label(res.request_id, y)
                recs.append((int(res.model), pred == y))
            if record:
                with lock:
                    oracle.extend(recs)

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    try:
        eng.warmup()
        TrafficGenerator(eng, clients=range(population), seed=14,
                         concurrency=8).run(100)    # unlabeled warm
        rec0 = _serve_recompiles()

        # phase A — clean labeled traffic: streaming estimate vs oracle
        n_a = window * 2
        labeled_run(n_a, seed=21, record=True)
        snap_a = eng.quality.snapshot()
        per_model = {}
        gaps = [0.0]
        by_model: dict = {}
        for m, ok in oracle:
            by_model.setdefault(m, []).append(ok)
        for m, oks in sorted(by_model.items()):
            # oracle over the estimator's own window, not all history —
            # both sides then summarize the same tail of the stream
            tail = oks[-window:]
            o = float(np.mean(tail))
            lw = (snap_a.get("per_model") or {}).get(str(m)) or {}
            live = lw.get("accuracy")
            row = {"oracle_accuracy": round(o, 4),
                   "live_accuracy": live, "labeled": len(oks)}
            if live is not None and len(tail) >= 30:
                row["gap"] = round(abs(live - o), 4)
                gaps.append(row["gap"])
            per_model[str(m)] = row
        oracle_acc = float(np.mean([ok for _, ok in oracle][-window:]))
        live_acc = snap_a.get("accuracy")
        if live_acc is not None:
            gaps.append(abs(live_acc - oracle_acc))
        print(json.dumps({"partial": "quality@clean",
                          "live_accuracy": live_acc,
                          "oracle_accuracy": round(oracle_acc, 4)}),
              file=sys.stderr)

        # phase B — drifting traffic: shift the input distribution so the
        # read-path entropy stream moves (KS detector; not gated)
        def shifted_x(r):
            return (6.0 * r.standard_normal(eng._example_shape)
                    + 4.0).astype(eng._example_dtype, copy=False)
        TrafficGenerator(eng, clients=range(population), seed=15,
                         concurrency=8,
                         make_x=shifted_x).run(300 if smoke else 600)
        drift_suspected = int(eng.quality.snapshot()["drift_suspected"])

        # phase C — canaried swaps: clean merge commits, corrupt merge
        # rolls back (labels keep flowing so both verdicts close on
        # samples, not timeout)
        def run_canary(rec: dict) -> dict:
            n_before = len(ctl.verdicts)
            eng.apply_cluster_event(rec)
            for i in range(40):
                if len(ctl.verdicts) > n_before:
                    break
                labeled_run(64, seed=1000 + 37 * i)
            if len(ctl.verdicts) == n_before:
                return {"verdict": "hung"}
            v = ctl.verdicts[-1]
            print(json.dumps({"partial": f"quality@{rec['kind']}"
                                         f":{rec.get('merged')}",
                              **{k: v[k] for k in ("verdict", "decided_by",
                                                   "live_acc", "shadow_acc",
                                                   "lineage_ids")}}),
                  file=sys.stderr)
            return v

        good = run_canary({"kind": "cluster_merge", "base": 0, "merged": 1,
                           "iteration": 1})
        bad = run_canary({"kind": "cluster_merge", "base": 2, "merged": 3,
                          "iteration": 2})
        clean_rollbacks = sum(
            1 for v in ctl.verdicts
            if v.get("verdict") == "rollback" and v is not bad)

        # phase D — shadow overhead on identical traffic: INTERLEAVED
        # canary-off/on legs. A single off/on pair is hostage to closed-
        # loop throughput drift on a shared host (observed swings ~±10%
        # dwarf the <5% signal); alternating the modes and comparing
        # medians cancels the monotone warm-up/scheduler component.
        n_perf = 1500 if smoke else 3000
        pairs = 3
        eng.reset_latency_stats()

        def _leg(seed: int, canary_on: bool) -> dict:
            if canary_on:
                ctl.fraction = 0.1
                eng.apply_cluster_event(
                    {"kind": "cluster_merge", "base": 2, "merged": 3,
                     "iteration": 100 + seed})
            r = TrafficGenerator(eng, clients=range(population),
                                 seed=seed, concurrency=32).run(n_perf)
            if canary_on:
                ctl.abort()   # no labels flow here: cancel, next leg is
            return r          # truly canary-idle

        _leg(15, False)       # unmeasured: warm BOTH modes before any
        _leg(15, True)        # measured leg (first-open canary setup —
        off_legs, on_legs = [], []  # lineage replay etc — is one-time)
        for k in range(pairs):
            # alternate which mode goes first: closed-loop throughput
            # drifts monotonically as the host warms, so a fixed order
            # would systematically favor one mode
            modes = (True, False) if k % 2 else (False, True)
            for canary_on in modes:
                r = _leg(16 + 2 * k + int(canary_on), canary_on)
                (on_legs if canary_on else off_legs).append(r)
        recompiles = _serve_recompiles() - rec0
    finally:
        eng.close()

    off_rps = [r["requests_per_s"] for r in off_legs]
    on_rps = [r["requests_per_s"] for r in on_legs]
    off = {"requests_per_s": float(np.median(off_rps)),
           "p99_ms": float(np.median(
               [r["p99_ms"] for r in off_legs if r.get("p99_ms")])),
           "errors": sum(int(r["errors"]) for r in off_legs)}
    on = {"requests_per_s": float(np.median(on_rps)),
          "errors": sum(int(r["errors"]) for r in on_legs)}
    ratio = (round(on["requests_per_s"] / off["requests_per_s"], 4)
             if off["requests_per_s"] else None)
    max_gap = round(max(gaps), 4)
    row = {
        "variant": "drifting_serve",
        "population": population,
        "num_models": cfg.num_models,
        "window": window,
        "label_noise": eps,
        "labeled": int(snap_a["labeled"]),
        "live_accuracy": live_acc,
        "oracle_accuracy": round(oracle_acc, 4),
        "live_oracle_gap": max_gap,
        "per_model": per_model,
        "drift_suspected": drift_suspected,
        "good_merge": {k: good.get(k) for k in
                       ("verdict", "decided_by", "samples", "live_acc",
                        "shadow_acc", "acc_delta", "agreement",
                        "lineage_ids")},
        "bad_merge": {k: bad.get(k) for k in
                      ("verdict", "decided_by", "samples", "live_acc",
                       "shadow_acc", "acc_delta", "agreement",
                       "lineage_ids")},
        "good_merge_committed": int(good.get("verdict") == "commit"),
        "bad_merge_rolled_back": int(bad.get("verdict") == "rollback"),
        "clean_canary_rollbacks": int(clean_rollbacks),
        "shadow_overhead": {"requests": n_perf, "concurrency": 32,
                            "fraction": 0.1, "pairs": pairs,
                            "off_rps": [round(v, 1) for v in off_rps],
                            "on_rps": [round(v, 1) for v in on_rps]},
        "shadow_overhead_ratio": ratio,
        "requests_per_s": round(off["requests_per_s"], 2),
        "p99_ms": round(off["p99_ms"], 3) if off.get("p99_ms") else None,
        "errors": int(off["errors"]) + int(on["errors"]),
        "steady_recompiles": int(recompiles),
    }
    print(json.dumps({"partial": "quality", **row}), file=sys.stderr)
    return row


def _megastep_cfg(smoke: bool, K: int):
    """Megastep K-sweep config: the canonical SEA geometry under the
    drift-OBLIVIOUS single model, which certifies an unbounded
    megastep_horizon — the canonical softcluster decides drift every
    iteration (decision_cadence=1) and would clamp every block to K=1,
    measuring nothing. 16 iterations divide evenly by every swept K, so
    no run ever compiles a second (tail-sized) megastep program."""
    return _canonical_cfg(
        smoke, concept_drift_algo="oblivious", concept_drift_algo_arg="",
        concept_num=1, megastep_k=K,
        train_iterations=16, comm_round=10 if smoke else 20,
        sample_num=50, batch_size=50,
        cost_model="lowered")     # exact-HBM capture not worth the compiles here


def _megastep_pop_cfg(smoke: bool, K: int):
    """Composed megastep geometry: 10^4 registered population (10^3 under
    --smoke), 10-client cohorts with 2 overprovision slots, a 3-edge
    hierarchy closing every round with trimmed-mean, plus straggler/churn
    chaos — the ISSUE-13 acceptance config. Device shapes stay cohort-
    sized; only the host-side plan (registry draw, cohort gather, mask
    stacking) sees the population, which is exactly the overhead the
    K-deep block is meant to amortize.

    Short rounds (comm_round=3) on purpose: the megastep amortizes the
    PER-ITERATION host round-trip (dispatch, opt-state init, phase
    syncs, eval fetches), so the sweep runs the cross-silo-style
    few-local-rounds regime where that round-trip dominates — at long
    R the in-program training compute swamps both paths equally and
    the axis measures nothing. Many short iterations (48 full / 16
    smoke, both divisible by every swept K) keep the steady-state
    sample large without a tail-sized second program."""
    return _canonical_cfg(
        smoke, concept_drift_algo="oblivious", concept_drift_algo_arg="",
        concept_num=1, megastep_k=K,
        population_size=1000 if smoke else 10000,
        cohort_size=10, cohort_overprovision=2,
        straggler_prob=0.1, churn_leave_prob=0.01, churn_join_prob=0.02,
        hierarchy_edges=3, edge_robust_agg="trimmed_mean",
        train_iterations=16 if smoke else 48, comm_round=3,
        sample_num=50, batch_size=50,
        cost_model="lowered")


def _drive_megastep(exp, t: int) -> int:
    """Advance one block through the runner's greedy fusion loop
    (run_iteration never fuses; run_megastep fuses the granted span)."""
    span = exp._megastep_span(t)
    if span > 1:
        return t + exp.run_megastep(t, span)
    exp.run_iteration(t)
    return t + 1


def _measure_megastep_sweep(cfgs) -> list:
    """Measure all K points of one megastep variant INTERLEAVED.

    The K sweep's headline number is a RATIO (K>1 rounds/s over the same
    variant's K=1), so the two measurements must see the same host: on a
    small shared box, minutes of load drift between sequentially-measured
    points swings either side of the ratio by 30% — more than the effect
    under test. Countermeasures, in order of leverage:

      - interleave: every experiment is constructed and warmed up front,
        then the steady state advances round-robin in equal-iteration
        turns (max swept K per turn), so a load burst hits every K point
        instead of whichever one was running;
      - MIN per-iteration wall over turns, not total elapsed: steady
        turns are identical work and scheduler noise is strictly
        additive, so the fastest turn is the tightest upper bound on
        the true cost (same paired-min reasoning as perf_gate's ops
        stage; the total stays in wall_s).

    Warm-up is each experiment's first block (first two iterations when
    K=1, matching _measure); the instruments registry resets after ALL
    warm-ups, so the shared snapshot counts steady-state retraces across
    the sweep — every row must show ZERO, and a nonzero count correctly
    poisons the whole variant."""
    from feddrift_tpu import obs
    from feddrift_tpu.obs import costmodel
    from feddrift_tpu.simulation.runner import Experiment

    costmodel.clear()
    exps = [Experiment(c) for c in cfgs]
    ts = []
    for exp, c in zip(exps, cfgs):
        t = 0
        while t < max(c.megastep_k, 2):        # warm-up: first block
            t = _drive_megastep(exp, t)
        ts.append(t)
    obs.registry().reset()
    costmodel.refresh_gauges()
    starts = list(ts)
    chunk = max(c.megastep_k for c in cfgs)
    walls = [[] for _ in exps]                 # per-turn (iters, seconds)
    hofs = [[] for _ in exps]
    elapsed = [0.0 for _ in exps]
    while any(t < c.train_iterations for t, c in zip(ts, cfgs)):
        for i, (exp, c) in enumerate(zip(exps, cfgs)):
            target = min(ts[i] + chunk, c.train_iterations)
            if ts[i] >= target:
                continue
            n0 = ts[i]
            b0 = time.perf_counter()
            while ts[i] < target:
                ts[i] = _drive_megastep(exp, ts[i])
                if exp.last_round_breakdown is not None:
                    hofs[i].append(
                        exp.last_round_breakdown["host_overhead_frac"])
            jax.block_until_ready(exp.pool.params)
            dt = time.perf_counter() - b0
            walls[i].append((ts[i] - n0, dt))
            elapsed[i] += dt
    instruments = obs.registry().snapshot()
    out = []
    for i, (exp, c) in enumerate(zip(exps, cfgs)):
        per_iter = sorted(w / max(n, 1) for n, w in walls[i])
        best = per_iter[0] if per_iter else None
        rounds = c.comm_round * (c.train_iterations - starts[i])
        rps = (c.comm_round / best) if best \
            else rounds / max(elapsed[i], 1e-9)
        out.append({
            "value": round(rps, 3),
            "unit": "rounds/s",
            "wall_s": round(elapsed[i], 2),
            "rounds": rounds,
            "final_test_acc": round(float(exp.logger.last("Test/Acc")), 4),
            "host_overhead_frac": (round(sum(hofs[i]) / len(hofs[i]), 6)
                                   if hofs[i] else None),
            "round_wall_p99_s": (_round_wall_quantiles(instruments)
                                 or {}).get("0.99"),
            "instruments": instruments,
        })
    return out


def _megastep_bench(smoke: bool) -> list:
    """rounds/s + host-overhead fraction + steady-state recompiles vs the
    fused-iterations-per-dispatch factor K, over TWO variants:

    - ``dense`` (K in 1,2,4,8): the PR-10 canonical all-clients-resident
      geometry — K=1 is the historical fused-iteration path;
    - ``pop_hier`` (K in 1,4): the ISSUE-13 composed geometry — 10^4
      population cohorts + 3-edge trimmed-mean hierarchy + chaos, where
      every previously-gating feature now rides the outer scan.

    The MEGASTEP artifact the `regress` gate checks: per-K throughput must
    hold within the rounds tolerance, steady-state recompiles must stay
    ZERO across K and both variants, K>1 must keep host_overhead_frac
    strictly below its own variant's K=1, and the composed pop_hier K>1
    must clear an ABSOLUTE >= 2x speedup over its own K=1 — the
    acceptance bar for fusing the feature matrix, not just the dense
    fast path.

    pop_hier holds an absolute RATIO floor on a 1-core shared host, so
    its sweep runs 3 times and the rep with the MEDIAN K-max/K-1 ratio
    is reported whole (pairing preserved: both sides of the ratio come
    from the same interleaved rep). The zero-recompile gate stays
    absolute across ALL reps — a recompile in a discarded rep still
    poisons the row."""
    from feddrift_tpu.obs.regress import _compile_counts

    out = []
    sweeps = [("dense", _megastep_cfg, (1, 2, 4, 8), 1),
              ("pop_hier", _megastep_pop_cfg, (1, 4), 3)]
    for variant, mk_cfg, ks, reps in sweeps:
        rep_results = [
            _measure_megastep_sweep([mk_cfg(smoke, K) for K in ks])
            for _ in range(reps)]
        def _ratio(rr):
            v0, vn = rr[0].get("value"), rr[-1].get("value")
            return (vn / v0) if v0 and vn else 0.0
        rep_results.sort(key=_ratio)
        results = rep_results[len(rep_results) // 2]
        k1_rps = None
        for i, (K, r) in enumerate(zip(ks, results)):
            recompiles = max(_compile_counts(rr[i])[1]
                             for rr in rep_results)
            entry = {
                "variant": variant,
                "megastep_k": K,
                "rounds_per_sec": r.get("value"),
                "final_test_acc": r.get("final_test_acc"),
                "wall_s": r.get("wall_s"),
                "host_overhead_frac": r.get("host_overhead_frac"),
                "steady_recompiles": recompiles,
            }
            if K == 1:
                k1_rps = entry["rounds_per_sec"]
            entry["speedup_vs_k1"] = (
                round(entry["rounds_per_sec"] / k1_rps, 3)
                if k1_rps and entry["rounds_per_sec"] else None)
            out.append(entry)
            print(json.dumps({"partial": f"megastep@{variant}:{K}",
                              **entry}),
                  file=sys.stderr)
    return out


def _precision_cfg(smoke: bool, policy: str):
    """Compute-bound real-workload preset for the precision axis:
    resnet8 on FMoW-shaped synthetic satellite images (data/fmow.py,
    32x32x3) — the first runnable bench preset pairing the two; the
    canonical fnn is ~21k params, so its precision deltas are noise by
    construction. Drift-oblivious single model: the axis measures the
    round program's dtype economics, not cluster dynamics. Geometry is
    sized so one local step per (client, round) keeps the CPU-emulated
    bf16 sweep affordable while the conv tower still dominates bytes."""
    return _canonical_cfg(
        smoke, dataset="fmow", model="resnet8",
        concept_drift_algo="oblivious", concept_drift_algo_arg="",
        concept_num=1, change_points="A", precision=policy,
        client_num_in_total=4, client_num_per_round=4,
        epochs=1, batch_size=32, sample_num=32,
        train_iterations=4, comm_round=3 if smoke else 10,
        frequency_of_the_test=3 if smoke else 10,
        cost_model="compiled")    # exact per-program HBM is the point here


def _precision_bench(smoke: bool) -> list:
    """End-to-end precision-policy axis (ISSUE 15): the f32 / bf16_mixed /
    bf16_pure presets over the compute-bound resnet8-on-FMoW preset.

    The PRECISION artifact the `regress` gate checks: rounds/s floor per
    policy, every reduced-precision row's accuracy within
    --tol-precision-acc of the same artifact's OWN f32 row, ZERO
    steady-state recompiles (a policy is one jit signature per program,
    compiled in warm-up), and ABSOLUTE ceilings on the bf16_mixed ratios
    — program_bytes_accessed <= 0.60x and wire bytes/round <= 0.55x of
    the paired f32 row. On CPU the bf16 arithmetic is emulated, so
    rounds/s is NOT the portable signal; the bytes ratios are (XLA's
    accounting of the same programs). The MXU-rate effect on the chip:
    not measured.

    Wire bytes go through the real frame encoder at each policy's wire
    dtype ("none" codec on purpose: the codec axis is COMM's; this axis
    isolates the dtype width, headers included)."""
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from feddrift_tpu.comm.compress import encode_frame
    from feddrift_tpu.core.precision import PRESETS
    from feddrift_tpu.data.registry import make_dataset
    from feddrift_tpu.models import create_model
    from feddrift_tpu.obs.regress import _compile_counts

    cfg0 = _precision_cfg(smoke, "f32")
    ds = make_dataset(cfg0)
    module = create_model(cfg0.model, ds, cfg0)
    leaves = jax.tree_util.tree_leaves(
        module.init(jax.random.PRNGKey(0),
                    jnp.asarray(ds.x[0, 0, :2]))["params"])
    wire_np = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}

    def wire_bytes_per_round(policy: str) -> int:
        dt = wire_np[PRESETS[policy].wire_dtype]
        one_update = sum(
            len(json.dumps(encode_frame(np.asarray(l).astype(dt), "none",
                                        name=f"p{i}")))
            for i, l in enumerate(leaves))
        return one_update * cfg0.client_num_per_round

    out = []
    f32_row = None
    for policy in ("f32", "bf16_mixed", "bf16_pure"):
        cfg = _precision_cfg(smoke, policy)
        r = _measure(cfg)
        _, recompiles = _compile_counts(r)
        costs = r.get("program_costs") or {}
        # Pre-optimization accounting: buffers at the widths the program
        # declares. The optimized-HLO bytes_accessed is backend-specialized
        # — XLA:CPU emulates bf16 math in f32 with convert traffic, which
        # would report a bf16 program as COSTLIER than f32 (measured 1.25x
        # on this preset) purely as an emulation artifact.
        bytes_accessed = sum(c.get("lowered_bytes_accessed")
                             or c.get("bytes_accessed") or 0
                             for c in costs.values()) or None
        pol = PRESETS[policy]
        entry = {
            "variant": "resnet",
            "policy": policy,
            "param_dtype": pol.param_dtype,
            "agg_dtype": pol.agg_dtype,
            "wire_dtype": pol.wire_dtype,
            "rounds_per_sec": r.get("value"),
            "final_test_acc": r.get("final_test_acc"),
            "wall_s": r.get("wall_s"),
            "steady_recompiles": recompiles,
            "program_bytes_accessed": bytes_accessed,
            "peak_hbm_bytes": r.get("hbm_peak_bytes"),
            "wire_bytes_per_round": wire_bytes_per_round(policy),
        }
        if policy == "f32":
            f32_row = entry
        elif f32_row is not None:
            def _ratio(key):
                a, b = entry.get(key), f32_row.get(key)
                return round(a / b, 4) if a and b else None
            entry["bytes_accessed_ratio"] = _ratio("program_bytes_accessed")
            entry["peak_hbm_ratio"] = _ratio("peak_hbm_bytes")
            entry["wire_bytes_ratio"] = _ratio("wire_bytes_per_round")
        out.append(entry)
        print(json.dumps({"partial": f"precision@{policy}", **entry}),
              file=sys.stderr)
    return out


def _conv_cfg(smoke: bool, **overrides):
    base = dict(
        dataset="cifar10", model="resnet8",
        concept_drift_algo="win-1", concept_drift_algo_arg="",
        concept_num=1, change_points="A",
        batch_size=128, compute_dtype="bfloat16",
        train_iterations=3 if smoke else 4,
        comm_round=10 if smoke else 50)
    base.update(overrides)                    # callers may override any of it
    return _canonical_cfg(smoke, **base)


def _mfu_batch_sweep() -> list:
    """MFU vs per-client batch size on the conv config. The fused round
    program vmaps C=10 clients, so device batch is 10x the per-client
    figure. Short runs: the sweep wants the MFU trend, not steady-state
    wall-clock (the headline conv_bench covers that). TPU only, never
    under --smoke (both gated at the call site)."""
    out = []
    for bs in (128, 256, 512, 1024):
        cfg = _conv_cfg(False, batch_size=bs, train_iterations=3,
                        comm_round=20)
        r = _measure(cfg)
        out.append({"batch_per_client": bs, "device_batch": bs * 10,
                    "rounds_per_sec": r.get("value"),
                    "mfu": r.get("mfu_estimate")})
        print(json.dumps({"partial": f"mfu_sweep@{bs}", **out[-1]}),
              file=sys.stderr)
    return out


def main() -> None:
    smoke = "--smoke" in sys.argv
    cpu_mode = "--cpu" in sys.argv     # explicit test mode, labelled as CPU
    if cpu_mode:
        jax.config.update("jax_platforms", "cpu")
    from feddrift_tpu.obs import costmodel
    from feddrift_tpu.utils.cache import enable_compile_cache
    enable_compile_cache()
    device = costmodel.device_info()   # first backend use of this process
    if not cpu_mode and device["platform"] != "tpu":
        # the timed path never lands on another platform by itself
        sys.exit(f"bench.py: no TPU (platform={device['platform']!r}, "
                 f"device_kind={device['device_kind']!r}, "
                 f"device_count={device['device_count']}); pass --cpu for "
                 f"the CPU test mode")
    on_tpu = device["platform"] == "tpu"

    # Measured baselines (see module docstring). Skipped under --smoke (the
    # CI-sized check must stay fast; vs_baseline is reported null there).
    # Disk-cached: they cost ~35 min of backend-independent single-core work.
    baseline_rps = None if smoke else _baseline_cache(
        "cpu_per_round_rps", lambda: _measure_cpu_baseline(smoke))
    ref_shape = None if smoke else _baseline_cache(
        "torch_reference_shape", _measure_reference_shape)

    baseline_obj = ({"rounds_per_sec": round(baseline_rps, 3),
                     "what": "same config, this host CPU, per-round "
                             "dispatch path (reference-shaped)"}
                    if baseline_rps else None)

    # Optional profiler capture (FEDDRIFT_PROFILE_DIR): device-time traces
    # for the canonical + conv configs, captured on short replica runs
    # after the timed measurements.
    prof_root = os.environ.get("FEDDRIFT_PROFILE_DIR") or None

    res = _measure(_canonical_cfg(smoke))
    # the headline result goes out at once: a later config that fails
    # fails the run, but not silently and not without this line
    print(json.dumps({"partial": "canonical", **res}), file=sys.stderr)
    res["profile"] = (_profile_capture(_canonical_cfg(smoke),
                                       os.path.join(prof_root, "canonical"))
                      if prof_root else None)

    # Second datapoint on the TPU (or under --conv for local checks): a
    # bf16 conv config where the MXU actually has work — the canonical fnn
    # is ~21k params, so its MFU is noise by construction.
    conv = None
    if on_tpu or "--conv" in sys.argv:
        conv = {"metric": "cifar10 resnet8 bf16 round throughput "
                          "(win-1, 10 clients, batch 128)",
                **_measure(_conv_cfg(smoke))}
        if prof_root:
            conv["profile"] = _profile_capture(
                _conv_cfg(smoke), os.path.join(prof_root, "conv"))

    out = {
        "metric": "FedDrift SEA-4 round throughput (softcluster, "
                  "10 clients, M=4, fnn, batch 500)",
        **res,
        "vs_baseline": (round(res["value"] / baseline_rps, 3)
                        if baseline_rps else None),
        "baseline": baseline_obj,
        "baseline_torch_reference_shape": ref_shape,
        "vs_torch_reference_shape": (
            round(res["value"] / ref_shape["rounds_per_sec"], 3)
            if ref_shape and ref_shape.get("rounds_per_sec") else None),
        # TPU-only diagnostics: null under --cpu
        "dispatch_rtt": _dispatch_rtt() if on_tpu else None,
        "conv_bench": conv,
        "mfu_vs_batch": (_mfu_batch_sweep()
                         if on_tpu and not smoke else None),
        # population-scaling axis (opt-in: adds ~5 short population-mode
        # runs); committed as POPSCALE_r0*.json and gated by `regress`
        "popscale": (_popscale_bench(smoke)
                     if "--popscale" in sys.argv else None),
        # host-plane scaling axis (opt-in: population sweep with the
        # sampling profiler + subsystem ledger on, per-subsystem log-log
        # exponents of host-seconds/round and bytes vs P); committed as
        # HOSTSCALE_r1*.json and gated by `regress` (exponent ceilings,
        # bytes/client ceilings, rounds/s floor, zero steady recompiles)
        "hostscale": (_hostscale_bench(smoke)
                      if "--hostscale" in sys.argv else None),
        # two-tier wire axis (opt-in: pure-wire TCP broker measurement);
        # committed as COMM_r0*.json and gated by `regress`
        "hierarchy": (_hierarchy_bench(smoke)
                      if "--hierarchy" in sys.argv else None),
        # multi-iteration megastep axis (opt-in: K-sweep of fused
        # iteration blocks); committed as MEGASTEP_r1*.json and gated by
        # `regress` (rounds/s floor, zero steady recompiles, host
        # overhead strictly below K=1)
        "megastep": (_megastep_bench(smoke)
                     if "--megastep" in sys.argv else None),
        # end-to-end precision-policy axis (opt-in: paired f32 /
        # bf16_mixed / bf16_pure sweep on the resnet8-on-FMoW preset);
        # committed as PRECISION_r1*.json and gated by `regress`
        # (rounds/s floor, accuracy vs own f32 row, zero steady
        # recompiles, bytes_accessed <= 0.60x and wire <= 0.55x absolute)
        "precision": (_precision_bench(smoke)
                      if "--precision" in sys.argv else None),
        # serving read-path axis (opt-in: closed-loop inference over the
        # model pool across micro-batch buckets); committed as
        # SERVE_r1*.json and gated by `regress` (requests/s floor, p99
        # ceiling, batched >= 3x unbatched, zero steady recompiles)
        "serve": (_serve_bench(smoke)
                  if "--serve" in sys.argv else None),
        # secure-aggregation axis (opt-in: masked round modes vs
        # plaintext — wire bytes over the real TCP broker + engine wall
        # overhead at 2 cohort sizes, and a train run per mode);
        # committed as SECAGG_r1*.json and gated by `regress`
        # (bytes/wall tolerance per point, zero steady recompiles)
        "secure": (_secure_bench(smoke)
                   if "--secure" in sys.argv else None),
        # model-quality plane axis (opt-in: labeled drifting-traffic
        # serve bench with canaried swaps); committed as QUALITY_r1*.json
        # and gated by `regress` (live-vs-oracle accuracy gap, canary
        # verdicts, shadow overhead < 5%, zero steady recompiles)
        "quality": (_quality_bench(smoke)
                    if "--quality" in sys.argv else None),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
