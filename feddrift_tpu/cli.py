"""Command-line entry point.

Mirrors the reference experiment layer
(fedml_experiments/distributed/fedavg_cont_ens/main_fedavg.py:42-139 argparse
+ run_fedavg_distributed_pytorch.sh): the same flag names launch the same
experiment, but one process drives every time step (no per-iteration mpirun
re-exec, no MPI_Abort) and accepts ``--resume`` to continue from the atomic
checkpoint.

    python -m feddrift_tpu run --dataset sea --model fnn \
        --concept_drift_algo softcluster --concept_drift_algo_arg H_A_C_1_10_0 \
        --client_num_in_total 10 --comm_round 200 --epochs 5 \
        --train_iterations 10 --change_points A

    python -m feddrift_tpu resume --out_dir runs/my-run
    python -m feddrift_tpu list   # algorithms / datasets / models
    python -m feddrift_tpu report runs/my-run   # telemetry run report
    python -m feddrift_tpu report runs/my-run --trace   # + trace.json
    python -m feddrift_tpu report runs/my-run --follow  # live tail + alerts
    python -m feddrift_tpu lineage runs/my-run  # cluster genealogy + oracle ARI
    python -m feddrift_tpu regress bench_new.json --baseline bench_old.json
    python -m feddrift_tpu critical_path runs/my-run  # round segment breakdown
    python -m feddrift_tpu fleet 127.0.0.1:7777  # live multi-process ops table
    python -m feddrift_tpu incident runs/my-run  # post-mortem incident triage
    python -m feddrift_tpu lint feddrift_tpu/  # graftlint static analysis

Logging is configured in exactly one place (obs.setup_logging), driven by
the ``--log_level`` flag every subcommand accepts.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def _add_run_args(p: argparse.ArgumentParser) -> None:
    from feddrift_tpu.config import ExperimentConfig
    for f in dataclasses.fields(ExperimentConfig):
        if f.name == "mesh_shape":
            p.add_argument("--mesh_shape", type=str, default="",
                           help='JSON, e.g. {"clients": 8}')
            continue
        default = f.default if f.default is not dataclasses.MISSING else None
        if f.type in ("int", int):
            p.add_argument(f"--{f.name}", type=int, default=default)
        elif f.type in ("float", float):
            p.add_argument(f"--{f.name}", type=float, default=default)
        elif f.type in ("bool", bool):
            p.add_argument(f"--{f.name}", type=lambda s: s.lower() in ("1", "true"),
                           default=default)
        else:
            p.add_argument(f"--{f.name}", type=str, default=default)
    p.add_argument("--wandb", action="store_true", help="attach wandb if available")
    p.add_argument("--flat_out_dir", action="store_true",
                   help="write metrics/ckpt directly under --out_dir instead "
                        "of nesting an auto-named <dataset>-<model>-... "
                        "subdirectory (the committed-runs convention is "
                        "runs/<name>/metrics.jsonl; driver scripts pass this "
                        "so no post-hoc flattening is needed)")
    p.add_argument("--platform", type=str, default="",
                   help="force a JAX platform (e.g. 'cpu') from inside the "
                        "process, before its first backend use; same effect "
                        "as JAX_PLATFORMS in the environment")
    p.add_argument("--auto_resume", action="store_true",
                   help="if the run dir already holds a checkpoint (ckpt/ or "
                        "ckpt.old/), resume from it instead of clobbering — "
                        "the restart half of preemption handling "
                        "(docs/RESILIENCE.md); a no-op on a fresh dir")
    _add_multihost_args(p)


def _add_multihost_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--multihost", action="store_true",
                   help="join a multi-controller runtime "
                        "(jax.distributed.initialize) before building the "
                        "experiment; the client mesh axis then spans every "
                        "process (DCN). On TPU pods the coordinator "
                        "auto-detects; elsewhere pass the three flags below")
    p.add_argument("--coordinator_address", type=str, default=None,
                   help="host:port of process 0 (non-TPU multihost)")
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)


def _maybe_init_multihost(args: argparse.Namespace) -> None:
    if getattr(args, "multihost", False):
        from feddrift_tpu.comm import multihost
        multihost.initialize(
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes,
            process_id=args.process_id)


def _serve_listen(args: argparse.Namespace, buckets: tuple) -> int:
    """``serve --listen``: deploy the socket frontend (admission control +
    replica failover) and optionally drive generated traffic through the
    SOCKET path — the same bytes a real client would send."""
    import time

    from feddrift_tpu.platform import frontend as frontend_mod
    from feddrift_tpu.platform import serving

    fe = frontend_mod.build_frontend(
        args.run_dir, replicas=max(1, args.replicas),
        max_pending=args.max_pending, rate_rps=args.rate_rps,
        slo_p99_ms=args.slo_p99_ms, max_queue=args.max_queue,
        buckets=buckets, max_wait_s=args.max_wait_ms / 1e3)
    broker = None
    if args.broker:
        host, _, port = args.broker.rpartition(":")
        from feddrift_tpu.comm.netbroker import NetworkBrokerClient
        from feddrift_tpu.resilience import (ReconnectingBrokerClient,
                                             RetryPolicy)
        broker = ReconnectingBrokerClient(
            lambda: NetworkBrokerClient(host or "127.0.0.1", int(port)),
            retry=RetryPolicy(base_delay=0.05, max_delay=0.25,
                              max_attempts=400, deadline_s=120.0),
            heartbeat_interval=0.1, heartbeat_timeout=0.4,
            client_id="serve-frontend")
        # cluster-event hot swaps reach EVERY replica (fanout subscribe);
        # the NDJSON request plane + per-replica fleet lanes share the
        # same connection
        for eng in fe.replicas.engines:
            eng.attach_broker(broker,
                              topic=args.topic or serving.CLUSTER_TOPIC)
        fe.attach_broker(broker)
        fe.attach_ops(broker)
    ops = None
    if args.ops_port is not None:
        from feddrift_tpu.obs import live
        ops = live.OpsServer(port=args.ops_port).start()
    # black box + incident plane: a replica dying mid-traffic captures a
    # merged cross-process bundle under <run_dir>/incidents/ (per-replica
    # flight snapshots pulled over the broker when one is attached)
    from feddrift_tpu.obs import blackbox
    from feddrift_tpu.obs import events as obs_events
    from feddrift_tpu.obs import incident as incident_mod
    rec = blackbox.configure().attach(obs_events.get_bus())
    inc = incident_mod.IncidentManager(
        args.run_dir, recorder=rec).attach(obs_events.get_bus())
    fe.attach_incidents(inc, client=broker)
    incident_mod.install_process_hooks(inc)
    fe.start(port=args.listen)
    print(json.dumps({"listening": fe.url,
                      "replicas": fe.replicas.healthy_names()}))
    stats = None
    try:
        if args.requests > 0:
            client = frontend_mod.FrontendClient(fe.url)
            gen = serving.TrafficGenerator(
                client, list(range(fe.replicas.population)),
                seed=args.seed, concurrency=args.concurrency)
            deadline_s = (args.deadline_ms / 1e3
                          if args.deadline_ms > 0 else None)
            if args.open_rps > 0:
                stats = gen.run_open(args.requests, args.open_rps,
                                     deadline_s=deadline_s)
            else:
                stats = gen.run(args.requests)
            print(json.dumps({**stats, "frontend": fe.status()}, indent=2))
        else:
            while True:         # serve until interrupted
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        fe.close()
        if broker is not None:
            broker.close()
        if ops is not None:
            ops.close()
    return _serve_exit_code(stats, fe.replicas.engines)


def _serve_exit_code(stats: dict | None, engines) -> int:
    """0 only if every generated request was answered and no engine's
    dispatcher died: a serve command whose requests failed has failed."""
    errors = int((stats or {}).get("errors", 0))
    dead = [e for e in engines if e.failed is not None]
    if errors or dead:
        print(f"serve: FAILED — {errors} request error(s), "
              f"{len(dead)} dead engine(s)"
              + "".join(f"; {e.failed!r}" for e in dead), file=sys.stderr)
        return 1
    return 0


def _cfg_from_args(args: argparse.Namespace):
    from feddrift_tpu.config import ExperimentConfig
    known = {f.name for f in dataclasses.fields(ExperimentConfig)}
    d = {k: v for k, v in vars(args).items() if k in known and v is not None}
    if "mesh_shape" in d:
        d["mesh_shape"] = json.loads(d["mesh_shape"]) if d["mesh_shape"] else {}
    return ExperimentConfig(**d)


def _arm_faulthandler(run_dir: str | None = None):
    """Arm ``faulthandler`` so hard hangs and native crashes (wedged
    collectives, deadlocked dispatchers, segfaults in XLA) dump Python
    stacks instead of dying silently. Called once at CLI entry — BEFORE
    jax/backend init so every verb is diagnosable — and again with a run
    dir on run/resume to route dumps to ``<run_dir>/faulthandler.log``
    (``kill -QUIT`` capture lands there too; see obs/incident.py).

    Returns the dump file (kept open for the process lifetime:
    faulthandler holds the raw fd), or None when dumping to stderr.
    """
    import faulthandler
    import os

    fh = None
    if run_dir:
        os.makedirs(run_dir, exist_ok=True)
        fh = open(os.path.join(run_dir, "faulthandler.log"), "a")
    try:
        faulthandler.enable(file=fh if fh is not None else sys.stderr,
                            all_threads=True)
    except (ValueError, OSError, AttributeError):
        pass        # fd-less stderr (pytest capture, embedded interpreters)
    return fh


def main(argv: list[str] | None = None) -> int:
    _arm_faulthandler()
    parser = argparse.ArgumentParser(prog="feddrift_tpu")
    parser.add_argument("--log_level", type=str, default="info",
                        help="logging level for the feddrift_tpu loggers "
                             "(debug|info|warning|error)")
    sub = parser.add_subparsers(dest="cmd", required=True)

    run_p = sub.add_parser("run", help="run a drift-FL experiment")
    _add_run_args(run_p)

    res_p = sub.add_parser("resume", help="resume from a checkpoint")
    res_p.add_argument("--out_dir", type=str, required=True)
    res_p.add_argument("--wandb", action="store_true")
    res_p.add_argument("--platform", type=str, default="",
                       help="force a JAX platform (e.g. 'cpu')")
    _add_multihost_args(res_p)

    sub.add_parser("list", help="list algorithms / datasets / models")

    rep_p = sub.add_parser(
        "report", help="render a run report from events.jsonl + metrics.jsonl")
    rep_p.add_argument("run_dirs", nargs="+")
    rep_p.add_argument("--json", action="store_true")
    rep_p.add_argument("--trace", action="store_true",
                       help="also export <run_dir>/trace.json — a "
                            "Perfetto/chrome://tracing-loadable timeline "
                            "built from spans.jsonl + events.jsonl")
    rep_p.add_argument("--follow", action="store_true",
                       help="bounded tail mode: stream events + health "
                            "alerts (obs/alerts.py, evaluated offline) "
                            "until run_end or --follow-timeout, then "
                            "render the report")
    rep_p.add_argument("--follow-timeout", type=float, default=30.0)
    rep_p.add_argument("--poll", type=float, default=0.5)

    lin_p = sub.add_parser(
        "lineage", help="reconstruct the cluster genealogy DAG from a "
                        "run's events.jsonl — evidence-annotated "
                        "create/merge/split/delete with slot reuse "
                        "resolved into stable lineage ids, plus "
                        "per-iteration oracle ARI/purity for synthetic "
                        "ground truth (obs/lineage.py)")
    lin_p.add_argument("run_dir")
    lin_p.add_argument("--dot", type=str, default=None,
                       help="also write a Graphviz DOT export here")
    lin_p.add_argument("--json", action="store_true")

    reg_p = sub.add_parser(
        "regress", help="perf-regression gate: compare a bench.py artifact "
                        "against a baseline, exit 1 on regression "
                        "(obs/regress.py)")
    reg_p.add_argument("candidate")
    reg_p.add_argument("--baseline", required=True)
    reg_p.add_argument("--tol-rounds", type=float, default=None)
    reg_p.add_argument("--tol-wall", type=float, default=None)
    reg_p.add_argument("--tol-acc", type=float, default=None)
    reg_p.add_argument("--tol-compiles", type=float, default=None)
    reg_p.add_argument("--tol-host-overhead", type=float, default=None)
    reg_p.add_argument("--tol-p99", type=float, default=None)
    reg_p.add_argument("--tol-precision-acc", type=float, default=None)
    reg_p.add_argument("--tol-quality-acc", type=float, default=None)
    reg_p.add_argument("--tol-hostscale-exp", type=float, default=None)
    reg_p.add_argument("--json", action="store_true")

    cp_p = sub.add_parser(
        "critical_path",
        help="per-round segment breakdown + dominant-segment / straggler "
             "attribution from a run dir's spans.jsonl + events.jsonl "
             "(obs/critical_path.py)")
    cp_p.add_argument("run_dir")
    cp_p.add_argument("--json", action="store_true")
    cp_p.add_argument("--flame", action="store_true",
                      help="also print top folded host stacks from the "
                           "run's sampling profiler (hostprof.folded)")
    cp_p.add_argument("--flame-top", type=int, default=10, metavar="N")

    fl_p = sub.add_parser(
        "fleet",
        help="render a live multi-process ops table from <ns>/ops/* "
             "snapshots on a running broker (obs/live.py)")
    fl_p.add_argument("broker", help="broker address, host:port")
    fl_p.add_argument("--namespace", default="feddrift")
    fl_p.add_argument("--duration", type=float, default=5.0)
    fl_p.add_argument("--poll", type=float, default=0.2)
    fl_p.add_argument("--min-lanes", type=int, default=0)
    fl_p.add_argument("--stale-after", type=float, default=60.0,
                      help="evict lanes whose last snapshot is older than "
                           "this many seconds and mark them (stale) in the "
                           "table (<= 0 disables; default %(default)ss)")
    fl_p.add_argument("--json", action="store_true")

    inc_p = sub.add_parser(
        "incident",
        help="post-mortem triage: render the story from an incident "
             "bundle — what fired, the dominant critical-path segment, "
             "recent swaps/canary verdicts with lineage ids, and "
             "replica/broker health at capture (obs/incident.py; pass a "
             "bundle dir or a run dir to pick its newest bundle)")
    inc_p.add_argument("target",
                       help="incident bundle directory, or a run dir "
                            "holding <run_dir>/incidents/")
    inc_p.add_argument("--json", action="store_true")

    srv_p = sub.add_parser(
        "serve",
        help="cluster-routed inference over a finished run: load the "
             "checkpointed model pool + client registry, warm the "
             "micro-batching engine, drive seeded closed-loop traffic, "
             "print throughput/latency stats JSON "
             "(platform/serving.py; docs/SERVING.md)")
    srv_p.add_argument("run_dir", help="run directory holding ckpt/")
    srv_p.add_argument("--requests", type=int, default=500,
                       help="closed-loop requests to drive (default "
                            "%(default)s)")
    srv_p.add_argument("--concurrency", type=int, default=8,
                       help="closed-loop worker threads (default "
                            "%(default)s)")
    srv_p.add_argument("--seed", type=int, default=0,
                       help="traffic-generator seed (default %(default)s)")
    srv_p.add_argument("--buckets", type=str, default="1,2,4,8,16,32",
                       help="comma-separated admission batch buckets; each "
                            "is compiled once in warm-up (default "
                            "%(default)s)")
    srv_p.add_argument("--max_wait_ms", type=float, default=2.0,
                       help="admission-queue coalescing window (default "
                            "%(default)s ms)")
    srv_p.add_argument("--broker", type=str, default=None,
                       help="host:port of a live broker — subscribe the "
                            "cluster-event topic for hot-swaps under "
                            "drift, with auto-reconnect")
    srv_p.add_argument("--topic", type=str, default=None,
                       help="broker topic carrying cluster events "
                            "(default: serve/cluster)")
    srv_p.add_argument("--ops_port", type=int, default=None,
                       help="also expose /metrics + /healthz on this port "
                            "(0 = ephemeral)")
    srv_p.add_argument("--quality_window", type=int, default=0,
                       help="enable the streaming model-quality plane "
                            "with this label window (0 = off; "
                            "docs/OBSERVABILITY.md Model-quality plane)")
    srv_p.add_argument("--canary_fraction", type=float, default=0.0,
                       help="shadow-canary cluster events on this "
                            "fraction of affected traffic before "
                            "committing the swap (0 = swap immediately; "
                            "docs/SERVING.md Canarying hot swaps)")
    srv_p.add_argument("--listen", type=int, default=None,
                       help="deploy the socket frontend on this HTTP port "
                            "(0 = ephemeral): POST /v1/submit + /healthz "
                            "/metrics /status, admission control, replica "
                            "failover (platform/frontend.py; docs/"
                            "SERVING.md Deployment). Traffic (--requests"
                            "/--open_rps) then drives the SOCKET path; "
                            "--requests 0 serves until interrupted")
    srv_p.add_argument("--replicas", type=int, default=2,
                       help="engine replicas behind the frontend "
                            "(--listen only; default %(default)s)")
    srv_p.add_argument("--max_pending", type=int, default=64,
                       help="frontend admission window: pending requests "
                            "beyond this shed with 503 (default "
                            "%(default)s)")
    srv_p.add_argument("--max_queue", type=int, default=64,
                       help="per-replica engine queue bound; 0 = "
                            "unbounded (default %(default)s with "
                            "--listen, 0 otherwise)")
    srv_p.add_argument("--rate_rps", type=float, default=0.0,
                       help="token-bucket admission rate limit, "
                            "requests/s (0 = off)")
    srv_p.add_argument("--slo_p99_ms", type=float, default=0.0,
                       help="request-latency p99 objective in ms: burn "
                            "on it shrinks the admit window "
                            "(backpressure; 0 = off)")
    srv_p.add_argument("--open_rps", type=float, default=0.0,
                       help="drive OPEN-LOOP traffic at this fixed "
                            "offered rate instead of the closed loop "
                            "(measures saturation without coordinated "
                            "omission; 0 = closed loop)")
    srv_p.add_argument("--deadline_ms", type=float, default=0.0,
                       help="per-request propagated deadline for "
                            "generated traffic (0 = none)")
    srv_p.add_argument("--platform", type=str, default="",
                       help="force a JAX platform (e.g. 'cpu')")

    li_p = sub.add_parser(
        "lint",
        help="graftlint: static-analysis pass over the package "
             "(analysis/ rules R1-R6 — cfg registry, hot-path host "
             "syncs, tap re-entrancy, nondeterminism, jit-static "
             "hygiene, event-taxonomy drift); exit 1 on findings")
    li_p.add_argument("paths", nargs="*", default=["feddrift_tpu"],
                      help="files/directories to lint "
                           "(default: feddrift_tpu/)")
    li_p.add_argument("--json", action="store_true",
                      help="machine-readable findings (stable schema)")
    li_p.add_argument("--strict", action="store_true",
                      help="also fail warnings and dead event kinds")

    # --log_level is also accepted after the subcommand for convenience
    # (SUPPRESS default: an absent post-subcommand flag must not clobber a
    # pre-subcommand one — both write the same namespace attribute)
    for p in (run_p, res_p, rep_p, reg_p, lin_p, cp_p, fl_p, inc_p, srv_p,
              li_p):
        p.add_argument("--log_level", type=str, default=argparse.SUPPRESS,
                       help=argparse.SUPPRESS)

    args = parser.parse_args(argv)

    from feddrift_tpu.obs import setup_logging
    setup_logging(getattr(args, "log_level", None) or "info")

    if args.cmd == "report":
        # pure host-side: no jax / backend initialisation needed
        from feddrift_tpu.obs.report import main as report_main
        return report_main(args.run_dirs
                           + (["--json"] if args.json else [])
                           + (["--trace"] if args.trace else [])
                           + (["--follow",
                               "--follow-timeout", str(args.follow_timeout),
                               "--poll", str(args.poll)]
                              if args.follow else []))

    if args.cmd == "lineage":
        # pure host-side: no jax / backend initialisation needed
        from feddrift_tpu.obs.lineage import main as lineage_main
        return lineage_main([args.run_dir]
                            + (["--dot", args.dot] if args.dot else [])
                            + (["--json"] if args.json else []))

    if args.cmd == "regress":
        # pure host-side: no jax / backend initialisation needed
        from feddrift_tpu.obs.regress import main as regress_main
        argv_r = [args.candidate, "--baseline", args.baseline]
        for flag in ("tol_rounds", "tol_wall", "tol_acc", "tol_compiles",
                     "tol_host_overhead", "tol_p99", "tol_precision_acc",
                     "tol_quality_acc", "tol_hostscale_exp"):
            v = getattr(args, flag)
            if v is not None:
                argv_r += [f"--{flag.replace('_', '-')}", str(v)]
        if args.json:
            argv_r.append("--json")
        return regress_main(argv_r)

    if args.cmd == "critical_path":
        # pure host-side: no jax / backend initialisation needed
        from feddrift_tpu.obs.critical_path import main as cp_main
        return cp_main([args.run_dir]
                       + (["--json"] if args.json else [])
                       + (["--flame", "--flame-top", str(args.flame_top)]
                          if args.flame else []))

    if args.cmd == "fleet":
        # pure host-side: the netbroker client is stdlib + obs, no jax
        from feddrift_tpu.obs.live import fleet_main
        return fleet_main(
            [args.broker, "--namespace", args.namespace,
             "--duration", str(args.duration), "--poll", str(args.poll),
             "--min-lanes", str(args.min_lanes),
             "--stale-after", str(args.stale_after)]
            + (["--json"] if args.json else []))

    if args.cmd == "incident":
        # pure host-side: bundle reading + rendering is stdlib only, no jax
        from feddrift_tpu.obs.incident import incident_main
        return incident_main([args.target]
                             + (["--json"] if args.json else []))

    if args.cmd == "lint":
        # pure host-side: the AST engine imports neither jax nor the
        # package's device modules
        from feddrift_tpu.analysis.engine import run_lint
        return run_lint(args.paths, strict=args.strict, as_json=args.json)

    if getattr(args, "platform", ""):
        import jax
        jax.config.update("jax_platforms", args.platform)
    from feddrift_tpu.utils.cache import enable_compile_cache
    enable_compile_cache()
    _maybe_init_multihost(args)

    if args.cmd == "serve":
        from feddrift_tpu.platform import serving
        buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())

        if args.listen is not None:
            return _serve_listen(args, buckets)

        engine = serving.load_engine(args.run_dir, buckets=buckets,
                                     max_wait_s=args.max_wait_ms / 1e3)
        ops = None
        if args.ops_port is not None:
            from feddrift_tpu.obs import live
            ops = live.OpsServer(port=args.ops_port).start()
        broker = None
        if args.broker:
            host, _, port = args.broker.rpartition(":")
            from feddrift_tpu.comm.netbroker import NetworkBrokerClient
            from feddrift_tpu.resilience import (ReconnectingBrokerClient,
                                                 RetryPolicy)
            broker = ReconnectingBrokerClient(
                lambda: NetworkBrokerClient(host or "127.0.0.1", int(port)),
                retry=RetryPolicy(base_delay=0.05, max_delay=0.25,
                                  max_attempts=400, deadline_s=120.0),
                heartbeat_interval=0.1, heartbeat_timeout=0.4,
                client_id="serve-cli")
            engine.attach_broker(
                broker, topic=args.topic or serving.CLUSTER_TOPIC)
        if args.quality_window > 0:
            engine.enable_quality(window=args.quality_window)
        if args.canary_fraction > 0:
            from feddrift_tpu.platform.canary import CanaryController
            engine.attach_canary(CanaryController(
                engine, fraction=args.canary_fraction))
        if broker is not None:
            # fleet lane serve/<pid>: REQ/S, P99-REQ, POOL-VER, CANARY
            engine.attach_ops(broker)
        engine.start()
        engine.warmup()
        from feddrift_tpu import obs
        from feddrift_tpu.obs import costmodel

        def compiles() -> dict:
            return {k: int(v) for k, v in obs.registry().snapshot().items()
                    if k.startswith(("jit_compiles", "jit_recompiles"))}
        warm = compiles()
        stats = None
        try:
            gen = serving.TrafficGenerator(
                engine, list(range(engine.population)), seed=args.seed,
                concurrency=args.concurrency)
            if args.open_rps > 0:
                stats = gen.run_open(
                    args.requests, args.open_rps,
                    deadline_s=(args.deadline_ms / 1e3
                                if args.deadline_ms > 0 else None))
            else:
                stats = gen.run(args.requests)
            after = compiles()
            print(json.dumps({
                **stats, **engine.stats(), **costmodel.device_info(),
                # programs compiled by warm-up, and what traffic added on
                # top (any entry here is a steady-state recompile)
                "warmup_compiles": warm,
                "steady_compiles": {k: after[k] - warm.get(k, 0)
                                    for k in after
                                    if after[k] != warm.get(k, 0)},
            }, indent=2))
        finally:
            engine.close()
            if broker is not None:
                broker.close()
            if ops is not None:
                ops.close()
        return _serve_exit_code(stats, [engine])

    if args.cmd == "list":
        from feddrift_tpu.algorithms import available_algorithms
        from feddrift_tpu.data.registry import available_datasets
        from feddrift_tpu.models import available_models
        print(json.dumps({"algorithms": available_algorithms(),
                          "datasets": available_datasets(),
                          "models": available_models()}, indent=2))
        return 0

    from feddrift_tpu.simulation.runner import Experiment

    if args.cmd == "resume":
        import os
        from feddrift_tpu.config import ExperimentConfig
        with open(os.path.join(args.out_dir, "ckpt", "MANIFEST.json")) as f:
            cfg = ExperimentConfig.from_json(json.dumps(json.load(f)["config"]))
        fh_file = _arm_faulthandler(args.out_dir)
        exp = Experiment.resume(cfg, args.out_dir, use_wandb=args.wandb)
    else:
        cfg = _cfg_from_args(args)
        import os
        if getattr(args, "flat_out_dir", False):
            out_dir = cfg.out_dir
        else:
            out_dir = os.path.join(
                cfg.out_dir,
                f"{cfg.dataset}-{cfg.model}-{cfg.concept_drift_algo}"
                f"-{cfg.concept_drift_algo_arg}-s{cfg.seed}")
        ckpt = os.path.join(out_dir, "ckpt")
        fh_file = _arm_faulthandler(out_dir)
        if (getattr(args, "auto_resume", False)
                and (os.path.isdir(ckpt) or os.path.isdir(ckpt + ".old"))):
            exp = Experiment.resume(cfg, out_dir, use_wandb=args.wandb)
        else:
            exp = Experiment(cfg, use_wandb=args.wandb, out_dir=out_dir)

    if getattr(exp, "incidents", None) is not None:
        # kill -QUIT now dumps all-thread stacks to faulthandler.log AND
        # snapshots an incident bundle; uncaught exceptions in other
        # threads (sys.excepthook chain) get a bundle too
        from feddrift_tpu.obs import incident as incident_mod
        incident_mod.install_process_hooks(exp.incidents,
                                           faulthandler_file=fh_file)

    exp.run()
    from feddrift_tpu.obs import costmodel
    print(json.dumps({"Test/Acc": exp.logger.last("Test/Acc"),
                      "Train/Acc": exp.logger.last("Train/Acc"),
                      "rounds": exp.global_round,
                      "preempted": exp.preempted,
                      **costmodel.device_info(),
                      "mesh": dict(exp.mesh.shape),
                      "precision": exp.precision.name,
                      "compute_dtype": exp.precision.compute_dtype}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
