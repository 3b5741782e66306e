"""Run telemetry: structured events, cross-layer instruments, logging setup.

The reference's only instrumentation is wandb scalar series plus one ad-hoc
"aggregate time cost" print (SURVEY.md §5); our own early reproduction had a
wall-clock ``PhaseTracer`` and a flat ``MetricsLogger`` and nothing else —
the comm brokers, drift/cluster decisions, XLA compiles and injected faults
were all invisible. This package is the missing observability layer:

- ``obs.events``      — a process-local structured EVENT BUS. Typed events
  (``kind`` from a closed taxonomy, ``_ts``, current iteration/round
  context) are appended to ``events.jsonl`` next to ``metrics.jsonl``.
  Layers emit through the module-level ``emit()``; background threads
  (comm brokers) share the same bus safely.
- ``obs.instruments`` — counters / gauges / histograms with
  bounded-overhead recording and a Prometheus-textfile exporter, for
  quantities that are too hot to be one-event-per-occurrence
  (bytes on the comm path, per-phase latency histograms, compile counts).
- ``obs.report``      — renders a human-readable run report from
  ``events.jsonl`` + ``metrics.jsonl`` (CLI: ``python -m feddrift_tpu
  report <run_dir>``).
- ``obs.costmodel``   — XLA cost/memory accounting per compiled program
  (FLOPs, bytes accessed, peak HBM), live ``device.memory_stats()``
  watermarks, measured/datasheet peaks, and the roofline math behind
  ``bench.py``'s ``mfu_estimate``.
- ``obs.hostprof``    — the host-plane observatory: a sampling stack
  profiler (``cfg.hostprof_hz``, folded-stack text + trace.json lanes)
  and the per-subsystem ``HostLedger`` of host-seconds and host bytes
  behind the ``host_ledger`` event and ``bench.py --hostscale``.
- ``obs.spans``       — wall-clock span recording (``spans.jsonl``) and
  the Chrome-trace-event exporter behind ``report <run_dir> --trace``
  (Perfetto-loadable ``trace.json``, one lane per process/thread).
- ``obs.compile_spans`` — JAX's trace, lowering and compile (or cache
  load) of every program as ``cat="compile"`` spans on the span recorder,
  from ``jax.monitoring``; imports JAX, so it is not bound here.
- ``obs.regress``     — the perf-regression gate over bench artifacts
  (CLI: ``python -m feddrift_tpu regress <bench.json> --baseline ...``).
- ``obs.lineage``     — cluster genealogy DAG reconstruction + oracle
  ARI/purity scoring (CLI: ``python -m feddrift_tpu lineage <run_dir>``).
- ``obs.alerts``      — declarative rule-based health monitor: live as an
  event-bus tap (``cfg.alerts``) and offline via ``report --follow``,
  raising ``alert_raised`` events + ``alerts.jsonl``.
- ``obs.quantiles``   — streaming P² percentile sketches (O(1) memory)
  registrable alongside histograms for live p50/p95/p99 gauges.
- ``obs.live``        — the live ops plane: per-process /metrics,
  /healthz and /status HTTP endpoints, fleet snapshot publishing +
  ``FleetCollector`` merge over the broker (CLI: ``python -m
  feddrift_tpu fleet <broker>``), and an SLO engine whose error-budget
  burn-rate rules emit ``slo_burn`` events on the live tap.
- ``obs.blackbox``    — the always-on flight recorder: bounded
  in-memory rings over recent events/alerts/round_breakdowns plus
  periodic instrument snapshots, dumped into incident bundles.
- ``obs.incident``    — the incident plane: trigger taps (crit alerts,
  SLO burns, replica deaths, preemption, exceptions, SIGQUIT) debounced
  into self-contained forensic bundles under ``<run_dir>/incidents/``
  (CLI: ``python -m feddrift_tpu incident <bundle-or-run_dir>``).

Event kinds are a CLOSED set (``events.EVENT_KINDS``): ``emit()`` rejects
unknown kinds, and ``scripts/check_events_schema.py`` statically checks that
every kind emitted anywhere in the package is documented in
docs/OBSERVABILITY.md — new events cannot ship undocumented.

See docs/OBSERVABILITY.md for the taxonomy and formats.
"""

from __future__ import annotations

from feddrift_tpu.obs.events import (  # noqa: F401
    EVENT_KINDS,
    EventBus,
    capture,
    configure,
    emit,
    get_bus,
    set_context,
)
from feddrift_tpu.obs.instruments import (  # noqa: F401
    Registry,
    registry,
)
from feddrift_tpu.obs import (  # noqa: F401
    alerts,
    blackbox,
    costmodel,
    hostprof,
    incident,
    lineage,
    live,
    quantiles,
    spans,
)
# (import order: all depend only on obs.events/obs.instruments, bound above;
# lineage is numpy+stdlib only, alerts touches the bus solely via taps, and
# live — the ops-plane HTTP server / fleet publisher / SLO engine — is
# stdlib + events/instruments/alerts, importing comm transports lazily)

_LOG_FORMAT = "%(asctime)s %(name)s %(levelname)s %(message)s"


def setup_logging(level: str | int = "info") -> None:
    """The single logging configuration path for the package.

    Called from the CLI (``--log_level``) and usable from scripts; repeated
    calls reconfigure (``force=True``) so tests and multi-run processes can
    change verbosity. Configures the root handler AND pins the
    ``feddrift_tpu`` logger level, so ``--log_level debug`` surfaces the
    package's debug output without drowning in third-party debug noise
    (third-party loggers stay at the root level only).
    """
    import logging

    if isinstance(level, str):
        lvl = getattr(logging, level.upper(), None)
        if lvl is None:
            raise ValueError(f"unknown log level {level!r}")
    else:
        lvl = level
    logging.basicConfig(level=lvl, format=_LOG_FORMAT, force=True)
    logging.getLogger("feddrift_tpu").setLevel(lvl)
