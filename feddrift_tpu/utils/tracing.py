"""Tracing & profiling.

The reference's only instrumentation is wall-clock logging of the aggregate
step ("aggregate time cost", FedAvgEnsAggregatorSoftCluster.py:193-194) plus
setproctitle labels (SURVEY.md §5 'Tracing/profiling: nothing beyond...').
Here per-phase timing is first-class and the XLA profiler is one context
manager away.

Usage:
    tracer = PhaseTracer()
    with tracer.phase("cluster"):
        ...
    with tracer.phase("train_round"):
        ...
    tracer.summary()  # {"cluster": {"total_s": ..., "count": ...}, ...}

    with xla_trace("/tmp/trace"):   # TensorBoard-loadable XLA trace
        run_step()

PhaseTracer is thread-safe (the comm brokers run background threads that
may record phases) and nestable/re-entrant: each ``phase()`` entry keeps
its own start time on the context-manager frame, so overlapping phases on
one thread and concurrent phases across threads both accumulate
correctly. Pass ``registry=obs.registry()`` to additionally record each
phase duration into a ``phase_seconds{phase=...}`` histogram instrument
(bench snapshots read those), and ``spans=obs.spans.get_recorder()`` to
put every phase on the unified trace timeline
(``report <run_dir> --trace`` → Perfetto-loadable trace.json).

``xla_trace`` is no-op-safe under nesting: ``jax.profiler.start_trace``
raises when a trace is already active, so an inner ``xla_trace`` runs its
body without starting (or stopping) anything; each completed capture
emits a ``profile_captured`` event carrying the trace dir. A profiler that
cannot start raises.
"""

from __future__ import annotations

import contextlib
import logging
import threading
from collections import defaultdict
from typing import Iterator

from feddrift_tpu.obs.spans import SpanRecorder

log = logging.getLogger("feddrift_tpu")


class PhaseTracer:
    """Accumulates wall-clock per named phase; nestable, re-entrant, and
    thread-safe.

    The interval measurement itself lives in ``obs.spans.SpanRecorder``
    (one timing code path for the whole repo): ``phase()`` is a
    ``cat="phase"`` span whose duration goes through ``add``, the
    total/count accounting and the ``phase_seconds`` histogram. The runner
    feeds ``add`` from its ``cat="round"`` spans where one of those covers
    a phase (eval, the drift decision, the cohort preparation), so no
    interval is recorded twice. Without an explicit ``spans=`` recorder a
    private memory-only one measures; a disabled recorder counts the
    phases and measures nothing.
    """

    def __init__(self, registry=None, spans=None) -> None:
        self.totals: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()
        self._registry = registry
        self.spans = spans if spans is not None else SpanRecorder(None)

    def add(self, name: str, dt: float) -> None:
        """Account one completed interval of ``dt`` seconds to ``name``."""
        with self._lock:
            self.totals[name] += dt
            self.counts[name] += 1
        if self._registry is not None:
            self._registry.histogram("phase_seconds",
                                     phase=name).observe(dt)

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        sp = self.spans.span(name, cat="phase")
        try:
            with sp:
                yield
        finally:
            self.add(name, sp.dur)

    def summary(self) -> dict[str, dict[str, float]]:
        with self._lock:
            return {name: {"total_s": self.totals[name],
                           "count": self.counts[name],
                           "mean_s": self.totals[name] / max(self.counts[name], 1)}
                    for name in self.totals}

    def log_summary(self, prefix: str = "") -> None:
        for name, s in sorted(self.summary().items()):
            log.info("%sphase %-16s total=%.3fs mean=%.4fs n=%d",
                     prefix, name, s["total_s"], s["mean_s"], s["count"])

    def reset(self) -> None:
        with self._lock:
            self.totals.clear()
            self.counts.clear()


# True while an xla_trace capture is active in this process. jax raises
# on a nested start_trace; this flag makes the nested entry a clean no-op
# (body runs, outer capture owns the trace) instead of a warning-swallowed
# exception race with jax's own global state.
_trace_active = False
_trace_lock = threading.Lock()


@contextlib.contextmanager
def xla_trace(log_dir: str) -> Iterator[None]:
    """jax.profiler trace (TensorBoard format). Nesting is a no-op: if a
    trace is already active the body runs and the outer capture owns the
    trace. A profiler that cannot start raises — a capture that was asked
    for and silently did not happen is worse than no capture. Each
    completed capture emits a ``profile_captured`` event with the dir."""
    global _trace_active
    import jax
    with _trace_lock:
        nested = _trace_active
        if not nested:
            _trace_active = True
    if nested:
        log.debug("xla_trace: trace already active; nested capture of %s "
                  "is a no-op", log_dir)
        yield
        return
    try:
        jax.profiler.start_trace(log_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        from feddrift_tpu import obs
        obs.emit("profile_captured", trace_dir=log_dir)
    finally:
        with _trace_lock:
            _trace_active = False
