"""JAX's own stages as spans: one ``cat="compile"`` span for each trace,
lowering and compile of a program, recorded onto the process-wide span
recorder (``obs/spans.py``) through ``jax.monitoring``.

JAX reports three time spans for every program it builds:

- ``trace``: ``/jax/core/compile/jaxpr_trace_duration``, the Python function
  traced to a jaxpr;
- ``lower``: ``/jax/core/compile/jaxpr_to_mlir_module_duration``, the jaxpr
  lowered to an MLIR module;
- ``compile``: ``/jax/core/compile/backend_compile_duration``, around
  ``compiler.compile_or_get_cached``: an XLA compile, or a load from the
  persistent compilation cache.

Each becomes one completed span, recorded after the fact with
``SpanRecorder.record``: it sits on no thread's stack, so no other span's
self time changes and no segment of ``round_breakdown`` moves. Its ``args``:

- ``fn``: JAX's name of the function;
- ``caused_by``: the calling thread's innermost open span, with its ``fn``
  where it has one (``dispatch/train_round``, ``pool_init``, ...); None where
  no span is open;
- the recorder's ambient context (``iteration``, ``round``);
- on ``compile`` only, ``cache``: ``hit`` (loaded from the persistent cache,
  with ``cache_load_s``, the read's seconds), ``miss`` (the cache was asked
  and did not hold the program) or ``off`` (no cache was asked, or JAX
  asked one with no directory set: none exists);
- on ``trace`` only, ``nested``: a trace opened inside another on the same
  thread (an inner ``jit``, an eager op of a traced function) is folded
  into the outermost one, which counts them. A model's trace holds dozens.

JAX's start and end are ``time.time()`` readings; they are moved onto the
recorder's own clock (``perf_counter`` mapped by ``wall``) at the moment the
span ends, so a compile lies inside the ``dispatch`` span that caused it.

``register()`` hangs the listeners on ``jax.monitoring`` once per process
(``Experiment.__init__`` calls it before anything is built). They cost one
callback per JAX event and nothing while no program is built.
"""

from __future__ import annotations

import threading
import time

import jax

from feddrift_tpu.obs import spans

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
STAGES = {TRACE: "trace", LOWER: "lower", COMPILE: "compile"}
CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

_tls = threading.local()
_lock = threading.Lock()
_registered = False


def _state():
    """The calling thread's open traces and the cache's answer to its open
    compile."""
    st = getattr(_tls, "st", None)
    if st is None:
        st = _tls.st = {"depth": 0, "nested": 0, "asked": False,
                        "hit": False, "load_s": None}
    return st


def _on_scalar(event: str, value: float, **kw) -> None:
    """A stage opens (JAX records its start time as a scalar)."""
    if event == TRACE:
        _state()["depth"] += 1
    elif event == COMPILE:
        _state().update(asked=False, hit=False, load_s=None)


def _on_event(event: str, **kw) -> None:
    if event == CACHE_ASKED:
        _state()["asked"] = True
    elif event == CACHE_HIT:
        _state()["hit"] = True


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == CACHE_LOAD:
        _state()["load_s"] = secs


def _on_span(event: str, start: float, end: float, **kw) -> None:
    """A stage closes: one span, or for a nested trace one more count on
    the outermost."""
    stage = STAGES.get(event)
    if stage is None:
        return
    st = _state()
    args = {}
    if stage == "trace":
        st["depth"] = max(st["depth"] - 1, 0)
        if st["depth"]:
            st["nested"] += 1
            return
        args["nested"], st["nested"] = st["nested"], 0
    elif stage == "compile":
        args["cache"] = "hit" if st["hit"] else "miss" if st["asked"] \
            and jax.config.jax_compilation_cache_dir else "off"
        if st["hit"]:
            args["cache_load_s"] = st["load_s"]
    rec = spans.get_recorder()
    if not rec.enabled:
        return
    open_span = rec.innermost()
    cause = open_span.name
    if cause is not None and "fn" in open_span.args:
        cause = f"{cause}/{open_span.args['fn']}"
    shift = rec.wall(time.perf_counter()) - time.time()
    rec.record(stage, start + shift, end - start, cat="compile",
               **{**rec.context, "fn": kw.get("fun_name"),
                  "caused_by": cause, **args})


def register() -> None:
    """Hang the listeners on ``jax.monitoring``; a second call does
    nothing."""
    global _registered
    with _lock:
        if _registered:
            return
        jax.monitoring.register_scalar_listener(_on_scalar)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_time_span_listener(_on_span)
        _registered = True
