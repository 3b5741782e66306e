"""Unified trace timeline: spans + events → one Chrome-trace JSON.

A *span* is a named wall-clock interval (phase, iteration, comm publish)
recorded live; an *event* (obs/events.py) is a point occurrence. This
module records the former to ``<run_dir>/spans.jsonl`` and folds BOTH
into a single Chrome-trace-event JSON that Perfetto / ``chrome://tracing``
loads directly:

    python -m feddrift_tpu report <run_dir> --trace   # writes trace.json

Timeline layout: one **process lane per host process** (multihost runs
stamp ``jax.process_index()`` into every span, so merged traces keep one
lane each), and within a process one **thread lane per recording thread**
(the runner's main thread, comm-broker background threads) plus one
reserved ``events`` lane where every ``events.jsonl`` record appears as
an instant. Span ``ts`` is unix epoch microseconds — the same clock
events carry in ``_ts`` — so the two sources interleave correctly.

Recording is O(1) per span (one lock, one append, one buffered line for
the optional file sink) and the recorder is disabled until ``configure()``
arms it, so un-instrumented processes pay one attribute check on the hot
path. The ring is the record: the JSONL sink is flushed when an
``iteration`` span is recorded, on rotation, on ``flush()`` (the flight
recorder's dump) and on ``close()``, never per span.

``span()`` keeps a per-thread stack of open spans, so every closed span
has its **self time** next to its duration: the duration less what its
child spans on that thread covered. The thread's completion hook
(``set_hook``) receives both; an accumulator that sums self times
partitions the wall, and nothing nested is counted twice. A span also
enters the ``annotate`` factory the owner handed the recorder (the runner
passes ``jax.profiler.TraceAnnotation``), which puts the program's spans
into the host plane of any profiler capture; this module itself never
imports JAX.
"""

from __future__ import annotations

import collections
import inspect
import json
import os
import threading
import time
import uuid
import weakref
from typing import Any, Callable

RING_SIZE = 8192

# the clock of span(): perf_counter seconds (tests put their own here)
_now = time.perf_counter

# tid of the reserved per-process instant-event lane in trace.json
EVENTS_LANE_TID = 0


# ----------------------------------------------------------------------
# Trace context: the W3C-style (trace_id, span_id, parent_span_id) triple
# that rides broker frames so one client update is followable
# client -> compress -> wire -> edge -> server across process lanes.
# A context is a plain JSON dict; every hop that *receives* one records
# its own span as a child (``child_of``) and forwards its OWN context, so
# the chain is parent-linked end to end and ``build_trace`` can emit
# Perfetto flow arrows between the slices.

def new_trace() -> dict:
    """Root context for a fresh causal chain."""
    return {"trace_id": uuid.uuid4().hex[:16],
            "span_id": uuid.uuid4().hex[:16]}


def child_of(ctx: dict | None) -> dict:
    """Continue a received context: same trace, new span, parent linked.
    A None/malformed context starts a new root (never raises — tracing
    stays passive)."""
    if not isinstance(ctx, dict) or "trace_id" not in ctx:
        return new_trace()
    out = {"trace_id": str(ctx["trace_id"]),
           "span_id": uuid.uuid4().hex[:16]}
    if ctx.get("span_id"):
        out["parent_span_id"] = str(ctx["span_id"])
    return out


class _NullSpan:
    """What a disabled recorder's ``span()`` hands out."""

    __slots__ = ()
    dur = 0.0

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **args: Any) -> None:
        """Arguments known only at the end of the interval; dropped here."""

    def add(self, **counts: float) -> None:
        """Counts that callers inside the interval add up; dropped here."""


_NULL_SPAN = _NullSpan()


class _Span:
    """One open interval on its thread's stack (``SpanRecorder.span``);
    ``dur`` is its duration in seconds once it has closed."""

    __slots__ = ("_rec", "_name", "_cat", "_args", "_frame", "_ann", "dur")

    def __init__(self, rec, name, cat, args) -> None:
        self._rec, self._name, self._cat, self._args = rec, name, cat, args
        self._frame = self._ann = None
        self.dur = 0.0

    def set(self, **args: Any) -> None:
        """Arguments known only at the end of the interval."""
        self._args.update(args)

    def add(self, **counts: float) -> None:
        """Counts that several callers inside the interval add up."""
        for k, v in counts.items():
            self._args[k] = self._args.get(k, 0) + v

    def __enter__(self) -> "_Span":
        rec = self._rec
        if rec.annotate is not None:
            self._ann = rec.annotate(self._name)
            self._ann.__enter__()
        self._frame = rec._begin(_now(), self)
        return self

    def __exit__(self, *exc) -> None:
        rec = self._rec
        self.dur, self_s = rec._end(self._frame, _now())
        if self._ann is not None:
            self._ann.__exit__(*exc)
        rec.record(self._name, rec.wall(self._frame[0]), self.dur,
                   self._cat, **self._args)
        hook = getattr(rec._local, "hook", None)
        hook = hook() if isinstance(hook, weakref.WeakMethod) else hook
        if hook is not None:
            hook(self._name, self._cat, self.dur, self_s)


class SpanRecorder:
    """Thread-safe span sink: in-memory ring + optional JSONL file.

    ``max_bytes`` (0 = unbounded, the default) caps the JSONL sink:
    when a write pushes the file past the cap it is rotated to
    ``<path>.1`` (one generation kept) and a loud ``obs_rotated`` event
    marks the boundary, so 10^5-round runs cannot fill the disk.

    ``annotate`` is a factory ``name -> context manager`` entered with
    every ``span()`` of an enabled recorder. ``set_hook`` gives the calling
    thread a completion hook ``(name, cat, dur_s, self_s)``, called after
    each of its ``span()``s closes (the runner's segment accumulator).
    ``set_context`` fields (the runner's ``iteration`` and ``round``) are
    stamped into the ``args`` of every ``span()``; explicit args win.
    ``dropped`` counts the spans the full ring has pushed out.
    """

    def __init__(self, path: str | None = None, pid: int = 0,
                 enabled: bool = True, max_bytes: int = 0,
                 annotate: Callable[[str], Any] | None = None) -> None:
        self._lock = threading.Lock()
        self.ring: collections.deque = collections.deque(maxlen=RING_SIZE)
        self.dropped = 0
        self.pid = pid
        self.enabled = enabled
        self.path = path
        self.max_bytes = int(max_bytes)
        self.rotations = 0
        self.annotate = annotate
        self._context: dict[str, Any] = {}
        self._local = threading.local()     # .stack of open spans, .hook
        # spans are timed on perf_counter alone; this puts them on the
        # unix clock the events carry in _ts
        self._epoch = time.time() - time.perf_counter()
        self._fh = None
        self._size = 0
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._fh = open(path, "a")
            self._size = self._fh.tell()

    def wall(self, perf: float) -> float:
        """Unix seconds of a ``time.perf_counter()`` reading."""
        return self._epoch + perf

    def set_context(self, **ctx: Any) -> None:
        """Ambient ``args`` of every later ``span()``; None removes a key.
        The dict is replaced, never mutated: readers take no lock."""
        new = {**self._context, **ctx}
        self._context = {k: v for k, v in new.items() if v is not None}

    def set_hook(self, hook: Callable[[str, str, float, float], None]
                 | None) -> None:
        """The calling thread's completion hook (None takes it off). One
        per thread: two experiments driven from two threads each see their
        own spans, whichever layer recorded them. A bound method is held
        weakly: the process-wide recorder outlives an experiment, and
        holding its hook would hold the experiment, its pool and whatever
        they keep on the device."""
        self._local.hook = weakref.WeakMethod(hook) \
            if inspect.ismethod(hook) else hook

    # -- the self-time stack -------------------------------------------
    def _begin(self, now: float, span: "_Span") -> list:
        """Open an interval at ``now`` (perf_counter seconds) on this
        thread's stack; the frame is [start, seconds covered by children,
        the span that opened it]."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        frame = [now, 0.0, span]
        stack.append(frame)
        return frame

    def _end(self, frame: list, now: float) -> tuple[float, float]:
        """Close ``frame`` at ``now``: (duration, self time). The duration
        is charged to the enclosing open interval as child time."""
        stack = self._local.stack
        # a frame closed out of order takes the frames above it along
        while stack and stack.pop() is not frame:
            pass
        dur = now - frame[0]
        frame[2] = None     # the span holds the frame: no cycle left behind
        if stack:
            stack[-1][1] += dur
        return dur, max(dur - frame[1], 0.0)

    def innermost(self) -> "_Span | _NullSpan":
        """The innermost ``span()`` still open on the calling thread, for a
        layer that counts something on behalf of whichever segment called
        it; the null span where none is open."""
        stack = getattr(self._local, "stack", None)
        return stack[-1][2] if stack else _NULL_SPAN

    def record(self, name: str, ts: float, dur: float, cat: str = "phase",
               **args: Any) -> dict | None:
        """Record one completed span. ``ts`` unix seconds, ``dur`` seconds."""
        if not self.enabled:
            return None
        rec = {"name": name, "cat": cat,
               "ts": round(ts * 1e6, 1),          # µs — trace-event unit
               "dur": round(dur * 1e6, 1),
               "pid": self.pid, "tid": threading.get_ident()}
        if args:
            rec["args"] = args
        rotated_bytes = 0
        with self._lock:
            if len(self.ring) == self.ring.maxlen:
                self.dropped += 1
            self.ring.append(rec)
            if self._fh is not None:
                line = json.dumps(rec) + "\n"
                self._fh.write(line)
                self._size += len(line)        # json.dumps emits ASCII
                if name == "iteration":
                    self._fh.flush()
                if self.max_bytes and self._size >= self.max_bytes:
                    rotated_bytes = self._rotate_locked()
        if rotated_bytes:
            # the bus lock is unrelated to ours, but emit outside our own
            # lock anyway: an event tap may legally record a span
            from feddrift_tpu.obs import events as _events
            try:
                _events.emit("obs_rotated", file=os.path.basename(self.path),
                             rotated_bytes=rotated_bytes,
                             generation=self.rotations)
            except Exception:   # noqa: BLE001 — observability stays passive
                pass
        return rec

    def _rotate_locked(self) -> int:
        """Swap the sink to a fresh file (caller holds the lock); returns
        the size of the rotated-out generation."""
        size = self._size
        self._fh.close()               # flushes the generation it ends
        try:
            os.replace(self.path, self.path + ".1")
        except OSError:
            pass
        self._fh = open(self.path, "a")
        self._size = 0
        self.rotations += 1
        return size

    def span(self, name: str, cat: str = "phase",
             **args: Any) -> "_Span | _NullSpan":
        """Context manager recording the enclosed interval; a disabled
        recorder measures nothing. ``with ... as sp`` gives
        ``sp.set(**args)`` for what is known only at the end and, once
        closed, ``sp.dur`` (seconds). The thread's hook (``set_hook``)
        fires after the span is recorded: the one completion path every
        accumulator hangs on."""
        if not self.enabled:
            return _NULL_SPAN
        ctx = self._context
        return _Span(self, name, cat, {**ctx, **args} if ctx else args)

    def spans(self, name: str | None = None) -> list[dict]:
        with self._lock:
            out = list(self.ring)
        return out if name is None else [s for s in out if s["name"] == name]

    def flush(self) -> None:
        """Push the buffered tail of the JSONL sink to the file."""
        with self._lock:
            if self._fh is not None:
                self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self) -> "SpanRecorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ----------------------------------------------------------------------
# Process-local default recorder, mirroring obs.events: layers record
# through the module-level helpers, the runner re-points the sink per run.
# Starts disabled so library use without a run context costs ~nothing.
_recorder = SpanRecorder(None, enabled=False)
_rec_lock = threading.Lock()


def get_recorder() -> SpanRecorder:
    return _recorder


def configure(path: str | None, pid: int = 0, max_bytes: int = 0,
              annotate: Callable[[str], Any] | None = None) -> SpanRecorder:
    """Install a fresh default recorder writing to ``path`` (None =
    memory-only, still enabled). Closes the previous recorder's sink."""
    global _recorder
    with _rec_lock:
        old, _recorder = _recorder, SpanRecorder(
            path, pid=pid, max_bytes=max_bytes, annotate=annotate)
        old.close()
    return _recorder


def span(name: str, cat: str = "phase", **args: Any):
    return _recorder.span(name, cat, **args)


def innermost():
    return _recorder.innermost()


def record(name: str, ts: float, dur: float, cat: str = "phase",
           **args: Any) -> dict | None:
    return _recorder.record(name, ts, dur, cat, **args)


# ----------------------------------------------------------------------
# Chrome-trace export
def _load_jsonl(path: str) -> list[dict]:
    rows: list[dict] = []
    if not os.path.isfile(path):
        return rows
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                continue                         # tolerate a torn tail line
    return rows


def build_trace(run_dir: str) -> dict:
    """Chrome-trace-event JSON (object form) for one run directory.

    Sources ``spans.jsonl`` (duration events, ``ph: "X"``) and
    ``events.jsonl`` (instant events, ``ph: "i"``, one reserved lane per
    process). Output invariants, tested in tests/test_obs_perf.py: every
    event has name/ph/ts/pid/tid, durations are non-negative, the list is
    sorted by ts, and each (pid, tid) lane carries metadata naming it.

    Spans carrying trace-context args (``span_id`` + ``parent_span_id``,
    see ``new_trace``/``child_of``) additionally get Perfetto **flow
    arrows** (``ph: "s"``/``"f"`` pairs sharing an id) from each parent
    slice to its child slice — the rendering of one update's causal chain
    across pid lanes. A run with no trace contexts emits no flow events.
    """
    spans = _load_jsonl(os.path.join(run_dir, "spans.jsonl"))
    events = _load_jsonl(os.path.join(run_dir, "events.jsonl"))
    # rotated-out generations still belong to the timeline
    for fname in ("spans.jsonl.1", "events.jsonl.1"):
        extra = _load_jsonl(os.path.join(run_dir, fname))
        if fname.startswith("spans"):
            spans = extra + spans
        else:
            events = extra + events
    # sampling-profiler slices (obs/hostprof.py) share the span schema;
    # their string tids ("hostprof:<thread>") become their own named lanes
    spans = spans + _load_jsonl(os.path.join(run_dir, "hostprof.jsonl"))

    trace: list[dict] = []
    # (pid, raw tid) -> compact per-process tid; tid 0 = events lane
    lanes: dict[tuple[int, Any], int] = {}
    pids: set[int] = set()

    def lane(pid: int, raw_tid: Any) -> int:
        key = (pid, raw_tid)
        if key not in lanes:
            lanes[key] = 1 + sum(1 for (p, _) in lanes if p == pid)
        return lanes[key]

    for s in spans:
        pid = int(s.get("pid", 0))
        pids.add(pid)
        ev = {"name": s.get("name", "?"), "cat": s.get("cat", "phase"),
              "ph": "X", "ts": float(s.get("ts", 0.0)),
              "dur": max(float(s.get("dur", 0.0)), 0.0),
              "pid": pid, "tid": lane(pid, s.get("tid", "main"))}
        if s.get("args"):
            ev["args"] = s["args"]
        trace.append(ev)

    # Perfetto flow arrows between trace-context-linked spans: "s" bound
    # to the parent slice, "f" (bp "e": bind to enclosing slice) to the
    # child. Flow pairs are matched by (cat, id); ids are sequential —
    # each parent->child edge is its own arrow.
    by_span_id = {ev["args"]["span_id"]: ev for ev in trace
                  if "args" in ev and ev["args"].get("span_id")}
    flow_id = 0
    flows: list[dict] = []
    for ev in trace:
        parent_id = ev.get("args", {}).get("parent_span_id")
        parent = by_span_id.get(parent_id) if parent_id else None
        if parent is None or parent is ev:
            continue
        flow_id += 1
        flows.append({"name": "trace", "cat": "trace", "ph": "s",
                      "id": flow_id, "ts": parent["ts"],
                      "pid": parent["pid"], "tid": parent["tid"]})
        flows.append({"name": "trace", "cat": "trace", "ph": "f", "bp": "e",
                      "id": flow_id, "ts": max(ev["ts"], parent["ts"]),
                      "pid": ev["pid"], "tid": ev["tid"]})
    trace.extend(flows)

    for e in events:
        if "_ts" not in e or "kind" not in e:
            continue
        pid = int(e.get("pid", 0))
        pids.add(pid)
        args = {k: v for k, v in e.items()
                if k not in ("_ts", "kind", "pid") and _json_scalarish(v)}
        trace.append({"name": e["kind"], "cat": "event", "ph": "i",
                      "s": "t", "ts": round(float(e["_ts"]) * 1e6, 1),
                      "pid": pid, "tid": EVENTS_LANE_TID, "args": args})

    trace.sort(key=lambda ev: ev["ts"])

    meta: list[dict] = []
    for pid in sorted(pids):
        meta.append({"ph": "M", "name": "process_name", "pid": pid,
                     "tid": 0, "args": {"name": f"process {pid}"}})
        meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                     "tid": EVENTS_LANE_TID, "args": {"name": "events"}})
    for (pid, raw), tid in sorted(lanes.items(), key=lambda kv: kv[1]):
        # descriptive raw tids (e.g. "hostprof:140…") name the lane
        # directly; integer thread idents keep the compact label
        name = raw if isinstance(raw, str) and not raw.isdigit() \
            else f"thread {tid}"
        meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                     "tid": tid, "args": {"name": name}})

    return {"traceEvents": meta + trace, "displayTimeUnit": "ms"}


def _json_scalarish(v: Any) -> bool:
    return isinstance(v, (str, int, float, bool, list)) or v is None


def write_trace(run_dir: str, out_path: str | None = None) -> str:
    """Build + write ``trace.json`` for a run dir; returns the path."""
    trace = build_trace(run_dir)
    out_path = out_path or os.path.join(run_dir, "trace.json")
    with open(out_path, "w") as f:
        json.dump(trace, f)
    return out_path
