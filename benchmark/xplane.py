"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

``load`` reads the file with ``jax.profiler.ProfileData`` into plain lists;
everything after that is interval arithmetic on those lists, so that it can
be checked by hand on a small recorded trace and on made-up intervals.

On a TPU every chip is a plane ``/device:TPU:<n>`` with the lines
``XLA Modules`` (one event per executed program) and ``XLA Ops`` (one per
executed HLO op; a ``while`` spans the ops of its body). Host threads are
lines of the plane ``/host:CPU``; the benchmark's own annotations
(``bench_sync``, ``bench_time_step``) are events there, on the same clock.
All times are seconds on the trace's clock.
"""

from __future__ import annotations

import re

WINDOW_EVENT = "bench_time_step"
SYNC_EVENT = "bench_sync"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(path: str) -> dict:
    """``{"devices": {n: {"modules": [...], "ops": [...]}}, "host": [...]}``;
    each event is ``(name, start_s, end_s)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    host = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [(ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (WINDOW_EVENT, SYNC_EVENT):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"devices": devices, "host": host}


# ----------------------------------------------------------------------
# interval arithmetic
def union(intervals) -> list[tuple[float, float]]:
    """Disjoint sorted cover of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """The part of the disjoint sorted cover ``a`` that ``b`` (likewise)
    leaves uncovered."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(cover, lo: float, hi: float) -> list[tuple[float, float]]:
    return subtract([(lo, hi)], cover)


def self_times(events) -> list[tuple[str, float, float, float]]:
    """``(name, start, end, self_s)``: an event's time less that of the
    events nested in it on the same line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_s = [e[2] - e[1] for e in events]
    stack: list[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_s[stack[-1]] -= (e - s)
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][2], max(self_s[i], 0.0))
            for i in range(len(events))]


def short_name(name: str) -> str:
    """``%add_maximum_fusion = bf16[3,5000,32,32,16]{...} fusion(...)`` as
    ``add_maximum_fusion bf16[3,5000,32,32,16]``: the op's name and the
    shape it produces, without layouts and operands."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    shape = rhs[: rhs.find(")") + 1] if rhs.startswith("(") \
        else rhs.split(" ", 1)[0]
    return f"{lhs.lstrip('%')} {shape}"[:80]


def attribute(gap, spans) -> str:
    """The shortest host span that holds the middle of the gap."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside the program's spans"


# ----------------------------------------------------------------------
def reduce(raw: dict, *, sync_wall: float | None = None, host_spans=(),
           rounds: int = 0) -> dict:
    """The traced window's numbers. The window runs from the start of the
    first ``bench_time_step`` event to the end of the last. ``host_spans``
    are ``(name, wall_start_s, dur_s)`` on the host's wall clock, brought
    onto the trace's clock through the ``bench_sync`` event whose wall time
    is ``sync_wall``."""
    steps = [(s, e) for n, s, e in raw["host"] if n == WINDOW_EVENT]
    if not steps:
        raise ValueError(f"no {WINDOW_EVENT!r} event in the trace")
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    window = hi - lo
    spans = []
    sync = [s for n, s, _ in raw["host"] if n == SYNC_EVENT]
    if sync and sync_wall is not None:
        off = sync[0] - sync_wall
        spans = [(n, w + off, w + off + d) for n, w, d in host_spans]
    busy = {}
    for n, dev in sorted(raw["devices"].items()):
        busy[n] = total(union(clip([(s, e) for _, s, e in dev["ops"]],
                                   lo, hi)))
    if not busy:
        raise ValueError("no /device:TPU plane in the trace")
    first = raw["devices"][min(raw["devices"])]
    module_s: dict[str, float] = {}
    for name, s, e in first["modules"]:
        for (cs, ce) in clip([(s, e)], lo, hi):
            module_s[name] = module_s.get(name, 0.0) + (ce - cs)
    op_s: dict[str, float] = {}
    for name, s, e, self_s in self_times(first["ops"]):
        if e > lo and s < hi:
            op_s[name] = op_s.get(name, 0.0) + self_s
    cover = union(clip([(s, e) for _, s, e in first["ops"]], lo, hi))
    idle = sorted(gaps(cover, lo, hi), key=lambda g: g[0] - g[1])[:10]
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_per_device": busy,
        "module_s": module_s,
        "rounds": rounds,
        "breakdown": {
            "device_ops": [[short_name(n), s] for n, s in top],
            "idle_gaps": [[attribute(g, spans), g[1] - g[0]] for g in idle],
        },
    }
