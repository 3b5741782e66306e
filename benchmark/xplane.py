"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

``load`` reads the file's protobuf wire format itself (``XSpace`` -> planes
-> ``event_metadata``, ``stat_metadata``, lines -> events) into plain
lists: ``jax.profiler.ProfileData`` hands out an event's name and times but
not the stats of its metadata, where XLA puts the ``tf_op`` (the
``jax.named_scope`` path of one of the op's instructions), ``flops``,
``bytes_accessed`` and ``hlo_category``. Times are cut to whole
nanoseconds as ``ProfileData`` cuts them. Everything after ``load`` is
interval arithmetic on those lists, so that it can be checked by hand on a
small recorded trace and on made-up intervals.

On a TPU every chip is a plane ``/device:TPU:<n>`` with the lines
``XLA Modules`` (one event per executed program) and ``XLA Ops`` (one per
executed HLO op; a ``while`` spans the ops of its body). Host threads are
lines of the plane ``/host:CPU``; the benchmark's own annotations
(``bench_sync``, ``bench_time_step``) are events there, on the same clock.
All times are seconds on the trace's clock.
"""

from __future__ import annotations

import re
import struct

WINDOW_EVENT = "bench_time_step"
SYNC_EVENT = "bench_sync"
OUTSIDE = "outside"
OP_STATS = ("tf_op", "flops", "bytes_accessed", "hlo_category")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


# ----------------------------------------------------------------------
# the wire format (tsl/profiler/protobuf/xplane.proto), as far as it is read
def _varint(buf, pos):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos, end):
    """``(number, value)`` of each field of the message in ``buf[pos:end]``:
    a varint's number, ``(start, end)`` of a length-delimited field's bytes,
    the eight or four bytes of a fixed one."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: no xplane file")
        yield key >> 3, value


def _message(buf, span, repeated=()) -> dict:
    """``{field number: value}`` of the message in ``buf[span[0]:span[1]]``,
    with the list of its values for each number in ``repeated``."""
    out: dict = {n: [] for n in repeated}
    for number, value in _fields(buf, *span):
        if number in repeated:
            out[number].append(value)
        else:
            out[number] = value
    return out


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace") if span else ""


def _stat(buf, span, stat_names):
    """(name, value) of an ``XStat``: a number, a string, or the string a
    ``ref_value`` points at."""
    name = value = None
    for number, v in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif number in (5, 6):
            value = _text(buf, v)
        elif number == 7:
            value = stat_names.get(v)
    return name, value


def _events(buf, span, t0_ns, metadata, keep=None):
    """The ``XEvent``s of an ``XLine`` as ``(name, start_s, end_s, stats)``,
    name and stats being those of the event's ``XEventMetadata`` (one dict an
    op, shared by its events). ``keep``: the names wanted, every name where
    it is None."""
    out = []
    for number, v in _fields(buf, *span):
        if number != 4:
            continue
        mid = off = dur = 0
        pos, end = v
        while pos < end:         # the event's own stats are skipped unread
            key = buf[pos]       # XEvent's field numbers are under 16
            pos += 1
            if key & 7 == 0:
                value, pos = _varint(buf, pos)
                if key == 0x08:
                    mid = value
                elif key == 0x10:
                    off = value
                elif key == 0x18:
                    dur = value
            elif key & 7 == 2:
                n, pos = _varint(buf, pos)
                pos += n
            else:
                pos += 8 if key & 7 == 1 else 4
        name, stats = metadata.get(mid, ("", {}))
        if keep is None or name in keep:
            start_ns = t0_ns + off // 1000
            out.append((name, start_ns * 1e-9,
                        (start_ns + dur // 1000) * 1e-9, stats))
    return out


def load(path: str) -> dict:
    """``{"devices": {n: {"modules": [...], "ops": [...]}}, "host": [...]}``;
    a host event and a program's are ``(name, start_s, end_s)``, an op's
    ``(name, start_s, end_s, stats)`` with the ``OP_STATS`` that XLA wrote
    into the op's metadata: ``{}`` where it wrote none."""
    with open(path, "rb") as f:
        buf = f.read()
    devices: dict[int, dict] = {}
    host = []
    for plane in _message(buf, (0, len(buf)), repeated=(1,))[1]:
        # XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5; an
        # entry of either map: key 1, value 2
        plane = _message(buf, plane, repeated=(3, 4, 5))
        name = _text(buf, plane.get(2))
        m = _DEVICE.match(name)
        if not m and not name.startswith("/host:"):
            continue
        entries = [_message(buf, e) for e in plane[5]]
        stat_names = {e.get(1, 0): _text(buf, _message(buf, e[2]).get(2))
                      for e in entries if 2 in e}
        metadata = {}
        for e in (_message(buf, e) for e in plane[4]):
            # XEventMetadata: name 2, stats 5
            md = _message(buf, e[2], repeated=(5,)) if 2 in e else {5: []}
            stats = dict(_stat(buf, s, stat_names) for s in md[5])
            stats = {k: stats[k] for k in OP_STATS
                     if stats.get(k) is not None}
            if "tf_op" in stats:
                # XLA writes ``<op_name>:<op_type>``; JAX gives no type
                stats["tf_op"] = stats["tf_op"].rstrip(":")
            metadata[e.get(1, 0)] = (_text(buf, md.get(2)), stats)
        dev = {"modules": [], "ops": []}
        for line in plane[3]:
            # XLine: name 2, timestamp_ns 3, events 4
            head = _message(buf, line)
            line_name, t0 = _text(buf, head.get(2)), head.get(3, 0)
            if not m:
                host += [ev[:3] for ev in _events(
                    buf, line, t0, metadata, keep=(WINDOW_EVENT, SYNC_EVENT))]
            elif line_name == "XLA Ops":
                dev["ops"] = _events(buf, line, t0, metadata)
            elif line_name == "XLA Modules":
                dev["modules"] = [ev[:3] for ev in _events(
                    buf, line, t0, metadata)]
        if m:
            devices[int(m.group(1))] = dev
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
    """``(name, start, end, self_s)`` of events ``(name, start, end, ...)``,
    in their order: an event's time less that of the events nested in it on
    the same line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_s = [e[2] - e[1] for e in events]
    stack: list[int] = []
    for i in order:
        s, e = events[i][1], events[i][2]
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


def scope_of(tf_op: str | None, scopes) -> str:
    """The first of ``scopes`` whose name occurs in the op's ``tf_op``:
    forward (``.../jvp(MLAMoEDecoder)/lm_head/dot_general``), backward
    (``.../transpose(jvp(MLAMoEDecoder))/lm_head/dot_general``) and
    rematerialised (``.../checkpoint/rematted_computation/...``) alike;
    ``OUTSIDE`` where none does, or the op carries no ``tf_op``."""
    for scope in scopes if tf_op else ():
        if scope in tf_op:
            return scope
    return OUTSIDE


# ----------------------------------------------------------------------
def reduce(raw: dict, *, sync_wall: float | None = None, host_spans=(),
           rounds: int = 0, scopes=()) -> dict:
    """The traced window's numbers. The window runs from the start of the
    first ``bench_time_step`` event to the end of the last. ``host_spans``
    are ``(name, wall_start_s, dur_s)`` on the host's wall clock, brought
    onto the trace's clock through the ``bench_sync`` event whose wall time
    is ``sync_wall``. ``scopes`` are the ``jax.named_scope`` names of the
    model family (``DEVICE_SCOPES`` in its file), in the order they are
    tried: ``scope_s`` books the first chip's op self times, the same that
    ``breakdown.device_ops`` sums, to each op's scope. A fusion carries one
    ``tf_op`` (that of the product it is built around, where it holds one),
    so one fused across a scope's edge is booked whole to one side.
    ``scope_ops`` are each scope's five ops with most self time, ``[short
    name, seconds, tf_op]``, for the run's log. Both are None where no scope
    is asked for or no op of the window carries a ``tf_op``."""
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
    covers = {n: union(clip([ev[1:3] for ev in dev["ops"]], lo, hi))
              for n, dev in sorted(raw["devices"].items())}
    if not covers:
        raise ValueError("no /device:TPU plane in the trace")
    busy = {n: total(cover) for n, cover in covers.items()}
    first = raw["devices"][min(raw["devices"])]
    module_s: dict[str, float] = {}
    for name, s, e in first["modules"]:
        for (cs, ce) in clip([(s, e)], lo, hi):
            module_s[name] = module_s.get(name, 0.0) + (ce - cs)
    op_s: dict[tuple[str, str], float] = {}
    path_of: dict[tuple[str, str], str | None] = {}
    scope_s = {scope: 0.0 for scope in (*scopes, OUTSIDE)}
    scope_by_op: dict[str | None, str] = {}
    for ev, (name, s, e, self_s) in zip(first["ops"],
                                        self_times(first["ops"])):
        if e > lo and s < hi:
            tf_op = ev[3].get("tf_op") if len(ev) > 3 else None
            if tf_op not in scope_by_op:
                scope_by_op[tf_op] = scope_of(tf_op, scopes)
            scope = scope_by_op[tf_op]
            op_s[scope, name] = op_s.get((scope, name), 0.0) + self_s
            path_of[scope, name] = tf_op
            scope_s[scope] += self_s
    by_self_s = sorted(op_s.items(), key=lambda kv: -kv[1])
    scope_ops = {scope: [[short_name(n), s, path_of[k, n]]
                         for (k, n), s in by_self_s if k == scope][:5]
                 for scope in scope_s}
    if not scopes or not any(scope_by_op):
        scope_s = scope_ops = None
    idle = sorted(gaps(covers[min(covers)], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    breakdown = {
        "device_ops": [[(short_name(n) if scope == OUTSIDE
                         else f"{scope}: {short_name(n)}")[:80], s]
                       for (scope, n), s in by_self_s[:10]],
        "idle_gaps": [[attribute(g, spans), g[1] - g[0]] for g in idle],
    }
    if scope_s is not None:
        breakdown["device_scopes"] = sorted(
            ([k, v] for k, v in scope_s.items()), key=lambda kv: -kv[1])
    return {
        "window_s": window,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_per_device": busy,
        "module_s": module_s,
        "scope_s": scope_s,
        "scope_ops": scope_ops,
        "rounds": rounds,
        "breakdown": breakdown,
    }
