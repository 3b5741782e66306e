"""``xplane.load`` reads the trace's protobuf wire format itself: on the trace
recorded on a v5e it finds what XLA wrote into an op's metadata, the events
and times that ``jax.profiler.ProfileData`` hands out, and what the
generated ``xplane_pb2`` classes read (where ``tensorflow`` is installed; in
a process of its own, so that this one imports nothing it does not import
today); on bytes made by hand, each kind of stat value."""

import json
import os
import struct
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402

TRACE = os.path.join(ROOT, "benchmark", "fixtures", "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def raw():
    return xplane.load(TRACE)


def test_an_op_carries_what_xla_wrote_into_its_metadata(raw):
    ops = raw["devices"][0]["ops"]
    fusion = [ev for ev in ops if ev[0].startswith("%fusion.8 = ")]
    assert len(fusion) == 16                 # four steps of four programs
    assert all(ev[3] is fusion[0][3] for ev in fusion)   # one dict an op
    assert fusion[0][3] == {
        "tf_op": "jit(fixture_step)/while/body/closed_call/dot_general",
        "flops": 33816576, "bytes_accessed": 393216,
        "hlo_category": "convolution fusion"}
    # an op that no instruction of the program's stands behind has none
    start = next(ev for ev in ops if ev[0].startswith("%copy-start.1 = "))
    assert start[3] == {"hlo_category": "copy-start", "flops": 0,
                        "bytes_accessed": 393220}
    assert all(len(ev) == 3 for ev in raw["devices"][0]["modules"])
    assert all(len(ev) == 3 for ev in raw["host"])


def test_events_and_times_are_those_profiledata_hands_out(raw):
    from jax.profiler import ProfileData
    theirs = {"modules": [], "ops": [], "host": []}
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name) \
                if plane.name == "/device:TPU:0" else \
                "host" if plane.name.startswith("/host:") else None
            if key is None:
                continue
            theirs[key] += [
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events if key != "host"
                or ev.name in (xplane.WINDOW_EVENT, xplane.SYNC_EVENT)]
    dev = raw["devices"][0]
    assert [ev[:3] for ev in dev["ops"]] == theirs["ops"]
    assert len(theirs["ops"]) == 52
    assert dev["modules"] == theirs["modules"]
    assert raw["host"] == theirs["host"] and len(raw["host"]) == 3


_WITH_PB2 = """
import json, sys
try:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
except Exception as e:
    print(json.dumps({"skip": repr(e)})); sys.exit(0)
space = xplane_pb2.XSpace()
with open(sys.argv[1], "rb") as f:
    space.ParseFromString(f.read())
plane = next(p for p in space.planes if p.name == "/device:TPU:0")
names = {k: v.name for k, v in plane.stat_metadata.items()}
out = []
for line in plane.lines:
    if line.name != "XLA Ops":
        continue
    for ev in line.events:
        md = plane.event_metadata[ev.metadata_id]
        stats = {}
        for s in md.stats:
            kind = s.WhichOneof("value")
            value = names[s.ref_value] if kind == "ref_value" \\
                else getattr(s, kind)
            if names[s.metadata_id] in sys.argv[2:]:
                stats[names[s.metadata_id]] = value
        out.append([md.name, line.timestamp_ns, ev.offset_ps, ev.duration_ps,
                    stats])
print(json.dumps({"ops": out}))
"""


def test_ops_and_their_stats_are_those_the_generated_classes_read(raw):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, "-c", _WITH_PB2, TRACE, *xplane.OP_STATS],
        capture_output=True, text=True, timeout=300, env=env)
    assert done.returncode == 0, done.stderr[-2000:]
    theirs = json.loads(done.stdout.strip().splitlines()[-1])
    if "skip" in theirs:
        pytest.skip(f"no xplane_pb2 here: {theirs['skip']}")
    mine = raw["devices"][0]["ops"]
    assert len(mine) == len(theirs["ops"]) == 52
    for (name, start, end, stats), (t_name, t0, off, dur, t_stats) in zip(
            mine, theirs["ops"]):
        assert name == t_name
        assert start == (t0 + off // 1000) * 1e-9
        assert end == (t0 + off // 1000 + dur // 1000) * 1e-9
        if "tf_op" in t_stats:
            t_stats["tf_op"] = t_stats["tf_op"].rstrip(":")
        assert stats == t_stats


def _varint(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_every_kind_of_stat_value_on_bytes_made_by_hand(tmp_path):
    """A device plane whose one op's metadata carries its ``tf_op`` as a
    reference to a stat's name, a negative ``int64``, an unsigned number and
    a double (which ``OP_STATS`` does not keep), and ids that take more than
    one byte; a host plane with the window's annotation."""
    def entry(key, message):
        return _field(1, key) + _field(2, message)
    stat_names = {1: b"tf_op", 2: b"flops", 3: b"bytes_accessed",
                  4: b"occupancy", 300: b"jit(f)/lm_head/dot_general:"}
    op = (_field(1, 70000) + _field(2, b"%fusion.1 = f32[8]{0} fusion()")
          + _field(5, _field(1, 1) + _field(7, 300))
          + _field(5, _field(1, 2) + _field(4, -5))
          + _field(5, _field(1, 3) + _field(3, 2 ** 40))
          + _field(5, _field(1, 4) + _field(2, 0.5)))
    event = (_field(1, 70000) + _field(2, 2_000_999) + _field(3, 3_000_999)
             + _field(4, _field(1, 4) + _field(2, 1.0)))
    device = (_field(2, b"/device:TPU:0")
              + _field(3, _field(2, b"XLA Ops") + _field(3, 10)
                       + _field(4, event) + _field(4, event))
              + _field(4, entry(70000, op))
              + b"".join(_field(5, entry(k, _field(1, k) + _field(2, v)))
                         for k, v in stat_names.items()))
    mark = _field(1, 1) + _field(2, xplane.WINDOW_EVENT.encode())
    host = (_field(2, b"/host:CPU")
            + _field(3, _field(2, b"python3") + _field(3, 5)
                     + _field(4, _field(1, 1) + _field(2, 1000)
                              + _field(3, 7000)))
            + _field(4, entry(1, mark)))
    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host)
                     + _field(4, b"some-host"))
    raw = xplane.load(str(path))
    stats = {"tf_op": "jit(f)/lm_head/dot_general", "flops": -5,
             "bytes_accessed": 2 ** 40}
    # picoseconds are cut to whole nanoseconds, as ProfileData cuts them
    assert raw["devices"][0]["ops"] == [
        ("%fusion.1 = f32[8]{0} fusion()", 2010 * 1e-9, 5010 * 1e-9, stats)
    ] * 2
    assert raw["devices"][0]["modules"] == []
    assert raw["host"] == [(xplane.WINDOW_EVENT, 6 * 1e-9, 13 * 1e-9)]
    path.write_bytes(b"\x0b\x00")          # a group: no field of an XSpace
    with pytest.raises(ValueError):
        xplane.load(str(path))
