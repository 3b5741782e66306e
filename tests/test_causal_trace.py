"""Causal tracing + round critical-path attribution (ISSUE 9).

Fast tier: trace-context algebra, size-cap rotation of the JSONL sinks,
client -> edge -> server stitching over a real loopback TCP broker (zero
orphan spans, Perfetto flow arrows), trace continuity across a broker
kill/restart (the chaos_smoke [8/8] scenario), the `critical_path` verb
on synthetic streams, and the regress gate's host-overhead ceiling.

Slow tier: a real tiny run emits round_breakdown whose segments cover
the iteration wall, and `critical_path` renders it.

Every blocking operation carries an explicit timeout (test_resilience.py
convention): socket-level scenarios must not wedge the fast tier.
"""

import json
import os
import queue
import time

import numpy as np
import pytest

from feddrift_tpu import obs
from feddrift_tpu.comm.compress import UpdateReceiver, UpdateSender
from feddrift_tpu.comm.netbroker import NetworkBroker, NetworkBrokerClient
from feddrift_tpu.obs import critical_path, regress, spans
from feddrift_tpu.platform.hierarchical import EdgeRelay
from feddrift_tpu.resilience import ReconnectingBrokerClient, RetryPolicy

E2E_DEADLINE = 60.0


@pytest.fixture()
def bus():
    """Fresh memory-only event bus per test."""
    b = obs.configure(None)
    yield b
    obs.configure(None)


@pytest.fixture()
def run_dir(tmp_path):
    """Arm the process-default span recorder on a run dir; restore the
    disabled library default afterwards so other tests see no spans."""
    d = str(tmp_path / "run")
    os.makedirs(d, exist_ok=True)
    spans.configure(os.path.join(d, "spans.jsonl"))
    yield d
    spans.configure(None)
    spans.get_recorder().enabled = False


def _sync(*clients, timeout=10.0):
    """TCP subscribe is async: loopback one message per client so every
    subscription registered before it is live on the broker."""
    for c in clients:
        q = c.subscribe("__sync__")
        c.publish("__sync__", "ready")
        assert q.get(timeout=timeout) == "ready"


# ----------------------------------------------------------------------
class TestTraceContext:
    def test_child_continues_trace(self):
        root = spans.new_trace()
        child = spans.child_of(root)
        assert child["trace_id"] == root["trace_id"]
        assert child["span_id"] != root["span_id"]
        assert child["parent_span_id"] == root["span_id"]
        grand = spans.child_of(child)
        assert grand["trace_id"] == root["trace_id"]
        assert grand["parent_span_id"] == child["span_id"]

    def test_malformed_context_starts_new_root(self):
        for bad in (None, {}, {"span_id": "x"}, "not-a-dict", 42):
            ctx = spans.child_of(bad)
            assert ctx["trace_id"] and ctx["span_id"]
            assert "parent_span_id" not in ctx

    def test_roots_are_distinct(self):
        a, b = spans.new_trace(), spans.new_trace()
        assert a["trace_id"] != b["trace_id"]


# ----------------------------------------------------------------------
class TestRotation:
    def test_span_sink_rotates_at_cap(self, tmp_path, bus):
        path = str(tmp_path / "spans.jsonl")
        rec = spans.SpanRecorder(path, max_bytes=2000)
        n = 0
        while rec.rotations < 1 and n < 500:
            rec.record("s", time.time(), 0.001, i=n)
            n += 1
        rec.close()
        assert rec.rotations >= 1
        assert os.path.exists(path + ".1")
        # loud boundary marker, carrying the rotated-out size
        rot = [e for e in bus.events() if e["kind"] == "obs_rotated"]
        assert rot and rot[0]["file"] == "spans.jsonl"
        assert rot[0]["rotated_bytes"] >= 2000
        assert rot[0]["generation"] == 1

    def test_no_span_lost_at_rotation_boundary(self, tmp_path, bus):
        """The write that trips the cap lands in the rotated-out file and
        the next one in the fresh file — no record falls in the crack."""
        path = str(tmp_path / "spans.jsonl")
        rec = spans.SpanRecorder(path, max_bytes=600)
        total = 0
        while rec.rotations < 1:
            rec.record("s", time.time(), 0.001, i=total)
            total += 1
        rec.record("s", time.time(), 0.001, i=total)   # first post-rotation
        total += 1
        rec.close()
        rows = []
        for p in (path + ".1", path):
            rows += [json.loads(l) for l in open(p)]
        assert [r["args"]["i"] for r in rows] == list(range(total))

    def test_event_sink_rotates_and_marks(self, tmp_path):
        from feddrift_tpu.obs.events import EventBus
        path = str(tmp_path / "events.jsonl")
        b = EventBus(path, max_bytes=1500)
        n = 0
        while b.rotations < 1 and n < 500:
            b.emit("run_start", i=n)
            n += 1
        assert b.rotations >= 1
        assert os.path.exists(path + ".1")
        # the marker is emitted into the FRESH generation (re-entrant
        # emit after the rotation completes), so it is never rotated away
        kinds = [json.loads(l)["kind"] for l in open(path)]
        assert "obs_rotated" in kinds

    def test_alert_tap_survives_rotation_reentry(self, tmp_path):
        """Regression: when an ``alert_raised`` write trips the size cap,
        the bus re-entrantly emits ``obs_rotated`` and taps the
        AlertMonitor back on the same thread while its lock is still
        held. With a non-reentrant lock this deadlocked a real run; the
        monitor must use an RLock. Run in a daemon thread so a
        regression fails the assert instead of hanging pytest."""
        import threading

        from feddrift_tpu.obs.alerts import AlertMonitor
        from feddrift_tpu.obs.events import EventBus
        b = EventBus(str(tmp_path / "events.jsonl"), max_bytes=1200)
        mon = AlertMonitor(path=None).attach(b)

        def pump():
            # each client_killed fires the client_outage rule (cooldown 1,
            # iteration advances every emit), so alert_raised writes keep
            # landing until one trips the rotation mid-tap
            for i in range(200):
                b.emit("client_killed", iteration=i, client=i)

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive(), "alert tap deadlocked on rotation re-entry"
        assert b.rotations >= 1
        assert mon.alerts

    def test_build_trace_folds_rotated_generation(self, tmp_path, bus):
        d = str(tmp_path)
        path = os.path.join(d, "spans.jsonl")
        rec = spans.SpanRecorder(path, max_bytes=600)
        total = 0
        while rec.rotations < 1:
            rec.record("s", time.time(), 0.001, i=total)
            total += 1
        for _ in range(3):
            rec.record("s", time.time(), 0.001, i=total)
            total += 1
        rec.close()
        tr = spans.build_trace(d)
        xs = [e for e in tr["traceEvents"] if e.get("ph") == "X"]
        assert len(xs) == total
        assert sorted(e["args"]["i"] for e in xs) == list(range(total))

    def test_uncapped_recorder_never_rotates(self, tmp_path, bus):
        path = str(tmp_path / "spans.jsonl")
        rec = spans.SpanRecorder(path)          # max_bytes=0: unbounded
        for i in range(300):
            rec.record("s", time.time(), 0.001, i=i)
        rec.close()
        assert rec.rotations == 0
        assert not os.path.exists(path + ".1")
        assert not [e for e in bus.events() if e["kind"] == "obs_rotated"]


# ----------------------------------------------------------------------
class TestWireStitching:
    def test_zero_orphans_and_client_edge_server_flows(self, run_dir, bus):
        """The acceptance smoke: a two-tier (E=3) exchange over the real
        TCP broker — one client per edge, each EdgeRelay forwarding its
        summary to the server. Every edge's chain client -> edge ->
        server shares one trace_id, every parent_span_id resolves to a
        recorded span (zero orphans), and the exported trace.json
        connects the hops with Perfetto flow arrows."""
        E = 3
        broker = NetworkBroker()
        clients = []

        def _client():
            c = NetworkBrokerClient(broker.host, broker.port)
            clients.append(c)
            return c

        try:
            rx_srv = UpdateReceiver(_client(), "fl/up")
            relays, txs = [], []
            for e in range(E):
                down = f"fl/e{e}/down"
                rx_down = UpdateReceiver(_client(), down)
                tx_up = UpdateSender(_client(), "fl/up", codec="none")
                relays.append(EdgeRelay(rx_down, tx_up, edge_id=e))
                txs.append(UpdateSender(_client(), down, codec="none"))
            _sync(*clients)

            for e in range(E):
                txs[e].send(f"w{e}", np.arange(8, dtype=np.float32) + e)
                assert relays[e].relay_round(1, timeout=10.0) is not None
            summaries = [rx_srv.recv(timeout=10.0) for _ in range(E)]
            assert all(s is not None and s[0] == "edge_summary"
                       for s in summaries)
        finally:
            for c in clients:
                c.close()
            broker.close()

        recorded = [s for s in spans.get_recorder().spans()
                    if s.get("args", {}).get("span_id")]
        by_name = {}
        for s in recorded:
            by_name.setdefault(s["name"], []).append(s)
        # every hop of every chain made it onto the timeline
        for hop in ("send_update", "recv_update", "broker_publish",
                    "broker_deliver"):
            assert by_name.get(hop), f"missing {hop} span"
        assert len(by_name["send_update"]) == 2 * E   # clients + edges
        assert len(by_name["recv_update"]) == 2 * E   # edges + server

        # one trace per client update, threaded end to end: each root
        # send (no parent) is continued by exactly 3 more update hops
        # (edge recv, edge send, server recv)
        roots = [s for s in by_name["send_update"]
                 if "parent_span_id" not in s["args"]]
        assert len(roots) == E
        for root in roots:
            tid = root["args"]["trace_id"]
            chain = [s for s in by_name["send_update"]
                     + by_name["recv_update"]
                     if s["args"]["trace_id"] == tid]
            assert len(chain) == 4, \
                f"trace {tid} not threaded client->edge->server: {chain}"

        # zero orphan spans: every parent link resolves
        ids = {s["args"]["span_id"] for s in recorded}
        for s in recorded:
            parent = s["args"].get("parent_span_id")
            assert parent is None or parent in ids, \
                f"orphan span {s['name']}: parent {parent} unrecorded"

        # the exported trace.json carries flow arrows bound to slices
        d = os.path.dirname(spans.get_recorder().path)
        spans.get_recorder().close()
        trace_path = spans.write_trace(d)
        evs = json.load(open(trace_path))["traceEvents"]
        starts = [e for e in evs if e.get("ph") == "s"]
        finishes = [e for e in evs if e.get("ph") == "f"]
        assert len(starts) >= 3 * E and len(starts) == len(finishes)
        assert {e["id"] for e in starts} == {e["id"] for e in finishes}
        for e in starts + finishes:
            assert e["cat"] == "trace"

    def test_trace_survives_broker_reconnect(self, run_dir, bus):
        """chaos_smoke [8/8]: a frame published through the reconnect
        layer keeps its trace context across a broker kill/restart — the
        resent frame carries the same trace_id, so the causal chain stays
        connected through the outage."""
        broker = NetworkBroker()
        host, port = broker.host, broker.port
        cli = ReconnectingBrokerClient(
            lambda: NetworkBrokerClient(host, port),
            retry=RetryPolicy(base_delay=0.05, max_delay=0.2,
                              max_attempts=60, deadline_s=30, seed=0),
            ack_timeout=0.2)
        broker2 = None
        try:
            q = cli.subscribe("t")
            ctx0 = spans.new_trace()
            cli.publish("t", "before", trace=ctx0)
            assert q.get(timeout=10.0) == "before"

            broker.close()                        # broker dies
            time.sleep(0.2)
            ctx1 = spans.new_trace()
            cli.publish("t", "while-down", trace=ctx1)   # buffered
            broker2 = NetworkBroker(host=host, port=port)
            got = set()
            end = time.monotonic() + E2E_DEADLINE
            while "while-down" not in got and time.monotonic() < end:
                try:
                    got.add(q.get(timeout=0.25))
                except queue.Empty:
                    pass
            assert "while-down" in got           # replayed after reconnect
            assert cli.reconnects >= 1
        finally:
            cli.close()
            broker.close()
            if broker2 is not None:
                broker2.close()

        # the delivered resend still carried ctx1's trace: its
        # broker_deliver span continues the same trace_id
        def _by_trace(name, tid):
            return [s for s in spans.get_recorder().spans(name)
                    if s.get("args", {}).get("trace_id") == tid]
        end = time.monotonic() + 10.0
        while not _by_trace("broker_deliver", ctx1["trace_id"]) \
                and time.monotonic() < end:
            time.sleep(0.05)
        assert _by_trace("broker_publish", ctx1["trace_id"])
        assert _by_trace("broker_deliver", ctx1["trace_id"]), \
            "resent frame lost its trace context across the reconnect"
        # and the pre-outage publish kept its own, distinct chain
        assert _by_trace("broker_deliver", ctx0["trace_id"])


# ----------------------------------------------------------------------
def _write_run(tmp_path, walls, breakdown_iters=None, stragglers=(),
               edge_fails=()):
    """Synthetic run dir: iteration spans (µs trace-event units) +
    round_breakdown / fault events whose segments sum to the wall."""
    d = str(tmp_path / "run")
    os.makedirs(d, exist_ok=True)
    t0 = 1_700_000_000.0
    with open(os.path.join(d, "spans.jsonl"), "w") as f:
        for it, wall in enumerate(walls):
            f.write(json.dumps({
                "name": "iteration", "cat": "runner",
                "ts": round(t0 * 1e6, 1), "dur": round(wall * 1e6, 1),
                "pid": 0, "tid": 1, "args": {"iteration": it}}) + "\n")
            t0 += wall
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        for it, wall in enumerate(walls):
            if breakdown_iters is not None and it not in breakdown_iters:
                continue
            segs = {"dispatch": round(0.1 * wall, 6),
                    "device_compute": round(0.6 * wall, 6),
                    "writeback": round(0.1 * wall, 6),
                    "dispatch_gap": round(0.2 * wall, 6)}
            f.write(json.dumps({
                "_ts": 1_700_000_000.0, "kind": "round_breakdown",
                "iteration": it, "wall_s": wall, "rounds": 4,
                "segments": segs,
                "dispatch_gap_s": segs["dispatch_gap"],
                "host_overhead_frac": 0.4}) + "\n")
        for it in stragglers:
            f.write(json.dumps({
                "_ts": 1_700_000_000.0, "kind": "straggler_masked",
                "iteration": it, "part_round": 3, "clients": [5, 9],
                "on_time": 8, "deadline": 2.0}) + "\n")
        for it in edge_fails:
            f.write(json.dumps({
                "_ts": 1_700_000_000.0, "kind": "edge_failed",
                "iteration": it, "fault_round": 2, "edges": [0],
                "reason": "killed"}) + "\n")
    return d


class TestCriticalPath:
    def test_segments_cover_iteration_wall(self, tmp_path):
        d = _write_run(tmp_path, [1.0, 1.0, 1.0])
        out = critical_path.analyze(d)
        assert len(out["iterations"]) == 3
        for row in out["iterations"]:
            assert abs(row["coverage"] - 1.0) <= 0.05
        assert out["dominant_segment"] == "device_compute"
        assert out["host_overhead_frac_mean"] == pytest.approx(0.4)

    def test_straggler_attribution_on_extended_iteration(self, tmp_path):
        d = _write_run(tmp_path, [1.0, 1.0, 2.0], stragglers=(2,))
        out = critical_path.analyze(d)
        rows = {r["iteration"]: r for r in out["iterations"]}
        assert not rows[0]["extended"] and not rows[1]["extended"]
        assert rows[2]["extended"]
        assert "straggler client(s) [5, 9]" in rows[2]["attribution"]
        assert "round 3" in rows[2]["attribution"]

    def test_edge_failure_attribution(self, tmp_path):
        d = _write_run(tmp_path, [1.0, 2.5, 1.0], edge_fails=(1,))
        out = critical_path.analyze(d)
        row = [r for r in out["iterations"] if r["iteration"] == 1][0]
        assert row["extended"]
        assert "edge(s) [0] failed (killed)" in row["attribution"]

    def test_extension_without_fault_is_named_variance(self, tmp_path):
        d = _write_run(tmp_path, [1.0, 1.0, 3.0])
        out = critical_path.analyze(d)
        row = [r for r in out["iterations"] if r["iteration"] == 2][0]
        assert row["attribution"] == "no fault recorded (host-side variance)"

    def test_breakdown_event_alone_suffices(self, tmp_path):
        """A run dir whose spans.jsonl was rotated away still renders —
        wall falls back to the event's own wall_s."""
        d = _write_run(tmp_path, [1.0, 1.0])
        os.remove(os.path.join(d, "spans.jsonl"))
        out = critical_path.analyze(d)
        assert len(out["iterations"]) == 2
        assert all(abs(r["coverage"] - 1.0) <= 0.05
                   for r in out["iterations"])

    def test_render_names_critical_path(self, tmp_path, capsys):
        d = _write_run(tmp_path, [1.0, 1.0])
        assert critical_path.main([d]) == 0
        out = capsys.readouterr().out
        assert "critical path: device_compute dominates" in out
        assert "host_overhead_frac (mean): 0.4000" in out

    def test_cli_verb_routes(self, tmp_path, capsys):
        from feddrift_tpu.cli import main
        d = _write_run(tmp_path, [1.0, 1.0])
        assert main(["critical_path", d]) == 0
        capsys.readouterr()
        assert main(["critical_path", d, "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["dominant_segment"] == "device_compute"

    def test_missing_run_dir_exits_2(self, tmp_path, capsys):
        assert critical_path.main([str(tmp_path / "nope")]) == 2
        empty = tmp_path / "empty"
        empty.mkdir()
        (empty / "events.jsonl").write_text("")
        assert critical_path.main([str(empty)]) == 2


# ----------------------------------------------------------------------
def _bench_fixture(value=100.0, wall=10.0, rounds=1000, acc=0.86,
                   host_overhead=None):
    d = {"value": value, "wall_s": wall, "rounds": rounds,
         "final_test_acc": acc,
         "instruments": {'jit_compiles{fn="train_round"}': 3.0,
                         'jit_recompiles{fn="train_round"}': 0.0}}
    if host_overhead is not None:
        d["host_overhead_frac"] = host_overhead
    return d


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)
    return str(path)


class TestRegressHostOverhead:
    def test_overhead_past_ceiling_fails(self, tmp_path, capsys):
        base = _write(tmp_path / "b.json", _bench_fixture(host_overhead=0.2))
        cand = _write(tmp_path / "c.json", _bench_fixture(host_overhead=0.5))
        assert regress.main([cand, "--baseline", base]) == 1
        assert "host_overhead_frac" in capsys.readouterr().out

    def test_tolerance_waives(self, tmp_path):
        base = _write(tmp_path / "b.json", _bench_fixture(host_overhead=0.2))
        cand = _write(tmp_path / "c.json", _bench_fixture(host_overhead=0.5))
        assert regress.main([cand, "--baseline", base,
                             "--tol-host-overhead", "0.35"]) == 0

    def test_within_default_tolerance_passes(self, tmp_path):
        base = _write(tmp_path / "b.json", _bench_fixture(host_overhead=0.2))
        cand = _write(tmp_path / "c.json", _bench_fixture(host_overhead=0.25))
        assert regress.main([cand, "--baseline", base]) == 0

    def test_missing_field_skips_not_fails(self, tmp_path, capsys):
        """Artifacts predating ISSUE 9 carry no host_overhead_frac: the
        row is skipped so old baselines stay comparable."""
        base = _write(tmp_path / "b.json", _bench_fixture())
        cand = _write(tmp_path / "c.json", _bench_fixture(host_overhead=0.9))
        assert regress.main([cand, "--baseline", base]) == 0


# ----------------------------------------------------------------------
@pytest.mark.slow
class TestRoundBreakdownEndToEnd:
    def test_tiny_run_breakdown_covers_wall(self, tmp_path, capsys):
        """A real run emits round_breakdown whose segments close the
        iteration wall budget, and the critical_path verb renders it."""
        from feddrift_tpu.config import ExperimentConfig
        from feddrift_tpu.simulation.runner import Experiment

        d = str(tmp_path / "run")
        cfg = ExperimentConfig(
            dataset="sea", model="fnn", concept_drift_algo="win-1",
            train_iterations=2, comm_round=2, epochs=1, sample_num=16,
            batch_size=8, client_num_in_total=4, client_num_per_round=4,
            concept_num=2, frequency_of_the_test=1, report_client=0,
            chunk_rounds=False, trace_sync=True, out_dir=d)
        exp = Experiment(cfg, out_dir=d)
        exp.run()

        evs = [json.loads(l) for l in open(os.path.join(d, "events.jsonl"))]
        bds = [e for e in evs if e["kind"] == "round_breakdown"]
        assert len(bds) == 2
        for bd in bds:
            seg_sum = sum(bd["segments"].values())
            assert seg_sum == pytest.approx(bd["wall_s"], rel=0.05)
            assert bd["segments"]["device_compute"] > 0
            assert 0.0 <= bd["host_overhead_frac"] <= 1.0
            assert bd["rounds"] == cfg.comm_round
        assert exp.last_round_breakdown["iteration"] == 1

        # the gauge + histogram landed in the registry
        snap = obs.registry().snapshot()
        assert "host_overhead_frac" in snap
        assert any(k.startswith("round_wall_seconds") for k in snap)

        assert critical_path.main([d]) == 0
        out = capsys.readouterr().out
        assert "critical path:" in out
        result = critical_path.analyze(d)
        for row in result["iterations"]:
            assert abs(row["coverage"] - 1.0) <= 0.05
