"""Performance-observability tests: XLA cost model, unified trace
timeline, and the perf-regression gate (feddrift_tpu/obs/{costmodel,
spans,regress}.py + the xla_trace no-op guard). Pure host logic plus tiny
jit programs; the Experiment-sized integration and the full perf gate are
slow-tier."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

from feddrift_tpu import obs
from feddrift_tpu.obs import costmodel, regress, spans

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


@pytest.fixture()
def fresh_bus():
    """Memory-only event bus + empty cost store for isolated assertions."""
    bus = obs.configure(None)
    costmodel.clear()
    yield bus
    obs.configure(None)
    costmodel.clear()


# ----------------------------------------------------------------------
class TestCostModel:
    def test_capture_compiled_level(self, fresh_bus):
        """A tiny jitted matmul yields XLA's own FLOPs/bytes + static HBM
        accounting, one program_cost event, and refreshed gauges."""
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda a, b: (a @ b).sum())
        a = jnp.ones((32, 32))
        pc = costmodel.capture("toy_matmul", f, (a, a), level="compiled")
        assert pc is not None
        assert pc.flops and pc.flops >= 2 * 32 ** 3  # at least the matmul
        assert pc.bytes_accessed and pc.bytes_accessed > 0
        assert pc.peak_hbm_bytes and pc.peak_hbm_bytes > 0
        assert pc.argument_bytes == 2 * 32 * 32 * 4
        assert costmodel.get("toy_matmul") is pc
        (ev,) = fresh_bus.events("program_cost")
        assert ev["fn"] == "toy_matmul" and ev["level"] == "compiled"
        snap = obs.registry().snapshot()
        assert snap['program_flops{fn="toy_matmul"}'] == pc.flops
        assert snap["hbm_peak_bytes"] == pc.peak_hbm_bytes

    def test_capture_lowered_level_no_memory(self, fresh_bus):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda a: a * 2 + 1)
        pc = costmodel.capture("toy_scale", f, (jnp.ones((16,)),),
                               level="lowered")
        assert pc is not None and pc.flops is not None
        assert pc.peak_hbm_bytes is None          # memory needs "compiled"

    def test_capture_off_and_unknown_level(self, fresh_bus):
        assert costmodel.capture("x", None, (), level="off") is None
        with pytest.raises(ValueError, match="unknown cost-capture level"):
            costmodel.capture("x", None, (), level="sideways")

    def test_hbm_watermark_graceful_none_on_cpu(self, fresh_bus):
        """CPU backends expose no memory_stats: no event, no raise."""
        assert costmodel.device_memory_stats() is None
        assert costmodel.record_hbm_watermark(iteration=0) is None
        assert fresh_bus.events("hbm_watermark") == []

    def test_peaks_keyed_by_device_kind_with_source(self):
        """One table, keyed by the device_kind string the chip reports;
        every entry names where its numbers come from."""
        v, src = costmodel.peak_flops("TPU v5 lite", "bfloat16")
        assert v == costmodel.DEVICE_PEAKS["TPU v5 lite"]["flops"]["bfloat16"]
        assert "TPU v5e" in src
        assert costmodel.peak_bytes_per_s("TPU v5 lite")[0] == 819e9
        for row in costmodel.DEVICE_PEAKS.values():
            assert row["source"]
        # no published f32 matmul peak: not measured, not invented
        assert costmodel.peak_flops("TPU v5 lite", "float32")[0] is None

    def test_unknown_device_kind_raises(self):
        """A device that is not in the table is an error, not a default."""
        with pytest.raises(KeyError, match="TPU v9 imaginary"):
            costmodel.peak_flops("TPU v9 imaginary", "bfloat16")
        with pytest.raises(KeyError, match="DEVICE_PEAKS"):
            costmodel.peak_bytes_per_s("tpu")

    def test_cpu_has_no_peak(self):
        """A CPU run never gets an MFU/roofline denominator: null, not a
        measured stand-in under a device metric's name."""
        assert costmodel.peak_flops("cpu") == (None, "not measured")
        assert costmodel.peak_bytes_per_s("cpu") == (None, "not measured")
        assert costmodel.roofline(1e9, 1e9, 1.0, "cpu") is None
        info = costmodel.device_info()
        assert info["platform"] == "cpu" and info["device_count"] >= 1
        assert costmodel.peak_flops(info["device_kind"])[0] is None

    def test_roofline_math(self):
        r = costmodel.roofline(flops=197e12, bytes_accessed=8.19e11,
                               seconds=1.0, device_kind="TPU v5 lite",
                               dtype="bfloat16")
        assert r["flops_utilization"] == 1.0
        assert r["bandwidth_utilization"] == 1.0
        assert r["bound"] in ("compute", "memory")
        r = costmodel.roofline(flops=1e9, bytes_accessed=8.19e11,
                               seconds=1.0, device_kind="TPU v5 lite",
                               dtype="bfloat16")
        assert r["bound"] == "memory"
        assert costmodel.roofline(None, None, 1.0, "TPU v5 lite") is None

    def test_round_flops_prefers_captured_program(self, fresh_bus):
        """The fused round program's own cost wins over the analytic rule,
        normalized by the rounds one dispatch executes."""
        with costmodel._lock:
            costmodel._costs["train_iteration_eval"] = costmodel.ProgramCost(
                fn="train_iteration_eval", level="lowered",
                flops=2000.0, bytes_accessed=4000.0)
        exp = types.SimpleNamespace(cfg=types.SimpleNamespace(
            comm_round=20, frequency_of_the_test=5))
        flops, source = costmodel.round_flops(exp)
        assert flops == 100.0 and source == "cost_analysis"
        assert costmodel.round_bytes(exp) == 200.0


# ----------------------------------------------------------------------
class TestSpans:
    def test_recorder_and_sink(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        rec = spans.SpanRecorder(path, pid=3)
        with rec.span("train_round", cat="phase", r=1):
            pass
        rec.record("iteration", ts=100.0, dur=2.5, cat="runner", iteration=0)
        rec.close()
        rows = [json.loads(l) for l in open(path)]
        assert [r["name"] for r in rows] == ["train_round", "iteration"]
        assert all(r["pid"] == 3 for r in rows)
        assert rows[1]["ts"] == 100.0 * 1e6 and rows[1]["dur"] == 2.5 * 1e6
        assert rows[0]["args"] == {"r": 1}
        assert rec.spans("iteration")[0]["args"] == {"iteration": 0}

    def test_disabled_recorder_noops(self):
        rec = spans.SpanRecorder(None, enabled=False)
        with rec.span("x"):
            pass
        assert rec.record("y", 0.0, 1.0) is None
        assert rec.spans() == []

    def _synthetic_run_dir(self, tmp_path) -> str:
        """A two-process run: spans on two pids + a few instant events."""
        with open(tmp_path / "spans.jsonl", "w") as f:
            for pid, tid, name, ts, dur in (
                    (0, 111, "iteration", 1_000_000.0, 500_000.0),
                    (0, 111, "train_round", 1_050_000.0, 300_000.0),
                    (0, 222, "publish", 1_100_000.0, 10_000.0),
                    (1, 333, "iteration", 1_010_000.0, 480_000.0)):
                f.write(json.dumps({"name": name, "cat": "phase", "ts": ts,
                                    "dur": dur, "pid": pid, "tid": tid}) + "\n")
        with open(tmp_path / "events.jsonl", "w") as f:
            for ts, kind in ((1.2, "eval"), (1.3, "jit_compile"),
                             (1.1, "drift_detected")):
                f.write(json.dumps({"_ts": ts, "kind": kind,
                                    "iteration": 0}) + "\n")
        return str(tmp_path)

    def test_trace_golden_structure(self, tmp_path):
        """Valid Chrome-trace-event JSON: envelope fields on every event,
        non-negative monotonically consistent ts/dur, sorted timeline, one
        process lane per pid with named metadata."""
        trace = spans.build_trace(self._synthetic_run_dir(tmp_path))
        evs = trace["traceEvents"]
        assert trace["displayTimeUnit"] == "ms"
        meta = [e for e in evs if e["ph"] == "M"]
        data = [e for e in evs if e["ph"] != "M"]
        for e in evs:
            assert {"name", "ph", "pid", "tid"} <= set(e)
        for e in data:
            assert e["ts"] >= 0
            assert e["ph"] in ("X", "i")
            if e["ph"] == "X":
                assert e["dur"] >= 0
        # sorted timeline (monotonic ts across the data events)
        ts = [e["ts"] for e in data]
        assert ts == sorted(ts)
        # one process lane per pid, each named
        pids = {e["pid"] for e in data}
        assert pids == {0, 1}
        proc_meta = {e["pid"] for e in meta if e["name"] == "process_name"}
        assert proc_meta == pids
        # distinct recording threads get distinct per-process lanes,
        # disjoint from the reserved instant-events lane (tid 0)
        lanes_p0 = {e["tid"] for e in data
                    if e["pid"] == 0 and e["ph"] == "X"}
        assert len(lanes_p0) == 2 and spans.EVENTS_LANE_TID not in lanes_p0
        instants = [e for e in data if e["ph"] == "i"]
        assert len(instants) == 3
        assert all(e["tid"] == spans.EVENTS_LANE_TID for e in instants)
        assert {e["name"] for e in instants} == {"eval", "jit_compile",
                                                 "drift_detected"}

    def test_write_trace_and_report_cli(self, tmp_path, capsys):
        """`report <dir> --trace` writes the Perfetto-loadable file."""
        run_dir = self._synthetic_run_dir(tmp_path)
        # report needs metrics or events: events.jsonl already present
        from feddrift_tpu.cli import main
        assert main(["report", run_dir, "--trace"]) == 0
        out_path = os.path.join(run_dir, "trace.json")
        assert os.path.isfile(out_path)
        trace = json.load(open(out_path))
        assert trace["traceEvents"]
        assert "trace written:" in capsys.readouterr().out


# ----------------------------------------------------------------------
class TestReportCostModel:
    def _summary(self, tmp_path, rows):
        with open(tmp_path / "events.jsonl", "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        from feddrift_tpu.obs.report import summarize
        return summarize(str(tmp_path))["cost_model"]

    def test_roofline_section_from_events(self, tmp_path, capsys):
        """The report CLI derives achieved-vs-peak roofline utilization
        from program_cost + iteration_end events against the published
        peak of the device_kind run_start names, and carries the device
        into the section."""
        cm = self._summary(tmp_path, [
            {"_ts": 1.0, "kind": "run_start", "backend": "tpu",
             "device_kind": "TPU v5 lite", "device_count": 1,
             "compute_dtype": "bfloat16"},
            {"_ts": 1.1, "kind": "program_cost", "fn": "train_iteration_eval",
             "level": "compiled", "flops": 4.6e10 * 20,
             "bytes_accessed": 1e9, "peak_hbm_bytes": 2_000_000_000},
            {"_ts": 2.0, "kind": "iteration_end", "wall_s": 2.0,
             "rounds": 20, "examples": 100},
            {"_ts": 3.0, "kind": "hbm_watermark", "bytes_in_use": 1e9,
             "peak_bytes": 2.1e9},
            {"_ts": 3.5, "kind": "profile_captured", "trace_dir": "/tmp/p"},
        ])
        roof = cm["roofline"]
        # fused program: 920 GFLOP per 20-round dispatch → 46 G/round,
        # 20 rounds in 2 s → 460 GFLOP/s → 0.2335% of 197 TFLOP/s bf16
        assert roof["flops_per_round"] == pytest.approx(4.6e10)
        assert roof["achieved_flops_per_s"] == pytest.approx(4.6e11)
        assert roof["flops_utilization"] == pytest.approx(0.002335)
        assert roof["source"] == "cost_analysis"
        assert (roof["backend"], roof["device_kind"],
                roof["device_count"]) == ("tpu", "TPU v5 lite", 1)
        assert cm["hbm_peak_bytes"] == pytest.approx(2.1e9)  # live > static
        from feddrift_tpu.obs.report import main
        assert main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "cost model (XLA accounting):" in out
        assert "% of Google Cloud documentation" in out

    def test_lowered_cost_is_divided_over_the_chips(self, tmp_path):
        """XLA counts a lowered program whole and a compiled one per
        device: only the former is spread over device_count chips."""
        def util(level):
            return self._summary(tmp_path, [
                {"_ts": 1.0, "kind": "run_start", "backend": "tpu",
                 "device_kind": "TPU v5 lite", "device_count": 4,
                 "compute_dtype": "bfloat16"},
                {"_ts": 1.1, "kind": "program_cost", "fn": "train_round",
                 "level": level, "flops": 197e12},
                {"_ts": 2.0, "kind": "iteration_end", "wall_s": 1.0,
                 "rounds": 1},
            ])["roofline"]["flops_utilization"]
        assert util("compiled") == pytest.approx(1.0)
        assert util("lowered") == pytest.approx(0.25)

    def test_no_utilization_for_cpu_runs(self, tmp_path):
        """CPU runs report achieved rates only: there is no peak."""
        roof = self._summary(tmp_path, [
            {"_ts": 1.0, "kind": "run_start", "backend": "cpu",
             "device_kind": "cpu", "device_count": 1},
            {"_ts": 1.1, "kind": "program_cost", "fn": "train_round",
             "level": "lowered", "flops": 1e6},
            {"_ts": 2.0, "kind": "iteration_end", "wall_s": 1.0,
             "rounds": 10},
        ])["roofline"]
        assert roof["achieved_flops_per_s"] == pytest.approx(1e7)
        assert "flops_utilization" not in roof
        assert "peak_source" not in roof

    def test_unknown_device_kind_is_an_error(self, tmp_path):
        with pytest.raises(KeyError, match="DEVICE_PEAKS"):
            self._summary(tmp_path, [
                {"_ts": 1.0, "kind": "run_start", "backend": "tpu",
                 "device_kind": "TPU v9 imaginary", "device_count": 1},
                {"_ts": 1.1, "kind": "program_cost", "fn": "train_round",
                 "level": "lowered", "flops": 1e6},
                {"_ts": 2.0, "kind": "iteration_end", "wall_s": 1.0,
                 "rounds": 10},
            ])


# ----------------------------------------------------------------------
def _bench_fixture(value=100.0, wall=10.0, rounds=1000, acc=0.86,
                   compiles=3.0, recompiles=0.0, wrap=False, **extra):
    d = {"value": value, "wall_s": wall, "rounds": rounds,
         "final_test_acc": acc,
         "instruments": {'jit_compiles{fn="train_round"}': compiles,
                         'jit_recompiles{fn="train_round"}': recompiles},
         **extra}
    return {"parsed": d, "rc": 0} if wrap else d


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)
    return str(path)


class TestRegress:
    def test_identical_snapshots_pass(self, tmp_path, capsys):
        p = _write(tmp_path / "b.json", _bench_fixture())
        assert regress.main([p, "--baseline", p]) == 0
        out = capsys.readouterr().out
        assert "OK: 0 regressed" in out

    def test_thirty_pct_slowdown_fails(self, tmp_path, capsys):
        base = _write(tmp_path / "base.json", _bench_fixture())
        slow = _write(tmp_path / "slow.json",
                      _bench_fixture(value=70.0, wall=10.0 / 0.7))
        assert regress.main([slow, "--baseline", base]) == 1
        out = capsys.readouterr().out
        assert "REGRESS" in out and "rounds_per_s" in out

    def test_compile_count_regression(self, tmp_path):
        base = _write(tmp_path / "base.json", _bench_fixture())
        more = _write(tmp_path / "more.json",
                      _bench_fixture(recompiles=2.0))
        assert regress.main([more, "--baseline", base]) == 1
        # an explicit tolerance waives it
        assert regress.main([more, "--baseline", base,
                             "--tol-compiles", "2"]) == 0

    def test_accuracy_absolute_tolerance(self, tmp_path):
        base = _write(tmp_path / "base.json", _bench_fixture())
        worse = _write(tmp_path / "worse.json", _bench_fixture(acc=0.83))
        assert regress.main([worse, "--baseline", base]) == 1
        assert regress.main([worse, "--baseline", base,
                             "--tol-acc", "0.05"]) == 0

    def test_wall_skipped_when_rounds_differ(self, tmp_path, capsys):
        base = _write(tmp_path / "base.json", _bench_fixture(rounds=1600))
        cand = _write(tmp_path / "cand.json",
                      _bench_fixture(rounds=20, wall=99.0))
        assert regress.main([cand, "--baseline", base]) == 0
        assert "rounds differ" in capsys.readouterr().out

    def test_wrapper_format_and_missing_instruments(self, tmp_path, capsys):
        """Driver-wrapper artifacts load; artifacts that predate
        the instruments snapshot skip compile gating instead of failing."""
        base = _bench_fixture(wrap=True)
        del base["parsed"]["instruments"]
        bp = _write(tmp_path / "base.json", base)
        cp = _write(tmp_path / "cand.json", _bench_fixture())
        assert regress.main([cp, "--baseline", bp]) == 0
        assert "no instruments snapshot" in capsys.readouterr().out

    def test_cli_verb_routes(self, tmp_path):
        from feddrift_tpu.cli import main
        p = _write(tmp_path / "b.json", _bench_fixture())
        assert main(["regress", p, "--baseline", p]) == 0
        slow = _write(tmp_path / "s.json", _bench_fixture(value=1.0))
        assert main(["regress", slow, "--baseline", p]) == 1

    def test_load_errors_exit_2(self, tmp_path):
        p = _write(tmp_path / "b.json", _bench_fixture())
        assert regress.main([str(tmp_path / "nope.json"),
                             "--baseline", p]) == 2
        bad = tmp_path / "bad.json"
        bad.write_text("not json at all")
        assert regress.main([str(bad), "--baseline", p]) == 2


# ----------------------------------------------------------------------
class TestXlaTraceGuard:
    def test_nested_trace_is_noop_and_event_emitted(self, tmp_path,
                                                    fresh_bus):
        """jax raises on nested start_trace; xla_trace must instead run
        the inner body without starting, and the OUTER capture completes
        with one profile_captured event."""
        import jax.numpy as jnp
        from feddrift_tpu.utils import tracing

        outer, inner = str(tmp_path / "o"), str(tmp_path / "i")
        with tracing.xla_trace(outer):
            with tracing.xla_trace(inner):       # no-op, must not raise
                x = jnp.ones((4,)) * 2
        assert float(x.sum()) == 8.0
        evs = fresh_bus.events("profile_captured")
        assert [e["trace_dir"] for e in evs] == [outer]
        assert tracing._trace_active is False    # guard released

    def test_reentry_after_capture(self, tmp_path, fresh_bus):
        from feddrift_tpu.utils import tracing

        for i in range(2):                       # sequential captures: fine
            with tracing.xla_trace(str(tmp_path / f"t{i}")):
                pass
        assert len(fresh_bus.events("profile_captured")) == 2


# ----------------------------------------------------------------------
class TestSchemaList:
    def test_list_mode_prints_taxonomy(self):
        from feddrift_tpu.obs.events import EVENT_KINDS
        out = subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "scripts", "check_events_schema.py"),
             "--list"],
            capture_output=True, text=True)
        assert out.returncode == 0
        assert out.stdout.split() == sorted(EVENT_KINDS)
        for kind in ("program_cost", "profile_captured", "hbm_watermark"):
            assert kind in out.stdout.split()


# ----------------------------------------------------------------------
@pytest.mark.slow
class TestEndToEnd:
    def test_runner_emits_spans_costs_and_trace(self, tmp_path, capsys):
        """A real (tiny) run produces spans.jsonl + program_cost events,
        and `report --trace` exports a loadable timeline from them."""
        from feddrift_tpu.config import ExperimentConfig
        from feddrift_tpu.simulation.runner import Experiment

        costmodel.clear()
        d = str(tmp_path / "run")
        cfg = ExperimentConfig(
            dataset="sea", model="fnn", concept_drift_algo="win-1",
            train_iterations=2, comm_round=2, epochs=1, sample_num=16,
            batch_size=8, client_num_in_total=4, client_num_per_round=4,
            concept_num=2, frequency_of_the_test=1, report_client=0,
            cost_model="compiled", out_dir=d)
        Experiment(cfg, out_dir=d).run()

        span_rows = [json.loads(l) for l in open(os.path.join(
            d, "spans.jsonl"))]
        names = {r["name"] for r in span_rows}
        assert {"iteration", "train_round", "cluster"} <= names
        pc = costmodel.get("train_iteration_eval")
        assert pc is not None and pc.flops > 0 and pc.peak_hbm_bytes > 0

        from feddrift_tpu.cli import main
        assert main(["report", d, "--trace"]) == 0
        trace = json.load(open(os.path.join(d, "trace.json")))
        evs = trace["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        instants = [e for e in evs if e["ph"] == "i"]
        assert {e["name"] for e in xs} >= {"iteration", "train_round"}
        assert any(e["name"] == "program_cost" for e in instants)
        assert any(e["name"] == "iteration_end" for e in instants)
        data_ts = [e["ts"] for e in evs if e["ph"] != "M"]
        assert data_ts == sorted(data_ts)
        # the cost-model section renders in the text report
        assert main(["report", d]) == 0
        assert "cost model" in capsys.readouterr().out

    def test_perf_gate(self):
        """scripts/perf_gate.sh: two warm smoke benches, cost-model field
        assertions, regress self-comparison."""
        out = subprocess.run(
            ["bash", os.path.join(ROOT, "scripts", "perf_gate.sh")],
            capture_output=True, text=True, timeout=1500)
        assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
        assert "perf_gate: OK" in out.stdout
