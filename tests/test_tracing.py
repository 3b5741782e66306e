"""Tracing subsystem tests."""

import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow   # heavy compiles: full-tier only


class TestPhaseTracer:
    def test_accumulates(self):
        from feddrift_tpu.utils.tracing import PhaseTracer
        tr = PhaseTracer()
        for _ in range(3):
            with tr.phase("a"):
                time.sleep(0.01)
        with tr.phase("b"):
            pass
        s = tr.summary()
        assert s["a"]["count"] == 3 and s["a"]["total_s"] >= 0.03
        assert s["b"]["count"] == 1
        assert abs(s["a"]["mean_s"] - s["a"]["total_s"] / 3) < 1e-9
        tr.reset()
        assert tr.summary() == {}

    def test_exception_still_recorded(self):
        from feddrift_tpu.utils.tracing import PhaseTracer
        tr = PhaseTracer()
        try:
            with tr.phase("boom"):
                raise RuntimeError
        except RuntimeError:
            pass
        assert tr.summary()["boom"]["count"] == 1

    def test_runner_integration(self):
        from feddrift_tpu.config import ExperimentConfig
        from feddrift_tpu.simulation.runner import Experiment
        cfg = ExperimentConfig(dataset="sea", model="fnn",
                               concept_drift_algo="win-1",
                               train_iterations=1, comm_round=2, epochs=1,
                               sample_num=16, batch_size=8,
                               client_num_in_total=4, client_num_per_round=4,
                               concept_num=2, frequency_of_the_test=1)
        exp = Experiment(cfg)
        exp.run_iteration(0)
        s = exp.last_phase_summary
        # fused path: the whole iteration is ONE device program, evals fetched
        # in one bulk transfer
        assert s["train_round"]["count"] == 1
        assert s["eval"]["count"] == 1
        assert s["cluster"]["count"] == 2   # begin + end
        assert all(np.isfinite(v["total_s"]) for v in s.values())
        # per-iteration deltas: tracer resets between iterations
        assert exp.tracer.summary() == {}

        # per-round path: one train_round/eval phase per round
        from dataclasses import replace
        exp2 = Experiment(replace(cfg, chunk_rounds=False))
        exp2.run_iteration(0)
        s2 = exp2.last_phase_summary
        assert s2["train_round"]["count"] == 2
        assert s2["eval"]["count"] == 2


class TestAnnotate:
    def test_program_spans_in_the_profilers_host_plane(self, tmp_path):
        """Under xla_trace the runner's spans are TraceAnnotations of the
        same name: a capture shows the runner next to the device ops."""
        import glob
        import os

        import jax
        from feddrift_tpu.config import ExperimentConfig
        from feddrift_tpu.simulation.runner import Experiment
        from feddrift_tpu.utils.tracing import xla_trace
        cfg = ExperimentConfig(dataset="sea", model="fnn",
                               concept_drift_algo="win-1",
                               train_iterations=2, comm_round=2, epochs=1,
                               sample_num=16, batch_size=8,
                               client_num_in_total=4, client_num_per_round=4,
                               concept_num=2, frequency_of_the_test=1,
                               report_client=0, chunk_rounds=False)
        exp = Experiment(cfg)
        exp.run_iteration(0)
        with xla_trace(str(tmp_path)):
            exp.run_iteration(1)
        path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        host, = [p for p in jax.profiler.ProfileData.from_file(path).planes
                 if p.name == "/host:CPU"]
        names = {ev.name for line in host.lines for ev in line.events}
        assert {"dispatch", "device_compute", "guard", "writeback",
                "round_prep", "eval"} <= names
