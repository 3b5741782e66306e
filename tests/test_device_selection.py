"""No path that was not asked for the CPU can end up there, and no failure
exits 0: the compile-cache placement, bench.py / chip_smoke.py without a
TPU, and ``serve`` when requests fail (ISSUE 21)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from feddrift_tpu.utils import cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run(argv, **env):
    return subprocess.run(
        [sys.executable, *argv], cwd=ROOT, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


class TestCompileCachePlacement:
    @pytest.fixture()
    def updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them."""
        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda key, value: seen.__setitem__(key, value))
        return seen

    def test_env_var_places_the_cache(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert cache.enable_compile_cache() == "/some/dir"
        # JAX reads the variable itself: no directory is set in code
        assert [k for k in updates if k.endswith("cache_dir")] == []

    def test_fixed_checkout_path_otherwise(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert cache.enable_compile_cache() == want
        assert [v for k, v in updates.items()
                if k.endswith("cache_dir")] == [want]

    def test_cache_that_cannot_be_configured_raises(self, monkeypatch):
        def refuse(key, value):
            raise RuntimeError("cache refused")
        monkeypatch.setattr(jax.config, "update", refuse)
        with pytest.raises(RuntimeError, match="cache refused"):
            cache.enable_compile_cache()


class TestNoTpuFailsLoudly:
    def test_bench_without_cpu_flag_exits_nonzero(self):
        out = _run(["bench.py", "--smoke"])
        assert out.returncode != 0
        assert "platform='cpu'" in out.stderr and "--cpu" in out.stderr
        assert out.stdout.strip() == ""          # no result of any kind

    def test_chip_smoke_exits_nonzero_within_seconds(self):
        out = _run(["chip_smoke.py"])
        assert out.returncode != 0
        assert "platform='cpu'" in out.stderr
        assert '"ok"' not in out.stdout

    def test_chip_smoke_alone_exits_nonzero(self, tmp_path):
        """In a directory that holds chip_smoke.py and nothing else."""
        with open(os.path.join(ROOT, "chip_smoke.py")) as f:
            (tmp_path / "chip_smoke.py").write_text(f.read())
        out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                             capture_output=True, text=True, timeout=60)
        assert out.returncode != 0 and out.stdout.strip() == ""


class TestServeExitCode:
    def _serve(self, monkeypatch, capsys, engine):
        from feddrift_tpu import cli, obs
        from feddrift_tpu.platform import serving
        monkeypatch.setattr(serving, "load_engine", lambda *a, **k: engine)
        # serve reports the process-wide jit_compiles* counters: start them
        # from nought, whatever ran before this test on its worker
        obs.registry().reset()
        rc = cli.main(["serve", "unused-run-dir", "--requests", "8",
                       "--concurrency", "2", "--buckets", "1,2,4"])
        return rc, json.loads(capsys.readouterr().out)

    def test_failed_requests_fail_the_command(self, monkeypatch, capsys):
        from tests.test_serving import _engine, _pool
        engine = _engine(_pool(), np.array([0, 1, 2, 0]))

        def device_lost(batch):
            raise RuntimeError("injected device loss")
        engine._serve_batch = device_lost     # kills the dispatcher
        rc, stats = self._serve(monkeypatch, capsys, engine)
        assert rc == 1
        assert stats["errors"] == 8 and stats["completed"] == 0
        assert engine.failed is not None

    def test_clean_traffic_exits_zero_and_names_the_device(
            self, monkeypatch, capsys):
        from tests.test_serving import _engine, _pool
        engine = _engine(_pool(), np.array([0, 1, 2, 0]))
        rc, stats = self._serve(monkeypatch, capsys, engine)
        assert rc == 0 and stats["errors"] == 0
        assert stats["platform"] == "cpu" and stats["device_kind"]
        assert len(stats["warmup_compiles"]) == 3     # one per bucket
        assert stats["steady_compiles"] == {}
