"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh)."""

import jax
import numpy as np
import pytest

from tests.test_ring_attention import naive_attention, _qkv


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("L", [64, 100])
    def test_matches_naive(self, causal, L):
        from feddrift_tpu.parallel.pallas_attention import flash_attention
        q, k, v = _qkv(jax.random.PRNGKey(0), L=L)
        out = flash_attention(q, k, v, causal, 32, 32, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(naive_attention(q, k, v, causal)),
            atol=1e-5)

    def test_gradients_match_naive(self):
        from feddrift_tpu.parallel.pallas_attention import flash_attention
        q, k, v = _qkv(jax.random.PRNGKey(1), L=64)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, True, 32, 32, True).sum()

        def loss_naive(q, k, v):
            return naive_attention(q, k, v, True).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_jit_and_small_blocks(self):
        from feddrift_tpu.parallel.pallas_attention import flash_attention
        q, k, v = _qkv(jax.random.PRNGKey(2), B=1, H=1, L=24, D=8)
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 16, 16,
                                                    True))
        out = f(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(naive_attention(q, k, v, True)),
            atol=1e-5)


class TestAutoSelection:
    """`attention_impl="auto"` picks the Mosaic kernel only where the
    program is not partitioned: GSPMD refuses to split a Mosaic kernel over
    a mesh (v5e 2x2, PR 21), so on more than one device auto is blockwise."""

    @pytest.mark.parametrize("backend,devices,want", [
        ("cpu", 1, "blockwise"), ("cpu", 8, "blockwise"),
        ("tpu", 1, "pallas"), ("tpu", 4, "blockwise")])
    def test_auto_follows_backend_and_device_count(self, monkeypatch,
                                                   backend, devices, want):
        from feddrift_tpu.models import transformer
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(jax, "device_count", lambda: devices)
        assert transformer.resolve_attention_impl("auto") == want
        # a forced implementation is never second-guessed
        assert transformer.resolve_attention_impl("pallas") == "pallas"
        assert transformer.resolve_attention_impl("blockwise") == "blockwise"

    def test_unknown_impl_rejected(self):
        from feddrift_tpu.models.transformer import resolve_attention_impl
        with pytest.raises(ValueError, match="attention_impl"):
            resolve_attention_impl("flashiest")

    def test_run_start_names_the_resolved_impl(self):
        from feddrift_tpu.config import ExperimentConfig
        from feddrift_tpu.simulation.runner import Experiment
        kw = dict(train_iterations=1, comm_round=1, epochs=1, sample_num=8,
                  batch_size=8, client_num_in_total=8, client_num_per_round=8)
        for model, dataset, want in (("transformer", "shakespeare",
                                      "blockwise"), ("fnn", "sea", None)):
            exp = Experiment(ExperimentConfig(dataset=dataset, model=model,
                                              concept_drift_algo="win-1",
                                              **kw))
            (start,) = [e for e in exp.events.ring
                        if e["kind"] == "run_start"]
            assert start["attention_impl"] == want
            assert start["device_kind"] and start["device_count"] == 8
            assert start["mesh"] == {"clients": 8}
