"""JAX's own stages as spans (``obs/compile_spans.py``): one ``trace``, one
``lower`` and one ``compile`` span (``cat="compile"``) for each program JAX
builds, recorded onto the process-wide recorder; nested traces folded into
the outermost; the persistent cache's answer on the ``compile`` span. (Six
tests: ``tests/benchmark/test_manifest.py`` says why a file keeps to seven.)
"""

import contextlib

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from feddrift_tpu.obs import compile_spans, spans


@pytest.fixture
def rec(monkeypatch):
    """A fresh process-wide recorder, the one before it put back after."""
    compile_spans.register()
    fresh = spans.SpanRecorder(None)
    monkeypatch.setattr(spans, "_recorder", fresh)
    return fresh


@contextlib.contextmanager
def cache_at(path):
    """The persistent compilation cache in ``path`` (None: no cache), taking
    every program; the settings as they were, after, so that no later test
    in the process sees them."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        yield
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


@pytest.fixture
def no_cache():
    """No persistent cache, whatever an earlier test in the process set:
    a cache a run before this one filled would serve the program."""
    with cache_at(None):
        yield


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compilation cache in an empty directory."""
    with cache_at(str(tmp_path)):
        yield tmp_path


def stages(rec):
    return [(s["name"], s["args"]["fn"]) for s in rec.spans()
            if s["cat"] == "compile"]


def test_a_fresh_jit_is_one_trace_one_lowering_and_one_compile(rec,
                                                                no_cache):
    def double(x):
        return x * 2.0
    x = np.ones(3, np.float32)
    jax.jit(double)(x).block_until_ready()
    assert stages(rec) == [("trace", "double"), ("lower", "jit(double)"),
                           ("compile", "jit(double)")]
    trace, lower, compile_ = rec.spans()
    # jnp's own jitted functions (the multiply) are traced inside it
    assert trace["args"]["nested"] >= 0 and "nested" not in lower["args"]
    assert compile_["args"]["cache"] == "off"
    assert all(s["args"]["caused_by"] is None for s in rec.spans())
    # one after the other on the calling thread
    assert trace["ts"] + trace["dur"] <= lower["ts"]
    assert lower["ts"] + lower["dur"] <= compile_["ts"]
    assert {s["tid"] for s in rec.spans()} == {trace["tid"]}


def test_traces_inside_a_trace_are_folded_into_the_outermost(rec):
    @jax.jit
    def inner_a(x):
        return x + 1.0

    @jax.jit
    def inner_b(x):
        return x * 3.0

    def outer(x):
        return inner_a(x) + inner_b(x)
    x = np.ones(4, np.float32)
    jax.jit(outer)(x).block_until_ready()
    traces = [s for s in rec.spans() if s["name"] == "trace"]
    assert [s["args"]["fn"] for s in traces] == ["outer"]
    assert traces[0]["args"]["nested"] >= 2
    # the inner programs are lowered into the outer one's module
    assert stages(rec)[1:] == [("lower", "jit(outer)"),
                               ("compile", "jit(outer)")]


def test_a_program_built_before_records_nothing_on_its_next_call(rec):
    f = jax.jit(lambda x: x - 1.0)
    x = np.ones(5, np.float32)
    f(x).block_until_ready()
    n = len(rec.spans())
    assert n == 3
    for _ in range(3):
        f(x).block_until_ready()
    assert len(rec.spans()) == n


def test_a_disabled_recorder_records_nothing(rec):
    def halve(x):
        return x / 2.0
    rec.enabled = False
    jax.jit(halve)(np.ones(6, np.float32)).block_until_ready()
    assert rec.spans() == []
    # the fold of nested traces is kept all the same: the same function's
    # next trace, recorded, counts what it holds and no more
    rec.enabled = True
    jax.jit(halve)(np.ones(8, np.float32)).block_until_ready()
    jax.jit(halve)(np.ones(10, np.float32)).block_until_ready()
    first, second = rec.spans("trace")
    assert first["args"]["nested"] == second["args"]["nested"]


def test_registering_twice_records_once(rec):
    compile_spans.register()
    compile_spans.register()
    x = np.ones(7, np.float32)
    jax.jit(lambda x: x + 5.0)(x).block_until_ready()
    assert [s["name"] for s in rec.spans()] == ["trace", "lower", "compile"]


def test_the_cache_reads_miss_then_hit_and_off_without_one(rec, cache_dir):
    def halve_and_shift(x):
        return x * 0.5 + 0.25
    x = np.ones(9, np.float32)
    jax.jit(halve_and_shift)(x).block_until_ready()
    jax.clear_caches()               # the process forgets; the directory not
    jax.jit(halve_and_shift)(x).block_until_ready()
    compiles = [s["args"] for s in rec.spans() if s["name"] == "compile"]
    assert [c["cache"] for c in compiles] == ["miss", "hit"]
    assert "cache_load_s" not in compiles[0]
    assert compiles[1]["cache_load_s"] > 0
    # with no directory there is no cache to ask
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    jax.clear_caches()
    jax.jit(halve_and_shift)(x).block_until_ready()
    assert rec.spans("compile")[-1]["args"]["cache"] == "off"
