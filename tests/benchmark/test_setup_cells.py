"""The set-up metrics in the manifest and on a whole run: five entries
appended after the accepted ones, and a tiny CPU job through the driver
that leaves set-up spans all five readers read. No number from here is a
device number."""

import importlib

from tests.benchmark import bench_driving as bd

NEW = ["setup_trace_s", "setup_lower_s", "setup_compile_s",
       "setup_cache_misses", "setup_build_s"]
CELLS = ["resnet20.ifca_perround", "kanana2.ifca_perround"]


def test_the_five_entries_come_after_the_accepted_ones():
    """The 22 accepted metrics first, in their order; the five after them,
    each moving ``setup_s`` in the resnet and the kanana2 cell (the SDAR
    cell's test pins its reported list whole); every cell reports what it
    reported before, and these five after it where it lists them."""
    manifest = bd.MANIFEST
    new = manifest["per_layer"][22:]
    assert [m["name"] for m in new] == NEW
    for m in new:
        assert m["workloads"] == CELLS
        assert (m["moves"], m["better"]) == ("setup_s", "lower")
    assert [(m["unit"], m["source"]) for m in new] == \
        [("s", "program_span")] * 3 + [("count", "program_counter"),
                                       ("s", "program_span")]
    accepted = dict(manifest, per_layer=manifest["per_layer"][:22])
    for cell in manifest["workloads"]:
        before = [m["name"] for m in bd.bench.metrics_of(
            accepted, "per_layer", cell["name"])]
        now = [m["name"] for m in bd.bench.metrics_of(
            manifest, "per_layer", cell["name"])]
        assert now == before + (NEW if cell["name"] in CELLS else [])


def test_a_tiny_job_leaves_setup_spans_all_five_read(monkeypatch, tmp_path):
    """The resnet cell at the rehearsal's sizes into an empty persistent
    cache: set-up builds every program it runs, so it misses the cache;
    the comparison's programs come after the window and are not set-up's."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from benchmark.drivers import train
    from feddrift_tpu.obs import spans
    steps = []
    real = train.time_step_record

    def noted(*a, **kw):
        steps.append(real(*a, **kw))
        return steps[-1]
    monkeypatch.setattr(train, "time_step_record", noted)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        result = bd.drive()
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
    assert result["correct"] and steps
    recorder = spans.get_recorder()
    assert recorder.dropped == 0
    got = {n: importlib.import_module(f"benchmark.metrics.{n}").read(
        {"time_steps": steps}, None, {}) for n in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["setup_cache_misses"] >= 1
    assert got["setup_compile_s"] > 0 and got["setup_trace_s"] > 0
    build = recorder.spans("experiment_build")[-1]["dur"] * 1e-6
    assert 0 < got["setup_build_s"] <= build
    # what set-up built is the build's, the harness's and the warm-up's
    start = next(s["ts"] for s in recorder.spans("iteration")
                 if s["args"]["iteration"] == steps[0]["t"])
    after = [s for s in recorder.spans() if s["cat"] == "compile"
             and s["ts"] >= start]
    assert after, "the comparison builds programs after the window"
