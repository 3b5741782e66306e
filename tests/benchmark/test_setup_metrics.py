"""The five per-layer metrics of set-up (``metrics/setup_*.py``, on
``metrics/_setup_spans.py``): hand-worked answers on a made-up ring, None
where there is nothing sound to read, and nothing after the window counted.
No number from here is a device number. (Seven tests:
``test_manifest.py`` says why a file keeps to seven.)"""

import importlib

import pytest

METRICS = ("setup_trace_s", "setup_lower_s", "setup_compile_s",
           "setup_cache_misses", "setup_build_s")


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}")


def records(first=2):
    return {"time_steps": [{"t": first, "rounds": 5, "segments": {}},
                           {"t": first + 1, "rounds": 5, "segments": {}}]}


@pytest.fixture()
def ring(monkeypatch):
    """A recorder in the program's place, filled as the program fills it
    (times in seconds): the build from 100 to 110 with its children and
    the programs it builds, warm-up time steps 0 and 1, the window from
    time step 2 at 130, and a program built after the window."""
    from feddrift_tpu.obs import spans
    rec = spans.SpanRecorder(None)
    monkeypatch.setattr(spans, "_recorder", rec)

    def stage(name, a, b, **args):
        rec.record(name, a, b - a, cat="compile", fn="f", **args)
    stage("trace", 101, 102, nested=3)
    stage("lower", 102, 103)
    stage("compile", 103, 105, cache="miss")
    stage("trace", 106, 106.5, nested=0)
    stage("lower", 106.5, 107)
    for name, a, b in (("data", 100, 101), ("pool_init", 101, 107),
                       ("data_to_device", 107, 107.5),
                       ("algorithm", 107.5, 108)):
        rec.record(name, a, b - a, cat="setup")
    stage("compile", 108, 109, cache="hit", cache_load_s=0.2)
    rec.record("experiment_build", 100, 10, cat="setup")
    # time step 0: a trace on a second thread overlaps the first
    stage("trace", 113, 114, nested=1, iteration=0)
    stage("trace", 113.5, 114.5, nested=0, iteration=0)
    stage("lower", 114, 115, iteration=0)
    stage("compile", 115, 118, cache="off", iteration=0)
    rec.record("iteration", 112, 8, cat="runner", iteration=0)
    rec.record("iteration", 121, 8, cat="runner", iteration=1)
    # a trace that ends after the window has started is not set-up's
    stage("trace", 129.5, 130.5, nested=0, iteration=2)
    rec.record("iteration", 130, 5, cat="runner", iteration=2)
    rec.record("iteration", 135, 5, cat="runner", iteration=3)
    return rec


@pytest.mark.parametrize("name,answer", [
    ("setup_trace_s", 3.0),        # 101-102, 106-106.5, 113-114.5
    ("setup_lower_s", 2.5),        # 102-103, 106.5-107, 114-115
    ("setup_compile_s", 6.0),      # 103-105, 108-109, 115-118
    ("setup_cache_misses", 2),     # a miss and an off; the hit is a load
    ("setup_build_s", 4.0),        # 10 less 101-105, 106-107, 108-109
])
def test_hand_worked_answers(ring, name, answer):
    assert reader(name).read(records(), None, {}) == pytest.approx(answer)


def test_none_where_there_is_nothing_sound_to_read(ring):
    for name in METRICS:
        read = reader(name).read
        assert read({"time_steps": []}, None, {}) is None       # no window
        # no iteration span of the window's first time step
        assert read(records(first=7), None, {}) is None
        ring.dropped = 1          # set-up's spans are the first to go
        assert read(records(), None, {}) is None
        ring.dropped = 0
        assert read(records(), None, {}) is not None
    # a program that does not record its build (the parent's)
    ring.ring.remove(ring.spans("experiment_build")[0])
    assert all(reader(n).read(records(), None, {}) is None for n in METRICS)


def test_programs_built_after_the_window_do_not_count(ring):
    before = {n: reader(n).read(records(), None, {}) for n in METRICS}
    for a in (140, 150):
        ring.record("trace", a, 1, cat="compile", fn="g", nested=0)
        ring.record("lower", a + 1, 1, cat="compile", fn="g")
        ring.record("compile", a + 2, 5, cat="compile", fn="g", cache="miss")
    ring.record("experiment_build", 160, 3, cat="setup")
    assert {n: reader(n).read(records(), None, {}) for n in METRICS} \
        == before
    # zero is a reading: a set-up that compiled nothing misses nothing
    ring.ring = type(ring.ring)(
        (s for s in ring.spans() if s["name"] != "compile"),
        maxlen=ring.ring.maxlen)
    assert reader("setup_cache_misses").read(records(), None, {}) == 0
    assert reader("setup_compile_s").read(records(), None, {}) == 0.0
