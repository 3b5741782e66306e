"""The three per-layer metrics that read the program's spans (ISSUE 25):
hand-worked answers on made-up records and a made-up ring, None where there
is nothing sound to read, and a tiny CPU job on the cell's traffic that
leaves spans the readers can read. No number from here is a device number."""

import collections
import importlib

import pytest

from tests.benchmark import bench_driving as bd

METRICS = ("runner_host_share", "dispatches_per_round",
           "host_syncs_per_round")


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}")


def records():
    """Two time steps of five rounds: 10 s of wall, 7.5 s of it waiting."""
    return {"time_steps": [
        {"t": 2, "wall_s": 4.0, "rounds": 5,
         "segments": {"device_compute": 3.0, "dispatch": 0.2,
                      "dispatch_gap": 0.8}},
        {"t": 3, "wall_s": 6.0, "rounds": 5,
         "segments": {"device_compute": 4.5, "dispatch": 0.3,
                      "dispatch_gap": 1.2}}]}


@pytest.fixture()
def ring(monkeypatch):
    """A recorder in the program's place, filled as the program fills it:
    a warm-up time step that is not the window's, then per window time step
    15 dispatches and 13 waits (12 fetches, one explicit block)."""
    from feddrift_tpu.obs import spans
    rec = spans.SpanRecorder(None)
    monkeypatch.setattr(spans, "_recorder", rec)
    clock = iter(range(10 ** 6))
    for t in (1, 2, 3):
        for i in range(15):
            rec.record("dispatch", next(clock), 0.5, cat="round",
                       iteration=t, round=5 * t + i // 3, fn="train_round",
                       track_us=1.0)
        for i in range(13):
            rec.record("device_compute", next(clock), 0.5, cat="round",
                       iteration=t, round=5 * t + i // 3)
        rec.record("iteration", next(clock), 1.0, cat="runner", iteration=t)
    return rec


@pytest.mark.parametrize("name,answer", [
    ("runner_host_share", 25.0),          # 100 x (10 - 7.5) / 10
    ("dispatches_per_round", 3.0),        # 30 spans / 10 rounds
    ("host_syncs_per_round", 2.6),        # 26 spans / 10 rounds
])
def test_hand_worked_answers(ring, name, answer):
    assert reader(name).read(records(), None, {}) == pytest.approx(answer)


@pytest.mark.parametrize("name", METRICS)
def test_none_on_empty_records(ring, name):
    assert reader(name).read({"time_steps": []}, None, {}) is None


@pytest.mark.parametrize("name", METRICS)
def test_none_when_a_time_step_has_no_device_compute_segment(ring, name):
    """The parent's per-round path: the wait is sampled on one round in
    ten, so every second time step lacks the segment."""
    rec = records()
    del rec["time_steps"][1]["segments"]["device_compute"]
    assert reader(name).read(rec, None, {}) is None


@pytest.mark.parametrize("name", METRICS)
def test_none_when_the_ring_has_dropped_the_windows_start(ring, name):
    kept = [s for s in ring.spans() if s["args"]["iteration"] >= 2][5:]
    ring.ring = collections.deque(kept, maxlen=len(kept))
    ring.dropped = 34                   # the warm-up step and five more
    assert reader(name).read(records(), None, {}) is None
    # it dropped some, but still holds the end of the time step before
    ring.ring.appendleft({"name": "writeback", "cat": "round",
                          "args": {"iteration": 1}})
    assert reader(name).read(records(), None, {}) is not None
    # a ring that dropped nothing starts where the program started
    ring.ring.popleft()
    ring.dropped = 0
    assert reader(name).read(records(), None, {}) is not None


def test_only_the_rounds_own_spans_are_counted(ring):
    """A span of another category under the same name (a phase, a comm
    publish) is no dispatch and no wait of the round."""
    for t in (2, 3):
        ring.record("dispatch", 0.0, 0.5, cat="phase", iteration=t)
        ring.record("device_compute", 0.0, 0.5, cat="comm", iteration=t)
    assert reader("dispatches_per_round").read(records(), None, {}) == 3.0
    assert reader("host_syncs_per_round").read(records(), None, {}) == 2.6
    # a program that records no dispatch span: nothing to read, not zero
    ring.ring = collections.deque(
        (s for s in ring.spans() if s["name"] != "dispatch"), maxlen=8192)
    assert reader("dispatches_per_round").read(records(), None, {}) is None


def test_a_tiny_job_on_the_cells_traffic_leaves_spans_to_read():
    """IFCA ``hard-r`` on the per-round path at the rehearsal's size (2
    rounds a time step, an evaluation behind both). Per time step 2
    ``train_round``, 1 ``fresh_opt_states`` (the time step's optimizer
    states as one tracked program, PR 26) and 4 ``acc_matrix`` (the
    re-assignment after each round and the test half of each evaluation;
    the drift decision's and the evaluations' train halves are served from
    the store of evaluated accuracy counts, PR 32): seven tracked dispatches
    and six fetches. Each ``train_round`` runs K x C = 1 x 2 pairs."""
    from benchmark.drivers import train
    from feddrift_tpu.parallel.mesh import make_mesh
    from feddrift_tpu.simulation.runner import Experiment
    cell, config, traffic, sizes = bd.files()
    clients = int(sizes["clients_per_chip"])
    cfg = train.experiment_config(config, traffic, sizes, bd.SEED, clients)
    exp = Experiment(cfg, mesh=make_mesh(num_devices=1))
    steps = []
    for t in range(4):
        exp.run_iteration(t)
        if t >= 2:
            steps.append(train.time_step_record(
                exp, t, exp.last_round_breakdown["wall_s"], clients, cfg))
    rec = {"time_steps": steps}
    assert all(s["rounds"] == 2 for s in steps)
    assert reader("dispatches_per_round").read(rec, None, cell) == 3.5
    assert reader("host_syncs_per_round").read(rec, None, cell) == 3.0
    assert reader("pairs_run_per_round").read(rec, None, cell) == 2.0
    assert 0.0 < reader("runner_host_share").read(rec, None, cell) < 100.0
    for s in steps:
        assert s["segments"]["device_compute"] > 0
        assert sum(s["segments"].values()) == pytest.approx(s["wall_s"],
                                                            abs=2e-5)
