"""Fresh optimizer states from one tracked program (ISSUE 26): the
``[M, C, ...]`` stack of a time step is one ``fresh_opt_states`` dispatch,
equal to the pure body ``init_opt_states`` leaf for leaf and placed as
``train_round`` returns its states, so that no round program meets a second
signature."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from feddrift_tpu import obs
from feddrift_tpu.core.precision import PRECISION_PRESETS

FN = "fresh_opt_states"
ITERATIONS, ROUNDS = 2, 3


def _cfg(**kw):
    from feddrift_tpu.config import ExperimentConfig
    base = dict(
        dataset="sea", model="fnn", concept_drift_algo="win-1",
        train_iterations=ITERATIONS, comm_round=ROUNDS, epochs=1,
        sample_num=16, batch_size=8, client_num_in_total=4,
        client_num_per_round=4, concept_num=3, frequency_of_the_test=2,
        report_client=0, divergence_warmup_rounds=0, trace_sync=False)
    base.update(kw)
    return ExperimentConfig(**base)


def _dispatches(spans, fn=FN):
    return [s for s in spans if s["name"] == "dispatch"
            and s["cat"] == "round" and s["args"]["fn"] == fn]


# ----------------------------------------------------------------------
# the program against the pure body
@pytest.mark.parametrize("precision", PRECISION_PRESETS)
@pytest.mark.parametrize("optimizer", ["adam", "sgd"])
def test_the_program_gives_what_the_pure_body_gives(optimizer, precision):
    from feddrift_tpu.simulation.runner import Experiment
    exp = Experiment(_cfg(client_optimizer=optimizer, precision=precision))
    step, params = exp.step, exp.pool.params
    M, C = exp.pool.num_models, exp.C_pad
    eager = step.init_opt_states(params, M, C)
    before = len(_dispatches(obs.spans.get_recorder().spans()))
    tracked = step.fresh_opt_states(params, C)
    assert len(_dispatches(obs.spans.get_recorder().spans())) == before + 1
    assert jax.tree_util.tree_structure(tracked) == \
        jax.tree_util.tree_structure(eager)
    leaves = jax.tree_util.tree_leaves(tracked)
    assert bool(leaves) == (optimizer == "adam")
    for got, want in zip(leaves, jax.tree_util.tree_leaves(eager)):
        assert got.shape == want.shape and got.shape[:2] == (M, C)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # benchmark/sizing.py's call: the pure body under eval_shape gives the
    # same tree of shapes and dtypes, and is no dispatch: no span, and no
    # entry in the signature table
    seen = {fn: len(sigs) for fn, sigs in step._signatures.items()}
    n_spans = len(obs.spans.get_recorder().spans())
    shapes = jax.eval_shape(lambda p: step.init_opt_states(p, M, C), params)
    assert len(obs.spans.get_recorder().spans()) == n_spans
    assert {fn: len(sigs) for fn, sigs in step._signatures.items()} == seen
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(eager)
    assert [(s.shape, s.dtype) for s in jax.tree_util.tree_leaves(shapes)] \
        == [(l.shape, l.dtype) for l in jax.tree_util.tree_leaves(eager)]


# ----------------------------------------------------------------------
# a two-time-step run of each driver, on one device and on four
DRIVERS = {
    # IFCA steers every round: train_round + acc_matrix, as the cell does
    "per_round": dict(concept_drift_algo="softclusterwin-1",
                      concept_drift_algo_arg="hard-r", chunk_rounds=False),
    "fused": dict(chunk_rounds=True),
    "megastep": dict(chunk_rounds=True, megastep_k=2),
}
ROUND_PROGRAM = {"per_round": "train_round", "fused": "train_iteration_eval",
                 "megastep": "train_megastep"}


class Run:
    def __init__(self, driver, devices):
        from feddrift_tpu.parallel.mesh import make_mesh
        from feddrift_tpu.simulation.runner import Experiment
        self.driver, self.devices = driver, devices
        exp = Experiment(_cfg(**DRIVERS[driver]), mesh=make_mesh(devices))
        self.jit_events = []
        exp.events.add_tap(lambda rec: self.jit_events.append(rec)
                           if rec["kind"].startswith("jit_") else None)
        # the registry is the process's: what this run adds to it
        before = obs.registry().snapshot()
        exp.run()
        after = obs.registry().snapshot()
        self.compiles = {
            k: v - before.get(k, 0) for k, v in after.items()
            if k.startswith("jit_compiles{") and v != before.get(k, 0)}
        self.spans = exp.spans.spans()
        self.exp = exp

    def nested_in_opt_init(self, d):
        return [o for o in self.spans
                if o["name"] == "opt_init" and o["cat"] == "round"
                and o["tid"] == d["tid"] and o["ts"] <= d["ts"] + 0.25
                and d["ts"] + d["dur"] <= o["ts"] + o["dur"] + 0.25]


@pytest.fixture(scope="module", params=[
    (driver, devices) for driver in sorted(DRIVERS) for devices in (1, 4)],
    ids=lambda p: f"{p[0]}-{p[1]}dev")
def run(request):
    return Run(*request.param)


def test_every_program_is_compiled_once(run):
    assert [e for e in run.jit_events if e["kind"] == "jit_recompile"] == []
    assert run.compiles[f'jit_compiles{{fn="{ROUND_PROGRAM[run.driver]}"}}'] \
        == 1
    assert all(v == 1 for v in run.compiles.values()), run.compiles
    # the round program's states have one placement from its first call on
    assert len(run.exp.step._signatures[ROUND_PROGRAM[run.driver]]) == 1


def test_each_time_step_has_one_dispatch_inside_its_opt_init(run):
    mine = _dispatches(run.spans)
    if run.driver == "megastep":
        # the block re-inits inside its scan: the pure body, in the trace
        assert mine == [] and FN not in run.exp.step._signatures
        return
    assert sorted(d["args"]["iteration"] for d in mine) == \
        list(range(ITERATIONS))
    assert [d["args"].get("event") for d in mine] == ["jit_compile", None]
    for d in mine:
        (outer,) = run.nested_in_opt_init(d)
        assert outer["args"]["iteration"] == d["args"]["iteration"]
    assert len([s for s in run.spans if s["name"] == "opt_init"]) == \
        ITERATIONS


def test_the_benchmarks_reader_counts_it(run):
    """``dispatches_per_round`` counts the program's ``dispatch`` spans, so
    a time step reads one more than before this program existed, where the
    hundreds of eager ops it replaces were never counted. (The tiny job of
    ``tests/benchmark/test_span_metrics.py`` pins the older count, 4.5 for
    its two rounds, and reads 5.0 now; that file is the benchmark's.)"""
    from benchmark.metrics import dispatches_per_round
    rec = {"time_steps": [{"t": 1, "rounds": ROUNDS,
                           "segments": {"device_compute": 1.0}}]}
    # per_round: R train_round, R acc_matrix, two evaluations of one (the
    # test half: the re-assignment behind the round has the train half in
    # the store, ISSUE 32), none in begin_iteration (the last evaluation's
    # test half), and the states; fused: the two programs
    due = {"per_round": 2 * ROUNDS + 2 + 0 + 1, "fused": 2, "megastep": 1}
    if run.driver == "megastep":    # the block's one dispatch is step 0's
        rec["time_steps"][0]["t"] = 0
    assert dispatches_per_round.read(rec, None, {}) == \
        pytest.approx(due[run.driver] / ROUNDS)


def test_the_states_are_spread_over_the_clients_axis(run):
    if run.driver == "megastep":        # no states outside the program
        assert not any(FN in k for k in run.compiles)
        return
    states = run.exp.step.fresh_opt_states(run.exp.pool.params,
                                           run.exp.C_pad)
    for leaf in jax.tree_util.tree_leaves(states):
        assert leaf.committed
        assert len(leaf.sharding.device_set) == run.devices
        # no leaf whole on one device: each holds its share of the clients
        shard = leaf.addressable_shards[0].data.shape
        assert shard == (leaf.shape[0], leaf.shape[1] // run.devices,
                         *leaf.shape[2:])


@pytest.mark.parametrize("devices", [1, 4])
def test_a_rollback_makes_one_more(devices, monkeypatch):
    from feddrift_tpu.core.step import TrainStep
    orig, calls = TrainStep.train_round, []

    def poisoned_once(self, *a, **k):
        out = orig(self, *a, **k)
        calls.append(1)
        if len(calls) != 2:
            return out
        p, o, cp, n, losses, *rest = out
        return (p, o, cp, n, jnp.full_like(losses, jnp.nan), *rest)

    monkeypatch.setattr(TrainStep, "train_round", poisoned_once)
    run = Run("per_round", devices)
    assert len(run.exp.events.events("divergence_detected")) == 1
    mine = _dispatches(run.spans)
    assert len(mine) == ITERATIONS + 1
    # time step 0: the boundary's, and the rollback's in round 1
    assert sorted((d["args"]["iteration"], d["args"]["round"])
                  for d in mine) == [(0, 0), (0, 1), (1, ROUNDS)]
    assert all(len(run.nested_in_opt_init(d)) == 1 for d in mine)
    assert [e for e in run.jit_events if e["kind"] == "jit_recompile"] == []
    assert run.compiles['jit_compiles{fn="train_round"}'] == 1
