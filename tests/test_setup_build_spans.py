"""The experiment's build on the span recorder: the recorder is armed first
in ``Experiment.__init__``, the build is one ``cat="setup"`` span
``experiment_build`` with four children, and every program JAX builds lies,
as ``trace`` / ``lower`` / ``compile`` spans, inside the span that caused
it. None of it moves a segment of ``round_breakdown``."""

import collections

import pytest

from feddrift_tpu.config import ExperimentConfig

CHILDREN = ["data", "pool_init", "data_to_device", "algorithm"]
ROUNDS = 2


@pytest.fixture(scope="module")
def run():
    """IFCA ``hard-r`` on the per-round path at a tiny size (the cells'
    traffic), three time steps."""
    from feddrift_tpu.simulation.runner import Experiment
    cfg = ExperimentConfig(
        dataset="sea", model="fnn", concept_drift_algo="softclusterwin-1",
        concept_drift_algo_arg="hard-r", chunk_rounds=True,
        train_iterations=3, comm_round=ROUNDS, epochs=1, sample_num=16,
        batch_size=8, client_num_in_total=4, client_num_per_round=4,
        concept_num=2, frequency_of_the_test=1, report_client=0,
        trace_sync=False)
    exp = Experiment(cfg)
    breakdowns = []
    for t in range(3):
        exp.run_iteration(t)
        breakdowns.append(exp.last_round_breakdown)
    return exp, exp.spans.spans(), breakdowns


def inside(span, outer, slack_us=0.2):
    """``span`` lies in ``outer``'s interval, to the recorder's rounding."""
    return (outer["ts"] - slack_us <= span["ts"]
            and span["ts"] + span["dur"]
            <= outer["ts"] + outer["dur"] + slack_us)


def test_the_build_is_one_span_whose_four_children_partition_it(run):
    _, ring, _ = run
    setup = [s for s in ring if s["cat"] == "setup"]
    assert [s["name"] for s in setup] == CHILDREN + ["experiment_build"]
    build = setup[-1]
    children = setup[:-1]
    assert all(inside(c, build) for c in children)
    # one after the other, in the order the build runs them
    for a, b in zip(children, children[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    assert sum(c["dur"] for c in children) <= build["dur"]
    # the recorder was armed before anything was built: the data's span
    # is the first of the experiment's own spans in the ring
    assert ring.index(children[0]) == min(
        i for i, s in enumerate(ring) if s["cat"] != "compile")
    # the pool's init programs are built inside pool_init
    made = [s for s in ring if s["cat"] == "compile"
            and s["args"]["caused_by"] == "pool_init"]
    assert made and all(inside(s, children[1]) for s in made)


def test_a_compile_lies_inside_the_dispatch_that_caused_it(run):
    _, ring, _ = run
    first = {}
    for s in ring:
        if s["name"] == "dispatch" and s["cat"] == "round":
            first.setdefault(s["args"]["fn"], s)
    compiles = [s for s in ring if s["cat"] == "compile"
                and s["name"] == "compile"
                and s["args"]["caused_by"] == "dispatch/train_round"]
    assert compiles
    dispatch = first["train_round"]
    assert dispatch["args"]["event"] == "jit_compile"
    for s in compiles:
        assert inside(s, dispatch)
        assert s["args"]["iteration"] == dispatch["args"]["iteration"] == 0
        assert s["args"]["round"] == dispatch["args"]["round"]


def test_the_round_breakdown_is_the_one_it_was(run):
    """The segment keys of each time step, and its ``dispatch`` and
    ``device_compute`` spans, as the tree before the build was spanned
    gives them (per time step: 8 and 7, then 7 and 6: the second time step
    on serves one more evaluation from the store)."""
    _, ring, breakdowns = run
    keys = ["device_compute", "dispatch", "dispatch_gap", "drift_decision",
            "eval", "guard", "opt_init", "round_prep", "writeback"]
    for t, bd in enumerate(breakdowns):
        assert sorted(bd["segments"]) == keys
        assert bd["rounds"] == ROUNDS and "profiled_rounds" not in bd
        count = collections.Counter(
            s["name"] for s in ring if s["cat"] == "round"
            and s["args"].get("iteration") == t)
        assert (count["dispatch"], count["device_compute"]) == \
            ((8, 7) if t == 0 else (7, 6))
