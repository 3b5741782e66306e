"""The round's spans (ISSUE 25): a segment of ``round_breakdown`` is the
self time of spans recorded where the work happens — the tracked dispatch
wrappers of ``core/step.py``, ``multihost.fetch``, the runner's own
segments — on the per-round, the fused and the megastep path alike."""

import collections
from dataclasses import replace

import pytest

from feddrift_tpu import obs
from feddrift_tpu.obs.spans import SpanRecorder

PATHS = {
    "per_round": dict(chunk_rounds=False),
    # IFCA steers every round, and its drift decision asks the device
    "ifca": dict(concept_drift_algo="softclusterwin-1",
                 concept_drift_algo_arg="hard-r", concept_num=3),
    "fused": dict(chunk_rounds=True),
    "megastep": dict(chunk_rounds=True, megastep_k=2),
}
ITERATIONS, ROUNDS = 4, 3
# the dispatch that carries a round (per_round) or all the rounds of one or
# of K time steps; and the waits of the whole run that are no fetch: the
# block_until_ready after a fused dispatch, and the fused path's host
# snapshot of the pool for the guard's rollback
ROUND_PROGRAM = {"per_round": ("train_round", ITERATIONS * ROUNDS, 0),
                 "ifca": ("train_round", ITERATIONS * ROUNDS, 0),
                 "fused": ("train_iteration_eval", ITERATIONS,
                           2 * ITERATIONS),
                 "megastep": ("train_megastep", ITERATIONS // 2,
                              ITERATIONS // 2)}


EPS = 0.25             # ts and dur are rounded to a tenth of a microsecond


def self_times(spans):
    """Give every span of the stack (``span()``: cat round or phase) its
    self time in microseconds, worked out from the recorded intervals alone:
    the duration less the durations of its direct children on its thread."""
    by_thread = collections.defaultdict(list)
    for s in spans:
        if s["cat"] in ("round", "phase"):
            s["self"] = s["dur"]
            by_thread[s["tid"]].append(s)
    for mine in by_thread.values():
        open_spans = []
        for s in sorted(mine, key=lambda s: (s["ts"], -s["dur"])):
            while open_spans and open_spans[-1]["ts"] + \
                    open_spans[-1]["dur"] <= s["ts"] + EPS:
                open_spans.pop()
            if open_spans:
                open_spans[-1]["self"] -= s["dur"]
            open_spans.append(s)


class Run:
    def __init__(self, path):
        from feddrift_tpu.config import ExperimentConfig
        from feddrift_tpu.simulation.runner import Experiment
        cfg = ExperimentConfig(
            dataset="sea", model="fnn", concept_drift_algo="win-1",
            train_iterations=ITERATIONS, comm_round=ROUNDS, epochs=1,
            sample_num=16, batch_size=8, client_num_in_total=4,
            client_num_per_round=4, concept_num=2, frequency_of_the_test=2,
            report_client=0, divergence_guard=True, trace_sync=False)
        self.path = path
        exp = Experiment(replace(cfg, **PATHS[path]))
        self.breakdowns, self.ends = [], []
        exp.events.add_tap(lambda rec: self.breakdowns.append(rec)
                           if rec["kind"] == "round_breakdown" else None)
        exp.events.add_tap(lambda rec: self.ends.append(rec)
                           if rec["kind"] == "iteration_end" else None)
        # the registry is the process's: what this run adds to it
        before = obs.registry().snapshot().get("multihost_fetches", 0)
        exp.run()
        self.fetches = obs.registry().snapshot()["multihost_fetches"] - before
        self.spans = exp.spans.spans()
        self_times(self.spans)

    def named(self, name):
        return [s for s in self.spans
                if s["name"] == name and s["cat"] == "round"]


@pytest.fixture(scope="module", params=sorted(PATHS))
def run(request):
    return Run(request.param)


def test_the_round_program_is_a_dispatch_span_with_its_name(run):
    fn, calls, _ = ROUND_PROGRAM[run.path]
    mine = [s for s in run.named("dispatch") if s["args"]["fn"] == fn]
    assert len(mine) == calls
    assert all(s["args"]["track_us"] >= 0 for s in mine)
    # the first one traced and compiled inside the span, and no other: the
    # fresh optimizer states are placed as the program returns them
    events = [s["args"].get("event") for s in mine]
    assert events[0] == "jit_compile" and not any(events[1:])
    if run.path in ("per_round", "ifca"):
        assert sorted(s["args"]["round"] for s in mine) == \
            list(range(ITERATIONS * ROUNDS))


def test_every_time_step_has_its_segments_as_spans(run):
    due = ["dispatch", "device_compute", "guard", "writeback", "eval",
           "drift_decision", "round_prep"]
    for name in due:
        spans = run.named(name)
        # a megastep block's dispatch and waits carry its first time step
        steps = range(0, ITERATIONS, 2) if run.path == "megastep" \
            and name in ("dispatch", "device_compute") else range(ITERATIONS)
        assert {s["args"]["iteration"] for s in spans} == set(steps), name
        assert all(isinstance(s["args"]["round"], int) for s in spans), name
    if run.path in ("per_round", "ifca"):
        rounds = range(ITERATIONS * ROUNDS)
        for name in ("device_compute", "guard", "writeback", "round_prep"):
            assert {s["args"]["round"] for s in run.named(name)} == \
                set(rounds), name
        # an eval where it is due: rounds 0 and 2 of each time step
        assert sorted(s["args"]["round"] for s in run.named("eval")) == \
            [g for g in rounds if g % ROUNDS in (0, 2)]


def test_a_device_wait_is_a_fetch_or_an_explicit_block(run):
    waits = run.named("device_compute")
    assert len(waits) == run.fetches + ROUND_PROGRAM[run.path][2]
    # one thing recorded once: no interval twice under two names
    stamps = collections.Counter((s["tid"], s["ts"], s["dur"])
                                 for s in run.spans if s["dur"] > 0)
    assert max(stamps.values()) == 1
    assert not {"eval", "cluster", "cohort"} & \
        {s["name"] for s in run.spans if s["cat"] == "phase"}


def test_spans_of_a_thread_nest_or_are_disjoint(run):
    by_thread = collections.defaultdict(list)
    for s in run.spans:
        if s["cat"] in ("round", "phase"):
            by_thread[s["tid"]].append(s)
    eps = EPS
    for spans in by_thread.values():
        open_ends = []
        for s in sorted(spans, key=lambda s: (s["ts"], -s["dur"])):
            while open_ends and open_ends[-1] <= s["ts"] + eps:
                open_ends.pop()
            end = s["ts"] + s["dur"]
            assert not open_ends or end <= open_ends[-1] + eps, s
            open_ends.append(end)
    if run.path != "megastep":      # its iteration spans are shares of a block
        for it in (s for s in run.spans if s["name"] == "iteration"):
            t = it["args"]["iteration"]
            inside = [s for s in run.spans if s["cat"] == "round"
                      and s["args"]["iteration"] == t]
            assert inside and all(
                it["ts"] - eps <= s["ts"]
                and s["ts"] + s["dur"] <= it["ts"] + it["dur"] + eps
                for s in inside)


def test_self_times_and_the_gap_make_the_wall(run):
    assert len(run.breakdowns) == ITERATIONS
    walls = sum(b["wall_s"] for b in run.breakdowns)
    segments = collections.Counter()
    for b in run.breakdowns:
        assert sum(b["segments"].values()) == pytest.approx(b["wall_s"],
                                                            abs=2e-5)
        segments.update(b["segments"])
    # a span's self time goes to the segment of its name; inside the drift
    # decision, to that one
    decisions = run.named("drift_decision")
    self_s = collections.Counter()
    for s in run.spans:
        if s["cat"] == "round":
            owned = any(d is not s and d["ts"] <= s["ts"]
                        and s["ts"] + s["dur"] <= d["ts"] + d["dur"] + 0.25
                        for d in decisions)
            self_s["drift_decision" if owned else s["name"]] += \
                s["self"] * 1e-6
    assert set(self_s) == set(segments) - {"dispatch_gap"}
    for name, total in self_s.items():
        assert segments[name] == pytest.approx(total, abs=5e-5), name
    assert sum(self_s.values()) + segments["dispatch_gap"] == \
        pytest.approx(walls, abs=2e-4)
    assert segments["drift_decision"] == pytest.approx(
        sum(d["dur"] for d in decisions) * 1e-6, abs=5e-5)
    if run.path == "ifca":
        # acc_matrix_at in begin_iteration: time step 0 dispatches and waits
        # inside its decision; from then on the matrix is the last
        # evaluation's test half, served from the store (ISSUE 32), and a
        # decision has no span under it
        begins = [d for d in decisions if "acc_reused" in d["args"]]
        assert [(d["args"]["iteration"], d["args"]["acc_reused"],
                 d["args"]["acc_computed"]) for d in begins] == \
            [(0, 1, 1)] + [(t, 1, 0) for t in range(1, ITERATIONS)]
        assert begins[0]["self"] < begins[0]["dur"] - 1.0
        assert all(d["self"] == d["dur"] for d in begins[1:])


def test_the_phases_are_fed_from_the_spans_that_cover_them(run):
    """eval, cluster and cohort are no spans of their own any more: the
    eval and drift_decision spans feed the tracer's totals."""
    phases = collections.Counter()
    counts = collections.Counter()
    for i, e in enumerate(run.ends):
        for name, p in e["phases"].items():
            phases[name] += p["total_s"]
            # a megastep block's two time steps halve its seconds between
            # them and both carry its counts
            if run.path != "megastep" or i % 2 == 0:
                counts[name] += p["count"]
    for phase, name in (("eval", "eval"), ("cluster", "drift_decision")):
        spans = run.named(name)
        assert counts[phase] == len(spans)
        assert phases[phase] == pytest.approx(
            sum(s["dur"] for s in spans) * 1e-6, abs=1e-3)
    assert counts["train_round"] == ROUND_PROGRAM[run.path][1]


def test_every_round_is_profiled_without_trace_sync(run):
    for b in run.breakdowns:
        assert b["profiled_rounds"] == b["rounds"] == ROUNDS
        assert b["segments"]["device_compute"] > 0
        assert 0.0 <= b["host_overhead_frac"] < 1.0


def test_a_wait_nested_in_eval_is_counted_once(run):
    evals = run.named("eval")
    waits = run.named("device_compute")

    def inside(w, e):
        return e["ts"] <= w["ts"] and \
            w["ts"] + w["dur"] <= e["ts"] + e["dur"] + 0.25
    nested = 0
    for e in evals:
        children = [s for s in run.spans if s["cat"] == "round"
                    and s is not e and inside(s, e)]
        nested += sum(c["name"] == "device_compute" for c in children)
        assert e["self"] == pytest.approx(
            e["dur"] - sum(c["dur"] for c in children), abs=0.5)
    # the fused paths fetch their eval buffers inside the eval span, the
    # per-round path fetches acc_matrix's result there: one fetch for the
    # halves the store does not hold (under IFCA the test half alone)
    assert nested == len(evals) if run.path != "megastep" else nested == 0
    assert sum(b["segments"]["eval"] for b in run.breakdowns) == \
        pytest.approx(sum(e["self"] for e in evals) * 1e-6, abs=5e-5)
    assert all(w["self"] == w["dur"] for w in waits)


# ----------------------------------------------------------------------
# the self-time stack, on a clock the test sets
class Clock:
    """In the place of ``obs.spans._now``: every reading is set by hand."""

    def __init__(self, monkeypatch):
        from feddrift_tpu.obs import spans
        self.t = 0.0
        monkeypatch.setattr(spans, "_now", lambda: self.t)


@pytest.fixture()
def clock(monkeypatch):
    return Clock(monkeypatch)


def hooked():
    """A recorder whose hook notes (name, duration, self time)."""
    rec, closed = SpanRecorder(None), []
    rec.set_hook(lambda name, cat, dur, self_s:
                 closed.append((name, dur, self_s)))
    return rec, closed


class TestSelfTime:
    def test_a_child_is_taken_off_its_parent(self, clock):
        rec, closed = hooked()
        clock.t = 10.0
        with rec.span("outer") as outer:
            clock.t = 11.0
            with rec.span("inner"):
                clock.t = 13.0
            clock.t = 15.0
        assert closed == [("inner", 2.0, 2.0), ("outer", 5.0, 3.0)]
        assert outer.dur == 5.0
        assert [(s["name"], s["dur"]) for s in rec.spans()] == \
            [("inner", 2e6), ("outer", 5e6)]

    def test_siblings_add_up_and_grandchildren_count_once(self, clock):
        rec, closed = hooked()
        with rec.span("a"):
            clock.t = 1.0
            with rec.span("b"):
                clock.t = 2.0
                with rec.span("c"):
                    clock.t = 3.0
                clock.t = 5.0
            clock.t = 6.0
            with rec.span("d"):
                clock.t = 8.0
            clock.t = 10.0
        # a's children are b (4 s, which holds c) and d (2 s)
        assert closed == [("c", 1.0, 1.0), ("b", 4.0, 3.0),
                          ("d", 2.0, 2.0), ("a", 10.0, 4.0)]

    def test_a_span_left_open_goes_with_its_parent(self, clock):
        rec, closed = hooked()
        with rec.span("outer"):
            clock.t = 1.0
            rec.span("lost").__enter__()    # never closed (an exception path)
            clock.t = 4.0
        clock.t = 5.0
        with rec.span("again"):
            clock.t = 6.0
        assert closed == [("outer", 4.0, 4.0), ("again", 1.0, 1.0)]

    def test_threads_keep_their_own_stacks_and_hooks(self, clock):
        import threading
        rec, closed = hooked()
        theirs = []

        def other():
            rec.set_hook(lambda name, cat, dur, self_s:
                         theirs.append((name, dur, self_s)))
            with rec.span("theirs"):
                pass
        with rec.span("mine"):
            clock.t = 1.0
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=10)
            clock.t = 4.0
        assert not th.is_alive() and theirs == [("theirs", 0.0, 0.0)]
        assert closed == [("mine", 4.0, 4.0)]

    def test_span_records_the_context_and_late_args(self):
        rec, closed = hooked()
        rec.set_context(iteration=3, round=7)
        with rec.span("outer", cat="round") as sp:
            with rec.span("inner", cat="round", round=8):
                pass
            sp.set(late=1)
        inner, outer = rec.spans()
        assert inner["args"] == {"iteration": 3, "round": 8}
        assert outer["args"] == {"iteration": 3, "round": 7, "late": 1}
        assert "self" not in outer          # the hook's, not the record's
        (_, inner_dur, _), (_, dur, self_s) = closed
        assert self_s == pytest.approx(dur - inner_dur)
        assert dur == pytest.approx(outer["dur"] * 1e-6, abs=1e-6)
        rec.set_context(round=None)
        with rec.span("later"):
            pass
        assert rec.spans("later")[0]["args"] == {"iteration": 3}
        rec.set_hook(None)
        with rec.span("unhooked"):
            pass
        assert len(closed) == 3

    def test_the_ring_says_what_it_dropped(self):
        rec = SpanRecorder(None)
        rec.ring = collections.deque(maxlen=3)
        for i in range(5):
            rec.record("dispatch", float(i), 0.5, cat="round")
            assert rec.dropped == max(i - 2, 0)
        assert [s["ts"] for s in rec.spans()] == [2e6, 3e6, 4e6]

    def test_a_disabled_recorder_measures_nothing_and_annotates_nothing(self):
        entered = []

        class Ann:
            def __init__(self, name):
                entered.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return None
        rec = SpanRecorder(None, enabled=False, annotate=Ann)
        with rec.span("x") as sp:
            sp.set(a=1)
        assert rec.spans() == [] and entered == []
        rec.enabled = True
        with rec.span("y"):
            pass
        assert entered == ["y"] and [s["name"] for s in rec.spans()] == ["y"]

    def test_the_sink_is_buffered_until_an_iteration_or_flush(self, tmp_path):
        path = tmp_path / "spans.jsonl"
        rec = SpanRecorder(str(path))
        rec.record("dispatch", 1.0, 0.5, cat="round")
        assert path.read_text() == ""               # the ring is the record
        assert len(rec.spans()) == 1
        rec.record("iteration", 1.0, 2.0, cat="runner", iteration=0)
        assert len(path.read_text().splitlines()) == 2
        rec.record("dispatch", 3.0, 0.5, cat="round")
        rec.flush()
        assert len(path.read_text().splitlines()) == 3
        rec.record("dispatch", 4.0, 0.5, cat="round")
        rec.close()
        assert len(path.read_text().splitlines()) == 4


def test_spans_module_imports_without_jax():
    """critical_path, incident and report --trace read spans before (or
    without) JAX; the runner hands the recorder the annotation factory."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, feddrift_tpu.obs.spans; "
         "sys.exit('jax' in sys.modules)"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
