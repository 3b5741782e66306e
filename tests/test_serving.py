"""Cluster-routed inference engine tests (platform/serving.py).

The serving read path has four load-bearing invariants, each pinned here:

- routing equals the trainer's ground truth (``ClientRegistry.cluster``
  with ``assign_hist`` fallback) — a client is answered by ITS cluster
  model, never slot 0;
- a coalesced mixed-cluster micro-batch is BITWISE identical to serving
  each request alone through ``pool.apply`` — batching is a pure
  throughput transform, not a numerics change;
- bucketed admission never recompiles at steady state: every bucket is
  compiled once in warm-up, then arbitrary batch sizes replay known
  signatures (the PR 1 compile detector is the witness);
- hot swaps under concurrent load are atomic: every answer is consistent
  with exactly ONE published generation (no torn params, no
  params/routing skew).
"""

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.core.pool import ModelPool
from feddrift_tpu.data.registry import make_dataset
from feddrift_tpu.models import create_model
from feddrift_tpu.platform.serving import (
    DeadlineExceededError, EngineOverloaded, EngineStopped,
    InferenceEngine, MalformedRequestError, RoutingTable,
    UnknownClientError)


def _pool(M=3, identical=False):
    cfg = ExperimentConfig(dataset="sea", train_iterations=2, sample_num=16)
    ds = make_dataset(cfg)
    mod = create_model("fnn", ds, cfg)
    return ModelPool.create(mod, jnp.zeros((2, 3)), M, seed=7,
                            identical=identical)


def _engine(pool, table, **kw):
    kw.setdefault("buckets", (1, 2, 4))
    kw.setdefault("max_wait_s", 0.002)
    return InferenceEngine(pool, RoutingTable(table), **kw)


class TestRoutingTable:
    def test_from_registry_matches_ground_truth(self):
        from feddrift_tpu.platform.registry import ClientRegistry
        reg = ClientRegistry(population=5, num_steps=4)
        # client 0/1: live assignment wins
        reg.cluster[0], reg.cluster[1] = 2, 0
        reg.assign_hist[0] = [0, 0, 1, 2]
        # client 2: live assignment cleared -> last known history entry
        reg.cluster[2] = -1
        reg.assign_hist[2] = [1, 2, -1, -1]
        # client 3: never assigned anywhere -> unroutable
        # client 4: history only
        reg.assign_hist[4] = [-1, 0, -1, -1]
        rt = RoutingTable.from_registry(reg)
        assert rt.route(0) == 2 and rt.route(1) == 0
        assert rt.route(2) == 2        # last non-negative hist entry
        assert rt.route(4) == 0
        with pytest.raises(UnknownClientError):
            rt.route(3)

    def test_out_of_population(self):
        rt = RoutingTable([0, 1])
        with pytest.raises(UnknownClientError):
            rt.route(2)
        with pytest.raises(UnknownClientError):
            rt.route(-1)


class TestBatchParity:
    def test_mixed_cluster_batch_bitwise_equals_per_request(self):
        """Drives the batcher itself (``_serve_batch``): which requests share
        a micro-batch, in which rows of which bucket, is written here. Eight
        submitter threads used to decide it, and under load one of them
        could land alone: the bucket of one is the one forward program that
        is not bitwise ``pool.apply`` on the CPU backend (an ulp, on three
        of these eight inputs), and a lone request is no coalesced batch."""
        from feddrift_tpu.obs import spans
        from feddrift_tpu.platform.serving import _Request
        pool = _pool(M=3)
        table = [0, 1, 2, 1, 0, 2, 2, 1]
        eng = _engine(pool, table)      # no dispatcher thread: batches below
        try:
            rng = np.random.RandomState(0)
            xs = rng.standard_normal((8, 3)).astype(np.float32)
            expect = [np.asarray(pool.apply(pool.slot(table[c]),
                                            xs[c][None])[0])
                      for c in range(8)]
            # two full buckets of 4, the same eight in another order, a
            # bucket of 2, and 3 requests padded into the bucket of 4
            for batch in ([0, 1, 2, 3], [4, 5, 6, 7], [7, 2, 4, 1],
                          [3, 6, 0, 5], [3, 4], [7, 0], [2, 7, 3], [4, 1, 6]):
                reqs = [_Request(c, xs[c], spans.new_trace(), rid)
                        for rid, c in enumerate(batch)]
                eng._serve_batch(reqs)
                for r in reqs:
                    assert r.done.is_set() and r.error is None
                    assert r.result.model == table[r.client]
                    np.testing.assert_array_equal(r.result.logits,
                                                  expect[r.client])
        finally:
            eng.close()


class TestZeroRecompiles:
    def test_bucketed_traffic_never_recompiles(self):
        from feddrift_tpu import obs

        def serve_counts():
            snap = obs.registry().snapshot()
            comp = sum(v for k, v in snap.items()
                       if k.startswith('jit_compiles{fn="serve_forward'))
            rec = sum(v for k, v in snap.items()
                      if k.startswith('jit_recompiles{fn="serve_forward'))
            return comp, rec

        pool = _pool(M=2)
        eng = _engine(pool, [0, 1, 0, 1, 0, 1], buckets=(1, 2, 4)).start()
        try:
            comp0, rec0 = serve_counts()
            eng.warmup()
            comp1, rec1 = serve_counts()
            assert comp1 - comp0 == 3       # one program per bucket
            assert rec1 == rec0
            # mixed batch sizes (1..6 concurrent) all pad to known buckets
            rng = np.random.RandomState(1)
            for n in (1, 2, 3, 4, 5, 6):
                with ThreadPoolExecutor(max_workers=n) as ex:
                    futs = [ex.submit(eng.submit, c % 6,
                                      rng.standard_normal(3)
                                         .astype(np.float32))
                            for c in range(n)]
                    for f in futs:
                        f.result(timeout=30)
            # a swap must replay the same signatures too (committed-ness
            # of the placed params matches warm-up)
            eng.swap(params=jax.tree_util.tree_map(lambda p: p + 1.0,
                                                   pool.params))
            eng.submit(0, np.zeros(3, np.float32))
            comp2, rec2 = serve_counts()
            assert comp2 == comp1, "steady state compiled a new program"
            assert rec2 == rec1, "steady state recompiled"
        finally:
            eng.close()


class TestHotSwap:
    def test_no_torn_params_under_concurrent_load(self):
        pool = _pool(M=2)
        table = [0, 1, 0, 1]
        eng = _engine(pool, table).start()
        try:
            eng.warmup()
            params_a = pool.params
            params_b = jax.tree_util.tree_map(lambda p: p + 1.0, params_a)
            x = np.ones(3, np.float32)
            # expected logits per (tag, model) — v1 serves A
            expect = {}
            for tag, params in (("A", params_a), ("B", params_b)):
                for m in range(2):
                    one = jax.tree_util.tree_map(lambda p: p[m], params)
                    expect[tag, m] = np.asarray(
                        pool.apply(one, x[None])[0])
            tag_of = {1: "A"}
            stop = threading.Event()

            def swapper():
                flip = 0
                while not stop.is_set():
                    flip += 1
                    p = params_b if flip % 2 else params_a
                    v = eng.swap(params=p, reason="test")
                    tag_of[v] = "B" if flip % 2 else "A"

            th = threading.Thread(target=swapper, daemon=True)
            th.start()
            try:
                with ThreadPoolExecutor(max_workers=8) as ex:
                    futs = [ex.submit(eng.submit, c % 4, x)
                            for c in range(200)]
                    results = [f.result(timeout=30) for f in futs]
            finally:
                stop.set()
                th.join(timeout=10)
            for c, r in enumerate(results):
                assert r.model == table[c % 4]
                tag = tag_of[r.version]
                np.testing.assert_array_equal(
                    r.logits, expect[tag, r.model],
                    err_msg=f"torn read: version {r.version} ({tag}) "
                            f"model {r.model}")
        finally:
            eng.close()

    def test_merge_reroutes_to_surviving_lineage(self):
        pool = _pool(M=3)
        eng = _engine(pool, [0, 1, 2]).start()
        try:
            eng.warmup()
            v = eng.apply_cluster_event(
                {"kind": "cluster_merge", "base": 0, "merged": 1})
            assert v == 2
            assert eng.submit(1, np.zeros(3, np.float32)).model == 0
            assert eng.submit(2, np.zeros(3, np.float32)).model == 2
        finally:
            eng.close()

    def test_split_moves_clients_and_copies_parent_slot(self):
        pool = _pool(M=3)
        eng = _engine(pool, [0, 0, 0]).start()
        try:
            eng.warmup()
            eng.apply_cluster_event(
                {"kind": "cluster_split", "model": 0, "new_model": 2,
                 "clients_kept": [0], "clients_moved": [1, 2]})
            x = np.ones(3, np.float32)
            r_kept, r_moved = eng.submit(0, x), eng.submit(1, x)
            assert r_kept.model == 0 and r_moved.model == 2
            # child slot inherits the parent's params until retrained
            np.testing.assert_array_equal(r_kept.logits, r_moved.logits)
        finally:
            eng.close()

    def test_delete_makes_clients_unroutable(self):
        pool = _pool(M=2)
        eng = _engine(pool, [0, 1]).start()
        try:
            eng.warmup()
            eng.apply_cluster_event(
                {"kind": "cluster_delete", "model": 1, "reason": "test"})
            with pytest.raises(UnknownClientError):
                eng.submit(1, np.zeros(3, np.float32))
            assert eng.submit(0, np.zeros(3, np.float32)).model == 0
        finally:
            eng.close()

    def test_broker_feed_applies_events(self):
        from feddrift_tpu.comm.pubsub import Broker
        pool = _pool(M=2)
        eng = _engine(pool, [0, 0]).start()
        broker = Broker()
        try:
            eng.warmup()
            eng.attach_broker(broker, topic="serve/cluster")
            broker.publish("serve/cluster", json.dumps(
                {"kind": "cluster_assign", "assignment": [1, 1]}))
            deadline = 50
            while eng.version < 2 and deadline:
                threading.Event().wait(0.05)
                deadline -= 1
            assert eng.version >= 2
            assert eng.submit(0, np.zeros(3, np.float32)).model == 1
        finally:
            eng.close()


class TestClusterEventSequences:
    """Multi-event lifecycles over ``apply_cluster_event``: the routing
    and params state must stay coherent across chained rewires, not just
    after a single one."""

    def test_split_then_merge_same_slot_roundtrips(self):
        pool = _pool(M=3)
        eng = _engine(pool, [0, 0, 0]).start()
        try:
            eng.warmup()
            eng.apply_cluster_event(
                {"kind": "cluster_split", "model": 0, "new_model": 2,
                 "clients_kept": [0], "clients_moved": [1, 2]})
            assert eng.submit(1, np.zeros(3, np.float32)).model == 2
            # the split's child is reabsorbed into its parent slot
            eng.apply_cluster_event(
                {"kind": "cluster_merge", "base": 0, "merged": 2})
            x = np.ones(3, np.float32)
            for c in range(3):
                r = eng.submit(c, x)
                assert r.model == 0
                expect = pool.apply(pool.slot(0), x[None])[0]
                np.testing.assert_array_equal(r.logits,
                                              np.asarray(expect))
        finally:
            eng.close()

    def test_delete_under_live_load_degrades_to_unroutable(self):
        pool = _pool(M=2)
        eng = _engine(pool, [0, 1, 1, 1]).start()
        try:
            eng.warmup()
            x = np.zeros(3, np.float32)
            outcomes = []

            def hammer(c):
                for _ in range(40):
                    try:
                        outcomes.append(("ok", eng.submit(c, x).model))
                    except UnknownClientError:
                        outcomes.append(("unroutable", None))

            with ThreadPoolExecutor(max_workers=3) as ex:
                futs = [ex.submit(hammer, c) for c in (1, 2, 3)]
                eng.apply_cluster_event(
                    {"kind": "cluster_delete", "model": 1,
                     "reason": "test"})
                for f in futs:
                    f.result(timeout=30)
            # every in-flight request either answered by the still-live
            # generation's model 1 or cleanly refused — never crashed,
            # never misrouted to another slot
            assert all(m == 1 for kind, m in outcomes if kind == "ok")
            # after the swap the clients are durably unroutable...
            with pytest.raises(UnknownClientError):
                eng.submit(2, x)
            # ...and untouched clients keep being served
            assert eng.submit(0, x).model == 0
        finally:
            eng.close()

    def test_event_replay_after_broker_reconnect(self):
        from feddrift_tpu.comm.netbroker import (NetworkBroker,
                                                 NetworkBrokerClient)
        from feddrift_tpu.resilience import (ReconnectingBrokerClient,
                                             RetryPolicy)
        import time as _time

        broker = NetworkBroker()
        host, port = broker.host, broker.port
        cli = ReconnectingBrokerClient(
            lambda: NetworkBrokerClient(host, port),
            retry=RetryPolicy(base_delay=0.05, max_delay=0.2,
                              max_attempts=60, deadline_s=30, seed=0),
            ack_timeout=0.2)
        pool = _pool(M=2)
        eng = _engine(pool, [0, 0]).start()
        broker2 = None
        try:
            eng.warmup()
            eng.attach_broker(cli, topic="serve/cluster")
            cli.publish("serve/cluster", json.dumps(
                {"kind": "cluster_assign", "assignment": [1, 1]}))
            deadline = _time.monotonic() + 30
            while eng.version < 2 and _time.monotonic() < deadline:
                _time.sleep(0.05)
            assert eng.submit(0, np.zeros(3, np.float32)).model == 1

            broker.close()                   # broker dies mid-stream
            _time.sleep(0.2)
            cli.publish("serve/cluster", json.dumps(
                {"kind": "cluster_assign", "assignment": [0, 0]}))
            broker2 = NetworkBroker(host=host, port=port)  # same address
            # the reconnect wrapper replays the subscription AND the
            # unconfirmed publish; the engine applies it on arrival
            deadline = _time.monotonic() + 60
            while _time.monotonic() < deadline:
                try:
                    if eng.submit(0, np.zeros(3, np.float32)).model == 0:
                        break
                except UnknownClientError:
                    pass
                _time.sleep(0.1)
            assert eng.submit(0, np.zeros(3, np.float32)).model == 0
            assert cli.reconnects >= 1
        finally:
            cli.close()
            eng.close()
            broker.close()
            if broker2 is not None:
                broker2.close()


class TestErrorPaths:
    def test_unknown_client(self):
        eng = _engine(_pool(M=2), [0, -1]).start()
        try:
            eng.warmup()
            with pytest.raises(UnknownClientError):
                eng.submit(7, np.zeros(3, np.float32))   # out of population
            with pytest.raises(UnknownClientError):
                eng.submit(1, np.zeros(3, np.float32))   # never assigned
        finally:
            eng.close()

    def test_malformed_request(self):
        eng = _engine(_pool(M=2), [0, 1]).start()
        try:
            with pytest.raises(MalformedRequestError):
                eng.submit("not-an-int", np.zeros(3, np.float32))
            with pytest.raises(MalformedRequestError):
                eng.submit(0, np.zeros(5, np.float32))   # wrong geometry
            with pytest.raises(MalformedRequestError):
                eng.submit(0, [["x", "y", "z"]])         # non-numeric body
        finally:
            eng.close()

    def test_submit_before_start(self):
        eng = _engine(_pool(M=2), [0, 1])
        with pytest.raises(RuntimeError):
            eng.submit(0, np.zeros(3, np.float32))


class TestLatencyExemplar:
    def test_p99_exemplar_rearms_past_max_age(self):
        # an ancient outlier must not pin the exemplar slot forever: past
        # exemplar_max_age_s the holder is replaced by the next request
        eng = _engine(_pool(M=2), [0, 1]).start()
        try:
            eng.warmup()
            eng._lat_p99_exemplar = (999.0, "ancient", 0, 0.0)
            eng.exemplar_max_age_s = 0.0     # everything is stale
            eng.submit(0, np.zeros(3, np.float32))
            lat, trace_id, _client, _armed = eng._lat_p99_exemplar
            assert lat < 999.0 and trace_id != "ancient"
        finally:
            eng.close()

    def test_reset_clears_exemplar(self):
        eng = _engine(_pool(M=2), [0, 1]).start()
        try:
            eng.warmup()
            eng.submit(0, np.zeros(3, np.float32))
            assert eng._lat_p99_exemplar[0] > 0.0
            eng.reset_latency_stats()
            assert eng._lat_p99_exemplar == (0.0, None, None, 0.0)
        finally:
            eng.close()


class TestShutdownAndAbandonment:
    """The two queue-lifecycle bugfixes: stop() must FAIL queued requests
    (explicitly, so a failover layer can react), and a timed-out caller's
    request must never reach the forward program."""

    @staticmethod
    def _stub_dispatcher(eng):
        # a finished-but-started thread passes the "engine started" check
        # without ever draining the queue — requests sit exactly where a
        # wedged dispatcher would leave them
        t = threading.Thread(target=lambda: None)
        t.start()
        t.join()
        eng._thread = t

    def test_close_fails_queued_requests_with_engine_stopped(self):
        eng = _engine(_pool(M=2), [0, 1])
        self._stub_dispatcher(eng)
        caught = {}

        def call():
            try:
                eng.submit(0, np.zeros(3, np.float32), timeout=10.0)
            except BaseException as e:       # noqa: BLE001 — the assert
                caught["e"] = e

        th = threading.Thread(target=call)
        th.start()
        deadline = time.perf_counter() + 5.0
        while not eng._queue and time.perf_counter() < deadline:
            time.sleep(0.005)
        assert eng._queue, "request never queued"
        eng.close()
        th.join(timeout=5)
        # the caller got the EXPLICIT shutdown error, not its own timeout
        assert isinstance(caught.get("e"), EngineStopped)
        # and post-stop submits fast-fail the same way
        with pytest.raises(EngineStopped):
            eng.submit(0, np.zeros(3, np.float32))

    def test_timed_out_caller_is_skipped_at_batch_formation(self):
        eng = _engine(_pool(M=2), [0, 1])
        # unnamed engines share the process-global registry counters:
        # assert DELTAS, not absolutes
        abandoned0 = int(eng._abandoned.value)
        served0 = int(eng._served.value)
        self._stub_dispatcher(eng)
        with pytest.raises(TimeoutError):
            eng.submit(0, np.zeros(3, np.float32), timeout=0.05)
        assert len(eng._queue) == 1
        assert eng._queue[0].abandoned       # marked, still queued
        # now let a REAL dispatcher at the queue: the abandoned request
        # must be skipped (counted), never served
        eng._thread = None
        eng.start()
        try:
            deadline = time.perf_counter() + 10.0
            while int(eng._abandoned.value) < abandoned0 + 1 \
                    and time.perf_counter() < deadline:
                time.sleep(0.01)
            assert int(eng._abandoned.value) == abandoned0 + 1
            assert int(eng._served.value) == served0
            # the engine is healthy for live callers afterwards
            assert eng.submit(1, np.zeros(3, np.float32)).model == 1
        finally:
            eng.close()

    def test_expired_deadline_dropped_at_batch_formation(self):
        from feddrift_tpu.obs import spans
        from feddrift_tpu.platform.serving import _Request
        eng = _engine(_pool(M=2), [0, 1]).start()
        expired0 = int(eng._expired.value)
        try:
            eng.warmup()
            req = _Request(0, np.zeros(3, np.float32), spans.new_trace(),
                           rid=10**9, deadline=time.perf_counter() - 1.0)
            with eng._cond:
                eng._queue.append(req)
                eng._cond.notify()
            assert req.done.wait(10.0)
            assert isinstance(req.error, DeadlineExceededError)
            assert req.result is None        # never reached the forward
            assert int(eng._expired.value) == expired0 + 1
        finally:
            eng.close()

    def test_bounded_queue_sheds_with_retry_hint(self):
        eng = _engine(_pool(M=2), [0, 1], max_queue=2)
        self._stub_dispatcher(eng)
        callers = []
        for _ in range(2):
            th = threading.Thread(
                target=lambda: pytest.raises(
                    EngineStopped,
                    eng.submit, 0, np.zeros(3, np.float32), 10.0))
            th.start()
            callers.append(th)
        deadline = time.perf_counter() + 5.0
        while len(eng._queue) < 2 and time.perf_counter() < deadline:
            time.sleep(0.005)
        with pytest.raises(EngineOverloaded) as ei:
            eng.submit(0, np.zeros(3, np.float32))
        assert ei.value.retry_after_s > 0
        eng.close()                          # releases the queued callers
        for th in callers:
            th.join(timeout=5)
