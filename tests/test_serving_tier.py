"""Serving tier (ISSUE 9): router semantics, continuous batching,
zero-downtime hot-swap, the child pool, and the persistent compile
cache's keying.

The expensive chaos e2e (subprocess replicas, SIGKILL + rolling swap +
cache-hit respawn) lives in scripts/serving_smoke.py (check.sh); these
tests pin the same semantics fast: stub HTTP replicas for router
behavior (no jax in the backend), the toy deploy net for real-engine
swaps, stub engines for batch-composition proofs."""

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest
import jax

from sparknet_tpu.serve.batcher import MicroBatcher
from sparknet_tpu.serve.compile_cache import cache_entries, net_fingerprint
from sparknet_tpu.serve.engine import InferenceEngine
from sparknet_tpu.serve.metrics import ServeMetrics
from sparknet_tpu.serve.router import Router
from sparknet_tpu.serve.server import InferenceServer

TOY_DEPLOY = """
name: "toy"
input: "data"
input_shape { dim: 1 dim: 3 dim: 8 dim: 8 }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
        inner_product_param { num_output: 5
          weight_filler { type: "gaussian" std: 0.2 } } }
layer { name: "prob" type: "Softmax" bottom: "ip1" top: "prob" }
"""


def toy_net(seed=7):
    from sparknet_tpu.nets.xlanet import XLANet
    from sparknet_tpu.proto import caffe_pb

    net = XLANet(caffe_pb.load_net(TOY_DEPLOY, is_path=False), "TEST")
    params, state = net.init(jax.random.PRNGKey(seed))
    return net, params, state


def toy_rows(n, seed=0):
    return (
        np.random.default_rng(seed)
        .normal(size=(n, 8, 8, 3))
        .astype(np.float32)
    )


# ------------------------------------------------------- stub replicas
class _StubReplica:
    """A scriptable replica: echoes the first row value back as the
    top-1 index, so the test can match answers to requests exactly.
    ``die_next`` drops one /classify connection with no response (the
    kill-mid-request shape); ``sick`` fails /healthz."""

    def __init__(self):
        self.generation = 0
        self.reloads = []
        self.served = []
        self.die_next = False
        self.sick = False
        self.reload_status = 200
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _reply(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz" and not outer.sick:
                    self._reply(200, {
                        "status": "ok", "generation": outer.generation,
                        "warmup_s": 0.1, "pid": None,
                    })
                else:
                    self._reply(500, {"error": "sick"})

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(length) or b"{}")
                if self.path == "/reload":
                    outer.reloads.append(req.get("weights"))
                    if outer.reload_status != 200:
                        self._reply(outer.reload_status,
                                    {"error": "scripted failure"})
                        return
                    outer.generation += 1
                    self._reply(200, {"generation": outer.generation,
                                      "source": req.get("weights")})
                    return
                if outer.die_next:
                    outer.die_next = False
                    self.connection.close()  # vanish mid-request
                    return
                rid = int(req["rows"][0][0])
                outer.served.append(rid)
                self._reply(200, {
                    "indices": [[rid]], "probs": [[1.0]],
                    "gen": outer.generation,
                })

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        self.host, self.port = self.httpd.server_address[:2]
        threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        ).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture
def stub_pair():
    a, b = _StubReplica(), _StubReplica()
    router = Router(
        [(a.host, a.port), (b.host, b.port)],
        model_name="stub", health_interval_s=0.1,
    )
    assert router.wait_healthy(timeout_s=10)
    yield a, b, router
    router.stop()
    a.stop()
    b.stop()


def _classify(router, rid):
    code, payload, _ = router.dispatch(
        json.dumps({"rows": [[float(rid)]]}).encode()
    )
    return code, json.loads(payload)


# ---------------------------------------------------------- router core
def test_router_retries_killed_replica_on_peer(stub_pair):
    """ISSUE 9 satellite: a replica dying mid-request costs latency,
    never answers — every request answered exactly once, correctly."""
    a, b, router = stub_pair
    a.die_next = True
    b.die_next = False
    rids = list(range(20))
    answers = []
    for rid in rids:
        code, doc = _classify(router, rid)
        assert code == 200, doc
        answers.append(doc["indices"][0][0])
    # zero dropped, zero duplicated: the echoed ids are exactly the
    # requested ids, and the one dropped connection was retried
    assert answers == rids
    assert sorted(a.served + b.served) == rids
    assert router.metrics.snapshot()["retries"] >= 1


def test_router_least_outstanding_spreads_load(stub_pair):
    a, b, router = stub_pair
    for rid in range(30):
        code, _ = _classify(router, rid)
        assert code == 200
    # both replicas served (ties round-robin; outstanding always 0 in
    # this serial loop, so the spread must come from rotation)
    assert a.served and b.served
    assert len(a.served) + len(b.served) == 30


def test_router_ejects_sick_replica_and_rejoins(stub_pair):
    a, b, router = stub_pair
    a.sick = True
    for _ in range(4):
        router.health_tick()
    hz = router.healthz()
    assert hz["replicas_healthy"] == 1 and hz["status"] == "degraded"
    # traffic flows around the ejected replica
    before = len(a.served)
    for rid in range(10):
        code, _ = _classify(router, rid)
        assert code == 200
    assert len(a.served) == before  # nothing routed to the sick one
    a.sick = False
    for _ in range(2):
        router.health_tick()
    assert router.healthz()["replicas_healthy"] == 2
    snap = router.metrics.snapshot()
    assert snap["ejects"] >= 1 and snap["rejoins"] >= 1


def test_router_rolling_reload_one_at_a_time(stub_pair):
    a, b, router = stub_pair
    code, doc = router.roll("/fake/w_iter_20.solverstate.npz")
    assert code == 200, doc
    assert [r["replica"] for r in doc["rolled"]] == [0, 1]
    assert a.reloads == ["/fake/w_iter_20.solverstate.npz"]
    assert b.reloads == ["/fake/w_iter_20.solverstate.npz"]
    assert router.healthz()["generations"] == [1]


def test_router_roll_stops_at_first_failure(stub_pair):
    """A bad snapshot fails on replica 0 and never reaches replica 1 —
    the tier keeps a serving majority on the old generation."""
    a, b, router = stub_pair
    a.reload_status = 409
    code, doc = router.roll("/fake/torn.solverstate.npz")
    assert code == 502
    assert doc["errors"] and not doc["rolled"]
    assert b.reloads == []  # the roll never advanced past the failure


def test_router_all_replicas_down_returns_503():
    a = _StubReplica()
    router = Router([(a.host, a.port)], health_interval_s=0.1)
    assert router.wait_healthy(timeout_s=10)
    a.stop()
    for _ in range(4):
        router.health_tick()
    code, payload, headers = router.dispatch(
        json.dumps({"rows": [[1.0]]}).encode()
    )
    assert code == 503
    assert dict(headers).get("Retry-After")
    router.stop()


# --------------------------------------------------- continuous batching
class _RecordingEngine:
    """Duck-typed engine: first call blocks until released (so tests
    can saturate the queue deterministically), every call's batch
    composition is recorded."""

    buckets = (1, 8)

    def __init__(self):
        self.calls = []
        self.release = threading.Event()
        self.started = threading.Event()

    def bucket_for(self, n):
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def infer(self, rows):
        self.started.set()
        assert self.release.wait(10)
        self.calls.append(np.asarray(rows).copy())
        return np.asarray(rows)


def _composition_run(mode):
    """Sentinel request (absorbs the cold start), then 16 two-row
    requests queued while the engine is blocked — from the release on,
    the batcher is saturated."""
    eng = _RecordingEngine()
    b = MicroBatcher(
        eng, max_batch=8, max_latency_us=500_000, max_queue=999,
        mode=mode,
    )
    sentinel = b.submit(np.full((1, 1), -1.0, np.float32))
    assert eng.started.wait(10)
    futs = [
        b.submit(np.full((2, 1), float(i), np.float32))
        for i in range(16)
    ]
    eng.release.set()
    assert sentinel.result(timeout=10) is not None
    for f in futs:
        f.result(timeout=10)
    b.drain()
    # compositions after the sentinel batch: the saturated phase
    return [tuple(c[:, 0].astype(int)) for c in eng.calls[1:]]


def test_continuous_equals_fill_at_saturation():
    """ISSUE 9 satellite: at saturation the continuous admitter is
    batch-for-batch identical to fill-then-flush — same compositions,
    same order (outputs are then trivially bit-equal)."""
    fill = _composition_run("fill")
    cont = _composition_run("continuous")
    assert fill == cont
    assert len(fill) == 4  # 16 requests x 2 rows in 8-row batches
    assert all(len(c) == 8 for c in fill)


def test_continuous_dispatches_small_bucket_at_low_rate():
    """A lone request must NOT wait out the co-rider window: with no
    predicted arrivals, waiting buys padding, not throughput."""

    class _Instant:
        buckets = (1, 8)

        def bucket_for(self, n):
            return 1 if n <= 1 else 8

        def infer(self, rows):
            return np.asarray(rows)

    window_s = 0.4
    b = MicroBatcher(
        _Instant(), max_batch=8, max_latency_us=int(window_s * 1e6),
        mode="continuous",
    )
    t0 = time.perf_counter()
    b.submit(np.zeros((1, 1), np.float32)).result(timeout=10)
    dt = time.perf_counter() - t0
    b.drain()
    assert dt < window_s / 2, (
        f"continuous admitter waited the window ({dt:.3f}s)"
    )


def test_fill_waits_window_baseline():
    """The contrast case: fill-then-flush DOES wait the window for a
    lone request — the p99 cost the continuous admitter removes."""

    class _Instant:
        buckets = (8,)

        def infer(self, rows):
            return np.asarray(rows)

    window_s = 0.3
    b = MicroBatcher(
        _Instant(), max_batch=8, max_latency_us=int(window_s * 1e6),
        mode="fill",
    )
    t0 = time.perf_counter()
    b.submit(np.zeros((1, 1), np.float32)).result(timeout=10)
    dt = time.perf_counter() - t0
    b.drain()
    assert dt >= window_s * 0.8


def test_batcher_rejects_unknown_mode():
    with pytest.raises(ValueError, match="fill|continuous"):
        MicroBatcher(_RecordingEngine(), mode="bogus")


# ------------------------------------------------------- engine hot-swap
def test_engine_swap_same_arch_no_recompile_new_outputs():
    net, params, state = toy_net(seed=1)
    eng = InferenceEngine(net, params, state, buckets=(4,)).warmup()
    rows = toy_rows(3)
    out0 = eng.infer(rows)
    n_exec = len(eng._cache)
    _, params2, state2 = toy_net(seed=2)
    gen = eng.swap(params2, state2, source="seed2")
    assert gen == 1 and eng.generation == 1
    assert len(eng._cache) == n_exec  # weights are arguments: no compile
    out1, tag = eng.infer_tagged(rows)
    assert tag == 1
    assert not np.array_equal(out0, out1)
    # bit-identical to a direct apply with the new weights
    import jax.numpy as jnp

    ref = net.apply(
        jax.tree_util.tree_map(jnp.asarray, params2),
        jax.tree_util.tree_map(jnp.asarray, state2),
        {"data": jnp.asarray(rows)}, train=False, rng=None,
    )[0]["prob"]
    np.testing.assert_array_equal(out1, np.asarray(ref))


def test_engine_generation_monotonic_across_swaps():
    net, params, state = toy_net()
    eng = InferenceEngine(net, params, state, buckets=(2,)).warmup()
    seen = []
    for i in range(4):
        _, gen = eng.infer_tagged(toy_rows(1))
        seen.append(gen)
        _, p, s = toy_net(seed=10 + i)
        eng.swap(p, s)
    _, gen = eng.infer_tagged(toy_rows(1))
    seen.append(gen)
    assert seen == sorted(seen) == [0, 1, 2, 3, 4]


def test_engine_swap_from_torn_snapshot_keeps_old_generation(tmp_path):
    from sparknet_tpu.solver.snapshot import SnapshotError, save_state

    net, params, state = toy_net()
    eng = InferenceEngine(net, params, state, buckets=(2,)).warmup()
    path = str(tmp_path / "w_iter_5.solverstate.npz")
    save_state(path, params=jax.device_get(eng.params),
               state=jax.device_get(eng.state))
    with open(path, "rb+") as fh:  # tear it
        fh.truncate(os.path.getsize(path) // 2)
    out0 = eng.infer(toy_rows(2))
    with pytest.raises(SnapshotError):
        eng.swap_from_file(path)
    assert eng.generation == 0  # the old weights keep serving
    np.testing.assert_array_equal(out0, eng.infer(toy_rows(2)))


def test_fingerprint_keys_arch_not_weights():
    """ISSUE 9 satellite (the stale-executable fix): the executable
    cache key carries the net/params fingerprint — same arch with new
    weights shares it, a different arch can never collide."""
    net, params, state = toy_net(seed=1)
    _, params2, state2 = toy_net(seed=2)
    fp1 = net_fingerprint(net, params, state)
    fp2 = net_fingerprint(net, params2, state2)
    assert fp1 == fp2  # weights are not part of the executable identity

    from sparknet_tpu.nets.xlanet import XLANet
    from sparknet_tpu.proto import caffe_pb

    other_proto = TOY_DEPLOY.replace("num_output: 5", "num_output: 6")
    net_b = XLANet(caffe_pb.load_net(other_proto, is_path=False), "TEST")
    params_b, state_b = net_b.init(jax.random.PRNGKey(1))
    assert net_fingerprint(net_b, params_b, state_b) != fp1

    eng = InferenceEngine(net, params, state, buckets=(2,)).warmup()
    # the executable cache is keyed by the engine's (dtype-qualified)
    # fingerprint — and a weights swap leaves that key unchanged
    assert all(key[0] == eng.fingerprint for key in eng._cache)
    eng.swap(params2, state2)
    assert all(key[0] == eng.fingerprint for key in eng._cache)
    # dtype still distinguishes entries for the same arch
    assert net_fingerprint(net, params, state, "bfloat16") != (
        net_fingerprint(net, params, state, "float32")
    )


def test_cache_entries_counts_files(tmp_path):
    assert cache_entries(str(tmp_path)) == 0
    assert cache_entries(str(tmp_path / "missing")) == 0
    (tmp_path / "jit_x-cache").write_bytes(b"x")
    (tmp_path / ".hidden").write_bytes(b"x")
    assert cache_entries(str(tmp_path)) == 1


# -------------------------------------------------------- snapshot watch
def test_snapshot_watcher_fires_on_newer_verified_only(tmp_path):
    from sparknet_tpu.serve.hotswap import SnapshotWatcher, newest_verified
    from sparknet_tpu.solver.snapshot import save_state

    prefix = str(tmp_path / "run" / "snap")
    tree = {"w": np.arange(4.0)}
    save_state(f"{prefix}_iter_10.solverstate.npz", params=tree)
    fired = []
    w = SnapshotWatcher(str(tmp_path / "run"), lambda it, p: fired.append(it))
    assert w.poll_once() == (10, f"{prefix}_iter_10.solverstate.npz")
    assert w.poll_once() is None  # nothing newer
    # a torn newest file is skipped, never swapped to
    torn = f"{prefix}_iter_20.solverstate.npz"
    save_state(torn, params=tree)
    with open(torn, "rb+") as fh:
        fh.truncate(os.path.getsize(torn) // 2)
    assert w.poll_once() is None
    assert w.torn_seen >= 1
    assert newest_verified(str(tmp_path / "run"))[0] == 10
    # an intact newer one fires
    save_state(f"{prefix}_iter_30.solverstate.npz", params=tree)
    assert w.poll_once()[0] == 30
    assert fired == [10, 30]


def test_snapshot_watcher_start_iter_suppresses_boot_snapshot(tmp_path):
    from sparknet_tpu.serve.hotswap import SnapshotWatcher
    from sparknet_tpu.solver.snapshot import save_state

    prefix = str(tmp_path / "snap")
    save_state(f"{prefix}_iter_10.solverstate.npz",
               params={"w": np.zeros(2)})
    w = SnapshotWatcher(prefix, lambda it, p: None, start_iter=10)
    assert w.poll_once() is None  # already serving iter 10


# ----------------------------------------------------------- child pool
def _fast_cfg(**kw):
    from sparknet_tpu.supervise.policy import Config

    kw.setdefault("backoff_s", 0.01)
    kw.setdefault("max_backoff_s", 0.02)
    kw.setdefault("flap_window_s", 9999.0)
    kw.setdefault("healthy_s", 9999.0)
    return Config(**kw)


def test_child_pool_respawns_then_gives_up():
    from sparknet_tpu.supervise.pool import GIVEN_UP, ChildPool

    pool = ChildPool(
        lambda i, s: [sys.executable, "-c", "import sys; sys.exit(3)"],
        1, config=_fast_cfg(max_restarts=2, flap_limit=99),
    ).start()
    events = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        events += pool.tick()
        if pool.children[0].state == GIVEN_UP:
            break
        time.sleep(0.02)
    child = pool.children[0]
    assert child.state == GIVEN_UP
    assert child.spawn_count == 3  # initial + 2 budgeted respawns
    kinds = [e["event"] for e in events]
    assert kinds.count("give_up") == 1
    assert "restart budget spent" in child.give_up_reason
    pool.stop()


def test_child_pool_clean_exit_stays_down():
    from sparknet_tpu.supervise.pool import STOPPED, ChildPool

    pool = ChildPool(
        lambda i, s: [sys.executable, "-c", "pass"], 1,
        config=_fast_cfg(),
    ).start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        pool.tick()
        if pool.children[0].state == STOPPED:
            break
        time.sleep(0.02)
    assert pool.children[0].state == STOPPED
    assert pool.children[0].spawn_count == 1  # never respawned
    pool.stop()


def test_child_pool_kill_and_respawn_flow():
    from sparknet_tpu.supervise.pool import RUNNING, ChildPool

    pool = ChildPool(
        lambda i, s: [sys.executable, "-c", "import time; time.sleep(60)"],
        2, config=_fast_cfg(max_restarts=5),
    ).start()
    try:
        first_pid = pool.children[0].pid
        assert pool.kill(0)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            pool.tick()
            c = pool.children[0]
            if c.state == RUNNING and c.pid != first_pid:
                break
            time.sleep(0.02)
        assert pool.children[0].pid != first_pid
        # the peer never flinched
        assert pool.children[1].spawn_count == 1
        assert len(pool.alive()) == 2
    finally:
        pool.stop()


def test_replica_kill_chaos_point_registered():
    from sparknet_tpu.chaos.plan import FAULT_POINTS, FaultPlan

    assert "serve.replica_kill" in FAULT_POINTS
    plan = FaultPlan("serve.replica_kill@tick=3:worker=1", seed=0)
    assert plan.match("serve.replica_kill", tick=3, worker=1) is not None
    assert plan.match("serve.replica_kill", tick=3, worker=0) is None
    assert plan.match("serve.replica_kill", tick=2, worker=1) is None


# --------------------------------------- real engine behind the router
def test_router_over_real_servers_swap_generations():
    """End-to-end in-process: two real engine replicas, HTTP loadgen
    through the router, a rolling swap mid-life — zero failures and
    monotone generations."""
    from sparknet_tpu.serve.loadgen import run_http_loadgen

    servers, engines = [], []
    for seed in (1, 2):
        net, params, state = toy_net(seed)
        m = ServeMetrics((4,))
        eng = InferenceEngine(
            net, params, state, buckets=(4,), metrics=m
        ).warmup()
        srv = InferenceServer(
            eng, metrics=m, port=0, model_name="toy",
            batcher=MicroBatcher(eng, max_latency_us=2000, metrics=m,
                                 mode="continuous"),
        ).start()
        servers.append(srv)
        engines.append(eng)
    router = Router(
        [(s.host, s.port) for s in servers],
        model_name="toy", health_interval_s=0.1,
    ).start()
    try:
        assert router.wait_healthy(timeout_s=10)
        rec = run_http_loadgen(
            router.host, router.port, (8, 8, 3),
            n_requests=30, sizes=(1, 2, 3), concurrency=3,
        )
        assert rec["failed_requests"] == 0
        assert rec["served_generations"] == [0]

        import tempfile

        from sparknet_tpu.solver.snapshot import save_state

        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "w_iter_9.solverstate.npz")
            save_state(path,
                       params=jax.device_get(engines[0].params),
                       state=jax.device_get(engines[0].state))
            code, doc = router.roll(path)
            assert code == 200 and len(doc["rolled"]) == 2
        rec2 = run_http_loadgen(
            router.host, router.port, (8, 8, 3),
            n_requests=20, sizes=(1, 2), concurrency=2,
        )
        assert rec2["failed_requests"] == 0
        assert rec2["served_generations"] == [1]
        assert router.healthz()["generations"] == [1]
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_server_reload_route_and_classify_gen(tmp_path):
    """Single replica surface: /reload swaps (manifest-verified), the
    response and /healthz carry the generation, torn files 409."""
    from sparknet_tpu.solver.snapshot import save_state

    net, params, state = toy_net()
    m = ServeMetrics((2,))
    eng = InferenceEngine(net, params, state, buckets=(2,),
                          metrics=m).warmup()
    srv = InferenceServer(
        eng, metrics=m, port=0,
        batcher=MicroBatcher(eng, metrics=m),
    ).start()
    try:
        c = srv.client()
        st, resp = c.classify(toy_rows(1))
        assert st == 200 and resp["gen"] == 0
        path = str(tmp_path / "w_iter_3.solverstate.npz")
        save_state(path, params=jax.device_get(eng.params),
                   state=jax.device_get(eng.state))
        st, resp = c.reload(path)
        assert st == 200 and resp["generation"] == 1
        st, hz = c.healthz()
        assert hz["generation"] == 1
        assert hz["weights_source"] == path
        st, resp = c.classify(toy_rows(1))
        assert resp["gen"] == 1
        # torn file -> 409, generation unchanged
        with open(path, "rb+") as fh:
            fh.truncate(os.path.getsize(path) // 2)
        st, resp = c.reload(path)
        assert st == 409 and "torn" in resp["error"]
        assert c.healthz()[1]["generation"] == 1
        snap = m.snapshot()
        assert snap["hot_swaps"] == 1 and snap["generation"] == 1
    finally:
        srv.stop()


def test_classify_from_decoded_batch_cache():
    """ISSUE 9 satellite: a replica attached read-only to the PR 8
    decoded-batch cache classifies by cache_key — the rows never cross
    the wire — and the data_cache counters surface in /healthz and
    /metrics."""
    from sparknet_tpu.data.cache import ShmBatchCache

    ns = f"servetier-{os.getpid()}"
    writer = ShmBatchCache(namespace=ns, max_bytes=int(8e6))
    reader = ShmBatchCache(namespace=ns, readonly=True)
    try:
        rows = toy_rows(2, seed=5)
        assert writer.put("batch-0", {"data": rows})
        assert not reader.put("nope", {"data": rows})  # readonly no-op

        net, params, state = toy_net()
        m = ServeMetrics((2,))
        eng = InferenceEngine(net, params, state, buckets=(2,),
                              metrics=m).warmup()
        srv = InferenceServer(
            eng, metrics=m, port=0, data_cache=reader,
            batcher=MicroBatcher(eng, metrics=m),
        ).start()
        try:
            c = srv.client()
            st, via_cache = c.classify_cached("batch-0", top_k=3)
            assert st == 200
            st, via_wire = c.classify(rows, top_k=3)
            assert via_cache["indices"] == via_wire["indices"]
            st, missing = c.classify_cached("no-such-batch")
            assert st == 404
            st, hz = c.healthz()
            assert hz["data_cache"]["hits"] >= 1
            # the counters also ride the Prometheus scrape via the
            # registry's data_cache source
            import urllib.request

            text = urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/metrics"
            ).read().decode()
            assert "data_cache" in text
        finally:
            srv.stop()
    finally:
        writer.clear()


# ------------------------------------------------------------------ dash
def test_dash_renders_router_section():
    from sparknet_tpu.telemetry.dash import render_html

    router_snap = {
        "replicas_healthy": 1, "replicas_total": 2,
        "generations": [3],
        "router": {
            "retries": 5, "failed": 0, "replica_deaths": 1,
            "respawns": 1, "rolls": 2,
            "request_latency": {"p99_ms": 12.5},
        },
        "replicas": [
            {"index": 0, "healthy": True, "addr": "h:1",
             "outstanding": 2, "generation": 3, "forwarded": 10,
             "latency": {"p50_ms": 4.0, "p99_ms": 9.0}},
            {"index": 1, "healthy": False, "addr": "h:2",
             "outstanding": 0, "generation": 2, "forwarded": 7,
             "latency": {}},
        ],
    }
    html = render_html({"uptime_s": 1.0}, router=router_snap)
    assert "Serving tier" in html
    assert "replica 0" in html and "replica 1" in html
    assert "ejected" in html and "1/2" in html
    # without a router snapshot the section is absent
    assert "Serving tier" not in render_html({"uptime_s": 1.0})
