"""End-to-end request tracing for the serving tier (ISSUE 11).

Pins: trace-context header round-trip, the allocation-free disabled
path (the PR 5 tracer discipline), bounded span storage, the inline
``X-Sparknet-Spans`` replica batch, the router's cross-process stitch
(>=5 spans, >=90% wall attribution), chaos forensics for a SIGKILLed
replica (failed hop + retry hop on one waterfall), the structured
``retry:`` line + ``router_events{event="retry_hop"}``, the SLO
burn-rate detector (deterministic on a synthetic series; surfaces in
``/healthz``), OpenMetrics exemplars and the loadgen's failed/slow trace
ids.
"""

import http.client
import json
import os
import signal
import sys
import threading
import time

import numpy as np
import pytest
import jax

from sparknet_tpu.serve.batcher import MicroBatcher
from sparknet_tpu.serve.engine import InferenceEngine
from sparknet_tpu.serve.metrics import ServeMetrics
from sparknet_tpu.serve.router import Router
from sparknet_tpu.serve.server import InferenceServer
from sparknet_tpu.telemetry import anomaly, reqtrace
from sparknet_tpu.telemetry.registry import REGISTRY, LatencyHistogram

TOY_DEPLOY = """
name: "toy"
input: "data"
input_shape { dim: 1 dim: 3 dim: 8 dim: 8 }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
        inner_product_param { num_output: 5
          weight_filler { type: "gaussian" std: 0.2 } } }
layer { name: "prob" type: "Softmax" bottom: "ip1" top: "prob" }
"""


@pytest.fixture(autouse=True)
def _isolate():
    reqtrace.reset()
    reqtrace.enable()
    anomaly.clear()
    anomaly.reset_detectors()
    yield
    reqtrace.reset()
    reqtrace.configure_from_env()
    anomaly.clear()
    anomaly.reset_detectors()


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


def toy_server(seed=7, buckets=(4,), **kw):
    net, params, state = toy_net(seed)
    m = ServeMetrics(buckets)
    eng = InferenceEngine(
        net, params, state, buckets=buckets, metrics=m
    ).warmup()
    srv = InferenceServer(
        eng, metrics=m, port=0, model_name="toy",
        batcher=MicroBatcher(eng, max_latency_us=2000, metrics=m,
                             mode="continuous"),
        **kw,
    ).start()
    return srv, eng, m


# ---------------------------------------------------------- primitives
def test_context_header_round_trip():
    ctx = reqtrace.mint()
    assert ctx.root and len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    back = reqtrace.parse(reqtrace.to_header(ctx))
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled == ctx.sampled
    assert not back.root  # a parsed context is never the stitch root
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id
    # garbage headers never raise — they just don't parse
    for bad in (None, "", "zz", "a-b-c", "0" * 32, f"{'x' * 32}-{'y' * 16}-01"):
        assert reqtrace.parse(bad) is None


def test_disabled_mode_is_allocation_free_noop():
    reqtrace.disable()
    try:
        assert reqtrace.mint() is None
        # ONE shared no-op object each — nothing allocated per call
        assert reqtrace.span(None, "x") is reqtrace.span(None, "y")
        assert reqtrace.span(None, "x") is reqtrace._NULL
        assert reqtrace.hop(None, "x") is reqtrace._NULL_HOP
        assert reqtrace.hop(None, "x").finish() is None
        assert reqtrace.record(None, "x", 0, 0.0) is None
        assert reqtrace.record_interval(None, "x", 0.0) is None
        assert reqtrace.parse("a" * 32 + "-" + "b" * 16 + "-00") is None
        assert reqtrace.finish(None, 0.0) is None
        assert reqtrace.completed() == []
    finally:
        reqtrace.enable()


def test_store_bounds_evict_and_count():
    before = REGISTRY.counter("reqtrace_dropped_spans").snapshot()
    # spans-per-trace cap
    ctx = reqtrace.mint()
    for i in range(reqtrace.MAX_SPANS_PER_TRACE + 10):
        reqtrace.record(ctx, f"s{i}", i, 1.0)
    assert len(reqtrace.take(ctx.trace_id)) == reqtrace.MAX_SPANS_PER_TRACE
    # open-trace cap: the oldest trace is evicted, newest survive
    first = reqtrace.mint()
    reqtrace.record(first, "old", 0, 1.0)
    for _ in range(reqtrace.MAX_TRACES):
        reqtrace.record(reqtrace.mint(), "fill", 0, 1.0)
    assert reqtrace.take(first.trace_id) == []
    assert REGISTRY.counter("reqtrace_dropped_spans").snapshot() > before


def test_spans_header_round_trip_and_truncation():
    spans = [{"name": f"s{i}", "span": "a" * 16, "parent": "b" * 16,
              "ts": i, "dur": 1.0, "pid": 1} for i in range(5)]
    val = reqtrace.spans_header_value(spans)
    assert "\n" not in val
    assert reqtrace.parse_spans_header(val) == spans
    assert reqtrace.parse_spans_header("not json") == []
    assert reqtrace.parse_spans_header(None) == []
    # oversized batches drop newest spans rather than breaking the wire
    big = [dict(s, name="x" * 4096) for s in spans] * 4
    val = reqtrace.spans_header_value(big)
    assert len(val) <= reqtrace.MAX_HEADER_BYTES
    assert len(reqtrace.parse_spans_header(val)) < len(big)


# --------------------------------------------------- single-process hop
def test_single_server_roots_and_completes_trace():
    srv, eng, m = toy_server()
    try:
        c = srv.client()
        st, resp = c.classify(toy_rows(2))
        assert st == 200 and "gen" in resp
        recs = reqtrace.completed()
        assert recs, "root server never completed its trace"
        rec = max(recs, key=lambda r: len(r["spans"]))
        names = {s["name"] for s in rec["spans"]}
        assert {"server.request", "batcher.wait", "engine.compute",
                "serve.serialize"} <= names
        assert reqtrace.coverage(rec) >= 0.9
        # parent chain: batcher/engine/serialize spans hang off the
        # server.request hop span
        server_span = next(
            s for s in rec["spans"] if s["name"] == "server.request"
        )
        for s in rec["spans"]:
            if s["name"] != "server.request":
                assert s["parent"] == server_span["span"]
    finally:
        srv.stop()


def test_replica_returns_span_batch_inline_when_not_root():
    """A replica under a router (= incoming trace header) hands its
    spans back in ``X-Sparknet-Spans`` instead of stitching locally."""
    srv, eng, m = toy_server()
    try:
        ctx = reqtrace.mint()
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
        conn.request(
            "POST", "/classify",
            body=json.dumps({"rows": toy_rows(1).tolist()}),
            headers={"Content-Type": "application/json",
                     reqtrace.HEADER: reqtrace.to_header(ctx)},
        )
        resp = conn.getresponse()
        spans_hdr = resp.getheader(reqtrace.SPANS_HEADER)
        echo = resp.getheader(reqtrace.HEADER)
        assert resp.status == 200
        resp.read()
        conn.close()
        assert echo and echo.startswith(ctx.trace_id)
        spans = reqtrace.parse_spans_header(spans_hdr)
        names = {s["name"] for s in spans}
        assert {"server.request", "batcher.wait", "engine.compute",
                "serve.serialize"} <= names
        # the server hop parents onto the caller's span id — the
        # cross-process link the router stitches on
        server_span = next(
            s for s in spans if s["name"] == "server.request"
        )
        assert server_span["parent"] == ctx.span_id
        # not the root: nothing stitched locally for this trace
        assert all(
            r["trace"] != ctx.trace_id for r in reqtrace.completed()
        )
    finally:
        srv.stop()


def test_disabled_tracing_serves_without_trace_headers():
    reqtrace.disable()
    srv, eng, m = toy_server()
    try:
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
        conn.request(
            "POST", "/classify",
            body=json.dumps({"rows": toy_rows(1).tolist()}),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader(reqtrace.HEADER) is None
        assert resp.getheader(reqtrace.SPANS_HEADER) is None
        resp.read()
        conn.close()
        assert reqtrace.completed() == []
    finally:
        srv.stop()
        reqtrace.enable()


# ------------------------------------------------------- stitched tier
def test_router_stitches_cross_hop_waterfall():
    """The acceptance bar: one classify through a 2-replica tier ->
    ONE stitched waterfall with >=5 spans attributing >=90% of wall
    latency, exported as Perfetto-loadable Chrome JSON."""
    servers = [toy_server(seed)[0] for seed in (1, 2)]
    router = Router(
        [(s.host, s.port) for s in servers],
        model_name="toy", health_interval_s=0.1,
    )
    try:
        assert router.wait_healthy(timeout_s=20)
        code, payload, headers = router.dispatch(
            json.dumps({"rows": toy_rows(2).tolist()}).encode()
        )
        assert code == 200
        hdr = dict(headers)
        assert reqtrace.HEADER in hdr  # the trace id reaches the client
        recs = [
            r for r in reqtrace.completed()
            if r["trace"] == reqtrace.parse(hdr[reqtrace.HEADER]).trace_id
        ]
        rec = max(recs, key=lambda r: len(r["spans"]))
        names = {s["name"] for s in rec["spans"]}
        assert len(rec["spans"]) >= 5
        assert {"router.dispatch", "server.request", "batcher.wait",
                "engine.compute", "serve.serialize"} <= names
        assert reqtrace.coverage(rec) >= 0.9
        # the replica's spans kept their origin pid; the dispatch hop
        # is the router's — two processes... here one process, but the
        # PARENT chain must cross the hop: server.request hangs off
        # the dispatch attempt's span id
        disp = next(s for s in rec["spans"] if s["name"] == "router.dispatch")
        serv = next(s for s in rec["spans"] if s["name"] == "server.request")
        assert serv["parent"] == disp["span"]
        # Perfetto-loadable export: X events with ts/dur/pid/tid + the
        # trace id in args
        doc = reqtrace.export_chrome([rec])
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len(evs) == len(rec["spans"])
        for e in evs:
            assert e["ph"] in ("X", "M")
            for k in ("name", "ts", "dur", "pid", "tid"):
                assert k in e, e
            assert e["args"]["trace"] == rec["trace"]
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_sigkilled_replica_leaves_forensic_trace(tmp_path, capsys):
    """ISSUE 11 satellite (chaos forensics): SIGKILL a real replica
    subprocess (the ``serve.replica_kill`` chaos surface,
    ``pool.kill``) and assert the survivor-answered request's stitched
    trace holds the failed hop span, the retry hop span, and >=90%
    wall-latency attribution — plus the structured ``retry:`` line and
    the ``router_events{event="retry_hop"}`` increment."""
    from sparknet_tpu.supervise.pool import ChildPool

    model = tmp_path / "toy.prototxt"
    model.write_text(TOY_DEPLOY)

    def argv(i, spawn):
        return [
            sys.executable, "-m", "sparknet_tpu.serve.replica",
            "--model", str(model), "--buckets", "1,4", "--port", "0",
            "--portfile", str(tmp_path / f"replica-{i}-s{spawn}.json"),
        ]

    pool = ChildPool(argv, 2, name="reqtrace-replica")
    router = Router(
        2, pool=pool,
        portfile_for=lambda i, s: str(tmp_path / f"replica-{i}-s{s}.json"),
        health_interval_s=0.2,
    )
    pool.start()
    try:
        assert router.wait_healthy(timeout_s=180)
        retry_before = REGISTRY.counter(
            "router_events", event="retry_hop"
        ).snapshot()
        # SIGKILL replica 0 through the pool — the serve.replica_kill
        # chaos point's kill surface — and dispatch before any health
        # sweep can eject it: the router discovers the death
        # mid-request and retries on the peer
        assert pool.kill(0, signal.SIGKILL)
        time.sleep(0.2)  # let the process die so the port refuses
        body = json.dumps({"rows": toy_rows(1).tolist()}).encode()
        stitched = None
        for _ in range(4):  # rr tie-break: within 2 picks one lands on 0
            code, payload, headers = router.dispatch(body)
            assert code == 200, payload  # a kill costs latency, never answers
            tid = reqtrace.parse(dict(headers)[reqtrace.HEADER]).trace_id
            rec = next(
                r for r in reqtrace.completed() if r["trace"] == tid
            )
            if any(s["name"] == "router.retry" for s in rec["spans"]):
                stitched = rec
                break
        assert stitched is not None, "no dispatch ever hit the dead replica"
        failed = [
            s for s in stitched["spans"]
            if s["name"] == "router.dispatch"
            and s.get("args", {}).get("outcome") == "error"
        ]
        retried = [
            s for s in stitched["spans"] if s["name"] == "router.retry"
        ]
        assert failed and failed[0]["args"]["error"]
        assert retried and retried[0]["args"]["outcome"] == "ok"
        assert retried[0]["args"]["retry_of"] == failed[0]["args"]["replica"]
        # the survivor's replica spans stitched in from another PROCESS
        assert any(
            s["name"] == "server.request" and s["pid"] != os.getpid()
            for s in stitched["spans"]
        )
        assert reqtrace.coverage(stitched) >= 0.9
        # structured retry record at the moment of re-dispatch
        assert REGISTRY.counter(
            "router_events", event="retry_hop"
        ).snapshot() > retry_before
        retry_lines = [
            json.loads(line[len("retry: "):])
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("retry: ")
        ]
        assert any(
            r["trace"] == stitched["trace"] and r["reason"]
            and r["from"] != r["to"]
            for r in retry_lines
        )
    finally:
        router.stop()


def test_retry_line_on_stub_replica_death(capsys):
    """The cheap (stub) version of the retry record: a connection
    dropped mid-request leaves the ``retry:`` JSON line and a
    ``retry_hop`` event even without real replica processes."""
    from tests.test_serving_tier import _StubReplica

    a, b = _StubReplica(), _StubReplica()
    router = Router(
        [(a.host, a.port), (b.host, b.port)], health_interval_s=0.1
    )
    try:
        assert router.wait_healthy(timeout_s=10)
        a.die_next = b.die_next = True  # whichever is picked first dies
        code, payload, _ = router.dispatch(
            json.dumps({"rows": [[1.0]]}).encode()
        )
        assert code == 200
        lines = [
            json.loads(ln[len("retry: "):])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("retry: ")
        ]
        assert lines and lines[0]["reason"]
        assert {"trace", "from", "to", "reason"} <= set(lines[0])
    finally:
        router.stop()
        a.stop()
        b.stop()


# ------------------------------------------------------- SLO burn rate
def test_slo_burn_detector_fires_on_sustained_violation_only():
    clock = {"t": 0.0}
    det = anomaly.SloBurnRateDetector(
        slo_ms=100.0, emit=lambda *_: None, now=lambda: clock["t"]
    )
    # 20 min of healthy scrapes: silence
    for _ in range(40):
        clock["t"] += 30
        assert det.observe(50.0) is None
    # sustained violation: fires exactly when BOTH windows burn (fast
    # 5m window saturates quickly; the slow 1h window crosses 25% at
    # the 14th violating sample: 14/54)
    events = []
    for _ in range(14):
        clock["t"] += 30
        got = det.observe(500.0)
        if got:
            events.append(got)
    assert len(events) == 1
    ev = events[0]
    assert ev["kind"] == "slo_burn" and ev["severity"] == "critical"
    assert ev["fast_burn"] >= 0.5 and ev["slow_burn"] >= 0.25
    assert anomaly.active("slo_burn")
    # recovery resets the episode; a later breach fires anew
    for _ in range(60):
        clock["t"] += 30
        det.observe(50.0)
    assert det._last_fire is None


def test_slo_burn_needs_both_windows():
    """A brief spike saturating only the fast window must NOT fire —
    the slow window is the 'error budget is really burning' gate."""
    clock = {"t": 0.0}
    det = anomaly.SloBurnRateDetector(
        slo_ms=100.0, emit=lambda *_: None, now=lambda: clock["t"]
    )
    for _ in range(100):
        clock["t"] += 30
        det.observe(50.0)
    for _ in range(8):  # 4 min of violation: fast burn 0.8, slow ~0.07
        clock["t"] += 30
        assert det.observe(500.0) is None


def test_healthz_degrades_on_slo_burn(monkeypatch):
    monkeypatch.setenv("SPARKNET_SLO_P99_MS", "0.0001")
    anomaly.reset_detectors()
    srv, eng, m = toy_server()
    try:
        c = srv.client()
        st, _ = c.classify(toy_rows(1))
        assert st == 200  # any real request's p99 >> 0.0001 ms
        for _ in range(6):  # scrapes feed the burn windows (min 5)
            st, hz = c.healthz()
        assert st == 200
        kinds = {a["kind"] for a in hz["anomalies"]}
        assert "slo_burn" in kinds
        assert hz["status"] == "degraded"
    finally:
        srv.stop()


# ------------------------------------------------- exemplars + loadgen
def test_sampled_traces_become_prometheus_exemplars():
    from sparknet_tpu.telemetry.exporter import render_prometheus
    from sparknet_tpu.telemetry.registry import Registry

    reg = Registry()
    h = reg.histogram("serve_request_latency_seconds")
    h.observe(0.010)  # no exemplar: plain bucket line
    h.observe(0.012, exemplar=("cafe" * 8, 0.012))
    text = render_prometheus(registry=reg)
    assert f'# {{trace_id="{"cafe" * 8}"}} 0.012' in text
    # exactly one exemplar (one bin), not one per bucket line
    assert text.count("trace_id=") == 1


def test_every_nth_mint_is_sampled():
    n = reqtrace._SAMPLE_N
    flags = [reqtrace.mint().sampled for _ in range(2 * n)]
    assert sum(flags) == 2
    assert flags[0]  # the counter was reset by the fixture


def test_loadgen_records_failed_and_slow_trace_ids():
    from sparknet_tpu.serve.loadgen import run_http_loadgen

    srv, eng, m = toy_server()
    try:
        rec = run_http_loadgen(
            srv.host, srv.port, (8, 8, 3),
            n_requests=30, sizes=(1, 2, 3), concurrency=3,
        )
        assert rec["failed_requests"] == 0
        assert rec["failed_request_traces"] == []
        assert rec["p50_exact_ms"] is not None
        assert rec["p99_exact_ms"] >= rec["p50_exact_ms"]
        # the >p99 stragglers are named by trace id, slowest first
        assert isinstance(rec["slow_request_traces"], list)
        for entry in rec["slow_request_traces"]:
            assert set(entry) == {"req", "trace", "ms"}
            assert len(entry["trace"]) == 32
            assert entry["ms"] > rec["p99_exact_ms"]
    finally:
        srv.stop()


# ------------------------------------------------------- dash + gates
def test_dash_renders_slow_request_panel():
    from sparknet_tpu.telemetry.dash import render_html

    recs = [{
        "trace": "ab" * 16, "wall_ms": 42.5, "t": 0.0, "sampled": True,
        "spans": [
            {"name": "router.dispatch", "span": "s1", "parent": "r",
             "ts": 0, "dur": 900.0, "pid": 1,
             "args": {"outcome": "error", "error": "ConnectionRefused"}},
            {"name": "router.retry", "span": "s2", "parent": "r",
             "ts": 1000, "dur": 41000.0, "pid": 1,
             "args": {"outcome": "ok"}},
            {"name": "server.request", "span": "s3", "parent": "s2",
             "ts": 1200, "dur": 40000.0, "pid": 2, "args": {}},
        ],
    }]
    html = render_html({"uptime_s": 1.0}, reqtrace=recs)
    assert "Slow requests" in html and "42.5 ms" in html
    assert "⟳ retried" in html  # retry hops flagged, not color alone
    assert 'data-hop="router.retry"' in html
    # absent records -> absent panel
    assert "Slow requests" not in render_html({"uptime_s": 1.0})
