"""Cluster observability plane (ISSUE 7): aggregation, anomaly
detection, flight recorder, dashboard.

The acceptance bar: 3-rank aggregation merges bounded, version-tagged
payloads; an injected slow rank trips the straggler detector
deterministically (pinned); a chaos-killed supervised child's failure
record references a readable flight-recorder dump; the ``/dash`` route
returns valid HTML with live numbers; and every disabled mode stays
the PR-5 allocation-free no-op.  CPU-only, tier-1.
"""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from sparknet_tpu.telemetry import (
    REGISTRY,
    aggregate,
    anomaly,
    dash,
    flight,
    timeline,
    trace,
)

@pytest.fixture(autouse=True)
def _isolation(monkeypatch):
    """No aggregator, advisory board, flight ring, tracer state, or
    supervision env may leak between tests."""
    for var in (
        "SPARKNET_SUPERVISE", "SPARKNET_SUPERVISE_DIR",
        "SPARKNET_SUPERVISE_GEN", "SPARKNET_FLIGHT",
        "SPARKNET_CLUSTER_TELEMETRY",
    ):
        monkeypatch.delenv(var, raising=False)
    anomaly.clear()
    anomaly.reset_detectors()
    aggregate.reset()
    flight.disable()
    yield
    anomaly.clear()
    anomaly.reset_detectors()
    aggregate.reset()
    flight.disable()
    trace.disable()
    timeline.set_current(None)
    os.environ.pop(trace.OWNER_PID_ENV, None)


def _payload(rank, seq, phases, wall, v=aggregate.PAYLOAD_VERSION, **extra):
    doc = {
        "v": v, "rank": rank, "seq": seq, "pid": 1000 + rank,
        "t": 0.0, "wall_s": wall,
        "phases": {k: list(tc) for k, tc in phases.items()},
        **extra,
    }
    return json.dumps(doc).encode()


_SILENT = lambda s: None  # detectors under test must not spam stdout


# -------------------------------------------------------------- payloads
def test_publisher_payload_is_bounded(monkeypatch):
    class HugeTimeline:
        enabled = True
        wall_s = 100.0

        def snapshot(self):
            return {
                "phases": {
                    f"phase_{i:04d}": {"total_s": 1.0, "count": i}
                    for i in range(2000)
                }
            }

    monkeypatch.setattr(timeline, "_current", HugeTimeline())
    before = REGISTRY.counter("cluster_payload_truncated").snapshot()
    raw = aggregate.RankPublisher(3).payload()
    assert len(raw) <= aggregate.MAX_PAYLOAD_BYTES
    doc = json.loads(raw)
    assert doc["v"] == aggregate.PAYLOAD_VERSION and doc["rank"] == 3
    # the 2000 synthetic phases could not fit: sections were shed (and
    # counted), the envelope survived
    assert len(doc["phases"]) < 2000
    assert REGISTRY.counter("cluster_payload_truncated").snapshot() > before


def test_three_rank_merge_and_version_skew():
    agg = aggregate.ClusterAggregator(
        detector=anomaly.StragglerDetector(emit=_SILENT)
    )
    for r in (0, 1, 2):
        assert agg.ingest(_payload(
            r, 1, {"compiled_step": [1.0 + r, 5], "input_wait": [0.5, 5]},
            wall=2.0 + r,
        ))
    snap = agg.snapshot()
    assert sorted(snap["ranks"]) == ["0", "1", "2"]
    assert snap["ranks"]["2"]["phases"]["compiled_step"]["total_s"] == 3.0
    # per-rank label series landed in the registry
    g = REGISTRY.gauge("cluster_phase_share_pct", rank=1, phase="compiled_step")
    assert g.snapshot()["value"] == pytest.approx(100 * 2.0 / 3.0, abs=0.1)
    # the cluster table renders one column per rank + skew
    table = agg.table()
    assert "r0" in table and "r1" in table and "r2" in table
    assert "compiled_step" in table and "max/med" in table

    # garbage and structurally-wrong payloads are counted, not fatal
    errors0 = REGISTRY.counter("cluster_payload_errors").snapshot()
    assert not agg.ingest(b"{torn json")
    assert not agg.ingest(b'["not an object"]')
    assert not agg.ingest(  # rank must be an integer
        json.dumps({"v": 1, "rank": "x", "phases": {}}).encode()
    )
    assert REGISTRY.counter("cluster_payload_errors").snapshot() >= errors0 + 3

    # version skew is tolerated: newer payload, unknown fields merged
    # past, known fields kept — and the skew counted
    skew0 = REGISTRY.counter("cluster_version_skew").snapshot()
    assert agg.ingest(_payload(
        1, 2, {"compiled_step": [2.5, 6]}, wall=3.5,
        v=aggregate.PAYLOAD_VERSION + 1, future_field={"x": 1},
    ))
    assert REGISTRY.counter("cluster_version_skew").snapshot() == skew0 + 1
    assert agg.snapshot()["ranks"]["1"]["phases"]["compiled_step"][
        "total_s"
    ] == 2.5


def test_ingest_never_raises_via_module_entry():
    assert aggregate.ingest(b"anything") is False  # no aggregator yet
    aggregate.init_aggregator()
    assert aggregate.ingest(b"\xff\xfe garbage") is False
    assert aggregate.ingest(_payload(1, 1, {"eval": [0.1, 1]}, 1.0))


# ------------------------------------------------------------ stragglers
def _round_payloads(agg, k, slow_rank=1, slow_factor=3.0):
    """One full aggregation round: every rank's cumulative phases."""
    for r in (0, 1, 2):
        factor = slow_factor if r == slow_rank else 1.0
        agg.ingest(_payload(
            r, k, {"compiled_step": [k * factor, 5 * k]}, wall=4.0 * k
        ))


def test_injected_slow_rank_trips_straggler_detector():
    """The acceptance pin: rank 1 runs compiled_step 3x the cluster
    median for 3 consecutive aggregation rounds -> exactly one
    straggler anomaly naming rank 1, counted + advisory raised."""
    lines = []
    det = anomaly.StragglerDetector(factor=2.0, rounds=3, emit=lines.append)
    agg = aggregate.ClusterAggregator(detector=det)
    fired0 = REGISTRY.counter("anomalies", kind="straggler").snapshot()
    # round 1 completes solo (ranks 1/2 unknown until they first
    # publish), so the 3-round streak needs 4 publish sweeps
    for k in (1, 2, 3, 4):
        _round_payloads(agg, k)
    assert agg.rounds == 4
    assert REGISTRY.counter("anomalies", kind="straggler").snapshot() == (
        fired0 + 1
    )
    (active,) = anomaly.active("straggler")
    assert active["rank"] == 1 and active["phase"] == "compiled_step"
    assert active["ratio"] == pytest.approx(3.0, abs=0.01)
    # the structured log line parses and names the rank
    (line,) = [ln for ln in lines if ln.startswith("anomaly: ")]
    doc = json.loads(line[len("anomaly: "):])
    assert doc["kind"] == "straggler" and doc["rank"] == 1
    # the cluster snapshot surfaces the advisory
    assert agg.snapshot()["stragglers"]


def test_straggler_streak_resets_below_threshold():
    det = anomaly.StragglerDetector(factor=2.0, rounds=3, emit=_SILENT)

    def round_of(slow):
        return {
            r: {"phases": {"compiled_step": (3.0 if r == 1 and slow else 1.0)},
                "wall_s": 4.0}
            for r in (0, 1, 2)
        }

    before = anomaly.fired_total()
    det.observe_round(round_of(True), 1)
    det.observe_round(round_of(True), 2)
    det.observe_round(round_of(False), 3)  # streak broken
    det.observe_round(round_of(True), 4)
    det.observe_round(round_of(True), 5)
    assert anomaly.fired_total() == before  # never reached 3 consecutive
    assert det.observe_round(round_of(True), 6)  # now it fires
    assert anomaly.fired_total() == before + 1


# --------------------------------------------------------------- outliers
def test_ema_mad_detector_is_deterministic():
    det = anomaly.EmaMadDetector(
        "step_time_spike", k=5.0, min_n=5, emit=_SILENT
    )
    # a mildly noisy plateau: no firings while the window warms up or after
    for x in (1.0, 1.1, 0.9, 1.0, 1.05, 0.95, 1.0, 1.02):
        assert det.observe(x) is None
    # a 10x spike deviates far past k * MAD
    ev = det.observe(10.0)
    assert ev is not None and ev["kind"] == "step_time_spike"
    assert ev["value"] == 10.0
    assert REGISTRY.counter("anomalies", kind="step_time_spike").snapshot() >= 1
    # same stream, fresh detector -> same single firing (determinism)
    det2 = anomaly.EmaMadDetector(
        "step_time_spike", k=5.0, min_n=5, emit=_SILENT
    )
    fires = [
        det2.observe(x) is not None
        for x in (1.0, 1.1, 0.9, 1.0, 1.05, 0.95, 1.0, 1.02, 10.0)
    ]
    assert fires == [False] * 8 + [True]


def test_ema_mad_min_samples_gate():
    det = anomaly.EmaMadDetector("loss_spike", k=5.0, min_n=5, emit=_SILENT)
    assert det.observe(1.0) is None
    assert det.observe(100.0) is None  # only 2 samples: never fires


# ------------------------------------------------------------ queue stalls
def test_queue_stall_detector_fires_and_resets():
    clock = [0.0]
    det = anomaly.QueueStallDetector(
        "serve", observations=3, min_interval_s=1.0,
        emit=_SILENT, now=lambda: clock[0],
    )

    def look(depth, progress):
        clock[0] += 1.0
        return det.observe(depth, progress)

    assert look(5, 10) is None  # first look: baseline
    assert look(5, 10) is None  # stall 1
    assert look(5, 10) is None  # stall 2
    ev = look(5, 10)            # stall 3 -> fire
    assert ev is not None and ev["kind"] == "queue_stall"
    assert ev["queue"] == "serve" and ev["depth"] == 5
    # progress resumes: streak resets, no refire
    assert look(5, 11) is None
    assert look(5, 11) is None and look(5, 11) is None
    # rapid-fire scrapes inside min_interval don't fake a stall
    det2 = anomaly.QueueStallDetector(
        "x", observations=2, min_interval_s=10.0,
        emit=_SILENT, now=lambda: clock[0],
    )
    assert det2.observe(1, 0) is None
    assert det2.observe(1, 0) is None  # same instant: not counted
    assert det2.observe(1, 0) is None


def test_pipeline_stall_poll_from_snapshot():
    # pre-seed the process-global detector with a zero min-interval so
    # the poll path is testable without real flush-cadence sleeps
    anomaly._pipeline_stall = anomaly.QueueStallDetector(
        "pipeline", observations=3, min_interval_s=0.0, emit=_SILENT
    )
    for _ in range(4):
        anomaly.observe_pipeline(
            {"reorder_depth": {"value": 2}, "batches": 7}
        )
    assert any(
        a["kind"] == "queue_stall" and a.get("queue") == "pipeline"
        for a in anomaly.active()
    )
    # malformed snapshots are ignored, never fatal
    anomaly.observe_pipeline({"nonsense": True})


# --------------------------------------------------------- advisory hook
def test_tau_controller_consumes_straggler_advisory():
    from sparknet_tpu.parallel.tau_controller import TauController

    # share 15% is below the normal 25% widen threshold...
    c = TauController(tau=4, tau_min=1, tau_max=64)
    assert c.observe_round(round_s=1.0, sync_s=0.15, loss=1.0) == 4
    # ...but above the halved threshold while a straggler is active
    c2 = TauController(tau=4, tau_min=1, tau_max=64)
    nxt = c2.observe_round(
        round_s=1.0, sync_s=0.15, loss=1.0,
        advisories=[{"kind": "straggler", "rank": 1}],
    )
    assert nxt == 8
    assert c2.decisions[-1]["action"] == "widen"
    assert c2.decisions[-1]["straggler_advisory"] is True


# ------------------------------------------------------ heartbeat piggyback
def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_heartbeat_piggyback_merges_worker_snapshots():
    """The tentpole socket path, in-process: a rank-1 heartbeat client
    publishes stats frames that rank 0's server merges — no
    jax.distributed, the fabric is plain TCP."""
    from sparknet_tpu.parallel.multihost import _Heartbeat

    tl = timeline.Timeline(fence=False)
    timeline.set_current(tl)
    tl.start()
    with tl.phase("compiled_step"):
        time.sleep(0.02)
    port = _free_port()
    hb0 = _Heartbeat("127.0.0.1", port, 0, 2, interval=0.05, timeout=10.0)
    hb1 = _Heartbeat("127.0.0.1", port, 1, 2, interval=0.05, timeout=10.0)
    try:
        agg = aggregate.get_aggregator()
        assert agg is not None  # rank 0's server created it
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            snap = agg.snapshot()
            if snap["ranks"].get("1", {}).get("phases"):
                break
            time.sleep(0.02)
        else:
            pytest.fail(f"rank 1 snapshot never merged: {agg.snapshot()}")
        assert "compiled_step" in snap["ranks"]["1"]["phases"]
        assert "r1" in agg.table()
    finally:
        hb1.close()
        hb0.close()


def test_piggyback_disabled_by_env(monkeypatch):
    monkeypatch.setenv("SPARKNET_CLUSTER_TELEMETRY", "0")
    from sparknet_tpu.parallel.multihost import _Heartbeat

    port = _free_port()
    hb0 = _Heartbeat("127.0.0.1", port, 0, 2, interval=0.05, timeout=5.0)
    hb1 = _Heartbeat("127.0.0.1", port, 1, 2, interval=0.05, timeout=5.0)
    try:
        assert aggregate.get_aggregator() is None
        assert hb1._publisher is None
        time.sleep(0.2)  # pings flow; no stats frames, no crash
    finally:
        hb1.close()
        hb0.close()


# --------------------------------------------------------- flight recorder
def test_flight_disabled_mode_is_allocation_free():
    assert not flight.enabled()
    f = print
    assert flight.tee_log(f) is f  # identity: nothing wrapped
    assert flight.note("x", a=1) is None
    assert flight.dump("/tmp", "t") is None
    assert flight.add_log("line") is None


def test_flight_rings_are_bounded_and_dump_round_trips(tmp_path):
    flight.enable(capacity=4, log_capacity=2)
    for i in range(10):
        flight.note("tick", i=i)
        flight.add_log(f"line {i}")
    snap = flight.snapshot()
    assert [e["i"] for e in snap["events"]] == [6, 7, 8, 9]
    assert snap["logs"] == ["line 8", "line 9"]
    path = flight.dump(str(tmp_path), tag="test")
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["version"] == 1 and len(doc["events"]) == 4
    assert "registry" in doc and "timeline" in doc


def test_flight_configure_from_env(tmp_path, monkeypatch):
    assert flight.configure_from_env() is False  # nothing armed
    monkeypatch.setenv("SPARKNET_SUPERVISE_DIR", str(tmp_path))
    assert flight.configure_from_env() is True  # supervised: armed
    flight.disable()
    monkeypatch.setenv("SPARKNET_FLIGHT", "0")
    assert flight.configure_from_env() is False  # explicit off wins


def test_failure_record_references_flight_dump(tmp_path, monkeypatch):
    from sparknet_tpu.supervise import records

    monkeypatch.setenv(records.RECORD_DIR_ENV, str(tmp_path))
    flight.enable()
    flight.add_log("about to die")
    flight.note("anomaly", anomaly_kind="loss_spike")
    path = records.write_failure_record(
        process_id=0, kind="exception", reason="test", exit_code=1
    )
    rec = json.load(open(path))
    assert rec["flight_recorder"] and os.path.exists(rec["flight_recorder"])
    dump = json.load(open(rec["flight_recorder"]))
    assert "about to die" in dump["logs"]
    assert any(e.get("kind") == "anomaly" for e in dump["events"])
    # dump sits next to the record, in failures/
    assert os.path.dirname(rec["flight_recorder"]) == os.path.dirname(path)


# ----------------------------------------------------------------- serve
class _StubEngine:
    buckets = (4,)
    output = "prob"
    metrics = None

    def infer(self, rows):
        rows = np.asarray(rows, np.float32)
        return rows.reshape(len(rows), -1)[:, :3]

    def postprocess(self, out, top_k):
        idx = np.argsort(-out, axis=-1)[:, :top_k]
        return idx, np.take_along_axis(out, idx, axis=-1)


def _get(host, port, path):
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read().decode()
    ctype = resp.getheader("Content-Type") or ""
    conn.close()
    return resp.status, ctype, body


def test_healthz_anomalies_field_and_degraded_status():
    from sparknet_tpu.serve.metrics import ServeMetrics
    from sparknet_tpu.serve.server import InferenceServer

    srv = InferenceServer(
        _StubEngine(), metrics=ServeMetrics((4,)), port=0, model_name="stub"
    ).start()
    try:
        st, _, body = _get(srv.host, srv.port, "/healthz")
        doc = json.loads(body)
        assert st == 200 and doc["status"] == "ok"
        assert doc["anomalies"] == []
        # a live stall advisory degrades the status without touching
        # the shed/cancelled machinery
        anomaly.fire("queue_stall", key="serve", queue="serve", depth=3,
                     emit=_SILENT)
        st, _, body = _get(srv.host, srv.port, "/healthz")
        doc = json.loads(body)
        assert doc["status"] == "degraded"
        assert any(a["kind"] == "queue_stall" for a in doc["anomalies"])
        # non-degrading anomaly kinds report but don't degrade
        anomaly.clear()
        anomaly.fire("loss_spike", value=9.0, emit=_SILENT)
        st, _, body = _get(srv.host, srv.port, "/healthz")
        doc = json.loads(body)
        assert doc["status"] == "ok" and len(doc["anomalies"]) == 1
    finally:
        srv.stop()


def test_dash_route_serves_live_html():
    from sparknet_tpu.serve.metrics import ServeMetrics
    from sparknet_tpu.serve.server import InferenceServer

    srv = InferenceServer(
        _StubEngine(), metrics=ServeMetrics((4,)), port=0, model_name="stub"
    ).start()
    try:
        c = srv.client()
        st, _ = c.classify(np.ones((2, 3)), top_k=2)
        assert st == 200
        anomaly.fire("loss_spike", value=9.0, emit=_SILENT)
        st, ctype, body = _get(srv.host, srv.port, "/dash")
        assert st == 200 and ctype.startswith("text/html")
        assert body.startswith("<!doctype html>")
        assert "sparknet" in body and "stub" in body
        # live numbers: the one classify request shows in the tiles
        assert '<div class="value">1</div>' in body
        # the anomaly feed rendered the firing
        assert "loss_spike" in body
    finally:
        srv.stop()


def test_dash_renders_cluster_bars_from_snapshot():
    agg = aggregate.ClusterAggregator(
        detector=anomaly.StragglerDetector(emit=_SILENT)
    )
    for r in (0, 1):
        agg.ingest(_payload(
            r, 1,
            {"compiled_step": [3.0, 5], "input_wait": [1.0, 5]},
            wall=4.0,
        ))
    html_ = dash.render_html(
        REGISTRY.snapshot(), serve_metrics={}, cluster=agg.snapshot()
    )
    assert "rank 0" in html_ and "rank 1" in html_
    assert 'data-phase="compiled_step"' in html_
    assert "<table" in html_  # the accessibility table view exists
    assert "legend" in html_


# ------------------------------------------------------------ trace counters
def test_trace_ring_drops_are_counted():
    trace.enable(capacity=4)
    try:
        for i in range(10):
            with trace.span(f"s{i}"):
                pass
        assert trace.dropped_spans() == 6
        assert REGISTRY.counter("trace_dropped_spans").snapshot() >= 6
    finally:
        trace.disable()


def test_sidecar_merge_failures_are_counted(tmp_path):
    path = str(tmp_path / "t.json")
    trace.enable(path)
    try:
        with trace.span("work"):
            pass
        with open(f"{path}.part-999.json", "w") as fh:
            fh.write("{torn")  # unreadable sidecar
        assert trace.write() == path
        assert trace.sidecar_errors() == 1
        assert REGISTRY.counter("trace_sidecar_errors").snapshot() >= 1
        json.load(open(path))  # the merge itself survived
    finally:
        trace.disable()


# ------------------------------------------------------------------- e2e
NET_TXT = """
name: "tiny"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 10
          weight_filler { type: "gaussian" std: 0.05 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
"""


def test_chaos_killed_child_leaves_referenced_flight_dump(
    tmp_path, monkeypatch, capfd
):
    """THE postmortem acceptance run: ``caffe train --supervise`` with
    a ``supervisor.child_crash`` injection — the killed child's failure
    record must reference a readable flight-recorder dump whose log
    ring holds the loop's last lines, and the supervisor's report must
    surface the dump path."""
    from sparknet_tpu import chaos
    from sparknet_tpu.supervise import records
    from sparknet_tpu.supervise.supervisor import REPORT_NAME
    from sparknet_tpu.tools import caffe as caffe_cli

    chaos.clear()
    monkeypatch.setenv("SPARKNET_SUPERVISE_RESTARTS", "3")
    monkeypatch.setenv("SPARKNET_SUPERVISE_BACKOFF", "0.05")
    monkeypatch.setenv("SPARKNET_SUPERVISE_BACKOFF_CAP", "0.1")
    d = str(tmp_path / "run")
    os.makedirs(d)
    with open(os.path.join(d, "net.prototxt"), "w") as fh:
        fh.write(NET_TXT)
    with open(os.path.join(d, "solver.prototxt"), "w") as fh:
        fh.write(
            'net: "net.prototxt"\nbase_lr: 0.05\nlr_policy: "fixed"\n'
            'momentum: 0.9\nmax_iter: 8\nsnapshot: 4\n'
            f'snapshot_prefix: "{d}/snap"\ndisplay: 0\n'
        )
    try:
        caffe_cli.main([
            "train", "--supervise",
            "--chaos=supervisor.child_crash@after=4",
            f"--solver={d}/solver.prototxt", "--synthetic",
            "--synthetic-n=64", "--batch-size=8", "--seed=3",
            "--data-workers=0", "--native-loader=off",
        ])
    finally:
        chaos.clear()
    (rec,) = records.read_failure_records(d)
    assert rec["kind"] == "chaos.child_crash"
    fpath = rec["flight_recorder"]
    assert fpath and os.path.exists(fpath), rec
    dump = json.load(open(fpath))
    assert dump["version"] == 1
    # the loop's log ring made it into the dump (snapshot lines at
    # iteration 4 precede the injected crash)
    assert any("Snapshotting" in ln for ln in dump["logs"]), dump["logs"]
    with open(os.path.join(d, REPORT_NAME)) as fh:
        report = json.load(fh)
    assert report["final_status"] == "done"
    assert fpath in report["generations"][0]["flight_recorders"]
    out = capfd.readouterr().out
    assert "flight recorder dump:" in out
