"""telemetry/ subsystem: registry, span tracer, timeline, exporters.

The acceptance bar (ISSUE 5): span nesting survives threads, a merged
multi-process trace validates against the Chrome trace-event schema,
registry label cardinality is bounded, the disabled tracer is an
allocation-free singleton, Prometheus text serves a counter + gauge +
histogram from the live HTTP server, and a CPU ``caffe train --trace``
e2e prints a step-time breakdown attributing ≥90% of measured loop
wall time.  All CPU-only and fast — tier-1, no ``slow`` marker.
"""

import gc
import json
import multiprocessing
import os
import re
import threading
import time

import numpy as np
import pytest

from sparknet_tpu.telemetry import exporter, timeline, trace
from sparknet_tpu.telemetry.registry import (
    REGISTRY,
    Counter,
    Gauge,
    LatencyHistogram,
    NamedCounters,
    Registry,
)

_HAVE_FORK = "fork" in multiprocessing.get_all_start_methods()
fork_only = pytest.mark.skipif(
    not _HAVE_FORK, reason="sidecar merge exercises forked children"
)


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """No tracer state, current timeline, or owner-pid env may leak
    between tests."""
    yield
    trace.disable()
    timeline.set_current(None)
    os.environ.pop(trace.OWNER_PID_ENV, None)
    os.environ.pop(trace.TRACE_ENV, None)


# ---------------------------------------------------------------- registry
def test_registry_primitives_and_labels():
    r = Registry()
    c = r.counter("events", kind="fire")
    c.inc(2)
    assert r.counter("events", kind="fire") is c  # same labels -> same series
    assert r.counter("events", kind="recover") is not c
    g = r.gauge("depth")
    g.set(3)
    g.add(-1)
    h = r.histogram("latency")
    h.observe(0.02)
    snap = r.snapshot()
    assert snap["metrics"]["events"]["kind=fire"] == 2
    assert snap["metrics"]["depth"][""] == {"value": 2, "max": 3}
    assert snap["metrics"]["latency"][""]["count"] == 1
    json.dumps(snap)  # the whole tree must stay JSON-able


def test_registry_type_conflicts_raise():
    r = Registry()
    r.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x")


def test_registry_label_cardinality_is_bounded():
    r = Registry(max_series=4)
    for i in range(10):
        r.counter("hot", request=i).inc()
    fam = r.families()["hot"]
    # 4 real series + the one shared overflow series
    assert len(fam["series"]) == 5
    assert r.dropped_series.snapshot() == 6
    # every overflow inc landed on the same shared series
    from sparknet_tpu.telemetry.registry import OVERFLOW_KEY

    assert fam["series"][OVERFLOW_KEY].snapshot() == 6
    # the overflow spill is visible in snapshots (and Prometheus)
    assert r.snapshot()["dropped_series"] == 6


def test_registry_sources_are_weak_and_newest_wins():
    r = Registry()

    class Src:
        def __init__(self, tag):
            self.tag = tag

        def snapshot(self):
            return {"tag": self.tag}

    a = Src("a")
    r.register_source("sub", a)
    assert r.snapshot()["sub"] == {"tag": "a"}
    b = Src("b")
    r.register_source("sub", b)  # newest registration wins
    assert r.snapshot()["sub"] == {"tag": "b"}
    del a, b
    gc.collect()
    assert "sub" not in r.snapshot()  # weakly held: dead sources drop out


def test_named_counters_shared_shape():
    nc = NamedCounters()
    nc.inc("restarts")
    nc.inc("restarts", 2)
    assert nc.count("restarts") == 3
    assert nc.count("missing") == 0
    assert nc.snapshot() == {"restarts": 3}
    nc.reset()
    assert nc.snapshot() == {}


# ------------------------------------------------------------------ tracer
def test_disabled_mode_is_an_allocation_free_singleton():
    assert not trace.enabled()
    s1 = trace.span("a", key=1)
    s2 = trace.span("b")
    assert s1 is s2  # ONE shared no-op object — nothing allocated
    with s1:
        pass
    assert trace.events() == []

    calls = []

    def fn(x):
        with trace.span("wrapped", cat="t", x=x) as s3:
            calls.append(x)
            assert s3 is s1  # a span with arguments is the same no-op
            return x + 1

    assert fn(1) == 2 and calls == [1]
    assert trace.events() == []
    # record() is also a no-op while disabled
    trace.record("x", 0, 1.0)
    assert trace.events() == []
    # and so is everything a feed reports with no timeline on: NULL takes
    # durations measured elsewhere and keeps nothing, a background phase
    # is one reusable object that leaves nothing in flight behind it
    n = timeline.NULL
    assert n.add("feed.produce", 1.0, 3) is None
    assert n.phase_seconds() == {} and n.snapshot() == {}
    assert timeline.current_phase("feed.source") is n.phase("input_wait")
    bg = timeline.background_phase("feed.source")
    with bg as entered:
        assert entered is bg
        assert timeline._in_flight[threading.get_ident()][0] == "feed.source"
    with bg:
        pass
    assert timeline._in_flight == {} and n.phase_seconds() == {}


def test_span_nesting_across_threads():
    trace.enable()
    try:
        with trace.span("outer", cat="t"):
            with trace.span("inner", cat="t"):
                time.sleep(0.002)

        # both alive at once: a thread that has ended lends its ident
        # (an address) to the next one started
        together = threading.Barrier(2)

        def worker():
            with trace.span("thread_outer"):
                with trace.span("thread_inner"):
                    together.wait(5)
                    time.sleep(0.002)

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = {(e["name"], e["tid"]): e for e in trace.events()}
        main_tid = threading.get_ident()
        outer = evs[("outer", main_tid)]
        inner = evs[("inner", main_tid)]
        # nesting: the inner span's interval is contained in the outer's
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        # thread-awareness: worker spans carry their own tids
        tids = {
            e["tid"] for e in trace.events() if e["name"] == "thread_inner"
        }
        assert len(tids) == 2 and main_tid not in tids
        for tid in tids:
            t_out = evs[("thread_outer", tid)]
            t_in = evs[("thread_inner", tid)]
            assert t_out["ts"] <= t_in["ts"]
            assert t_in["dur"] <= t_out["dur"] + 1
    finally:
        trace.disable()


def test_ring_buffer_is_bounded():
    trace.enable(capacity=8)
    try:
        for i in range(50):
            with trace.span(f"s{i}"):
                pass
        evs = trace.events()
        assert len(evs) == 8
        assert evs[-1]["name"] == "s49"  # newest kept, oldest evicted
    finally:
        trace.disable()


def _validate_chrome_trace(doc):
    """The trace-event schema subset Perfetto requires: a traceEvents
    list of events with name/ph/pid/tid, complete events carrying
    numeric ts+dur, metadata events carrying args."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    assert doc["traceEvents"], "empty trace"
    for e in doc["traceEvents"]:
        assert isinstance(e["name"], str)
        assert e["ph"] in ("X", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        else:
            assert "name" in e["args"]


def _sidecar_child(path):
    # runs in a forked child: the at-fork hook cleared inherited spans
    # and demoted us to sidecar; our spans land in a part file
    with trace.span("child_work", cat="test"):
        time.sleep(0.002)
    out = trace.flush_sidecar()
    os._exit(0 if out and os.path.exists(out) else 17)


@fork_only
def test_multiprocess_merge_validates_against_trace_event_schema(tmp_path):
    path = str(tmp_path / "merged.json")
    trace.enable(path)
    try:
        with trace.span("parent_work", cat="test"):
            ctx = multiprocessing.get_context("fork")
            procs = [
                ctx.Process(target=_sidecar_child, args=(path,))
                for _ in range(2)
            ]
            for p in procs:
                p.start()
            for p in procs:
                p.join(10)
            assert all(p.exitcode == 0 for p in procs)
        written = trace.write()
        assert written == path
    finally:
        trace.disable()
    doc = json.load(open(path))
    _validate_chrome_trace(doc)
    by_pid = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            by_pid.setdefault(e["pid"], []).append(e["name"])
    # merged by pid: the parent plus BOTH sidecar children
    assert len(by_pid) == 3
    assert sum("child_work" in names for names in by_pid.values()) == 2
    # part files are consumed by the merge
    assert not list(tmp_path.glob("merged.json.part-*"))


def test_fork_hook_drops_inherited_spans(tmp_path):
    if not _HAVE_FORK:
        pytest.skip("fork start method unavailable")
    path = str(tmp_path / "t.json")
    trace.enable(path)
    try:
        with trace.span("parent_only"):
            pass

        def child():
            # inherited buffer was cleared: only OUR span may appear
            names = [e["name"] for e in trace.events()]
            ok = "parent_only" not in names
            with trace.span("child_span"):
                pass
            out = trace.flush_sidecar()
            os._exit(0 if (ok and out) else 23)

        p = multiprocessing.get_context("fork").Process(target=child)
        p.start()
        p.join(10)
        assert p.exitcode == 0
        part = json.load(open(trace.part_path(path, p.pid)))
        names = [e["name"] for e in part if e["ph"] == "X"]
        assert names == ["child_span"]
    finally:
        trace.disable()


# ---------------------------------------------------------------- timeline
def test_timeline_nested_phases_attribute_exclusively(monkeypatch):
    """The arithmetic of exclusive attribution, on a clock the test
    moves: a "sleep" advances it and nothing else does, so every share
    is exact (a wall clock beside loaded workers overshoots a sleep by
    more than the phase is long)."""
    import types

    now = [100.0]

    def sleep(seconds):
        now[0] += seconds

    monkeypatch.setattr(timeline, "time", types.SimpleNamespace(
        perf_counter=lambda: now[0], time_ns=time.time_ns,
    ))
    tl = timeline.Timeline()
    tl.start()
    sleep(0.5)  # outside every phase
    with tl.phase("device_put"):
        sleep(0.25)
        with tl.phase("multihost_sync"):
            sleep(2.0)
        sleep(0.75)
    with tl.phase("multihost_sync"):  # a second, top-level entry
        sleep(0.5)
    tl.stop()
    sleep(8.0)  # a stopped timeline's wall does not run
    snap = tl.snapshot()
    phases = snap["phases"]
    # the inner phase owns its time; the outer keeps only its exclusive
    # share — so the table can never double-count
    assert phases["multihost_sync"] == {
        "total_s": 2.5, "count": 2, "mean_ms": 1250.0,
    }
    assert phases["device_put"] == {
        "total_s": 1.0, "count": 1, "mean_ms": 1000.0,
    }
    assert snap["wall_s"] == 4.0
    assert snap["attributed_s"] == 3.5
    assert snap["attributed_frac"] == 0.875
    table = tl.table()
    assert "device_put" in table and "multihost_sync" in table
    assert re.search(r"attributed 87\.5% of", table)


def test_timeline_threads_do_not_cross_nest():
    tl = timeline.Timeline()
    tl.start()

    def worker():
        with tl.phase("input_wait"):
            time.sleep(0.01)

    t = threading.Thread(target=worker)
    with tl.phase("compiled_step"):
        t.start()
        t.join()
    tl.stop()
    phases = tl.snapshot()["phases"]
    # the worker's phase ran on its own stack: compiled_step keeps its
    # full duration (no cross-thread child subtraction)
    assert phases["compiled_step"]["total_s"] >= 0.009
    assert phases["input_wait"]["total_s"] >= 0.009


def test_null_timeline_is_inert():
    n = timeline.NULL
    assert not n.enabled and not n.fence
    p1 = n.phase("a")
    assert p1 is n.phase("b")  # shared no-op context manager
    with p1:
        pass
    assert n.snapshot() == {} and n.table() == ""
    timeline.set_current(None)
    assert timeline.current() is timeline.NULL
    with timeline.current_phase("multihost_sync"):
        pass  # no-op without an active timeline


def test_timeline_add_takes_durations_measured_elsewhere():
    tl = timeline.Timeline()
    tl.start()
    with tl.phase("input_wait"):
        tl.add("feed.loader_blocked", 0.25)  # inside a phase: nothing
        tl.add("feed.produce", 1.5, count=3)  # is taken out of it
        time.sleep(0.01)
    tl.add("feed.produce", 0.5, count=1)
    tl.stop()
    seconds = tl.phase_seconds()
    assert seconds["feed.loader_blocked"] == pytest.approx(0.25)
    assert seconds["feed.produce"] == pytest.approx(2.0)
    assert seconds["input_wait"] >= 0.009
    assert tl.snapshot()["background"]["feed.produce"] == {
        "total_s": 2.0, "count": 4, "mean_ms": 500.0,
    }
    # a duration that began before the timeline was made is cut to it
    born = time.perf_counter()
    late = timeline.Timeline()
    late.add("feed.source", 10.0, began=born - 9.9)
    assert 0.09 < late.phase_seconds()["feed.source"] < 0.2


def test_feed_phases_are_beside_the_loop_not_attributed():
    tl = timeline.Timeline()
    tl.start()
    with tl.phase("compiled_step"):
        time.sleep(0.02)
    with tl.phase("feed.h2d"):  # a phase by name, wherever it ran
        time.sleep(0.005)
    tl.add("feed.produce", 5.0)  # worker seconds: more than the wall
    tl.stop()
    snap = tl.snapshot()
    assert set(snap["phases"]) == {"compiled_step"}
    assert set(snap["background"]) == {"feed.h2d", "feed.produce"}
    assert snap["attributed_s"] <= snap["wall_s"] + 1e-6
    assert 0.5 < snap["attributed_frac"] <= 1.0
    assert set(tl.phase_seconds()) == {
        "compiled_step", "feed.h2d", "feed.produce",
    }
    lines = tl.table().splitlines()
    attributed = next(
        i for i, ln in enumerate(lines) if ln.startswith("attributed")
    )
    first = "\n".join(lines[:attributed])
    second = "\n".join(lines[attributed + 1:])
    assert "compiled_step" in first and "feed." not in first
    assert "beside the loop" in second
    assert "feed.h2d" in second and "feed.produce" in second
    # with no background phase there is no second block
    plain = timeline.Timeline()
    with plain.phase("eval"):
        pass
    assert plain.table().splitlines()[-1].startswith("attributed")


def test_assigning_solver_timeline_makes_it_current():
    from sparknet_tpu.solver.trainer import Solver

    solver = Solver.__new__(Solver)  # the property needs no built net
    solver._timeline = timeline.NULL
    tl = timeline.Timeline(fence=False)
    solver.timeline = tl
    assert solver.timeline is tl and timeline.current() is tl
    with timeline.current_phase("multihost_sync"):
        time.sleep(0.002)
    assert tl.phase_seconds()["multihost_sync"] > 0.001
    solver.timeline = timeline.NULL
    assert solver.timeline is timeline.NULL
    assert timeline.current() is timeline.NULL
    with timeline.current_phase("multihost_sync"):
        pass
    assert tl.snapshot()["phases"]["multihost_sync"]["count"] == 1


def _staging_sum(source_s, consume_s, steps=12):
    """The three phases of prefetch_to_device's thread over ``steps``
    consumes, read from a timeline made after the feed has started and
    taken away before it stops, as a benchmark windows it."""
    from sparknet_tpu.data.prefetch import prefetch_to_device

    def source():
        while True:
            time.sleep(source_s)
            yield {"x": np.zeros(4, np.float32)}

    feed = prefetch_to_device(source(), size=2, put=lambda b: b)
    try:
        for _ in range(3):
            next(feed)
            time.sleep(consume_s)
        tl = timeline.Timeline(fence=False)
        timeline.set_current(tl)
        t0 = time.perf_counter()
        for _ in range(steps):
            next(feed)
            time.sleep(consume_s)
        timeline.set_current(None)
        wall = time.perf_counter() - t0
    finally:
        feed.close()
    return tl.phase_seconds(), wall


@pytest.mark.parametrize("slow", ["source", "consumer"])
def test_staging_phases_add_up_to_the_wall_time(slow):
    source_s, consume_s = (0.02, 0.0) if slow == "source" else (0.0, 0.02)
    seconds, wall = _staging_sum(source_s, consume_s)
    total = (
        seconds["feed.source"] + seconds["feed.h2d"]
        + seconds["feed.backpressure"]
    )
    # serial thread, phases in flight at either edge split at the edge
    assert total == pytest.approx(wall, rel=0.10), (seconds, wall)
    big = "feed.source" if slow == "source" else "feed.backpressure"
    assert seconds[big] > 0.8 * wall, (seconds, wall)
    assert timeline._in_flight == {}  # the thread has stopped


def test_a_replaced_timeline_takes_its_part_of_a_phase_in_flight():
    """A staging thread blocked across the end of a window: the window's
    timeline gets the seconds up to its replacement, the next one the
    rest, and nothing is counted twice."""
    first, second = timeline.Timeline(), timeline.Timeline()
    started, release = threading.Event(), threading.Event()

    def blocked():
        with timeline.background_phase("feed.backpressure"):
            started.set()
            release.wait(5)

    t = threading.Thread(target=blocked)
    t.start()
    started.wait(5)
    t0 = time.perf_counter()
    timeline.set_current(first)  # made while the phase is in flight
    time.sleep(0.03)
    timeline.set_current(second)
    t1 = time.perf_counter()
    sealed = first.phase_seconds()["feed.backpressure"]
    time.sleep(0.02)
    release.set()
    t.join()
    t2 = time.perf_counter()
    timeline.set_current(None)
    assert 0.03 <= sealed <= t1 - t0
    assert first.phase_seconds()["feed.backpressure"] == sealed
    rest = second.phase_seconds()["feed.backpressure"]
    assert 0.02 <= rest and sealed + rest <= t2 - t0
    # the phase ended once, in the second timeline's turn
    assert first.snapshot()["background"]["feed.backpressure"]["count"] == 0
    assert second.snapshot()["background"]["feed.backpressure"]["count"] == 1


def test_background_phases_race_the_change_of_timeline_safely():
    """Threads enter and leave background phases while the loop thread
    changes the current timeline (the benchmark does, twice a traced
    run): no error in either, and no second counted twice."""
    stop, errors = threading.Event(), []

    def staging():
        phase = timeline.background_phase("feed.source")
        try:
            while not stop.is_set():
                with phase:
                    pass
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=staging) for _ in range(4)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    made = []
    try:
        for _ in range(300):
            made.append(timeline.Timeline(fence=False))
            timeline.set_current(made[-1])
    finally:
        timeline.set_current(None)
        stop.set()
        for t in threads:
            t.join()
    wall = time.perf_counter() - t0
    assert errors == [] and timeline._in_flight == {}
    total = sum(tl.phase_seconds().get("feed.source", 0.0) for tl in made)
    assert 0 < total <= 4 * wall


# --------------------------------------------------------------- exporter
def test_prometheus_rendering_counter_gauge_histogram():
    r = Registry()
    r.counter("fires", point="pipeline").inc(3)
    r.gauge("depth").set(7)
    r.histogram("wait").observe(0.005)
    text = exporter.render_prometheus(registry=r)
    assert "# TYPE sparknet_fires_total counter" in text
    assert 'sparknet_fires_total{point="pipeline"} 3' in text
    assert "# TYPE sparknet_depth gauge" in text
    assert "sparknet_depth 7" in text
    assert "# TYPE sparknet_wait histogram" in text
    assert 'sparknet_wait_bucket{le="+Inf"} 1' in text
    assert "sparknet_wait_count 1" in text
    # cumulative: every bucket count is <= the next
    counts = [
        int(m.group(1))
        for m in re.finditer(r'sparknet_wait_bucket\{le="[^"]+"\} (\d+)', text)
    ]
    assert counts == sorted(counts)


def test_prometheus_rendering_of_serve_metrics():
    from sparknet_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics((4,))
    m.record_request(0.01, rows=2)
    m.record_batch(4, rows=2, padded_rows=2, device_s=0.004)
    m.set_queue_depth(3)
    text = exporter.render_prometheus(serve_metrics=m)
    assert "# TYPE sparknet_serve_requests_total counter" in text
    assert "sparknet_serve_requests_total 1" in text
    assert "# TYPE sparknet_serve_queue_depth gauge" in text
    assert (
        "# TYPE sparknet_serve_request_latency_seconds histogram" in text
    )
    assert "sparknet_serve_request_latency_seconds_count 1" in text
    assert 'sparknet_serve_batches_total{bucket="4"} 1' in text
    assert "sparknet_serve_healthy 1" in text


def test_periodic_flush_emits_and_stops():
    lines = []
    stop = exporter.maybe_start_periodic(emit=lines.append, interval_s=0.03)
    time.sleep(0.11)
    stop()
    n = len(lines)
    assert n >= 2  # ticks + the final line at stop
    for line in lines:
        assert line.startswith("telemetry: ")
        json.loads(line[len("telemetry: "):])
    time.sleep(0.08)
    assert len(lines) == n  # stopped means stopped


def test_periodic_flush_default_off(monkeypatch):
    monkeypatch.delenv(exporter.PERIODIC_ENV, raising=False)
    lines = []
    stop = exporter.maybe_start_periodic(emit=lines.append)
    time.sleep(0.03)
    stop()
    assert lines == []
    monkeypatch.setenv(exporter.PERIODIC_ENV, "nonsense")
    with pytest.raises(ValueError, match="must be a number"):
        exporter.periodic_interval()


# ------------------------------------------------------------- HTTP server
class _StubEngine:
    """Minimal engine contract for the HTTP layer (buckets + infer +
    postprocess); keeps the route tests off the XLA compile path."""

    buckets = (4,)
    output = "prob"
    metrics = None

    def infer(self, rows):
        rows = np.asarray(rows, np.float32)
        return rows.reshape(len(rows), -1)[:, :3]

    def postprocess(self, out, top_k):
        idx = np.argsort(-out, axis=-1)[:, :top_k]
        return idx, np.take_along_axis(out, idx, axis=-1)


def test_server_serves_prometheus_and_json_metrics():
    import http.client

    from sparknet_tpu.serve.metrics import ServeMetrics
    from sparknet_tpu.serve.server import InferenceServer

    m = ServeMetrics((4,))
    srv = InferenceServer(
        _StubEngine(), metrics=m, port=0, model_name="stub"
    ).start()
    try:
        c = srv.client()
        st, _ = c.classify(np.ones((2, 3)), top_k=2)
        assert st == 200
        # the JSON snapshot moved to /metrics.json; Client.metrics()
        # follows it and keeps its dict shape
        st, met = c.metrics()
        assert st == 200 and met["requests"] == 1

        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=10)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        conn.close()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/plain")
        # the acceptance bar: at least one counter, gauge and histogram
        assert "# TYPE sparknet_serve_requests_total counter" in body
        assert "sparknet_serve_requests_total 1" in body
        assert "# TYPE sparknet_serve_queue_depth gauge" in body
        assert (
            "# TYPE sparknet_serve_request_latency_seconds histogram"
            in body
        )
    finally:
        srv.stop()


# ------------------------------------------------------------------- e2e
_TINY_NET = """
name: "tiny"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 10
          weight_filler { type: "gaussian" std: 0.05 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
"""


@fork_only
def test_caffe_train_trace_e2e_attributes_wall_time(tmp_path, capsys):
    """The acceptance run: CPU ``caffe train --trace OUT.json`` emits
    valid Chrome trace JSON (workers' sidecars merged in by pid) and
    prints a step-time breakdown attributing ≥90% of loop wall time."""
    from sparknet_tpu.tools import caffe as caffe_cli

    (tmp_path / "net.prototxt").write_text(_TINY_NET)
    (tmp_path / "solver.prototxt").write_text(
        'net: "net.prototxt"\nbase_lr: 0.05\nlr_policy: "fixed"\n'
        'momentum: 0.9\nmax_iter: 6\nsnapshot: 6\n'
        f'snapshot_prefix: "{tmp_path}/snap"\ndisplay: 0\n'
    )
    out_json = tmp_path / "trace.json"
    caffe_cli.main([
        "train", f"--solver={tmp_path}/solver.prototxt", "--synthetic",
        "--synthetic-n=64", "--batch-size=8", "--seed=3",
        "--data-workers=2", "--native-loader=off",
        f"--trace={out_json}",
    ])
    out = capsys.readouterr().out
    # the breakdown table and its attribution line
    assert "telemetry: step-time breakdown" in out
    mt = re.search(r"attributed (\d+(?:\.\d+)?)% of ([\d.]+)s", out)
    assert mt, out
    assert float(mt.group(1)) >= 90.0, out
    for phase in ("input_wait", "compiled_step", "snapshot"):
        assert re.search(rf"{phase}\s+\d", out), out
    # valid, merged Chrome trace: the 2 pipeline workers' sidecars rode
    # in by pid alongside the trainer's spans
    doc = json.load(open(out_json))
    _validate_chrome_trace(doc)
    pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(pids) >= 3, pids
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"compiled_step", "input_wait", "pipeline.produce"} <= names
    # the run's own cleanup restored tracer state (finish_run)
    assert not trace.enabled()
    assert os.environ.get(trace.TRACE_ENV) in (None, "")


def test_trace_flag_does_not_change_results(tmp_path):
    """--trace observes; it must not perturb the batch stream or the
    trained weights (fencing changes timing only)."""
    from sparknet_tpu.tools import caffe as caffe_cli

    def run(tag, traced):
        d = tmp_path / tag
        d.mkdir()
        (d / "net.prototxt").write_text(_TINY_NET)
        (d / "solver.prototxt").write_text(
            'net: "net.prototxt"\nbase_lr: 0.05\nlr_policy: "fixed"\n'
            'momentum: 0.9\nmax_iter: 4\nsnapshot: 4\n'
            f'snapshot_prefix: "{d}/snap"\ndisplay: 0\n'
        )
        argv = [
            "train", f"--solver={d}/solver.prototxt", "--synthetic",
            "--synthetic-n=64", "--batch-size=8", "--seed=5",
            "--data-workers=0", "--native-loader=off",
        ]
        if traced:
            argv.append(f"--trace={d}/trace.json")
        caffe_cli.main(argv)
        with np.load(f"{d}/snap_iter_4.npz") as z:
            return {k: z[k].copy() for k in z.files}

    traced = run("traced", True)
    clean = run("clean", False)
    assert sorted(traced) == sorted(clean)
    for k in clean:
        np.testing.assert_array_equal(traced[k], clean[k], err_msg=k)


def test_the_per_test_alarm_fails_a_hung_test_by_name(request):
    """tests/conftest.py arms a SIGALRM for every test, so a hang costs
    one test and not the run's clock.  Re-armed short here: the handler
    the fixture installed interrupts a blocked main thread and fails the
    test with its own name."""
    import signal

    assert signal.getitimer(signal.ITIMER_REAL)[0] > 200  # armed, 300 s
    signal.setitimer(signal.ITIMER_REAL, 0.05)
    with pytest.raises(pytest.fail.Exception, match=re.escape(request.node.nodeid)):
        threading.Event().wait(5)  # where a subprocess test hangs
    assert signal.getitimer(signal.ITIMER_REAL)[0] == 0  # one shot


def test_finish_run_merges_the_device_track_and_prints_the_gaps(
    tmp_path, monkeypatch, capsys
):
    """--trace with --profile-dir: the loop is not fenced, and finish_run
    lays the profiler's executions (here: three recorded on the chip, on a
    device clock of their own) beside the spans, by the anchor."""
    from sparknet_tpu import telemetry
    from sparknet_tpu.solver.trainer import Solver
    from sparknet_tpu.utils import profiling

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "benchmark", "recorded_trace.json")) as fh:
        steps = json.load(fh)["devices"]["/device:TPU:0"]["modules"]
    solver = Solver.__new__(Solver)
    solver._timeline = timeline.NULL
    out_json, prof = str(tmp_path / "trace.json"), str(tmp_path / "prof")
    telemetry.install_for_training(solver, out_json, prof)
    assert solver.timeline.enabled and not solver.timeline.fence
    assert timeline.current() is solver.timeline
    # the anchor ran now; the device's clock started 7 s before the epoch's
    before_ns = time.time_ns()
    device_now = before_ns - 7_000_000_000
    modules = [("jit_sparknet_anchor(9)", device_now + 40_000, 5_000)] + [
        (n, device_now + 2_000_000 + s, d) for n, s, d in steps
    ]
    monkeypatch.setattr(profiling, "_anchor", {
        "log_dir": prof, "before_ns": before_ns, "after_ns": before_ns + 100_000,
    })
    monkeypatch.setattr(
        profiling, "device_modules", lambda d: {"/device:TPU:0": modules}
    )
    with solver.timeline.phase("compiled_step"):
        time.sleep(0.002)
    telemetry.finish_run()
    said = capsys.readouterr().out
    assert "device track of 3 executions of jit_fused(" in said
    assert "bracket 100000 ns" in said and "gap_ms" in said
    doc = json.load(open(out_json))
    _validate_chrome_trace(doc)
    device = [e for e in doc["traceEvents"] if e.get("cat") == "device"]
    assert len(device) == 4  # the anchor and the three steps
    offset_us = min(e["ts"] for e in device) - modules[0][1] / 1e3
    assert offset_us == pytest.approx(7e6, abs=60)  # to the bracket's width
    (track,) = [
        e for e in doc["traceEvents"]
        if e["ph"] == "M" and e["args"]["name"] == "device /device:TPU:0"
    ]
    assert {e["tid"] for e in device} == {track["tid"]}
    assert any(e["name"] == "compiled_step" for e in doc["traceEvents"])
    # without --profile-dir the timeline fences, as before
    telemetry.install_for_training(solver, str(tmp_path / "t2.json"))
    assert solver.timeline.fence
    telemetry.finish_run()
    assert "device track" not in capsys.readouterr().out
