"""Offline closed-loop load generator — ``serve --bench``.

Closed-loop: ``concurrency`` worker threads each keep exactly one
request in flight (submit, wait, repeat), the standard serving-bench
shape — throughput is governed by service latency rather than an
open-loop arrival rate, so the requests/s number is reproducible and
comparable across runs (one JSON record out).

Request sizes cycle through ``sizes`` so the bucket ladder is actually
exercised (mixed 1-row and many-row requests, padding on the odd
ones). Inputs are synthetic N(0,1) rows in the net's input shape —
serving cost is shape-dependent, not value-dependent.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional, Sequence

import numpy as np

from .batcher import MicroBatcher
from .metrics import ServeMetrics

#: Decode-heavy open-loop preset (docs/SERVING.md "Batched decode"):
#: interactive arrivals are multi-token session steps over a Zipf-hot
#: population, so nearly every request is decode work and the batched
#: step executable sees sustained multi-session occupancy.  Reusable
#: by the autoscale spike scenarios.  The script follows
#: :func:`sparknet_tpu.autoscale.traffic.parse_script` grammar: a warm
#: flat lane, a 3x decode burst, a recovery lane.
DECODE_HEAVY_SCRIPT = (
    "flat:rate=12,dur=3;"
    "spike:base=12,mult=3,warm=1,burst=2,cool=2"
)

#: Companion kwargs for :func:`run_open_loadgen` under
#: ``DECODE_HEAVY_SCRIPT`` — small hot session population (Zipf 1.1),
#: several greedy continuations per step, a thin batch-class lane so
#: admission control still has something to shed first.
DECODE_HEAVY_KNOBS = dict(
    sessions=8, session_zipf=1.1, session_steps=4, batch_frac=0.1,
)


def run_loadgen(
    engine,
    *,
    n_requests: int = 500,
    sizes: Sequence[int] = (1, 2, 5, 8, 3),
    concurrency: int = 4,
    batcher: Optional[MicroBatcher] = None,
    metrics: Optional[ServeMetrics] = None,
    seed: int = 0,
    timeout_s: float = 120.0,
) -> dict:
    """Push ``n_requests`` mixed-size requests through the batcher and
    return one bench-style record (requests/s, p50/p99, error count,
    the final metrics snapshot). Uses a caller-provided batcher/metrics
    pair when given (the CLI's, so the record and ``/metrics`` agree),
    else builds its own and drains it."""
    own_batcher = batcher is None
    if metrics is None:
        metrics = ServeMetrics(getattr(engine, "buckets", ()))
    if getattr(engine, "metrics", None) is None:
        engine.metrics = metrics
    if batcher is None:
        batcher = MicroBatcher(engine, metrics=metrics)
    input_shape = engine._row_shapes[engine.input_names[0]]
    counter = {"next": 0}
    lock = threading.Lock()
    errors = []

    def worker(wid: int):
        rng = np.random.default_rng(seed + wid)
        while True:
            with lock:
                i = counter["next"]
                if i >= n_requests:
                    return
                counter["next"] = i + 1
            n = int(sizes[i % len(sizes)])
            rows = rng.normal(size=(n,) + input_shape).astype(np.float32)
            try:
                fut = batcher.submit(rows, block=True, timeout=timeout_s)
                out = fut.result(timeout=timeout_s)
                if len(out) != n:
                    raise RuntimeError(
                        f"request {i}: {len(out)} rows back, sent {n}"
                    )
            except Exception as e:  # collected, not raised: the record
                # must say HOW MANY failed, not die on the first
                with lock:
                    errors.append(f"req {i}: {type(e).__name__}: {e}")

    # warm every bucket outside the timed window: the bench measures
    # steady-state serving, not first-request compilation
    if hasattr(engine, "warmup"):
        engine.warmup()
    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=worker, args=(w,), daemon=True)
        for w in range(max(1, concurrency))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout_s)
    dt = max(time.perf_counter() - t0, 1e-9)
    if own_batcher:
        batcher.drain()
    snap = metrics.snapshot()
    total_rows = sum(int(sizes[i % len(sizes)]) for i in range(n_requests))
    lat = snap["request_latency"]
    return {
        "metric": "serve_requests_per_sec",
        "value": round(n_requests / dt, 2),
        "unit": "requests/sec",
        "rows_per_sec": round(total_rows / dt, 2),
        "requests": n_requests,
        "rows": total_rows,
        "concurrency": max(1, concurrency),
        "sizes": list(sizes),
        "buckets": list(getattr(engine, "buckets", ())),
        "platform": _platform(),
        "p50_ms": lat["p50_ms"],
        "p95_ms": lat["p95_ms"],
        "p99_ms": lat["p99_ms"],
        "errors": len(errors),
        "error_samples": errors[:3],
        # serving throughput is host-bound on small nets: every record
        # names the cores it ran on (the PR 2 input_pipeline caveat —
        # a 1-CPU container's numbers are labeled, not trusted)
        "host_cpus": os.cpu_count(),
        "metrics": snap,
    }


def zipf_weights(n: int, a: float) -> np.ndarray:
    """Normalized Zipf pmf over ranks 1..n (``p(k) ∝ 1/k^a``); ``a=0``
    degenerates to uniform.  The hot-session skew shape: real session
    traffic concentrates on a few hot keys (ROADMAP item 4's
    traffic-model brick)."""
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), float(a))
    return w / w.sum()


def run_http_loadgen(
    host: str,
    port: int,
    input_shape: Sequence[int],
    *,
    n_requests: int = 500,
    sizes: Sequence[int] = (1, 2, 5, 8, 3),
    concurrency: int = 4,
    seed: int = 0,
    timeout_s: float = 120.0,
    retries: int = 4,
    sessions: int = 0,
    session_zipf: float = 1.1,
    session_steps: int = 1,
    session_vocab: int = 96,
) -> dict:
    """The closed-loop generator over the WIRE — drives a router (or a
    single replica) through :class:`~sparknet_tpu.serve.server.Client`,
    so replica kills, hot-swaps and 503 backpressure are exercised
    exactly as external traffic sees them.  Client-side retries
    (connection drops, 503) are part of the contract: a request only
    counts ``failed`` when its final answer is missing or non-200 —
    the zero-failed-requests bar the chaos scenarios are held to.
    Latency is measured per request *including* retries (a killed
    replica costs latency, never answers) and the record carries every
    distinct weights generation observed (``served_generations``).

    Every request mints its own trace context client-side
    (``telemetry/reqtrace.py``) and sends it in the
    ``X-Sparknet-Trace`` header, so the tier's stitched waterfalls are
    correlatable with this record: the trace ids of every **failed**
    and every **slower-than-p99** request ride the result dict
    (``failed_request_traces`` / ``slow_request_traces``) — a
    record can name the exact slow requests it measured.

    **Hot-session skew mode** (``sessions > 0``): instead of stateless
    ``/classify`` rows, every request is a session step — it draws a
    session id Zipf-distributed over ``sessions`` ids (exponent
    ``session_zipf``; hot sessions dominate, the realistic traffic
    shape — 0 is uniform), sends the session's FULL token prefix to
    ``/generate`` with ``session_steps`` greedy continuations, and
    appends the generated tokens to the session's history.  One
    request per session in flight at a time (a session IS sequential),
    so each session's prefix is deterministic given ``seed``.  The
    record gains ``sessions`` (count/zipf/per-cache-state counts/hit
    rate/migrations/hottest sessions) and ``session_failed_requests``
    — the zero-is-the-bar gate for chaos runs (docs/SERVING.md
    "Sessions")."""
    from ..telemetry import reqtrace
    from ..telemetry.registry import LatencyHistogram
    from .server import Client

    lat = LatencyHistogram()
    counter = {"next": 0}
    lock = threading.Lock()
    errors = []
    failed_traces = []
    samples = []  # (request index, trace id, latency seconds)
    generations = set()
    quants = set()
    # session-mode state: histories + per-session in-flight locks +
    # per-cache-state counts, all under `lock` except the step itself
    session_probs = (
        zipf_weights(sessions, session_zipf) if sessions > 0 else None
    )
    session_hist: dict = {}
    session_locks: dict = {}
    session_counts: dict = {}
    session_states: dict = {}
    session_migrated = [0]
    session_tokens = [0]  # greedy continuations actually delivered

    def _session_step(i: int, rng, client) -> None:
        k = int(rng.choice(sessions, p=session_probs))
        sid = f"s{k}"
        with lock:
            slock = session_locks.setdefault(sid, threading.Lock())
        ctx = reqtrace.mint()
        tid = ctx.trace_id if ctx is not None else None
        with slock:
            with lock:
                hist = list(
                    session_hist.setdefault(sid, [k % session_vocab])
                )
            t0 = time.perf_counter()
            try:
                status, resp = client.generate(
                    hist, session=sid, steps=session_steps,
                    trace=reqtrace.to_header(ctx) if ctx is not None
                    else None,
                )
                if status != 200:
                    raise RuntimeError(
                        f"HTTP {status}: {resp.get('error')}"
                    )
                if len(resp.get("tokens", ())) != session_steps:
                    raise RuntimeError(
                        f"{len(resp.get('tokens', ()))} tokens back, "
                        f"asked {session_steps}"
                    )
            except Exception as e:
                with lock:
                    errors.append(f"req {i}: {type(e).__name__}: {e}")
                    if tid is not None:
                        failed_traces.append({"req": i, "trace": tid})
                return
            dt = time.perf_counter() - t0
            with lock:
                lat.observe(dt)
                samples.append((i, tid, dt))
                session_hist[sid] = hist + [
                    int(t) for t in resp["tokens"]
                ]
                session_tokens[0] += len(resp["tokens"])
                session_counts[sid] = session_counts.get(sid, 0) + 1
                st = str(resp.get("cache_state", "?"))
                session_states[st] = session_states.get(st, 0) + 1
                if resp.get("migrated"):
                    session_migrated[0] += 1
                if "gen" in resp:
                    generations.add(int(resp["gen"]))
                if resp.get("quant"):
                    quants.add(str(resp["quant"]))

    def worker(wid: int):
        rng = np.random.default_rng(seed + wid)
        client = Client(host, port, timeout=timeout_s, retries=retries)
        while True:
            with lock:
                i = counter["next"]
                if i >= n_requests:
                    return
                counter["next"] = i + 1
            if sessions > 0:
                _session_step(i, rng, client)
                continue
            n = int(sizes[i % len(sizes)])
            rows = rng.normal(size=(n,) + tuple(input_shape)).astype(
                np.float32
            )
            ctx = reqtrace.mint()  # None while tracing is disabled
            tid = ctx.trace_id if ctx is not None else None
            t0 = time.perf_counter()
            try:
                status, resp = client.classify(
                    rows,
                    trace=reqtrace.to_header(ctx) if ctx is not None
                    else None,
                )
                if status != 200:
                    raise RuntimeError(f"HTTP {status}: {resp.get('error')}")
                if len(resp["indices"]) != n:
                    raise RuntimeError(
                        f"{len(resp['indices'])} rows back, sent {n}"
                    )
            except Exception as e:
                with lock:
                    errors.append(f"req {i}: {type(e).__name__}: {e}")
                    if tid is not None:
                        failed_traces.append({"req": i, "trace": tid})
                continue
            dt = time.perf_counter() - t0
            with lock:
                lat.observe(dt)
                samples.append((i, tid, dt))
                if "gen" in resp:
                    generations.add(int(resp["gen"]))
                if resp.get("quant"):
                    quants.add(str(resp["quant"]))

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=worker, args=(w,), daemon=True)
        for w in range(max(1, concurrency))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout_s * 2)
    dt = max(time.perf_counter() - t0, 1e-9)
    snap = lat.snapshot()
    total_rows = sum(int(sizes[i % len(sizes)]) for i in range(n_requests))
    # exact (not histogram-bin-resolution) percentiles from the raw
    # latency list: an A/B at equal load compares p50s that the
    # ~1.47x log-bin ladder is far too coarse to tell apart
    lats = sorted(s[2] for s in samples)
    p50_exact = lats[int(0.50 * (len(lats) - 1))] if lats else None
    p99_exact = lats[int(0.99 * (len(lats) - 1))] if lats else None
    slow_traces = [
        {"req": i, "trace": tid, "ms": round(s_dt * 1000, 3)}
        for i, tid, s_dt in sorted(samples, key=lambda s: -s[2])
        if p99_exact is not None and s_dt > p99_exact and tid is not None
    ][:20]
    return {
        "metric": "serve_http_requests_per_sec",
        "value": round((n_requests - len(errors)) / dt, 2),
        "unit": "requests/sec",
        "rows_per_sec": round(total_rows / dt, 2),
        "requests": n_requests,
        "failed_requests": len(errors),
        "error_samples": errors[:3],
        "concurrency": max(1, concurrency),
        "sizes": list(sizes),
        "p50_ms": snap["p50_ms"],
        "p95_ms": snap["p95_ms"],
        "p99_ms": snap["p99_ms"],
        "p50_exact_ms": (
            round(p50_exact * 1000, 3) if p50_exact is not None else None
        ),
        "p99_exact_ms": (
            round(p99_exact * 1000, 3) if p99_exact is not None else None
        ),
        # the exact requests this record measured as failed or slow —
        # look them up in the tier's /traces waterfalls by trace id
        "failed_request_traces": failed_traces[:20],
        "slow_request_traces": slow_traces,
        "served_generations": sorted(generations),
        # every precision variant that answered (the quant A/B's
        # client-side evidence, like served_generations for hot-swap)
        "served_quants": sorted(quants),
        "host_cpus": os.cpu_count(),
        **(
            {
                # hot-session skew mode: the affinity-realistic story —
                # how skewed the traffic was, what the cache did with
                # it, and how many sessions migrated (killed/ejected
                # holders); session_failed_requests is the chaos gate
                "sessions": {
                    "count": sessions,
                    "zipf": session_zipf,
                    "steps_per_request": session_steps,
                    "distinct": len(session_counts),
                    "states": dict(sorted(session_states.items())),
                    "hit_rate": (
                        round(
                            session_states.get("hit", 0)
                            / max(1, sum(session_states.values())), 4
                        )
                    ),
                    "migrated": session_migrated[0],
                    # aggregate decode throughput the bench's batched
                    # arm compares across SPARKNET_DECODE_BATCH on/off
                    "tokens_generated": session_tokens[0],
                    "tokens_per_sec": round(session_tokens[0] / dt, 2),
                    "hottest": sorted(
                        session_counts.items(),
                        key=lambda kv: -kv[1],
                    )[:5],
                },
                "session_failed_requests": len(errors),
            }
            if sessions > 0 else {}
        ),
    }


def run_open_loadgen(
    host: str,
    port: int,
    input_shape: Sequence[int],
    *,
    script: str,
    seed: int = 0,
    sizes: Sequence[int] = (1,),
    timeout_s: float = 30.0,
    retries: int = 2,
    batch_frac: float = 0.0,
    sessions: int = 0,
    session_zipf: float = 1.1,
    session_steps: int = 1,
    session_vocab: int = 96,
    batch_prefix: int = 1,
    slo_ms: Optional[float] = None,
    max_inflight: int = 256,
) -> dict:
    """**Open-loop** load generator — requests fire on a clock, not on
    completions.  ``script`` is a traffic script
    (:func:`sparknet_tpu.autoscale.traffic.parse_script` grammar); the
    whole plan — arrival offsets, per-request class, session ids — is
    materialized from ``seed`` before the first request, so two runs
    of the same (script, seed) offer byte-identical traffic
    (tests/test_autoscale.py pins this).  Unlike the closed loop
    above, a saturated tier here accumulates *backlog*: offered load
    never bends to served load, which is exactly what a 10x spike does
    to a real service and what the autoscale bench arm measures.

    ``batch_frac`` of arrivals carry ``X-Sparknet-Class: batch`` (the
    sheddable class); with ``sessions > 0`` interactive arrivals
    become ``/generate`` session steps over a Zipf-hot session
    population (serialized per session — a session IS sequential —
    with history appended only on success, so a shed or failed step
    never corrupts the prefix).

    Outcome taxonomy, per class: **ok** (200, right shape), **shed**
    (an explicit admission refusal — 429, or a final 503 after client
    retries), **failed** (transport error, timeout, wrong shape — the
    zero-is-the-bar gate).  Latency is measured from the *scheduled*
    arrival, so dispatch lateness and any backlog wait count against
    the SLO, and ``slo_ok_frac`` = within-SLO oks / offered — sheds
    and failures are SLO misses by definition.  The record's headline
    ``value`` is the **interactive** ``slo_ok_frac`` (the thing the
    tier exists to protect); ``client_overflow`` counts arrivals
    dropped because ``max_inflight`` in-flight threads were already
    outstanding (a loadgen-capacity artifact, reported so it can gate
    a run as unsound)."""
    from ..autoscale.traffic import schedule as _schedule
    from ..telemetry import reqtrace
    from .server import Client

    if slo_ms is None:
        raw = os.environ.get("SPARKNET_SLO_P99_MS", "").strip()
        slo_ms = float(raw) if raw else 250.0
    plan = _schedule(
        script, seed=seed, batch_frac=batch_frac,
        sessions=sessions, session_zipf=session_zipf,
    )
    rng_rows = np.random.default_rng(int(seed) + 2)
    lock = threading.Lock()
    sem = threading.Semaphore(max(1, int(max_inflight)))
    by_class: dict = {}   # class -> {"offered","ok","shed","failed","slo_ok"}
    lat_by_class: dict = {}           # class -> [latency seconds]
    tok_by_class: dict = {}           # class -> tokens delivered on ok
    shed_reasons: dict = {}           # reason/status -> count
    errors: list = []
    failed_traces: list = []
    lateness: list = []
    generations = set()
    session_hist: dict = {}
    session_locks: dict = {}
    session_states: dict = {}
    session_migrated = [0]
    session_failed = [0]
    overflow = [0]

    def _bucket(cls: str) -> dict:
        return by_class.setdefault(cls, {
            "offered": 0, "ok": 0, "shed": 0, "failed": 0, "slo_ok": 0,
        })

    def _finish(cls, i, tid, sched_t, status, err, tokens=0):
        """Classify one outcome under the lock.  ``err`` is an error
        string (failed), ``status`` the final HTTP status; ``tokens``
        is the decode-token count an ok reply delivered (0 for
        classify — the per-class tokens/sec ledger counts generated
        continuations, not classified rows)."""
        dt = time.monotonic() - sched_t
        with lock:
            b = _bucket(cls)
            if err is not None:
                b["failed"] += 1
                errors.append(f"req {i}: {err}")
                if tid is not None:
                    failed_traces.append({"req": i, "trace": tid})
            elif status in (429, 503):
                b["shed"] += 1
                shed_reasons[str(status)] = (
                    shed_reasons.get(str(status), 0) + 1
                )
            else:
                b["ok"] += 1
                lat_by_class.setdefault(cls, []).append(dt)
                tok_by_class[cls] = tok_by_class.get(cls, 0) + tokens
                if dt * 1000.0 <= slo_ms:
                    b["slo_ok"] += 1

    def _one(i: int, cls: str, sid: Optional[int], sched_t: float,
             rows) -> None:
        try:
            client = Client(host, port, timeout=timeout_s, retries=retries)
            ctx = reqtrace.mint()
            tid = ctx.trace_id if ctx is not None else None
            trace = reqtrace.to_header(ctx) if ctx is not None else None
            if sid is not None and cls != "batch":
                _session_step(i, sid, client, trace, tid, sched_t)
                return
            if sessions > 0 and cls == "batch":
                # session-mode tiers (char-rnn) have no /classify
                # shape: batch-class traffic is sessionless /generate
                # — a full cold rebuild per request, the honest
                # throughput-tier cost
                _batch_generate(i, client, trace, tid, sched_t)
                return
            try:
                status, resp = client.classify(
                    rows, trace=trace,
                    cls=cls if cls == "batch" else None,
                )
            except Exception as e:
                _finish(cls, i, tid, sched_t,
                        None, f"{type(e).__name__}: {e}")
                return
            if status == 200 and len(resp.get("indices", ())) != len(rows):
                _finish(cls, i, tid, sched_t, status,
                        f"{len(resp.get('indices', ()))} rows back, "
                        f"sent {len(rows)}")
                return
            if status not in (200, 429, 503):
                _finish(cls, i, tid, sched_t, status,
                        f"HTTP {status}: {resp.get('error')}")
                return
            _finish(cls, i, tid, sched_t, status, None)
            if status == 200:
                with lock:
                    if "gen" in resp:
                        generations.add(int(resp["gen"]))
        finally:
            sem.release()

    def _batch_generate(i, client, trace, tid, sched_t) -> None:
        # batch_prefix sets the sessionless rebuild cost — O(prefix)
        # decode steps per request — so a spike script can saturate
        # service capacity on any host speed
        toks = [(i + j) % session_vocab
                for j in range(max(1, batch_prefix))]
        try:
            status, resp = client.generate(
                toks, steps=session_steps,
                trace=trace, cls="batch",
            )
        except Exception as e:
            _finish("batch", i, tid, sched_t,
                    None, f"{type(e).__name__}: {e}")
            return
        if status not in (200, 429, 503):
            _finish("batch", i, tid, sched_t, status,
                    f"HTTP {status}: {resp.get('error')}")
            return
        if status == 200 and len(resp.get("tokens", ())) != session_steps:
            _finish("batch", i, tid, sched_t, status,
                    f"{len(resp.get('tokens', ()))} tokens back, "
                    f"asked {session_steps}")
            return
        _finish("batch", i, tid, sched_t, status, None,
                tokens=session_steps if status == 200 else 0)

    def _session_step(i, k, client, trace, tid, sched_t) -> None:
        sid = f"s{k}"
        with lock:
            slock = session_locks.setdefault(sid, threading.Lock())
        with slock:
            with lock:
                hist = list(
                    session_hist.setdefault(sid, [k % session_vocab])
                )
            try:
                status, resp = client.generate(
                    hist, session=sid, steps=session_steps, trace=trace,
                )
            except Exception as e:
                with lock:
                    session_failed[0] += 1
                _finish("interactive", i, tid, sched_t,
                        None, f"{type(e).__name__}: {e}")
                return
            if status in (429, 503):
                # refused, not corrupted: the prefix stays untouched
                _finish("interactive", i, tid, sched_t, status, None)
                return
            if status != 200:
                with lock:
                    session_failed[0] += 1
                _finish("interactive", i, tid, sched_t, status,
                        f"HTTP {status}: {resp.get('error')}")
                return
            if len(resp.get("tokens", ())) != session_steps:
                # the session-correctness bar: wrong continuation length
                with lock:
                    session_failed[0] += 1
                _finish("interactive", i, tid, sched_t, status,
                        f"{len(resp.get('tokens', ()))} tokens back, "
                        f"asked {session_steps}")
                return
            _finish("interactive", i, tid, sched_t, status, None,
                    tokens=len(resp["tokens"]))
            with lock:
                session_hist[sid] = hist + [
                    int(t) for t in resp["tokens"]
                ]
                st = str(resp.get("cache_state", "?"))
                session_states[st] = session_states.get(st, 0) + 1
                if resp.get("migrated"):
                    session_migrated[0] += 1
                if "gen" in resp:
                    generations.add(int(resp["gen"]))

    threads: list = []
    t_start = time.monotonic()
    for i, offset in enumerate(plan.times):
        cls = plan.classes[i]
        sid = plan.session_ids[i] if plan.session_ids is not None else None
        # rows are drawn on the scheduler thread so the draw ORDER (and
        # with it determinism) is independent of reply timing
        n = int(sizes[i % len(sizes)])
        rows = rng_rows.normal(size=(n,) + tuple(input_shape)).astype(
            np.float32
        )
        while True:
            late = time.monotonic() - (t_start + offset)
            if late >= 0.0:
                break
            time.sleep(min(-late, 0.05))
        with lock:
            _bucket(cls)["offered"] += 1
            lateness.append(max(0.0, late))
        if not sem.acquire(blocking=False):
            with lock:
                overflow[0] += 1
                _bucket(cls)["failed"] += 1
                errors.append(f"req {i}: client overflow "
                              f"(max_inflight={max_inflight})")
            continue
        th = threading.Thread(
            target=_one, args=(i, cls, sid, t_start + offset, rows),
            daemon=True,
        )
        th.start()
        threads.append(th)
    deadline = time.monotonic() + timeout_s * 2
    for th in threads:
        th.join(max(0.1, deadline - time.monotonic()))
    wall_s = time.monotonic() - t_start

    def _pct(vals, q):
        vals = sorted(vals)
        return (
            round(vals[int(q * (len(vals) - 1))] * 1000, 3)
            if vals else None
        )

    classes_out = {}
    for cls, b in sorted(by_class.items()):
        lats = lat_by_class.get(cls, [])
        toks = tok_by_class.get(cls, 0)
        classes_out[cls] = {
            **b,
            "slo_ok_frac": round(b["slo_ok"] / b["offered"], 4)
            if b["offered"] else None,
            "p50_ms": _pct(lats, 0.50),
            "p99_ms": _pct(lats, 0.99),
            # decode-token ledger: continuations delivered on ok
            # replies (0 for classify traffic), over the run's wall —
            # the per-class aggregate the batched-decode bench reads
            "tokens": toks,
            "tokens_per_sec": round(toks / max(wall_s, 1e-9), 2),
        }
    inter = classes_out.get("interactive", {})
    total_failed = sum(b["failed"] for b in by_class.values())
    return {
        "metric": "serve_open_loop_slo_ok_frac",
        "value": inter.get("slo_ok_frac"),
        "unit": "fraction",
        "script": plan.script,
        "seed": plan.seed,
        "slo_ms": slo_ms,
        "duration_s": round(plan.duration, 3),
        "wall_s": round(wall_s, 3),
        "offered": len(plan),
        "offered_rate_rps": round(plan.offered_rate(), 3),
        "classes": classes_out,
        "shed": dict(sorted(shed_reasons.items())),
        "failed_requests": total_failed,
        "error_samples": errors[:5],
        "failed_request_traces": failed_traces[:20],
        "client_overflow": overflow[0],
        "lateness_p99_ms": _pct(lateness, 0.99),
        "served_generations": sorted(generations),
        "host_cpus": os.cpu_count(),
        **(
            {
                "sessions": {
                    "count": sessions,
                    "zipf": session_zipf,
                    "steps_per_request": session_steps,
                    "distinct": len(session_hist),
                    "states": dict(sorted(session_states.items())),
                    "migrated": session_migrated[0],
                    "tokens_generated": sum(tok_by_class.values()),
                    "tokens_per_sec": round(
                        sum(tok_by_class.values())
                        / max(wall_s, 1e-9), 2
                    ),
                },
                "session_failed_requests": session_failed[0],
            }
            if sessions > 0 else {}
        ),
    }


def _platform() -> str:
    try:
        import jax

        return jax.devices()[0].platform
    except Exception:
        return "unknown"
