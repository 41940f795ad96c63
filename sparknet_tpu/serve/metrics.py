"""Serving observability: the serving-specific metrics registry.

Same discipline as ``utils/profiling``'s StepTimer: everything is
windowed against wall-clock and dumpable as
ONE JSON line, so a sweep log line or a ``/metrics.json`` scrape
carries the whole serving picture — request/error counts, per-bucket
batch counts and padding waste, p50/p95/p99 latencies, queue depth —
without any external metrics stack.  ``GET /metrics`` additionally
serves the same state in Prometheus text format via
``telemetry/exporter.py``.

The primitives (``Counter``/``Gauge``/``LatencyHistogram``) moved to
:mod:`sparknet_tpu.telemetry.registry` — this grew from the serving
stack into the process-wide substrate — and are re-exported here
unchanged for back-compat (deprecated import path; new code should
import from ``sparknet_tpu.telemetry``).
"""

from __future__ import annotations

import json
import threading
import time
from typing import Dict

# Deprecated re-export location: the primitives live in
# telemetry/registry.py now.  Kept so every historical
# ``from sparknet_tpu.serve.metrics import Counter`` keeps working.
from ..telemetry.registry import (  # noqa: F401
    REGISTRY,
    Counter,
    Gauge,
    LatencyHistogram,
)


class ServeMetrics:
    """One registry per serving process. The engine reports device-side
    per-bucket execution, the batcher reports end-to-end request
    latency and queue depth, the server reports errors."""

    def __init__(self, buckets=()):
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._window_t0 = self._t0
        self._window_requests = 0
        self.requests = 0
        self.rows = 0
        self.errors = 0
        # requests dropped without compute: shed = expired deadline,
        # cancelled = abandoned by the caller (e.g. the HTTP handler's
        # timeout).  Either marks the server degraded for a window —
        # /healthz surfaces it so a balancer can back off.
        self.shed = 0
        self.cancelled = 0
        # weight hot-swaps (serve/engine.py swap()): count + the newest
        # generation served, so /metrics and loadgen records carry the
        # rolling-update story next to the latency story
        self.hot_swaps = 0
        self.generation = 0
        self._last_degraded_t: float = float("-inf")
        self._queue_depth = Gauge()
        self.request_latency = LatencyHistogram()
        # batched decode (ISSUE 17): one dispatch = one batched step
        # executable run; rows = live session rows stepped (== real
        # tokens produced/replayed), padded_rows = masked filler slots.
        # Occupancy (rows / compiled slots) is THE utilization gauge of
        # the continuous token-level batcher.
        self.decode_dispatches = 0
        self.decode_rows = 0
        self.decode_padded_rows = 0
        self.decode_retired = 0
        self.decode_shed = 0
        self._window_decode_rows = 0
        self.decode_device = LatencyHistogram()
        self.decode_per_width: Dict[int, dict] = {}
        self.per_bucket: Dict[int, dict] = {}
        for b in buckets:
            self._bucket(int(b))
        # the process registry's "serve" source: telemetry.snapshot()
        # and the periodic flush line carry this registry too (weakly
        # referenced — a dropped server takes its metrics with it)
        REGISTRY.register_source("serve", self)

    def _bucket(self, bucket: int) -> dict:
        entry = self.per_bucket.get(bucket)
        if entry is None:
            entry = self.per_bucket[bucket] = {
                "batches": 0,
                "rows": 0,
                "padded_rows": 0,
                "device": LatencyHistogram(),
            }
        return entry

    # ------------------------------------------------------------- writes
    def record_batch(
        self, bucket: int, rows: int, padded_rows: int, device_s: float
    ) -> None:
        with self._lock:
            e = self._bucket(bucket)
            e["batches"] += 1
            e["rows"] += rows
            e["padded_rows"] += padded_rows
            e["device"].observe(device_s)

    def record_decode_step(
        self, width: int, rows: int, padded_rows: int, device_s: float
    ) -> None:
        """One batched decode dispatch: ``rows`` live session rows
        advanced one token each through the compiled ``width``-wide
        step (``padded_rows`` slots were masked filler)."""
        with self._lock:
            self.decode_dispatches += 1
            self.decode_rows += rows
            self.decode_padded_rows += padded_rows
            self._window_decode_rows += rows
            self.decode_device.observe(device_s)
            w = self.decode_per_width.get(int(width))
            if w is None:
                w = self.decode_per_width[int(width)] = {
                    "dispatches": 0, "rows": 0, "padded_rows": 0,
                }
            w["dispatches"] += 1
            w["rows"] += rows
            w["padded_rows"] += padded_rows

    def record_decode_done(self, retired: int = 0, shed: int = 0) -> None:
        """Row lifecycle exits: ``retired`` rows completed, ``shed``
        rows hit their per-token deadline mid-window (a shed also
        degrades health, same as a queue-level shed)."""
        with self._lock:
            self.decode_retired += retired
            self.decode_shed += shed
            if shed:
                self._last_degraded_t = time.perf_counter()

    def record_request(
        self, latency_s: float, rows: int = 1, exemplar=None
    ) -> None:
        """``exemplar``: an optional ``(trace_id, seconds)`` pair from a
        sampled request trace — becomes an OpenMetrics exemplar on the
        latency histogram (telemetry/reqtrace.py)."""
        with self._lock:
            self.requests += 1
            self._window_requests += 1
            self.rows += rows
            self.request_latency.observe(latency_s, exemplar=exemplar)

    def record_error(self, n: int = 1) -> None:
        with self._lock:
            self.errors += n

    def record_shed(self, n: int = 1) -> None:
        """Requests whose deadline expired before compute."""
        with self._lock:
            self.shed += n
            self._last_degraded_t = time.perf_counter()

    def record_cancelled(self, n: int = 1) -> None:
        """Requests abandoned by their caller before compute."""
        with self._lock:
            self.cancelled += n
            self._last_degraded_t = time.perf_counter()

    def record_hot_swap(self, generation: int) -> None:
        """A weight hot-swap landed; ``generation`` is the new gen."""
        with self._lock:
            self.hot_swaps += 1
            self.generation = max(self.generation, int(generation))

    def set_queue_depth(self, depth: int) -> None:
        self._queue_depth.set(depth)

    # ------------------------------------------------------------- health
    DEGRADED_WINDOW_S = 60.0

    def health(self) -> str:
        """"ok" or "degraded": degraded while a shed/cancelled request
        happened within the last window — load is outrunning the
        deadline budget, so /healthz tells balancers to back off."""
        with self._lock:
            t = self._last_degraded_t
        if time.perf_counter() - t < self.DEGRADED_WINDOW_S:
            return "degraded"
        return "ok"

    # -------------------------------------------------------------- reads
    def decode_summary(self) -> dict:
        """The healthz-scrape view of batched decode: occupancy +
        lifetime tokens/sec + lifecycle counters.  Deliberately NOT
        ``snapshot()["decode"]`` — a health scrape must not roll the
        windowed-rate accounting other readers depend on."""
        with self._lock:
            uptime = max(time.perf_counter() - self._t0, 1e-9)
            return {
                "dispatches": self.decode_dispatches,
                "rows": self.decode_rows,
                "padded_rows": self.decode_padded_rows,
                "occupancy": round(
                    self.decode_rows
                    / max(self.decode_rows + self.decode_padded_rows, 1),
                    4,
                ),
                "retired": self.decode_retired,
                "shed": self.decode_shed,
                "tokens_per_sec": round(self.decode_rows / uptime, 2),
            }

    def snapshot(self) -> dict:
        """JSON-able state. Also rolls the requests/s window (StepTimer
        style): ``window_requests_per_sec`` covers the span since the
        previous snapshot."""
        with self._lock:
            now = time.perf_counter()
            uptime = max(now - self._t0, 1e-9)
            window = max(now - self._window_t0, 1e-9)
            out = {
                "uptime_s": round(uptime, 3),
                "requests": self.requests,
                "rows": self.rows,
                "errors": self.errors,
                "shed": self.shed,
                "cancelled": self.cancelled,
                "hot_swaps": self.hot_swaps,
                "generation": self.generation,
                "health": (
                    "degraded"
                    if now - self._last_degraded_t < self.DEGRADED_WINDOW_S
                    else "ok"
                ),
                "requests_per_sec": round(self.requests / uptime, 2),
                "window_requests_per_sec": round(
                    self._window_requests / window, 2
                ),
                "queue_depth": self._queue_depth.value,
                "queue_depth_max": self._queue_depth.max,
                "request_latency": self.request_latency.snapshot(),
                "decode": {
                    "dispatches": self.decode_dispatches,
                    "rows": self.decode_rows,
                    "padded_rows": self.decode_padded_rows,
                    # batch occupancy: real rows per compiled slot —
                    # 1.0 means every dispatched lane carried a session
                    "occupancy": round(
                        self.decode_rows
                        / max(self.decode_rows + self.decode_padded_rows, 1),
                        4,
                    ),
                    "retired": self.decode_retired,
                    "shed": self.decode_shed,
                    # aggregate decode throughput: one live row stepped
                    # = one token (replayed or generated)
                    "tokens_per_sec": round(self.decode_rows / uptime, 2),
                    "window_tokens_per_sec": round(
                        self._window_decode_rows / window, 2
                    ),
                    "device_latency": self.decode_device.snapshot(),
                    "per_width": {
                        str(w): dict(e)
                        for w, e in sorted(self.decode_per_width.items())
                    },
                },
                "per_bucket": {
                    str(b): {
                        "batches": e["batches"],
                        "rows": e["rows"],
                        "padded_rows": e["padded_rows"],
                        # padding waste: fraction of device rows that
                        # were padding (compiled-shape rows vs real)
                        "padding_waste": round(
                            e["padded_rows"]
                            / max(e["rows"] + e["padded_rows"], 1),
                            4,
                        ),
                        "device_latency": e["device"].snapshot(),
                    }
                    for b, e in sorted(self.per_bucket.items())
                },
            }
            self._window_t0 = now
            self._window_requests = 0
            self._window_decode_rows = 0
            return out

    def json_line(self) -> str:
        """The one-line dump ``/metrics`` serves and sweep logs append."""
        return json.dumps(self.snapshot())
