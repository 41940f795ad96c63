"""MicroBatcher — dynamic micro-batching between requests and engine.

Requests arrive one-at-a-time with a few rows each; the engine is
fastest fed full buckets. The batcher sits between: a bounded
thread-safe queue feeds a single worker thread that coalesces queued
requests into engine batches. The bounded queue is the backpressure
surface: when it is full, ``submit`` fails fast with
:class:`Backpressure` (the HTTP layer maps it to 503) instead of
letting latency grow without bound.

Two admission policies (``mode=``):

- ``"fill"`` (the PR 1 policy): gather until ``max_batch`` rows or the
  oldest request has waited ``max_latency_us`` — fill-then-flush, the
  classic fixed throughput/latency dial.  Its failure mode is mixed
  load: a lone small request always waits out the whole window hoping
  for co-riders that never come.
- ``"continuous"``: a continuous admitter.  Late arrivals join the
  assembling batch **up to the dispatch instant** (one final
  non-blocking drain right before the engine call), and the wait
  itself is decided per-tick by *deadline-aware bucket selection*:
  keep waiting only while (a) the arrival-rate EWMA predicts enough
  co-rider rows to reach a **bigger** bucket within the remaining
  window — otherwise waiting buys padding, not throughput: dispatch
  the small bucket now — and (b) the tightest request deadline can
  still absorb the per-bucket service-time EWMA after the wait.  At
  saturation (backlogged queue) the admitter drains straight to
  ``max_batch`` and is batch-for-batch identical to fill-then-flush
  (tests/test_serving_tier.py pins bit-equality); under mixed load it
  dispatches early (what that does to p99 at an equal offered rate has
  no measurement: no cell of the benchmark serves yet, PERF.md §7).

A single worker thread is deliberate: the engine serializes on one
device anyway, and one consumer keeps request ordering FIFO.
``drain()`` stops intake, lets the worker finish everything queued,
and joins it — the graceful-shutdown path the server and the load
generator both use; a worker still alive past the join timeout raises
instead of silently abandoning in-flight requests.

Requests can carry a **deadline** (``deadline_s``, per-batcher default
or per-submit): at flush time, expired requests are shed *before*
compute — their futures fail with :class:`DeadlineExceeded`, the shed
count marks the server degraded in ``/healthz`` — and requests whose
future was cancelled by the caller (the HTTP handler's 504 path) are
dropped the same way, so the device never computes a reply nobody
reads.  The ``serve.engine_stall`` chaos point injects a stall right
before the engine call to make both paths testable.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..telemetry import reqtrace as _reqtrace
from ..telemetry import trace as _trace

# EWMA smoothing for the continuous admitter's two estimators
# (arrival rows/s, per-bucket service seconds): recent-biased enough to
# track load shifts within tens of requests, smooth enough not to
# whipsaw on one burst
_EWMA_ALPHA = 0.3


def decode_batching_enabled() -> bool:
    """The ISSUE 17 A/B flag: ``SPARKNET_DECODE_BATCH=0`` keeps the
    PR 13 serial decode path (one ``engine.generate`` per worker turn)
    as the baseline; default on routes ``/generate`` through
    :meth:`MicroBatcher.submit_decode` and the batched token loop."""
    raw = os.environ.get("SPARKNET_DECODE_BATCH", "1").strip().lower()
    return raw not in ("0", "off", "false", "no")


class Backpressure(RuntimeError):
    """Raised by submit() when the bounded request queue is full."""


class DeadlineExceeded(RuntimeError):
    """A request's deadline expired while it waited in the queue; it
    was shed before reaching the engine."""


class _Pending:
    __slots__ = ("rows", "n", "future", "t_enq", "deadline", "ctx", "fn",
                 "decode")

    def __init__(self, rows: Optional[np.ndarray],
                 deadline_s: Optional[float] = None,
                 ctx=None, fn=None, decode=None):
        # a rows request (coalescable into engine batches), a callable
        # request (``submit_call``), or a decode request (``submit_
        # decode`` — a dict riding the batched token loop): all three
        # share the queue, the FIFO order, backpressure, and the
        # deadline-shed machinery
        self.rows = rows
        self.fn = fn
        self.decode = decode
        self.n = 1 if rows is None else len(rows)
        self.future: Future = Future()
        self.t_enq = time.perf_counter()
        self.deadline = (
            None if deadline_s is None else self.t_enq + deadline_s
        )
        # request-trace context (telemetry/reqtrace.py): when set, the
        # queue wait, deadline shed and engine compute become spans on
        # the request's cross-process waterfall
        self.ctx = ctx


class MicroBatcher:
    def __init__(
        self,
        engine,
        *,
        max_batch: int = 0,
        max_latency_us: int = 2000,
        max_queue: int = 256,
        deadline_s: Optional[float] = None,
        metrics=None,
        mode: str = "fill",
    ):
        """``engine``: anything with ``infer(rows) -> rows`` (the
        InferenceEngine; tests substitute stubs). ``max_batch``: row
        budget per engine call — defaults to the engine's largest
        bucket. ``max_latency_us``: longest the oldest queued request
        waits for co-riders before the batch is flushed anyway.
        ``max_queue``: bound on queued requests (backpressure).
        ``deadline_s``: default per-request deadline — a request still
        queued past it is shed before compute (None disables).
        ``mode``: ``"fill"`` or ``"continuous"`` (module docstring)."""
        from .. import chaos

        if mode not in ("fill", "continuous"):
            raise ValueError(f"MicroBatcher mode {mode!r}: want "
                             f"fill|continuous")
        self.engine = engine
        self.mode = mode
        self.max_batch = int(max_batch) or max(
            getattr(engine, "buckets", (32,))
        )
        self.max_latency_s = max_latency_us / 1e6
        self.deadline_s = deadline_s
        self.metrics = metrics
        # cached once: the disabled chaos path is one `is None` test
        self._chaos = chaos.get_plan()
        self._flushes = 0
        # continuous-mode estimators (written by submit / _run, read by
        # the worker's admission loop)
        self._est_lock = threading.Lock()
        self._arrival_rows_per_s = 0.0
        self._last_arrival_t: Optional[float] = None
        self._service_s: Dict[int, float] = {}
        self._q: "queue.Queue[_Pending]" = queue.Queue(maxsize=max_queue)
        # one item the decode window's admitter pulled but must not run
        # (the first non-decode item ends continuous admission so total
        # FIFO order holds); the worker loop consumes it before the
        # next queue get
        self._stash: Optional[_Pending] = None
        self._open = True
        self._worker = threading.Thread(
            target=self._loop, name="serve-batcher", daemon=True
        )
        self._worker.start()

    # ------------------------------------------------------------------
    def submit(
        self,
        rows,
        *,
        block: bool = False,
        timeout: Optional[float] = None,
        deadline_s: Optional[float] = None,
        ctx=None,
    ) -> Future:
        """Enqueue one request of N rows; resolves to the engine output
        for exactly those rows. ``block=False`` (the server's mode)
        raises :class:`Backpressure` when the queue is full; closed-loop
        clients pass ``block=True`` to wait for room instead.
        ``deadline_s`` overrides the batcher-level default deadline.
        ``ctx``: an optional request-trace context — its queue wait and
        engine compute are recorded as waterfall spans."""
        if not self._open:
            raise RuntimeError("MicroBatcher is drained/closed")
        item = _Pending(
            np.asarray(rows),
            self.deadline_s if deadline_s is None else deadline_s,
            ctx,
        )
        if item.n == 0:
            raise ValueError("submit: empty request")
        try:
            self._q.put(item, block=block, timeout=timeout)
        except queue.Full:
            raise Backpressure(
                f"request queue full ({self._q.maxsize} pending)"
            ) from None
        if self.mode == "continuous":
            self._note_arrival(item)
        if self.metrics is not None:
            self.metrics.set_queue_depth(self._q.qsize())
        return item.future

    def submit_call(
        self,
        fn,
        *,
        block: bool = False,
        timeout: Optional[float] = None,
        deadline_s: Optional[float] = None,
        ctx=None,
    ) -> Future:
        """Enqueue one callable request (a session ``generate``): it
        runs **in queue position** on the single worker thread, so
        stateful decode and batched classify share one serialized
        engine feed, one backpressure bound and one deadline-shed path
        — a generate can never race a classify onto the device, and an
        expired generate is shed before compute exactly like rows."""
        if not self._open:
            raise RuntimeError("MicroBatcher is drained/closed")
        item = _Pending(
            None,
            self.deadline_s if deadline_s is None else deadline_s,
            ctx, fn=fn,
        )
        try:
            self._q.put(item, block=block, timeout=timeout)
        except queue.Full:
            raise Backpressure(
                f"request queue full ({self._q.maxsize} pending)"
            ) from None
        if self.metrics is not None:
            self.metrics.set_queue_depth(self._q.qsize())
        return item.future

    def submit_decode(
        self,
        request: dict,
        *,
        block: bool = False,
        timeout: Optional[float] = None,
        deadline_s: Optional[float] = None,
        ctx=None,
    ) -> Future:
        """Enqueue one decode request (``{"tokens": [...], "session":
        id?, "steps": K, "top_k": k}``) for the continuous batched
        token loop (``engine.decode_batch``).  FIFO position, back-
        pressure and deadlines work exactly like ``submit``/``submit_
        call``, but consecutive decode requests — and any that arrive
        while a decode window is running — share ONE window: K live
        sessions per dispatch instead of one ``generate`` per worker
        turn.  The future resolves the moment the request's row
        retires, not at window end."""
        if not self._open:
            raise RuntimeError("MicroBatcher is drained/closed")
        item = _Pending(
            None,
            self.deadline_s if deadline_s is None else deadline_s,
            ctx, decode=dict(request),
        )
        try:
            self._q.put(item, block=block, timeout=timeout)
        except queue.Full:
            raise Backpressure(
                f"request queue full ({self._q.maxsize} pending)"
            ) from None
        if self.metrics is not None:
            self.metrics.set_queue_depth(self._q.qsize())
        return item.future

    # ----------------------------------------------------- estimators
    def _note_arrival(self, item: _Pending) -> None:
        """Arrival-rate EWMA (rows/s) over inter-arrival gaps — the
        admitter's 'are co-riders coming?' signal."""
        with self._est_lock:
            last, self._last_arrival_t = self._last_arrival_t, item.t_enq
            if last is None:
                return  # first arrival: rate stays 0 -> dispatch eagerly
            inst = item.n / max(item.t_enq - last, 1e-6)
            self._arrival_rows_per_s = (
                (1 - _EWMA_ALPHA) * self._arrival_rows_per_s
                + _EWMA_ALPHA * inst
            )

    def _observe_service(self, bucket: int, seconds: float) -> None:
        with self._est_lock:
            prev = self._service_s.get(bucket)
            self._service_s[bucket] = (
                seconds if prev is None
                else (1 - _EWMA_ALPHA) * prev + _EWMA_ALPHA * seconds
            )

    def _service_estimate(self, bucket: int) -> float:
        """EWMA engine seconds for ``bucket``; falls back to the
        nearest known bucket (0 when nothing observed yet)."""
        with self._est_lock:
            if not self._service_s:
                return 0.0
            got = self._service_s.get(bucket)
            if got is not None:
                return got
            nearest = min(self._service_s, key=lambda b: abs(b - bucket))
            return self._service_s[nearest]

    def _arrival_rate(self) -> float:
        with self._est_lock:
            return self._arrival_rows_per_s

    def _bucket_for(self, n: int) -> int:
        fn = getattr(self.engine, "bucket_for", None)
        n = min(int(n), self.max_batch)
        return fn(n) if fn is not None else n

    # ------------------------------------------------------------------
    def _loop(self) -> None:
        gather = (
            self._gather_continuous if self.mode == "continuous"
            else self._gather_fill
        )
        while True:
            if self._stash is not None:
                first, self._stash = self._stash, None
            else:
                try:
                    first = self._q.get(timeout=0.05)
                except queue.Empty:
                    if not self._open:
                        return
                    continue
            batch, total = gather(first)
            if self.metrics is not None:
                self.metrics.set_queue_depth(self._q.qsize())
            self._run(batch, total)

    def _gather_fill(self, first: _Pending) -> Tuple[List[_Pending], int]:
        """Fill-then-flush: wait out the window unless the batch fills
        first (the PR 1 policy, kept as the A/B baseline)."""
        batch: List[_Pending] = [first]
        total = first.n
        deadline = time.perf_counter() + self.max_latency_s
        while total < self.max_batch:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                break
            try:
                item = self._q.get(timeout=remaining)
            except queue.Empty:
                break
            batch.append(item)
            total += item.n
        return batch, total

    def _gather_continuous(
        self, first: _Pending
    ) -> Tuple[List[_Pending], int]:
        """Continuous admission + deadline-aware bucket selection (see
        module docstring).  The final non-blocking drain means arrivals
        join right up to the dispatch instant."""
        batch: List[_Pending] = [first]
        total = first.n
        window_end = time.perf_counter() + self.max_latency_s
        while total < self.max_batch:
            # admit everything already queued — at saturation this runs
            # straight to max_batch and matches fill-then-flush
            # batch-for-batch
            try:
                while total < self.max_batch:
                    item = self._q.get_nowait()
                    batch.append(item)
                    total += item.n
                break
            except queue.Empty:
                pass
            now = time.perf_counter()
            wait = window_end - now
            if wait <= 0:
                break
            cur_bucket = self._bucket_for(total)
            # (b) the tightest deadline must absorb the wait AND the
            # estimated service time for the bucket we'd dispatch
            tight = min(
                (it.deadline for it in batch if it.deadline is not None),
                default=None,
            )
            if tight is not None:
                slack = tight - now - self._service_estimate(cur_bucket)
                wait = min(wait, slack)
                if wait <= 0:
                    break
            # (a) small bucket now vs bigger bucket later: wait only if
            # the predicted co-rider rows reach a bigger bucket
            predicted = total + self._arrival_rate() * wait
            if self._bucket_for(predicted) <= cur_bucket:
                break
            try:
                item = self._q.get(timeout=wait)
            except queue.Empty:
                break
            batch.append(item)
            total += item.n
        return batch, total

    def _run(self, batch: List[_Pending], total: int) -> None:
        if self._chaos is not None:
            rule = self._chaos.match("serve.engine_stall", batch=self._flushes)
            if rule is not None:
                time.sleep(float(rule.params.get("delay_ms", 50.0)) / 1e3)
        self._flushes += 1
        # shed-before-compute: expired deadlines fail fast, futures the
        # caller already cancelled (server 504 path) are dropped — the
        # engine never computes a reply nobody reads
        now = time.perf_counter()
        live: List[_Pending] = []
        shed = cancelled = 0
        for it in batch:
            if it.deadline is not None and now > it.deadline:
                shed += 1
                it.future.set_exception(DeadlineExceeded(
                    f"request expired after {now - it.t_enq:.3f}s in queue"
                ))
                if it.ctx is not None:
                    _reqtrace.record_interval(
                        it.ctx, "batcher.shed", it.t_enq,
                        reason="deadline", rows=it.n,
                    )
            elif not it.future.set_running_or_notify_cancel():
                cancelled += 1
                if it.ctx is not None:
                    _reqtrace.record_interval(
                        it.ctx, "batcher.shed", it.t_enq,
                        reason="cancelled", rows=it.n,
                    )
            else:
                live.append(it)
        if self.metrics is not None:
            if shed:
                self.metrics.record_shed(shed)
            if cancelled:
                self.metrics.record_cancelled(cancelled)
        if not live:
            return
        batch = live
        # admission wait: enqueue -> dispatch instant, per request (the
        # bucket wait is inside it — the continuous admitter's co-rider
        # window is queue time by construction)
        for it in batch:
            if it.ctx is not None:
                _reqtrace.record_interval(
                    it.ctx, "batcher.wait", it.t_enq,
                    rows=it.n, mode=self.mode,
                )
        # non-rows requests run in queue position: split the batch into
        # maximal same-kind runs, preserving FIFO — a rows run
        # coalesces into one engine batch exactly as before, a call
        # runs alone, and a DECODE run becomes one continuous batched
        # token window (K sessions per dispatch, ISSUE 17)
        if any(it.fn is not None or it.decode is not None for it in batch):
            i = 0
            while i < len(batch):
                if batch[i].decode is not None:
                    j = i
                    while j < len(batch) and batch[j].decode is not None:
                        j += 1
                    self._run_decode(batch[i:j])
                    i = j
                elif batch[i].fn is not None:
                    self._run_call(batch[i])
                    i += 1
                else:
                    j = i
                    while j < len(batch) and (
                        batch[j].fn is None and batch[j].decode is None
                    ):
                        j += 1
                    self._run_rows(
                        batch[i:j], sum(it.n for it in batch[i:j])
                    )
                    i = j
            return
        self._run_rows(batch, sum(it.n for it in batch))

    def _run_call(self, it: _Pending) -> None:
        t0 = time.perf_counter()
        try:
            out = it.fn()
        except Exception as e:
            if self.metrics is not None:
                self.metrics.record_error()
            if not it.future.cancelled():
                it.future.set_exception(e)
            return
        now = time.perf_counter()
        if it.ctx is not None:
            # the decode's slot on the stitched waterfall (recorded
            # before the future resolves, like engine.compute)
            _reqtrace.record_interval(
                it.ctx, "engine.generate", t0, now,
            )
        if not it.future.cancelled():
            it.future.set_result(out)
        if self.metrics is not None:
            lat = now - it.t_enq
            self.metrics.record_request(
                lat, rows=it.n,
                exemplar=(
                    (it.ctx.trace_id, lat)
                    if it.ctx is not None and it.ctx.sampled else None
                ),
            )

    def _run_decode(self, items: List[_Pending]) -> None:
        """One continuous batched-decode window: the items (already
        shed/cancel-filtered by ``_run``) seed ``engine.decode_batch``;
        while the window runs, further decode arrivals are admitted
        straight off the queue at step boundaries — continuous batching
        — until the first NON-decode item, which is stashed so total
        FIFO order holds (under decode-heavy load the queue is all
        decode and admission never closes).  Each item's future
        resolves the moment its row retires, so per-request latency is
        honest under continuous batching."""
        outstanding: Dict[int, _Pending] = {}

        def as_req(it: _Pending) -> dict:
            req = dict(it.decode)
            req["tag"] = id(it)
            req["deadline"] = it.deadline
            outstanding[id(it)] = it
            return req

        reqs = [as_req(it) for it in items]
        closed = [False]

        def admit(slots: int):
            if closed[0]:
                return ()
            got: List[dict] = []
            while len(got) < int(slots):
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt.decode is None:
                    # first non-decode item ends admission for this
                    # window (FIFO); the worker loop resumes with it
                    self._stash = nxt
                    closed[0] = True
                    break
                if not nxt.future.set_running_or_notify_cancel():
                    if self.metrics is not None:
                        self.metrics.record_cancelled(1)
                    continue
                if nxt.ctx is not None:
                    _reqtrace.record_interval(
                        nxt.ctx, "batcher.wait", nxt.t_enq,
                        rows=1, mode="decode",
                    )
                got.append(as_req(nxt))
            if self.metrics is not None:
                self.metrics.set_queue_depth(self._q.qsize())
            return got

        def on_result(tag: int, value) -> None:
            it = outstanding.pop(tag)
            now = time.perf_counter()
            if isinstance(value, Exception):
                if isinstance(value, DeadlineExceeded):
                    if self.metrics is not None:
                        self.metrics.record_shed(1)
                    if it.ctx is not None:
                        _reqtrace.record_interval(
                            it.ctx, "batcher.shed", it.t_enq,
                            reason="deadline", rows=1,
                        )
                elif self.metrics is not None:
                    self.metrics.record_error()
                if not it.future.cancelled():
                    it.future.set_exception(value)
                return
            if it.ctx is not None:
                # the request's slot on the stitched waterfall:
                # enqueue -> row retirement, tagged with the REAL step
                # count its row paid for
                _reqtrace.record_interval(
                    it.ctx, "engine.decode_batch", it.t_enq, now,
                    steps=value.get("steps_run"),
                    cache_state=value.get("cache_state"),
                )
            if not it.future.cancelled():
                it.future.set_result(value)
            if self.metrics is not None:
                lat = now - it.t_enq
                self.metrics.record_request(
                    lat, rows=1,
                    exemplar=(
                        (it.ctx.trace_id, lat)
                        if it.ctx is not None and it.ctx.sampled else None
                    ),
                )

        try:
            self.engine.decode_batch(
                reqs, admit=admit, on_result=on_result
            )
        except Exception as e:
            # window-level failure (per-row errors arrive via
            # on_result): fail whatever is still outstanding
            if self.metrics is not None and outstanding:
                self.metrics.record_error(len(outstanding))
            for it in outstanding.values():
                if not it.future.cancelled():
                    it.future.set_exception(e)
            outstanding.clear()

    def _run_rows(self, batch: List[_Pending], total: int) -> None:
        t0 = time.perf_counter()
        try:
            with _trace.span("serve.flush", cat="serve",
                             requests=len(batch), rows=total):
                rows_cat = (
                    batch[0].rows if len(batch) == 1
                    else np.concatenate([it.rows for it in batch])
                )
                # tagged path when the engine offers it: the weights
                # generation the WHOLE batch computed with (hot-swap
                # observability on every compute span)
                tagged = getattr(self.engine, "infer_tagged", None)
                if tagged is not None:
                    out, gen = tagged(rows_cat)
                else:
                    out = self.engine.infer(rows_cat)
                    gen = getattr(self.engine, "generation", 0)
        except Exception as e:
            if self.metrics is not None:
                self.metrics.record_error(len(batch))
            for it in batch:
                if not it.future.cancelled():
                    it.future.set_exception(e)
            return
        live_rows = sum(it.n for it in batch)
        if self.mode == "continuous":
            self._observe_service(
                self._bucket_for(live_rows), time.perf_counter() - t0
            )
        now = time.perf_counter()
        bucket = self._bucket_for(live_rows)
        ofs = 0
        for it in batch:
            if it.ctx is not None:
                # one compute span per co-riding request: same batch
                # interval, tagged with the bucket + weights generation.
                # Recorded BEFORE the future resolves — the handler
                # thread gathers the span batch the moment result()
                # returns, and a span landing after that gather would
                # miss the response header.
                _reqtrace.record_interval(
                    it.ctx, "engine.compute", t0, now,
                    bucket=bucket, rows=it.n, gen=gen,
                )
            if not it.future.cancelled():
                it.future.set_result(out[ofs : ofs + it.n])
            ofs += it.n
            if self.metrics is not None:
                lat = now - it.t_enq
                self.metrics.record_request(
                    lat, rows=it.n,
                    exemplar=(
                        (it.ctx.trace_id, lat)
                        if it.ctx is not None and it.ctx.sampled else None
                    ),
                )

    # ------------------------------------------------------------------
    def drain(self, timeout: Optional[float] = 30.0) -> None:
        """Graceful shutdown: refuse new requests, finish every queued
        one, stop the worker. Idempotent.  A worker still alive past
        the join timeout (engine wedged mid-call) raises — returning
        silently would abandon in-flight requests whose futures never
        resolve."""
        self._open = False
        self._worker.join(timeout)
        if self._worker.is_alive():
            raise RuntimeError(
                f"MicroBatcher worker did not stop within {timeout}s "
                f"(engine wedged?) — requests may still be in flight"
            )

    close = drain
