"""Device prefetch: overlap host preprocessing + H2D transfer with
device compute.

The apps' feeds run decode/augment in Python and hand numpy to the
jitted step, which then blocks on the transfer — on a fast chip the
loop becomes host-bound (the reference hides the same latency inside
its C++ data-prefetch thread; SURVEY.md data layer). This wrapper moves
``next(feed)`` + ``jax.device_put`` into a daemon worker thread with a
bounded queue, so the next batches' preprocessing and transfers run
while the device crunches the current one.

Order-preserving (single worker pulling sequentially) and therefore
bitwise-deterministic: the batch sequence is identical to the
unwrapped iterator's. Not for multi-host global assembly —
``make_array_from_process_local_data`` must stay on the main thread
with identical ordering across processes.

The staging thread's time is three phases, reported to the current
timeline (telemetry/timeline.py; background rows, beside the loop's):
``feed.source`` inside ``next(it)``, ``feed.h2d`` inside ``put``,
``feed.backpressure`` blocked on the full queue.  The thread is serial,
so over any window they add up to its wall time, and which one fills it
says what sets the pace: the source, the transfer, or the consumer.

Two double-buffering surfaces live here, both reporting hit/wait
counts through :class:`~.pipeline.PipelineMetrics` (``prefetch``
block) instead of being standalone:

- :func:`prefetch_to_device` — the H2D staging thread the apps wrap
  around every feed;
- :class:`DoubleBuffer` — a generic one-slot read-ahead the packed
  shard readers (``data/records.py``) use to open/index the next
  shard in plan order while the current one is being consumed.
"""

from __future__ import annotations

import atexit
import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

import jax

from ..telemetry import timeline as _timeline

_SENTINEL = object()
_JOIN_S = 5.0  # a closed feed waits this long for its staging thread
_NONE = object()  # DoubleBuffer's "no staged slot" marker (None is a key)


def _put_checked(q, stop, item) -> None:
    """Bounded put that gives up once the consumer signals stop, so the
    worker thread can always exit instead of blocking forever on a full
    queue holding staged device batches."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return
        except queue.Full:
            continue


def prefetch_to_device(
    it: Iterator[Any],
    size: int = 2,
    put: Optional[Callable[[Any], Any]] = None,
    metrics=None,
) -> Iterator[Any]:
    """Yield ``put(next(it))`` with up to ``size`` results staged ahead
    by a worker thread. ``put`` defaults to ``jax.device_put`` (async
    dispatch: the transfer is enqueued, not awaited). Exceptions from
    the source iterator re-raise at the consuming ``next()``; closing
    or abandoning the generator stops the worker and releases its
    staged batches (no thread or device memory pinned past the feed's
    lifetime).  ``metrics`` (a :class:`~.pipeline.PipelineMetrics`)
    counts each consume as a prefetch hit (batch already staged) or a
    wait (consumer blocked on the staging thread)."""
    if size <= 0:
        for b in it:
            yield (put or jax.device_put)(b)
        return
    putter = put or jax.device_put
    q: "queue.Queue" = queue.Queue(maxsize=size)
    stop = threading.Event()

    def worker():
        source = _timeline.background_phase("feed.source")
        h2d = _timeline.background_phase("feed.h2d")
        backpressure = _timeline.background_phase("feed.backpressure")
        try:
            source_it = iter(it)
            while True:
                with source:
                    b = next(source_it, _SENTINEL)
                if b is _SENTINEL:
                    break
                if stop.is_set():  # closed meanwhile: stage nothing more
                    return
                with h2d:
                    staged = putter(b)
                with backpressure:
                    _put_checked(q, stop, staged)
                if stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            _put_checked(q, stop, (_SENTINEL, e))
            return
        _put_checked(q, stop, (_SENTINEL, None))

    thread = threading.Thread(target=worker, daemon=True)

    def shutdown():
        """Stop the thread and wait for it to leave jax.  Also at exit,
        for a feed nobody closed: an interpreter that finalises while a
        daemon thread is inside ``device_put`` kills the thread there,
        and the forced unwind through jaxlib's C++ aborts the process
        ("FATAL: exception not rethrown", exit by SIGABRT after a run
        that had finished)."""
        stop.set()
        thread.join(timeout=_JOIN_S)

    atexit.register(shutdown)
    thread.start()
    try:
        while True:
            t0 = time.perf_counter()
            try:
                item = q.get_nowait()
                hit = True
            except queue.Empty:
                item = q.get()
                hit = False
            if metrics is not None:
                metrics.record_prefetch(hit, time.perf_counter() - t0)
            if (
                isinstance(item, tuple)
                and len(item) == 2
                and item[0] is _SENTINEL
            ):
                if item[1] is not None:
                    raise item[1]
                return
            yield item
    finally:
        atexit.unregister(shutdown)
        shutdown()
        while not q.empty():  # drop staged batches so they can free
            try:
                q.get_nowait()
            except queue.Empty:
                break


def maybe_prefetch(feed, args, parallel: str):
    """Stage host preprocessing + H2D ahead of the step loop
    (single-device, single-process solvers only: multi-host global
    assembly must stay on the main thread, and a mesh places its own
    batches; order-preserving, so determinism is unchanged).
    Shared by every app; ``--prefetch 0`` disables.  The wrapped feed's
    own ``PipelineMetrics`` (pipeline or packed reader) absorbs the
    staging hit/wait counts, so one ``input pipeline:`` line carries
    the whole host-side story."""
    size = getattr(args, "prefetch", 2)
    # --layout is a parallel solver too (its --parallel stays "none"):
    # staging there would put every whole batch on device 0 and leave
    # the step to reshard it; jit's in_shardings place the host batch
    if (
        size and parallel == "none" and not getattr(args, "layout", None)
        and jax.process_count() == 1
    ):
        return prefetch_to_device(
            feed, size=size, metrics=getattr(feed, "metrics", None)
        )
    return feed


class DoubleBuffer:
    """One-slot generic read-ahead: ``get(key)`` returns ``fetch(key)``,
    served from the slot a prior ``stage(key)`` filled in a background
    thread when the keys match (a *hit*), fetched synchronously
    otherwise.  The packed shard readers stage the next shard in plan
    order while the current one is consumed — the same overlap
    ``prefetch_to_device`` gives H2D, applied to shard open + index
    load.  Hits and waits land in the owning ``PipelineMetrics``.

    Threads are spawned per ``stage`` call and are short-lived (one
    fetch each); a stage that loses the race (consumer skipped past
    its key, or ``close()``) has its result discarded via ``.close()``
    when the fetched object supports it.  Exceptions from a staged
    fetch re-raise at the matching ``get``."""

    def __init__(self, fetch: Callable[[Any], Any], metrics=None):
        self._fetch = fetch
        self._metrics = metrics
        self._cv = threading.Condition()
        self._staged_key: Any = _NONE
        self._staged_val: Any = None
        self._staged_exc: Optional[BaseException] = None
        self._pending_key: Any = _NONE
        self._closed = False

    def stage(self, key: Any) -> None:
        """Start fetching ``key`` in the background (no-op when it is
        already staged or in flight, or after close)."""
        with self._cv:
            if (
                self._closed
                or key is None
                or key == self._staged_key
                or key == self._pending_key
            ):
                return
            self._pending_key = key

        def run():
            val, exc = None, None
            try:
                val = self._fetch(key)
            except BaseException as e:  # noqa: BLE001 — re-raised at get
                exc = e
            with self._cv:
                if self._pending_key == key and not self._closed:
                    self._discard()  # a stale staged slot, if any
                    self._staged_key = key
                    self._staged_val, self._staged_exc = val, exc
                    self._pending_key = _NONE
                    self._cv.notify_all()
                    return
            _close_quietly(val)  # lost the race: release the resource

        threading.Thread(
            target=run, daemon=True, name="snpk-shard-stage"
        ).start()

    def get(self, key: Any) -> Any:
        """``fetch(key)``, from the staged slot when possible."""
        t0 = time.perf_counter()
        with self._cv:
            while self._pending_key == key and not self._closed:
                self._cv.wait(timeout=0.1)
            if self._staged_key == key:
                val, exc = self._staged_val, self._staged_exc
                self._staged_key, self._staged_val = _NONE, None
                self._staged_exc = None
                if self._metrics is not None:
                    self._metrics.record_prefetch(
                        True, time.perf_counter() - t0
                    )
                if exc is not None:
                    raise exc
                return val
        val = self._fetch(key)
        if self._metrics is not None:
            self._metrics.record_prefetch(False, time.perf_counter() - t0)
        return val

    def _discard(self) -> None:
        """Release a stale staged value (caller holds the lock)."""
        if self._staged_key is not _NONE and self._staged_exc is None:
            _close_quietly(self._staged_val)
        self._staged_key, self._staged_val = _NONE, None
        self._staged_exc = None

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._discard()
            self._pending_key = _NONE
            self._cv.notify_all()


def _close_quietly(val: Any) -> None:
    try:
        getattr(val, "close", lambda: None)()
    except Exception:
        pass
