"""Parallel host input pipeline: multiprocess batch assembly +
preprocessing with shared-memory transport.

The serial feed (``ShardedDataset.batches`` + ``prefetch_to_device``)
produces every batch on ONE GIL-bound Python thread — decode, crop,
mirror, mean-subtract all run serially, so on a fast chip live-feed
training is host-bound (the reference hides the same cost inside
Caffe's C++ prefetch thread; the TensorFlow paper credits much of its
end-to-end throughput to exactly this overlap). This module fans the
batch work out to N worker *processes* without changing a single bit of
the batch stream:

- **Determinism / lineage.** A batch's content depends only on
  ``(seed, epoch, batch-index)`` — the ``ShardedDataset`` contract —
  never on which worker built it or in what order workers finish.
  Worker ``r`` runs the *same* serial enumeration as the plain feed but
  transforms only batches with ``index % workers == r`` (the others are
  slice-skipped, never transformed), so the union of worker outputs,
  reordered by sequence number, is bit-identical to the serial feed for
  ANY worker count. Changing ``SPARKNET_DATA_WORKERS`` can never change
  training results.
- **Shared-memory transport.** Batches return to the consumer through
  per-worker rings of ``multiprocessing.shared_memory`` slots: the
  worker writes the raw array bytes into one of its own ``depth`` slots
  and ships only a tiny descriptor (sequence number,
  dtypes/shapes/offsets) through the queue — no pickling of the image
  payload. The consumer memcpys out at *consumption* time and only then
  returns the slot to its owner, so slots are real backpressure: a
  worker can run at most ``depth`` batches ahead of the in-order
  stream's consumption of ITS batches (never unboundedly ahead while a
  straggler holds up the sequence), bounding staged batches at
  ``workers * depth``. Per-worker ownership keeps this deadlock-free: a
  slow worker's slot supply is never starved by fast workers' parked
  batches. A batch that outgrows its slot (shouldn't happen with fixed
  shapes) falls back to pickling through the queue — correct, slower,
  counted in the metrics.
- **Resume.** ``skip(n)`` before iteration starts is O(1): it offsets
  every worker's start index, so ``Solver.align_feed`` fast-forward
  stays bit-identical. After the workers have started it degrades to
  consume-and-discard.
- **Shutdown.** ``close()`` (also ``with``-exit, generator-style
  ``__del__``) stops the workers, joins them, and unlinks every
  shared-memory segment — tier-1 CI asserts no stray children or
  ``/dev/shm`` segments survive the tests.  A worker that ignores
  ``terminate()`` (wedged in C code) is escalated to ``kill()`` so a
  stuck child can never hang interpreter exit.
- **Self-healing.** The consumer supervises the workers: a rank that
  dies silently (nonzero exitcode, closed pipe — e.g. OOM-kill, or the
  ``pipeline.worker_crash`` chaos point) is respawned at the first
  batch it never delivered, and the per-batch-index RNG re-produces
  the lost batches bit-identically, so a crash costs latency, never
  correctness.  Respawns are budgeted (``SPARKNET_PIPELINE_RESPAWNS``
  per rank, default 2) with exponential backoff; past the budget the
  failure surfaces at its serial stream position exactly as before.
  A worker that *raises* (deterministic transform bug) still re-raises
  at its serial position — respawning would just hit the same bug.
  Every respawn increments ``PipelineMetrics.worker_respawns`` and the
  chaos registry's ``pipeline.worker_respawn`` recovery counter.
- **Observability.** :class:`PipelineMetrics` reuses the telemetry
  gauge/histogram primitives (``telemetry/registry.py``, where the
  serving metrics' primitives now live) to expose per-stage
  wait time (worker blocked on a free slot; consumer blocked waiting
  for the next in-order batch) and queue occupancy, so the apps can
  report host-bound vs device-bound directly: a consumer
  that never waits is device-bound; one that always waits is
  host-bound.

Workers are forked, not spawned: partition functions are closures
(lambdas over file paths / synthetic generators) that cannot pickle,
and fork inherits them for free. Workers only touch numpy and the
multiprocessing primitives — never JAX — so inheriting an initialized
JAX runtime is safe. On platforms without fork, callers should fall
back to the serial feed (``default_data_workers`` returns 0 there).

Compose with ``prefetch_to_device`` for the H2D stage::

    pipe = ParallelBatchPipeline(ds, bs, workers=4, transform=aug)
    feed = prefetch_to_device(pipe, size=2)
"""

from __future__ import annotations

import multiprocessing as mp
import os
import pickle
import queue as _queue
import sys
import threading
import time
import traceback
from multiprocessing import shared_memory
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

from ..telemetry import trace as _trace
from ..telemetry.registry import REGISTRY, Gauge, LatencyHistogram

# /dev/shm name prefix; the tests' leak fixture greps for it
SHM_PREFIX = "snpipe"


def default_data_workers() -> int:
    """Worker count for the apps' feeds: ``SPARKNET_DATA_WORKERS`` when
    set, else cpu-count-aware — leave one core for the consumer (device
    dispatch + H2D), cap at 4 (each worker replicates the cheap
    assembly slicing; past ~4 the shared source bandwidth dominates).
    0 means serial. Platforms without fork always resolve to 0."""
    if "fork" not in mp.get_all_start_methods():
        return 0
    env = os.environ.get("SPARKNET_DATA_WORKERS", "").strip()
    if env:
        return max(0, int(env))
    return max(0, min(4, (os.cpu_count() or 1) - 1))


def resolve_data_workers(requested: Optional[int]) -> int:
    """An app's ``--data-workers`` flag -> effective worker count:
    negative/None means auto (:func:`default_data_workers`)."""
    if requested is None or requested < 0:
        return default_data_workers()
    if requested and "fork" not in mp.get_all_start_methods():
        return 0
    return requested


class PipelineMetrics:
    """Input-pipeline observability, one JSON line (same discipline as
    ``serve/metrics.py``).

    The host-vs-device question reads directly off two histograms:
    ``consumer_wait`` is how long the training loop sat waiting for the
    next in-order batch (host-bound time); ``worker_wait`` is how long
    producers sat blocked on a free slot (device/consumer-bound —
    healthy backpressure). ``produce`` is the per-batch assembly +
    transform cost inside a worker.  The ``prefetch`` block counts the
    double-buffering layers (``prefetch_to_device`` staging, the packed
    readers' shard read-ahead): hits are consumes served from a staged
    slot, waits are the time blocked on one still in flight.

    ``source_name`` is the telemetry-registry source this instance
    registers under: ``"pipeline"`` for the multiprocess pipeline,
    ``"packed_reader"`` for a serial packed-shard feed — distinct names
    so a pipeline OVER a packed dataset reports both layers."""

    def __init__(self, source_name: str = "pipeline"):
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self.batches = 0
        self.rows = 0
        self.shm_fallbacks = 0
        self.worker_respawns = 0
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self.buffers_allocated = None  # a feed that reuses batch buffers sets it
        self.threads = None  # a feed whose threads share a batch sets it
        self.build_wall = LatencyHistogram()  # and a batch's latency
        self.produce = LatencyHistogram()
        self.worker_wait = LatencyHistogram()
        self.consumer_wait = LatencyHistogram()
        self.prefetch_wait = LatencyHistogram()
        self.reorder_depth = Gauge()  # batches parked awaiting their turn
        self.slots_free = Gauge()
        # the telemetry registry source: the periodic telemetry: line
        # and run reports see the live feed without extra wiring
        # (weakly held — dies with the pipeline/reader)
        REGISTRY.register_source(source_name, self)

    # ------------------------------------------------------------- writes
    def record_batch(
        self, rows: int, produce_s: float, worker_wait_s: float,
        fallback: bool = False,
    ) -> None:
        with self._lock:
            self.batches += 1
            self.rows += rows
            if fallback:
                self.shm_fallbacks += 1
            self.produce.observe(produce_s)
            self.worker_wait.observe(worker_wait_s)

    def record_consumer_wait(self, seconds: float) -> None:
        with self._lock:
            self.consumer_wait.observe(seconds)

    def record_buffers(self, allocated: int) -> None:
        """The batch buffers the feed has allocated so far (the native
        loader's pool); every other batch was written into a used one."""
        with self._lock:
            self.buffers_allocated = allocated

    def record_build(self, threads: int, wall_s: float) -> None:
        """One batch built by ``threads`` threads together (the native
        loader): ``wall_s`` from its start to its last image, beside
        ``produce``, which is the threads' time inside it added up."""
        with self._lock:
            self.threads = threads
            self.build_wall.observe(wall_s)

    def record_respawn(self) -> None:
        with self._lock:
            self.worker_respawns += 1

    def record_prefetch(self, hit: bool, wait_s: float) -> None:
        """One double-buffered consume: ``hit`` = served from a staged
        slot; the wait histogram shows what staging failed to hide."""
        with self._lock:
            if hit:
                self.prefetch_hits += 1
            else:
                self.prefetch_misses += 1
            self.prefetch_wait.observe(wait_s)

    # -------------------------------------------------------------- reads
    def snapshot(self) -> dict:
        with self._lock:
            dt = max(time.perf_counter() - self._t0, 1e-9)
            snap = {
                "uptime_s": round(dt, 3),
                "batches": self.batches,
                "rows": self.rows,
                "rows_per_sec": round(self.rows / dt, 2),
                "shm_fallbacks": self.shm_fallbacks,
                "worker_respawns": self.worker_respawns,
                "prefetch": {
                    "hits": self.prefetch_hits,
                    "misses": self.prefetch_misses,
                    "wait": self.prefetch_wait.snapshot(),
                },
                "produce": self.produce.snapshot(),
                "worker_wait": self.worker_wait.snapshot(),
                "consumer_wait": self.consumer_wait.snapshot(),
                "reorder_depth": self.reorder_depth.snapshot(),
                "slots_free": self.slots_free.snapshot(),
                # host copies the local-SGD round staging saved by
                # reusing its preallocated buffers (parallel/local_sgd
                # RoundBuffer) — surfaced here so the one input-
                # pipeline line answers the whole host-copy story
                "round_buffer": {
                    "reuses": REGISTRY.counter(
                        "round_buffer", event="reuse"
                    ).snapshot(),
                    "allocs": REGISTRY.counter(
                        "round_buffer", event="alloc"
                    ).snapshot(),
                },
            }
            if self.buffers_allocated is not None:
                reused = max(self.batches - self.buffers_allocated, 0)
                snap["buffers"] = {
                    "allocated": self.buffers_allocated,
                    "reused_pct": round(100.0 * reused / max(self.batches, 1), 2),
                }
            if self.threads is not None:
                snap["threads"] = self.threads
                snap["build_wall"] = self.build_wall.snapshot()
            return snap

    def json_line(self) -> str:
        import json

        return json.dumps(self.snapshot())


def _layout(arrs: Dict[str, np.ndarray]):
    """(total_bytes, [(key, dtype_str, shape, offset), ...]) for packing
    a batch's arrays into one slot at 64-byte-aligned offsets."""
    metas, off = [], 0
    for k, a in arrs.items():
        off = (off + 63) & ~63
        metas.append((k, a.dtype.str, a.shape, off))
        off += a.nbytes
    return off, metas


def _worker_main(
    rank, workers, first_seq, ds, batch_kw, transform, slot_bytes,
    stop, free_q, result_q, chaos_on=True,
):
    """One preprocessing worker: the serial batch enumeration with all
    batches not congruent to ``rank`` slice-skipped (never transformed),
    so this worker's transform RNG draws are exactly the serial feed's
    for its indices. Ships each batch through a shared-memory slot.
    ``first_seq`` is the first global batch index this worker produces
    (stride ``workers``) — a respawned worker resumes mid-stream at the
    first batch its predecessor never delivered.  ``chaos_on=False``
    disarms fault injection (respawned workers: the fault already
    killed the process once; re-firing at the same deterministic batch
    would crash-loop straight through the respawn budget)."""
    plan = None
    if chaos_on:
        from .. import chaos as _chaos

        plan = _chaos.get_plan()  # fork inherits the parent's plan
    shms: Dict[str, shared_memory.SharedMemory] = {}
    try:
        it = ds.batches(**batch_kw, transform=transform)
        it.skip(first_seq)
        seq = first_seq
        while not stop.is_set():
            if plan is not None:
                rule = plan.match(
                    "pipeline.worker_crash", batch=seq, worker=rank
                )
                if rule is not None:
                    # hard death, no goodbye message: the supervisor
                    # must detect it from the exitcode/closed pipe
                    os._exit(int(rule.params.get("exit_code", 3)))
                rule = plan.match(
                    "pipeline.slow_batch", batch=seq, worker=rank
                )
                if rule is not None:
                    time.sleep(
                        float(rule.params.get("delay_ms", 50.0)) / 1e3
                    )
            t0 = time.perf_counter()
            with _trace.span("pipeline.produce", cat="pipeline",
                             batch=seq, worker=rank):
                try:
                    batch = next(it)
                except StopIteration:
                    result_q.put(("done", rank))
                    return
                arrs = {
                    k: np.ascontiguousarray(v) for k, v in batch.items()
                }
            produce_s = time.perf_counter() - t0
            rows = len(next(iter(arrs.values())))
            total, metas = _layout(arrs)
            # stop-aware wait for a free slot (bounded-queue backpressure)
            t1 = time.perf_counter()
            slot = None
            while not stop.is_set():
                try:
                    slot = free_q.get(timeout=0.1)
                    break
                except _queue.Empty:
                    continue
            if slot is None:
                return
            wait_s = time.perf_counter() - t1
            if total <= slot_bytes:
                shm = shms.get(slot)
                if shm is None:
                    shm = shms[slot] = shared_memory.SharedMemory(name=slot)
                for (k, dt, shape, off) in metas:
                    dst = np.ndarray(
                        shape, np.dtype(dt), buffer=shm.buf, offset=off
                    )
                    dst[...] = arrs[k]
                result_q.put(("b", seq, slot, metas, produce_s, wait_s, rows))
            else:
                # batch outgrew the slot (remainder batches only shrink;
                # this needs a shape change mid-stream) — hand the slot
                # back unused and pickle through the queue instead
                free_q.put(slot)
                result_q.put((
                    "b", seq, None, pickle.dumps(arrs, protocol=-1),
                    produce_s, wait_s, rows,
                ))
            it.skip(workers - 1)
            seq += workers
    except BaseException:
        try:
            result_q.put(("err", rank, traceback.format_exc()))
        except Exception:
            pass
    finally:
        try:
            # multiprocessing children skip atexit: dump this worker's
            # spans for the owner's merged Chrome trace (no-op when
            # tracing is off; chaos os._exit deaths simply lose theirs)
            _trace.flush_sidecar()
        except Exception:
            pass
        for shm in shms.values():
            try:
                shm.close()
            except Exception:
                pass


class ParallelBatchPipeline:
    """Order-preserving multiprocess feed over ``ds.batches(...)``.

    Iterator of batches bit-identical to the serial
    ``ds.batches(batch_size, shuffle=shuffle, seed=seed, ...,
    transform=transform)`` stream, with assembly + transform fanned out
    to ``workers`` forked processes. See the module docstring for the
    determinism, transport, backpressure and shutdown contracts.

    ``depth`` is the number of shared-memory slots per worker (the ring
    size — total staged batches are bounded by ``workers * depth``).
    ``slot_bytes`` overrides the probe-derived slot size (tests use a
    tiny value to force the pickle fallback path).  ``max_respawns``
    bounds per-rank recoveries from silent worker death (default
    ``SPARKNET_PIPELINE_RESPAWNS``, 2); past it the death re-raises at
    its serial stream position.
    """

    def __init__(
        self,
        ds,
        batch_size: int,
        *,
        workers: int,
        shuffle: bool = True,
        seed: int = 0,
        epochs: Optional[int] = None,
        drop_remainder: bool = True,
        transform: Optional[Callable] = None,
        depth: int = 2,
        slot_bytes: Optional[int] = None,
        metrics: Optional[PipelineMetrics] = None,
        max_respawns: Optional[int] = None,
    ):
        if workers < 1:
            raise ValueError(
                "ParallelBatchPipeline needs workers >= 1 "
                "(use ds.batches() directly for a serial feed)"
            )
        if "fork" not in mp.get_all_start_methods():
            raise RuntimeError(
                "ParallelBatchPipeline requires the fork start method "
                "(partition closures don't pickle); use the serial feed"
            )
        self._ds = ds
        self._batch_kw = dict(
            shuffle=shuffle, seed=seed, epochs=epochs,
            drop_remainder=drop_remainder,
        )
        self._batch_size = batch_size
        self._transform = transform
        self.workers = workers
        self._depth = max(1, depth)
        self._slot_bytes = slot_bytes
        self.metrics = metrics or PipelineMetrics()
        self._ctx = mp.get_context("fork")
        self._started = False
        self._closed = False
        self._exhausted = False
        self._initial_skip = 0
        self._drop = 0
        self._buffer: Dict[int, Any] = {}
        self._done: set = set()
        self._errors: Dict[int, str] = {}
        self._procs: list = []
        self._shms: Dict[str, shared_memory.SharedMemory] = {}
        self._max_respawns = (
            max_respawns
            if max_respawns is not None
            else int(os.environ.get("SPARKNET_PIPELINE_RESPAWNS", "2") or 0)
        )
        self._respawns: Dict[int, int] = {}

    # ------------------------------------------------------------ control
    def skip(self, n: int) -> None:
        """Fast-forward past the next ``n`` batches. O(1) before the
        workers start (offsets every worker's start index — the resume
        path: ``Solver.align_feed`` runs before iteration); after start
        it consumes and discards."""
        if n <= 0:
            return
        if self._started:
            self._drop += n
        else:
            self._initial_skip += n

    def _start(self) -> None:
        self._started = True
        base = self._initial_skip
        # Probe batch: produced serially in-process. It both sizes the
        # shared-memory slots (payload bytes of a real transformed
        # batch) and becomes sequence number `base` — the workers start
        # one batch later.
        probe_it = self._ds.batches(
            self._batch_size, **self._batch_kw, transform=self._transform
        )
        probe_it.skip(base)
        t0 = time.perf_counter()
        try:
            self._probe = {
                k: np.ascontiguousarray(v)
                for k, v in next(probe_it).items()
            }
        except StopIteration:
            self._exhausted = True
            return
        finally:
            del probe_it
        total, _ = _layout(self._probe)
        self.metrics.record_batch(
            len(next(iter(self._probe.values()))),
            time.perf_counter() - t0, 0.0,
        )
        slot_bytes = self._slot_bytes or max(total, 64)
        self._slot_bytes = slot_bytes
        self._have_probe = True
        self._next_seq = base

        self._stop = self._ctx.Event()
        # per-worker slot rings: worker r's slots circulate ONLY through
        # free_qs[r], returned at in-order consumption — see the module
        # docstring's backpressure contract
        self._free_qs = [self._ctx.Queue() for _ in range(self.workers)]
        self._result_q = self._ctx.Queue()
        self._token = os.urandom(4).hex()
        for r in range(self.workers):
            for i in range(self._depth):
                name = f"{SHM_PREFIX}_{os.getpid()}_{self._token}_{r}_{i}"
                self._shms[name] = shared_memory.SharedMemory(
                    name=name, create=True, size=slot_bytes
                )
                self._free_qs[r].put(name)
        self.metrics.slots_free.set(self.workers * self._depth)
        self._worker_base = base + 1
        for r in range(self.workers):
            self._procs.append(
                self._spawn_worker(
                    r, self._worker_base + r, chaos_on=True,
                    name=f"{SHM_PREFIX}-worker-{r}",
                )
            )

    def _spawn_worker(self, rank, first_seq, chaos_on, name):
        import warnings

        p = self._ctx.Process(
            target=_worker_main,
            args=(
                rank, self.workers, first_seq, self._ds,
                dict(self._batch_kw, batch_size=self._batch_size),
                self._transform, self._slot_bytes, self._stop,
                self._free_qs[rank], self._result_q, chaos_on,
            ),
            daemon=True,
            name=name,
        )
        with warnings.catch_warnings():
            # jax warns that fork + its threads can deadlock; the
            # workers never call into jax (numpy + mp queues only),
            # which is the one case the warning doesn't cover
            warnings.filterwarnings(
                "ignore", message=r"os\.fork\(\) was called",
                category=RuntimeWarning,
            )
            p.start()
        return p

    # ---------------------------------------------------------- iteration
    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        if not self._started:
            self._start()
        while True:
            batch = self._pop_in_order()
            if batch is None:
                self._exhausted = True
                raise StopIteration
            if self._drop > 0:
                self._drop -= 1
                continue
            return batch

    def _owner(self, seq: int) -> int:
        return (seq - self._worker_base) % self.workers

    def _pop_in_order(self):
        """The batch with the next sequence number, or None when the
        stream is exhausted (finite epochs). Blocks on the result queue,
        recording the blocked time as consumer wait."""
        if self._exhausted:
            return None
        if getattr(self, "_have_probe", False):
            self._have_probe = False
            self._next_seq += 1
            probe, self._probe = self._probe, None
            return probe
        t0 = time.perf_counter()
        while True:
            if self._next_seq in self._buffer:
                entry = self._buffer.pop(self._next_seq)
                batch = self._materialize(entry, self._owner(self._next_seq))
                self.metrics.reorder_depth.set(len(self._buffer))
                self._next_seq += 1
                self.metrics.record_consumer_wait(time.perf_counter() - t0)
                return batch
            owner = self._owner(self._next_seq)
            if owner in self._errors:
                # raise at the SERIAL error position: every in-order
                # batch before the failing index was already yielded
                # (a worker races ahead of the consumer, so its error
                # message arrives early — the other workers' earlier
                # batches must still come out first)
                tb = self._errors[owner]
                self.close()
                raise RuntimeError(
                    f"input pipeline worker {owner} died:\n{tb}"
                )
            if owner in self._done:
                # per-process queue order means every batch that worker
                # produced was read before its "done" — the stream ends
                # at the first sequence number nobody will ever send
                return None
            try:
                msg = self._result_q.get(timeout=1.0)
            except _queue.Empty:
                # the worker owning the awaited sequence number died
                # without a word (kill -9, OOM, chaos worker_crash — a
                # transform exception raises through the "err" message
                # instead): respawn it and re-produce the lost batches
                # deterministically; past the budget, fail at the
                # serial position instead of hanging
                if (
                    not self._procs[owner].is_alive()
                    and self._result_q.empty()
                ):
                    if not self._respawn(owner):
                        exitcode = self._procs[owner].exitcode
                        self.close()
                        raise RuntimeError(
                            f"input pipeline worker {owner} exited "
                            f"(code {exitcode}) without finishing the "
                            f"stream (awaiting batch {self._next_seq}; "
                            f"{self._respawns.get(owner, 0)} respawns "
                            f"already spent)"
                        )
                continue
            self._handle(msg)

    def _respawn(self, owner: int) -> bool:
        """Replace a silently-dead worker: new process, same rank,
        resuming at the first batch the dead one never delivered (its
        shipping is in-order, so that is the first owner-congruent
        sequence number at/after the consumer cursor that isn't parked
        in the reorder buffer).  The per-batch-index RNG makes the
        re-produced batches bit-identical to what the dead worker would
        have sent.  Bounded per rank; exponential backoff between
        attempts so a crash loop can't busy-spin the host."""
        n = self._respawns.get(owner, 0)
        if n >= self._max_respawns:
            return False
        self._respawns[owner] = n + 1
        exitcode = self._procs[owner].exitcode
        time.sleep(min(2.0, 0.05 * (2 ** n)))
        seq = self._next_seq
        while self._owner(seq) != owner:
            seq += 1
        while seq in self._buffer:
            seq += self.workers
        # the dead worker may have died holding one popped-but-unshipped
        # slot; add a replacement so its ring keeps `depth` slots (a
        # message already in flight instead resolves as a duplicate —
        # see _handle — and returns its slot there)
        name = (
            f"{SHM_PREFIX}_{os.getpid()}_{self._token}_{owner}"
            f"_r{self._respawns[owner]}"
        )
        self._shms[name] = shared_memory.SharedMemory(
            name=name, create=True, size=self._slot_bytes
        )
        self._free_qs[owner].put(name)
        self.metrics.slots_free.add(1)
        self._procs[owner] = self._spawn_worker(
            owner, seq, chaos_on=False,
            name=f"{SHM_PREFIX}-worker-{owner}-r{self._respawns[owner]}",
        )
        self.metrics.record_respawn()
        from .. import chaos

        chaos.record_recovery("pipeline.worker_respawn")
        print(
            f"input pipeline: worker {owner} died (exit {exitcode}); "
            f"respawned at batch {seq} "
            f"(attempt {self._respawns[owner]}/{self._max_respawns})",
            file=sys.stderr, flush=True,
        )
        return True

    def _materialize(self, entry, owner: int):
        """Buffer entry -> batch dict. Slot-backed entries memcpy out
        of shared memory HERE, at consumption, and only then hand the
        slot back to its owning worker — deferring the release is what
        makes ``workers * depth`` a real bound on staged batches."""
        slot, payload = entry
        if slot is None:
            return payload
        shm = self._shms[slot]
        batch = {
            k: np.ndarray(
                shape, np.dtype(dt), buffer=shm.buf, offset=off
            ).copy()
            for (k, dt, shape, off) in payload
        }
        self._free_qs[owner].put(slot)
        self.metrics.slots_free.add(1)
        return batch

    def _handle(self, msg) -> None:
        kind = msg[0]
        if kind == "b":
            _, seq, slot, payload, produce_s, wait_s, rows = msg
            if seq < self._next_seq or seq in self._buffer:
                # duplicate after a respawn race: the dead worker's
                # message was still in the queue pipe when the respawn
                # re-produced the batch. Drop it — but hand the slot
                # back, or the ring loses capacity
                if slot is not None:
                    self._free_qs[self._owner(seq)].put(slot)
                return
            if slot is None:
                self._buffer[seq] = (None, pickle.loads(payload))
            else:
                self._buffer[seq] = (slot, payload)
                self.metrics.slots_free.add(-1)
            self.metrics.record_batch(
                rows, produce_s, wait_s, fallback=slot is None
            )
            self.metrics.reorder_depth.set(len(self._buffer))
        elif kind == "done":
            self._done.add(msg[1])
        elif kind == "err":
            # recorded, not raised: the raise happens when the stream
            # reaches the dead worker's next sequence number, so the
            # error surfaces at its serial position (_pop_in_order)
            _, rank, tb = msg
            self._errors[rank] = tb

    # ------------------------------------------------------------ cleanup
    def close(self) -> None:
        """Stop workers, join them, unlink every shared-memory segment.
        Idempotent; also runs from ``__del__`` and ``with``-exit so an
        abandoned pipeline can't leak processes or /dev/shm segments."""
        if self._closed:
            return
        self._closed = True
        if not self._started:
            return
        if hasattr(self, "_stop"):
            self._stop.set()
        for p in self._procs:
            p.join(timeout=10)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
            if p.is_alive():
                # SIGTERM ignored (worker wedged in uninterruptible C
                # code): escalate to SIGKILL — a stuck child must never
                # hang interpreter exit (the CI leak fixture relies on
                # close() actually reaping)
                p.kill()
                p.join(timeout=5)
        for q in [getattr(self, "_result_q", None)] + list(
            getattr(self, "_free_qs", [])
        ):
            if q is None:
                continue
            try:
                while True:
                    q.get_nowait()
            except Exception:
                pass
            q.close()
            q.cancel_join_thread()
        for shm in self._shms.values():
            try:
                shm.close()
                shm.unlink()
            except FileNotFoundError:
                pass
        self._shms.clear()
        self._buffer.clear()
        self._probe = None

    def __enter__(self) -> "ParallelBatchPipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort: tests assert the explicit path
        try:
            self.close()
        except Exception:
            pass
