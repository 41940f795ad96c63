"""ctypes bindings for the native data runtime (libsparknet_data.so).

The reference's JVM↔native boundary is JavaCPP over a C shim
(SURVEY.md §1-2; mount empty). Ours is ctypes over the same style of C
ABI — no pybind11 in the image. The library is built on demand with the
repo's ``native/Makefile`` (g++, baked in); every entry point degrades
gracefully: ``available()`` is False and callers fall back to the pure
-Python data path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import weakref
from typing import Optional, Tuple

import numpy as np

from ..telemetry import timeline as _timeline

_HERE = os.path.dirname(os.path.abspath(__file__))
_NATIVE_DIR = os.path.abspath(os.path.join(_HERE, "..", "..", "native"))
_LIB_PATH = os.path.join(_NATIVE_DIR, "libsparknet_data.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_why_not: Optional[str] = None  # set when the build or the load failed

_f32p = ctypes.POINTER(ctypes.c_float)
_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)
_u8p = ctypes.POINTER(ctypes.c_uint8)

# the newest entry point: a library on disk without it predates this
# source whatever its mtime says.  ABI 4 (all threads on one batch; the
# counters threads and build_wall_ns) brought no call of its own, so the
# library exports its number as a name.
_NEWEST_SYMBOL = b"sn_abi_4"
# sn_loader_stats' order (Loader's enum in sparknet_data.cpp)
STATS = (
    "batches_built", "build_ns", "put_wait_ns", "batches_taken",
    "get_wait_ns", "copy_ns", "depth_on_arrival", "buffers_allocated",
    "threads", "build_wall_ns",
)


def default_threads() -> int:
    """The loader's thread count where none is asked for: half the cores
    this process may run on, at most 8.  The other half is the step loop's,
    the staging thread's and the runtime's transfer threads'; past 8 the
    batch's 633 MB of writes bound the build, not the threads (PERF.md §6,
    PR 30's sweep)."""
    return max(1, min(8, len(os.sched_getaffinity(0)) // 2))


def resolve_threads(requested: Optional[int] = None) -> int:
    """``--data-workers`` on the native path: N >= 1 is N loader threads;
    auto (None or negative) is ``SPARKNET_DATA_WORKERS`` where that names
    one, else :func:`default_threads`.  0, the python feed's "serial", has
    no native meaning (the loader's threads are not forks) and takes the
    core-derived count too.  The batch stream is the same at any count."""
    if requested is not None and requested >= 1:
        return requested
    env = os.environ.get("SPARKNET_DATA_WORKERS", "").strip()
    if (requested is None or requested < 0) and env and int(env) >= 1:
        return int(env)
    return default_threads()


def _is_stale() -> bool:
    """A library on disk that lacks the newest entry point (make goes
    by mtimes, and a copied tree's say nothing)."""
    try:
        with open(_LIB_PATH, "rb") as fh:
            return _NEWEST_SYMBOL not in fh.read()
    except OSError:
        return False  # not there: make builds it


def _build() -> Optional[str]:
    """``make -C native`` — a no-op when the library is newer than its
    source, a rebuild when it is not (an ``.so`` on disk may predate
    ``sparknet_data.cpp``; git tracks only the source) or when it lacks
    the newest entry point.  Returns why the build failed, or None."""
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR] + (["-B"] if _is_stale() else []),
            check=True, capture_output=True, timeout=120,
        )
    except subprocess.CalledProcessError as e:
        said = " ".join((e.stderr or b"").decode(errors="replace").split())
        return f"make failed: {said[-300:]}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"make failed: {type(e).__name__}: {e}"
    return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _why_not
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _why_not = _build()
        if _why_not is not None:
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            _why_not = f"dlopen failed: {e}"
            return None
        lib.sn_version.restype = ctypes.c_int
        lib.sn_cifar_decode.argtypes = [_u8p, ctypes.c_int, _u8p, _i32p]
        lib.sn_transform_batch.argtypes = [
            _u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
            _f32p, _f32p, ctypes.c_float, _f32p, ctypes.c_int,
        ]
        lib.sn_loader_create.restype = ctypes.c_void_p
        lib.sn_loader_create.argtypes = [
            _u8p, _i32p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, _f32p, _f32p, ctypes.c_float, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_int,
        ]
        lib.sn_loader_next.restype = ctypes.c_int
        lib.sn_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p), _i32p,
        ]
        lib.sn_loader_destroy.argtypes = [ctypes.c_void_p]
        try:
            lib.sn_loader_stats.restype = ctypes.c_int
            lib.sn_loader_stats.argtypes = [
                ctypes.c_void_p, _i64p, ctypes.c_int,
            ]
            lib.sn_buffer_release.restype = None
            lib.sn_buffer_release.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            getattr(lib, _NEWEST_SYMBOL.decode())
        except AttributeError as e:
            _why_not = f"{_LIB_PATH} is stale: {e}"
            return None
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def unavailable_reason() -> Optional[str]:
    """Why the library could not be built or loaded; None while it is
    available or was never asked for.  The apps print it, so
    ``--native-loader auto`` never changes the feed without saying so."""
    return _why_not


def _as_u8p(a: np.ndarray):
    return a.ctypes.data_as(_u8p)


def _as_f32p(a: Optional[np.ndarray]):
    return a.ctypes.data_as(_f32p) if a is not None else None


def _prep_mean_channel(
    mean_channel: Optional[np.ndarray], c: int
) -> Optional[np.ndarray]:
    """Broadcast to (c,) — Caffe broadcasts a single mean_value to all
    channels; the C side reads exactly c floats."""
    if mean_channel is None:
        return None
    mc = np.ascontiguousarray(mean_channel, np.float32).reshape(-1)
    if len(mc) == 1:
        mc = np.full((c,), mc[0], np.float32)
    if len(mc) != c:
        raise ValueError(f"mean_channel has {len(mc)} values for {c} channels")
    return mc


def _check_crop(crop: int, h: int, w: int) -> None:
    if crop > h or crop > w:
        raise ValueError(f"crop_size {crop} exceeds image size {h}x{w}")


def cifar_decode(raw: bytes) -> Tuple[np.ndarray, np.ndarray]:
    """CIFAR binary records -> (NHWC uint8 images, int32 labels)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(raw) // 3073
    buf = np.frombuffer(raw, np.uint8)
    images = np.empty((n, 32, 32, 3), np.uint8)
    labels = np.empty((n,), np.int32)
    lib.sn_cifar_decode(
        _as_u8p(np.ascontiguousarray(buf)), n, _as_u8p(images),
        labels.ctypes.data_as(_i32p),
    )
    return images, labels


def transform_batch(
    images: np.ndarray,
    *,
    crop: int = 0,
    train: bool = False,
    mirror: bool = False,
    seed: int = 0,
    mean_image: Optional[np.ndarray] = None,
    mean_channel: Optional[np.ndarray] = None,
    scale: float = 1.0,
    num_threads: int = 4,
) -> np.ndarray:
    """Native crop/mirror/mean/scale over an NHWC uint8 batch."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    images = np.ascontiguousarray(images, np.uint8)
    n, h, w, c = images.shape
    _check_crop(crop, h, w)
    ch = crop or h
    cw = crop or w
    out = np.empty((n, ch, cw, c), np.float32)
    mi = (
        np.ascontiguousarray(mean_image, np.float32)
        if mean_image is not None else None
    )
    mc = _prep_mean_channel(mean_channel, c)
    lib.sn_transform_batch(
        _as_u8p(images), n, h, w, c, crop, int(train), int(mirror),
        ctypes.c_uint64(seed), _as_f32p(mi), _as_f32p(mc),
        ctypes.c_float(scale), out.ctypes.data_as(_f32p), num_threads,
    )
    return out


class _Lent:
    """One batch buffer of the library's pool, lent by ``sn_loader_next``:
    the ``.base`` of the array made over it, and so behind every view of
    that array.  Its finalizer gives the buffer back."""

    __slots__ = ("__array_interface__", "__weakref__")

    def __init__(self, address: int, shape: Tuple[int, ...]):
        self.__array_interface__ = {
            "version": 3, "typestr": "<f4", "shape": shape,
            "data": (address, False),
        }


class NativeLoader:
    """Threaded prefetching batch loader over an in-memory dataset.

    Yields {"data": f32 (B, crop, crop, C), "label": int32 (B,)} batches
    indefinitely (epochs wrap with a fresh deterministic shuffle). The
    full pipeline — shuffle, crop/mirror/mean, batch assembly — runs in
    native worker threads ahead of the consumer, all of them on one batch
    at a time (``num_threads``: :func:`resolve_threads`; the stream is the
    same at any count).

    **A batch's memory is yours while you hold it.**  ``data`` is not a
    copy: it is the buffer a worker wrote the batch into, lent by the
    library's pool.  It goes back, to be rewritten, when the last
    reference to it dies — the array, any view of it, or whatever either
    was handed to (``jax.device_put`` keeps the host array until its
    transfer ends, and for the life of the jax array where the CPU backend
    aliases it).  Nothing has to be released by hand, and ``close()``
    frees no batch that is still held.  Hold batches and the loader
    allocates more buffers (``buffers_allocated`` of :meth:`stats`); the
    set grows to what is held plus the window the workers run ahead, and
    is reused from then on.

    ``metrics`` is a :class:`~sparknet_tpu.data.pipeline.PipelineMetrics`
    (registry source ``native_loader``) fed at each ``__next__`` from the
    deltas of the library's counters (:meth:`stats`): ``produce`` is the
    threads' time inside a batch's pixel work added up, ``build_wall`` the
    batch's latency from its start to its last image (``produce`` over it
    is how many of the ``threads`` really worked), ``worker_wait`` their
    wait for room (back-pressure), ``consumer_wait`` the caller's wait for
    its batch, ``reorder_depth`` the batches it found ready.  The same
    deltas go to the current timeline as ``feed.loader_blocked``,
    ``feed.copy_out`` and ``feed.produce`` (telemetry/timeline.py).
    """

    def __init__(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        batch_size: int,
        *,
        crop: int = 0,
        train: bool = True,
        mirror: bool = False,
        mean_image: Optional[np.ndarray] = None,
        mean_channel: Optional[np.ndarray] = None,
        scale: float = 1.0,
        seed: int = 0,
        num_threads: Optional[int] = None,
        queue_cap: int = 4,
    ):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        images = np.ascontiguousarray(images, np.uint8)
        labels = np.ascontiguousarray(labels, np.int32)
        n, h, w, c = images.shape
        _check_crop(crop, h, w)
        self.batch_size = batch_size
        self.shape = (batch_size, crop or h, crop or w, c)
        mi = (
            np.ascontiguousarray(mean_image, np.float32)
            if mean_image is not None else None
        )
        mc = _prep_mean_channel(mean_channel, c)
        self._handle = lib.sn_loader_create(
            _as_u8p(images), labels.ctypes.data_as(_i32p), n, h, w, c,
            batch_size, crop, int(train), int(mirror), _as_f32p(mi),
            _as_f32p(mc), ctypes.c_float(scale), ctypes.c_uint64(seed),
            resolve_threads(num_threads), queue_cap,
        )
        if not self._handle:
            raise ValueError("sn_loader_create failed (check batch <= n)")
        self.batches_per_epoch = n // batch_size
        from ..data.pipeline import PipelineMetrics

        self.metrics = PipelineMetrics(source_name="native_loader")
        self._seen = dict.fromkeys(STATS, 0)
        # one caller inside the library at a time: close() from the main
        # thread waits for a staging thread's __next__ to return, and
        # never frees the loader under it
        self._lock = threading.RLock()

    def stats(self) -> dict:
        """The library's cumulative counters, by ``STATS``' names; the
        last reading once closed."""
        with self._lock:
            if not self._handle:
                return dict(self._seen)
            out = (ctypes.c_int64 * len(STATS))()
            self._lib.sn_loader_stats(self._handle, out, len(STATS))
            return dict(zip(STATS, out))

    def _account(self, started: Optional[float] = None) -> None:
        """Feed ``metrics`` and the current timeline from what the
        counters moved by since the last reading.  ``started`` is when
        the ``__next__`` that just returned began: its wait lies at its
        start, so a timeline made during the call takes only its part."""
        now, before = self.stats(), self._seen
        self._seen = now
        delta = {k: now[k] - before[k] for k in STATS}
        built = delta["batches_built"]
        for _ in range(built):
            self.metrics.record_batch(
                self.batch_size, 1e-9 * delta["build_ns"] / built,
                1e-9 * delta["put_wait_ns"] / built,
            )
            self.metrics.record_build(
                now["threads"], 1e-9 * delta["build_wall_ns"] / built
            )
        self.metrics.record_buffers(now["buffers_allocated"])
        tl = _timeline.current()
        if built:
            tl.add("feed.produce", 1e-9 * delta["build_ns"], built)
        if delta["batches_taken"]:  # one: the call that just returned
            waited = 1e-9 * delta["get_wait_ns"]
            copied = 1e-9 * delta["copy_ns"]
            self.metrics.record_consumer_wait(waited)
            self.metrics.reorder_depth.set(delta["depth_on_arrival"])
            tl.add("feed.loader_blocked", waited, began=started)
            # the copy lies at the call's end, which is about now
            tl.add("feed.copy_out", copied, began=_timeline.clock() - copied)

    def __iter__(self):
        return self

    def __next__(self):
        started = _timeline.clock()
        labels = np.empty((self.batch_size,), np.int32)
        data, pool = ctypes.c_void_p(), ctypes.c_void_p()
        with self._lock:
            rc = self._lib.sn_loader_next(
                self._handle, ctypes.byref(data), ctypes.byref(pool),
                labels.ctypes.data_as(_i32p),
            )
            if rc != 0:
                raise StopIteration
            lent = _Lent(data.value, self.shape)
            # not at exit: a thread may still read the memory then
            weakref.finalize(
                lent, self._lib.sn_buffer_release, pool.value, data.value
            ).atexit = False
            self._account(started)
        return {"data": np.asarray(lent), "label": labels}

    def close(self) -> None:
        with self._lock:
            if getattr(self, "_handle", None):
                self._account()  # what the workers built since the last batch
                self._lib.sn_loader_destroy(self._handle)
                self._handle = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
