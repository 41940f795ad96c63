"""Span tracer: bounded, thread-aware, Chrome-trace-event export.

``jax.profiler.trace`` (``utils/profiling.trace``) answers *op-level*
questions — what XLA did inside a dispatch.  This tracer answers the
*system-level* ones the paper's τ analysis is made of: how long the
train loop waited on host input, what the batcher flushed, when a
pipeline worker produced batch 37, where a supervisor generation ended.
Spans are cheap host-side intervals recorded into a bounded ring
buffer and exported as Chrome trace-event JSON — load the file in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.

Contracts:

- **Near-zero when disabled.**  ``span(...)`` returns one shared no-op
  context manager when tracing is off — no allocation, no clock read;
  the enabled check is a module bool.
- **Thread-aware.**  Events carry ``tid`` (`threading.get_ident`) and
  the export emits thread-name metadata, so batcher/prefetch/handler
  threads render as separate tracks.
- **Bounded.**  The ring buffer (default 65536 spans) evicts oldest;
  a long run keeps its tail, never grows without bound.
- **Multi-process.**  The process that calls :func:`enable` with a
  path becomes the *owner* (recorded in ``SPARKNET_TRACE_OWNER_PID``
  so every descendant knows); forked pipeline workers and exec'd
  children with a nonzero ``SPARKNET_PROCESS_ID`` become *sidecar*
  writers, dumping their spans to ``{path}.part-{pid}.json``.  The
  owner's :func:`write` merges every part file by pid/tid into the
  final ``{"traceEvents": [...]}`` document.  Fork hygiene: an
  ``os.register_at_fork`` hook clears the child's inherited buffer so
  parent spans are never double-written.

Timestamps are wall-clock microseconds (``time.time_ns`` at span
entry) so spans from different processes land on one timeline;
durations come from ``perf_counter`` deltas.

**The device's track.**  With ``--profile-dir`` beside ``--trace`` the
profiler's device plane joins the same file: :func:`anchor_offset`
finds what to add to the plane's clock from the anchor that
``utils/profiling.trace`` ran, :func:`device_track` turns the
executions into events, and :func:`longest_gaps` says which phases of
the loop and of the feed cover the device's longest idle stretches.
Plain functions over ``(name, start_ns, duration_ns)`` tuples.
"""

from __future__ import annotations

import atexit
import glob as _glob
import json
import os
import sys
import threading
import time
from collections import deque
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

OWNER_PID_ENV = "SPARKNET_TRACE_OWNER_PID"
TRACE_ENV = "SPARKNET_TRACE"

_lock = threading.Lock()
_enabled = False
_path: Optional[str] = None
_role = "owner"
_events: Optional[deque] = None
_thread_names: Dict[int, str] = {}
_atexit_armed = False
# ring evictions + part-file merge failures: truncation used to be
# silent — now both are registry counters (trace_dropped_spans /
# trace_sidecar_errors) and surface in the end-of-run table
_dropped_spans = 0
_sidecar_errors = 0


def dropped_spans() -> int:
    """Spans evicted by the bounded ring this enable-session."""
    with _lock:
        return _dropped_spans


def sidecar_errors() -> int:
    """Part files the owner's merge could not read (torn/racing)."""
    with _lock:
        return _sidecar_errors


def enabled() -> bool:
    return _enabled


class _NullSpan:
    """The disabled fast path: ONE shared instance, allocation-free."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "cat", "args", "_wall_us", "_t0")

    def __init__(self, name, cat, args):
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._wall_us = time.time_ns() // 1000
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        record(
            self.name,
            self._wall_us,
            (time.perf_counter() - self._t0) * 1e6,
            cat=self.cat,
            args=self.args,
        )
        return False


def span(name: str, cat: str = "", **args):
    """``with span("solver.step"): ...`` — a no-op singleton while
    tracing is disabled; a recorded interval while enabled."""
    if not _enabled:
        return _NULL
    return _Span(name, cat, args)


def record(
    name: str,
    wall_us: int,
    dur_us: float,
    cat: str = "",
    args: Optional[dict] = None,
) -> None:
    """Append one complete ("X") event; spans built by hand (the
    timeline's phases) use this directly."""
    if not _enabled:
        return
    tid = threading.get_ident()
    if tid not in _thread_names:
        _thread_names[tid] = threading.current_thread().name
    ev = {
        "name": name,
        "ph": "X",
        "ts": wall_us,
        "dur": round(dur_us, 1),
        "pid": os.getpid(),
        "tid": tid,
        "cat": cat or "sparknet",
    }
    if args:
        ev["args"] = args
    global _dropped_spans
    dropped = False
    with _lock:
        if _events is not None:
            if len(_events) == _events.maxlen:
                _dropped_spans += 1
                dropped = True
            _events.append(ev)
    if dropped:
        # counted outside the ring lock; the registry counter has its
        # own — scrapes see the drop, the end-of-run table prints it
        from .registry import REGISTRY

        REGISTRY.counter("trace_dropped_spans").inc()


def events() -> list:
    """A copy of the buffered events (tests, exporters)."""
    with _lock:
        return list(_events) if _events is not None else []


# ---------------------------------------------------------------- control
def enable(path: Optional[str] = None, capacity: int = 65536) -> None:
    """Turn tracing on.  ``path`` (optional) is where :func:`write`
    lands the Chrome JSON; the first enabling process under a path
    claims ownership via ``SPARKNET_TRACE_OWNER_PID`` and every
    descendant — forked worker or exec'd child inheriting the env —
    resolves to a sidecar writer.  Multi-host ranks other than 0 are
    sidecars regardless (``SPARKNET_PROCESS_ID``)."""
    global _enabled, _path, _role, _events, _atexit_armed
    global _dropped_spans, _sidecar_errors
    with _lock:
        _events = deque(maxlen=capacity)
        _dropped_spans = 0
        _sidecar_errors = 0
    _thread_names.clear()
    _path = path or None
    owner_pid = os.environ.get(OWNER_PID_ENV, "")
    if owner_pid and owner_pid != str(os.getpid()):
        _role = "sidecar"
    elif os.environ.get("SPARKNET_PROCESS_ID", "0") not in ("", "0"):
        _role = "sidecar"
    else:
        _role = "owner"
        if _path:
            os.environ[OWNER_PID_ENV] = str(os.getpid())
    _enabled = True
    if _path and not _atexit_armed:
        # normal processes flush at exit; forked mp workers (whose
        # atexit never runs) call flush_sidecar() explicitly
        atexit.register(_atexit_write)
        _atexit_armed = True


def disable() -> None:
    """Turn tracing off and drop state.  The owner releases its
    ownership claim so a later in-process enable (tests, repeated CLI
    main() calls) starts clean."""
    global _enabled, _path, _role, _events
    _enabled = False
    if _role == "owner" and os.environ.get(OWNER_PID_ENV) == str(os.getpid()):
        os.environ.pop(OWNER_PID_ENV, None)
    _path = None
    with _lock:
        _events = None
    _thread_names.clear()


def configure_from_env() -> Optional[str]:
    """``SPARKNET_TRACE=/path.json`` wiring for CLI processes; returns
    the path when tracing got (or already was) enabled."""
    p = os.environ.get(TRACE_ENV, "").strip()
    if p and not _enabled:
        enable(p)
    return _path


def _after_fork_child() -> None:
    # the child inherited the parent's buffer: drop those spans (the
    # parent owns them) and become a sidecar — its pid no longer
    # matches the ownership claim
    global _role
    if _enabled:
        with _lock:
            if _events is not None:
                _events.clear()
        _thread_names.clear()
        _role = "sidecar"


os.register_at_fork(after_in_child=_after_fork_child)


# ----------------------------------------------------------------- export
def _meta_events(evts) -> list:
    """Chrome metadata ("M") events naming this process and its
    threads, for every pid present in ``evts`` that is OUR pid (merged
    part files carry their own)."""
    pid = os.getpid()
    meta = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "tid": 0,
            "args": {"name": os.path.basename(sys.argv[0] or "python")},
        }
    ]
    for tid, tname in sorted(_thread_names.items()):
        meta.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": tname},
            }
        )
    return meta


def part_path(path: str, pid: Optional[int] = None) -> str:
    return f"{path}.part-{pid if pid is not None else os.getpid()}.json"


def flush_sidecar() -> Optional[str]:
    """Sidecar processes (forked pipeline workers, nonzero ranks) dump
    their events + metadata to ``{path}.part-{pid}.json`` for the owner
    to merge.  Explicit because multiprocessing children skip atexit.
    No-op for the owner or when tracing is off/pathless."""
    if not (_enabled and _role == "sidecar" and _path):
        return None
    out = part_path(_path)
    # atomic via safeio (the owner never reads a torn part); a full
    # disk drops the sidecar's trace, never the sidecar
    from ..utils import safeio

    if not safeio.best_effort_write_json(
        out, _meta_events(events()) + events(),
        site="flight", indent=None, fsync=False,
    ):
        return None
    return out


def write(
    path: Optional[str] = None, extra: Sequence[dict] = ()
) -> Optional[str]:
    """Owner-side export: merge this process's events with every
    ``{path}.part-*.json`` sidecar (consumed on merge) and the ``extra``
    events (the device's track) into the final Chrome trace document,
    sorted by timestamp.  Returns the written path, or None when there
    is nothing to write."""
    path = path or _path
    if not path:
        return None
    if _role == "sidecar":
        return flush_sidecar()
    global _sidecar_errors
    evts = _meta_events(events()) + events() + list(extra)
    for part in sorted(_glob.glob(f"{path}.part-*.json")):
        try:
            with open(part) as fh:
                evts.extend(json.load(fh))
            os.remove(part)
        except (OSError, ValueError):
            # a torn/racing part must not kill the export — but the
            # miss is counted, not silent
            with _lock:
                _sidecar_errors += 1
            from .registry import REGISTRY

            REGISTRY.counter("trace_sidecar_errors").inc()
            continue
    evts.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0)))
    doc = {"traceEvents": evts, "displayTimeUnit": "ms"}
    from ..utils import safeio

    safeio.atomic_write_json(
        path, doc, site="flight", indent=None, fsync=False
    )
    return path


# ------------------------------------------------------ the device's track
Event = Tuple[str, int, int]  # (name, start_ns, duration_ns)
DEVICE_TID = 1  # thread idents are addresses: no thread has it


def anchor_offset(
    modules: Sequence[Event], program: str, before_ns: int, after_ns: int
) -> Tuple[int, int]:
    """``(offset_ns, width_ns)``: what to add to the device plane's
    times to land them on the wall clock, and how sure that is.  The
    first execution of ``program`` (the anchor of
    ``utils/profiling.trace``) ran between the ``time.time_ns()``
    readings ``before_ns`` and ``after_ns``: the offset centres its
    event in that bracket, and is right to half the bracket's width
    less the event's.  An event longer than the bracket cannot have run
    inside it: ValueError."""
    runs = sorted((s, d) for name, s, d in modules if name.startswith(program))
    if not runs:
        raise ValueError(f"the device plane holds no execution of {program}")
    start, dur = runs[0]
    width = after_ns - before_ns
    if dur > width:
        raise ValueError(
            f"{program} ran {dur} ns on the device, longer than the "
            f"{width} ns between the readings around it"
        )
    return (before_ns + after_ns) // 2 - (start + dur // 2), width


def step_program(modules: Sequence[Event], but: str = "") -> str:
    """The program that ran most often (the training step), leaving out
    those whose name starts with ``but`` (the anchor)."""
    names = Counter(
        name for name, _s, _d in modules if not (but and name.startswith(but))
    )
    if not names:
        raise ValueError("the device plane holds no execution of a program")
    return names.most_common(1)[0][0]


def device_track(
    modules: Sequence[Event], offset_ns: int, label: str = "device"
) -> List[dict]:
    """Chrome events for one device's executions, shifted onto the wall
    clock, on a track of their own under this process."""
    pid, tid = os.getpid(), DEVICE_TID
    track = [{
        "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
        "args": {"name": label},
    }]
    for name, start, dur in modules:
        track.append({
            "name": name, "ph": "X", "ts": (start + offset_ns) / 1e3,
            "dur": dur / 1e3, "pid": pid, "tid": tid, "cat": "device",
        })
    return track


def _most_of(spans: Sequence[Event], lo: int, hi: int) -> Optional[tuple]:
    """``(name, share)`` of the span name that covers most of
    ``[lo, hi]``; None where none touches it."""
    cover: Dict[str, int] = {}
    for name, start, dur in spans:
        part = min(start + dur, hi) - max(start, lo)
        if part > 0:
            cover[name] = cover.get(name, 0) + part
    if not cover:
        return None
    name = max(cover, key=cover.get)
    return name, cover[name] / (hi - lo)


def longest_gaps(
    executions: Sequence[Event], loop: Sequence[Event],
    beside: Sequence[Event], n: int = 5,
) -> List[dict]:
    """The ``n`` longest stretches between successive ``executions`` (the
    device idle between two steps), longest first, each with the ``loop``
    phase and the ``beside`` (``feed.*``) phase that cover most of it.
    All three on one clock, in ns."""
    runs = sorted((s, s + d) for _name, s, d in executions)
    gaps = sorted(
        (
            (nxt[0] - cur[1], i, cur[1], nxt[0])
            for i, (cur, nxt) in enumerate(zip(runs, runs[1:]))
            if nxt[0] > cur[1]
        ),
        reverse=True,
    )[:n]
    return [
        {
            "after": i, "gap_ns": gap,
            "loop": _most_of(loop, lo, hi), "beside": _most_of(beside, lo, hi),
        }
        for gap, i, lo, hi in gaps
    ]


def gap_table(gaps: Sequence[dict]) -> str:
    """:func:`longest_gaps` as the lines the apps print."""
    said = lambda m: f"{m[0]} {m[1]:.0%}" if m else "-"
    lines = [
        f"{'gap_ms':>9} {'after':>6}  {'loop phase':<24} {'beside the loop':<24}"
    ]
    for g in gaps:
        lines.append(
            f"{g['gap_ns'] / 1e6:>9.2f} {'#' + str(g['after']):>6}  "
            f"{said(g['loop']):<24} {said(g['beside']):<24}"
        )
    return "\n".join(lines)


def _atexit_write() -> None:
    try:
        if _enabled and _path:
            write()
    except Exception:
        pass
