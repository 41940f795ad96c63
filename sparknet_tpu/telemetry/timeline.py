"""Per-iteration phase attribution for the training loop.

SparkNet's headline result is an accounting identity: a training step's
wall time decomposes into compute and communication/synchronization,
and τ local iterations amortize the latter.  This module makes that
decomposition measurable on the real loop instead of estimated: the
solver and the apps bracket each phase boundary —

- ``input_wait``     host blocked waiting for the next batch
- ``device_put``     H2D placement / multi-host global assembly
- ``multihost_sync`` cross-host collectives on the host path
                     (``multihost.put_global``; nests inside
                     ``device_put`` and is attributed exclusively)
- ``compiled_step``  the jitted train step, *fenced* with
                     ``block_until_ready`` so async dispatch cannot
                     smear compute into the next phase
- ``grad_allreduce`` the exposed (blocking) time of the bucketed
                     round-end reduction program (parallel/comm.py) —
                     distinguishable from ``multihost_sync``'s barrier
                     wait, so "waiting for peers" and "moving bytes"
                     read as separate rows
- ``eval``           TEST-phase evaluation
- ``snapshot``       solverstate/weights writes

— and the timeline prints a breakdown table whose rows sum to the
attributed share of loop wall time (the e2e test holds it to ≥90%).

Phases nest: an inner phase's time is attributed to the inner phase
only (the outer phase records its *exclusive* time), so the table's
total never double-counts.  When the span tracer is enabled each phase
also lands as a trace event, so the same boundaries are visible on the
Perfetto timeline.

Phases named ``feed.*`` are *background*: they belong to threads that
run beside the loop (the H2D staging thread of ``data/prefetch.py``,
the native loader's workers) and overlap the rows above, so they are
kept out of the attributed share and printed as a second block —

- ``feed.source``         staging thread inside ``next(source)``
- ``feed.h2d``            staging thread inside ``device_put``
- ``feed.backpressure``   staging thread blocked on its full queue: the
                          feed's slack
- ``feed.loader_blocked`` of ``feed.source``, the wait for a native
                          worker's in-order batch (reported as well as
                          ``feed.source``, not taken out of it)
- ``feed.copy_out``       of ``feed.source``, the copy of that batch out
                          of the native loader's queue
- ``feed.produce``        native worker seconds inside ``build``, all
                          threads together

The staging thread's three are serial and cover its whole time, so they
add up to the wall time of whatever window reads them.  That holds at a
window's edges too: :func:`background_phase` is always timed and credits
every second to the timeline that was current during it (a phase in
flight when ``Solver.timeline`` changes is split between the two).

``NULL`` is the disabled instance every solver starts with: its
``phase()`` returns one shared no-op context manager — the
uninstrumented loop pays an attribute load and a falsy test per
boundary, nothing else.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from . import trace as _trace

# canonical row order for the breakdown table
PHASES = (
    "input_wait",
    "device_put",
    "multihost_sync",
    "compiled_step",
    "grad_allreduce",
    "eval",
    "snapshot",
    "reshard",  # live layout migration (parallel/reshard.py)
)
BACKGROUND_PREFIX = "feed."
clock = time.perf_counter  # what Timeline.add's ``began`` is a reading of


class _NullPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_PHASE = _NullPhase()


class NullTimeline:
    """Disabled singleton: every operation is a no-op."""

    enabled = False
    fence = False

    def phase(self, name: str):
        return _NULL_PHASE

    def add(self, name, seconds, count=1, began=None) -> None:
        pass

    def start(self) -> None:
        pass

    def stop(self) -> None:
        pass

    def snapshot(self) -> dict:
        return {}

    def phase_seconds(self) -> Dict[str, float]:
        return {}

    def table(self) -> str:
        return ""


NULL = NullTimeline()


class _Phase:
    __slots__ = ("_tl", "_name", "_wall_us", "_t0")

    def __init__(self, tl: "Timeline", name: str):
        self._tl = tl
        self._name = name

    def __enter__(self):
        self._wall_us = time.time_ns() // 1000
        self._t0 = time.perf_counter()
        self._tl._push()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        self._tl._pop(self._name, dur)
        if _trace.enabled():
            _trace.record(
                self._name, self._wall_us, dur * 1e6, cat="timeline"
            )
        return False


class Timeline:
    """Accumulates exclusive per-phase time across a training loop.

    ``fence=True`` (default) asks the instrumented solver to
    ``block_until_ready`` inside the ``compiled_step`` phase — honest
    attribution at the cost of serializing dispatch, which is why the
    timeline is opt-in (``--trace`` / ``SPARKNET_TIMELINE=1``) rather
    than always-on."""

    enabled = True

    def __init__(self, fence: bool = True):
        self.fence = fence
        self._lock = threading.Lock()
        self._totals: Dict[str, list] = {}  # name -> [total_s, count]
        self._local = threading.local()  # per-thread nesting stacks
        self._t_start: Optional[float] = None
        self._wall = 0.0
        # durations that began before this are clipped to it: since when
        # this timeline takes time measured elsewhere (set_current moves it)
        self._since = time.perf_counter()

    # ------------------------------------------------------------- phases
    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def add(
        self, name: str, seconds: float, count: int = 1,
        began: Optional[float] = None,
    ) -> None:
        """``seconds`` measured elsewhere (the native loader's counters,
        a background thread's phase) under ``name``.  Leaves the nesting
        stacks alone: nothing is taken out of an enclosing phase.
        ``began`` is the ``perf_counter`` reading at which the duration
        started, where the caller knows it: the part that lies before
        this timeline became current is left out."""
        if began is not None:
            seconds = min(seconds, began + seconds - self._since)
        with self._lock:
            t = self._totals.setdefault(name, [0.0, 0])
            t[0] += max(0.0, seconds)
            t[1] += count

    def _seal(self, now: float) -> None:
        """This timeline stops being current at ``now``: it takes what
        every background phase in flight has run so far (the rest goes
        to its successor).  Called by :func:`set_current`, under
        ``_handover``."""
        for name, t0 in _in_flight.values():
            self.add(name, now - t0, count=0, began=t0)

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self) -> None:
        self._stack().append(0.0)  # child-time accumulator

    def _pop(self, name: str, dur: float) -> None:
        st = self._stack()
        child = st.pop()
        exclusive = max(0.0, dur - child)
        if st:
            st[-1] += dur  # the parent excludes OUR whole duration
        with self._lock:
            t = self._totals.get(name)
            if t is None:
                t = self._totals[name] = [0.0, 0]
            t[0] += exclusive
            t[1] += 1

    # --------------------------------------------------------------- wall
    def start(self) -> None:
        if self._t_start is None:
            self._t_start = time.perf_counter()

    def stop(self) -> None:
        if self._t_start is not None:
            self._wall += time.perf_counter() - self._t_start
            self._t_start = None

    @property
    def wall_s(self) -> float:
        running = (
            time.perf_counter() - self._t_start
            if self._t_start is not None
            else 0.0
        )
        return self._wall + running

    # -------------------------------------------------------------- reads
    def _read(self) -> Dict[str, list]:
        with self._lock:
            return {k: list(v) for k, v in self._totals.items()}

    def _rows(self, background: bool = False):
        """(name, seconds, count) of the loop's phases in table order,
        or of the background (``feed.*``) ones."""
        totals = {
            k: v for k, v in self._read().items()
            if k.startswith(BACKGROUND_PREFIX) == background
        }
        ordered = [p for p in PHASES if p in totals] + sorted(
            k for k in totals if k not in PHASES
        )
        return [(name, totals[name][0], totals[name][1]) for name in ordered]

    def attributed_s(self) -> float:
        return sum(t for _, t, _ in self._rows())

    def phase_seconds(self) -> Dict[str, float]:
        """Cumulative exclusive seconds per phase, the background ones
        with the rest — the tau controller's per-round signal is the
        delta between two of these."""
        return {k: v[0] for k, v in self._read().items()}

    def snapshot(self) -> dict:
        wall = self.wall_s
        attributed = self.attributed_s()

        def block(rows):
            return {
                name: {
                    "total_s": round(total, 4),
                    "count": count,
                    "mean_ms": round(1e3 * total / count, 3) if count else None,
                }
                for name, total, count in rows
            }

        return {
            "wall_s": round(wall, 4),
            "attributed_s": round(attributed, 4),
            "attributed_frac": (
                round(attributed / wall, 4) if wall > 0 else None
            ),
            "phases": block(self._rows()),
            "background": block(self._rows(background=True)),
        }

    def table(self) -> str:
        """The step-time breakdown the apps print — the paper's
        τ-vs-communication accounting read off the live loop — and
        under it the threads beside the loop."""
        wall = self.wall_s

        def block(rows):
            for name, total, count in rows:
                share = total / wall if wall > 0 else 0.0
                mean_ms = 1e3 * total / count if count else 0.0
                yield (
                    f"{name:<19} {total:>9.3f} {share:>6.1%} "
                    f"{count:>7d} {mean_ms:>9.2f}"
                )

        rows = self._rows()
        lines = [
            f"{'phase':<19} {'total_s':>9} {'share':>7} "
            f"{'count':>7} {'mean_ms':>9}",
            *block(rows),
        ]
        attributed = sum(t for _, t, _ in rows)
        frac = attributed / wall if wall > 0 else 0.0
        lines.append(
            f"attributed {frac:.1%} of {wall:.3f}s loop wall time"
        )
        beside = self._rows(background=True)
        if beside:
            lines.append("beside the loop (overlaps the rows above):")
            lines.extend(block(beside))
        return "\n".join(lines)


# ------------------------------------------------------- current timeline
# Module-level "current" timeline so deep call sites (multihost.put_global)
# can attribute to the active loop's timeline without threading it through
# every signature.  Single training loop per process — plain global.
_current: object = NULL
# what each thread beside the loop is inside and since when:
# thread ident -> (name, perf_counter at entry); see background_phase
_in_flight: Dict[int, tuple] = {}
# held while a background phase starts or ends and while the current
# timeline changes, so every second of a phase goes to one timeline
_handover = threading.Lock()


def _after_fork_child() -> None:
    # data/pipeline.py forks its workers, from a staging thread too: the
    # child has none of the parent's threads, so nothing is in flight,
    # and the lock may have been held by one of them at the fork
    global _handover
    _handover = threading.Lock()
    _in_flight.clear()


os.register_at_fork(after_in_child=_after_fork_child)


def set_current(tl) -> None:
    global _current
    tl = tl if tl is not None else NULL
    with _handover:
        if tl is _current:
            return
        now = clock()
        if isinstance(_current, Timeline):
            _current._seal(now)
        if isinstance(tl, Timeline):
            tl._since = now
        _current = tl


def current():
    return _current


class background_phase:
    """``with background_phase("feed.source"): ...`` in a thread that
    runs beside the loop.  Unlike a loop phase it is timed whether or not
    a timeline is on (two clock reads, a dict store and two uncontended
    lock takes, beside a batch), because the timeline that will ask may
    be made while the phase is in flight: at its end the phase goes to
    the timeline that is current *then*, less what lies before that one
    took over, and a timeline that is replaced takes its part of every
    phase in flight (:meth:`Timeline._seal`).  So a window's background
    seconds are those of the window, and a serial thread's phases add up
    to it however short it is.  One instance per call site and thread;
    re-enter it each time round."""

    __slots__ = ("_name", "_t0", "_wall_us")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._wall_us = time.time_ns() // 1000 if _trace.enabled() else 0
        with _handover:
            self._t0 = clock()
            _in_flight[threading.get_ident()] = (self._name, self._t0)
        return self

    def __exit__(self, *exc):
        with _handover:
            dur = clock() - self._t0
            del _in_flight[threading.get_ident()]
            _current.add(self._name, dur, began=self._t0)
        if self._wall_us and _trace.enabled():
            _trace.record(self._name, self._wall_us, dur * 1e6, cat="timeline")
        return False


def current_phase(name: str):
    """``with timeline.current_phase("multihost_sync"): ...`` at call
    sites that don't hold a timeline reference; no-op when no timeline
    is active."""
    return _current.phase(name)
