"""From a profiler trace to numbers: plain functions over events.

An event is ``(name, start, duration)`` with the times in nanoseconds on the
trace's one clock.  ``load_xplane`` turns the profiler's ``.xplane.pb`` into
such lists; everything else takes the lists, so a test can feed it a
recorded one.

What the planes of a TPU trace hold (jax 0.9.0, libtpu 0.0.34): plane
``/device:TPU:<n>`` has the lines ``XLA Modules`` (one event per execution
of a program, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (the operations the
core ran, named by their HLO text) and ``Async XLA Ops`` (the spans of
copy-start..copy-done pairs, which overlap the operations and are NOT time
the core was busy).  The host planes are not read: what the host does
between steps comes from ``Solver.step``'s own timeline, with the live feed
running and the profiler off (see ``run.traced_parts``).
"""

from __future__ import annotations

import glob
import os
from collections import Counter, defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]
Interval = Tuple[int, int]

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler.trace`` directory."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def events_of(planes: Iterable) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {"modules": [...], "ops": [...]}}`` from the profiler's
    planes (objects with ``name`` and ``lines``, a line with ``name`` and
    ``events``, an event with ``name``, ``start_ns`` and ``duration_ns``):
    the two lines that count of every device plane.  Every other line,
    ``Async XLA Ops`` among them, and every other plane is left out."""
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines: Dict[str, List[Event]] = {"modules": [], "ops": []}
        for line in plane.lines:
            key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
            if key:
                lines[key] = [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                ]
        out[plane.name] = lines
    return out


def load_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """:func:`events_of` the planes of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return events_of(ProfileData.from_file(path).planes)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Interval]:
    """The events' intervals, cut to ``[lo, hi]``; those outside dropped."""
    cut = []
    for _name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            cut.append((s, e))
    return cut


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def step_program(modules: Sequence[Event]) -> str:
    """The program that ran most often: the training step."""
    if not modules:
        raise ValueError("the trace holds no XLA Modules event")
    return Counter(name for name, _s, _d in modules).most_common(1)[0][0]


def reduce_device(
    modules: Sequence[Event], ops: Sequence[Event], skip: int, count: int
) -> Dict:
    """One device's numbers over ``count`` executions of the step program,
    leaving out the first ``skip`` (the profiler stalls the host as it
    starts).  The window runs from the start of the first counted execution
    to the end of the last; busy is the union of the operations in it, so
    two that overlap count once; a step's device time is the union of the
    operations inside its module event."""
    program = step_program(modules)
    runs = sorted(
        (s, s + d) for name, s, d in modules if name == program
    )
    if len(runs) < skip + count:
        raise ValueError(
            f"the trace holds {len(runs)} executions of {program}, "
            f"fewer than {skip} skipped + {count} counted"
        )
    runs = runs[skip: skip + count]
    lo, hi = runs[0][0], runs[-1][1]
    op_ns: Dict[str, int] = defaultdict(int)
    for name, start, dur in ops:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            op_ns[name] += e - s
    return {
        "program": program,
        "steps": count,
        "window_s": (hi - lo) / 1e9,
        "busy_s": length(union(clip(ops, lo, hi))) / 1e9,
        "device_step_s": [
            length(union(clip(ops, s, e))) / 1e9 for s, e in runs
        ],
        "op_seconds": {k: v / 1e9 for k, v in op_ns.items()},
    }


def reduce_trace(
    devices: Dict[str, Dict[str, List[Event]]], skip: int, count: int
) -> Dict:
    """:func:`reduce_device` of the one device plane that ran a program.
    Every cell takes one chip today; the ``benchmark`` PR that brings a cell
    on several brings the mean over their planes with it."""
    ran = [lines for _plane, lines in sorted(devices.items()) if lines["modules"]]
    if len(ran) != 1:
        raise ValueError(
            f"{len(ran)} device planes of the trace ran a program; the "
            f"reduction is for exactly one"
        )
    return reduce_device(ran[0]["modules"], ran[0]["ops"], skip, count)


def top(seconds: Dict[str, float], n: int = 10, width: int = 160) -> List:
    """The ``n`` largest entries as ``[name, seconds]``, names cut to
    ``width`` characters."""
    ranked = sorted(seconds.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:width], value] for name, value in ranked]
