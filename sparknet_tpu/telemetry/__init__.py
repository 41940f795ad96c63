"""Telemetry — the observability substrate every subsystem reports to.

Four subsystems grew their own counters and their own JSON print lines
(serve, the data pipeline, chaos, the supervisor); none of them could
answer the question the source paper is built on: *where does a
training step's wall time go* — host input, H2D, compiled compute,
cross-host sync, or snapshot I/O?  This package is the one substrate:

- :mod:`~sparknet_tpu.telemetry.registry` — metric primitives
  (Counter/Gauge/LatencyHistogram, moved here from ``serve/metrics``)
  plus the process-global, label-aware :data:`REGISTRY` whose
  ``snapshot()`` carries every family and every registered subsystem
  source in one JSON-able tree.
- :mod:`~sparknet_tpu.telemetry.trace` — a bounded, thread-aware span
  tracer exporting Chrome trace-event JSON (Perfetto-loadable), with
  sidecar files from pipeline workers / supervised children merged by
  pid/tid.  Near-zero cost when disabled.
- :mod:`~sparknet_tpu.telemetry.timeline` — per-iteration phase
  attribution in the train loop (input wait, device put, multihost
  sync, fenced compiled step, eval, snapshot) and the step-time
  breakdown table — the paper's τ-vs-communication accounting read
  off the live loop.
- :mod:`~sparknet_tpu.telemetry.exporter` — Prometheus text rendering
  (mounted on the serve server's ``GET /metrics``) and the periodic
  ``telemetry:`` log line (``SPARKNET_TELEMETRY_INTERVAL_S``).
- :mod:`~sparknet_tpu.telemetry.aggregate` — the *cluster* level:
  per-rank snapshots piggybacked on the multihost heartbeat fabric,
  merged on rank 0 into per-rank label series and a cluster-wide phase
  table with skew columns.
- :mod:`~sparknet_tpu.telemetry.anomaly` — deterministic detectors
  over the aggregated stream (stragglers, EMA+MAD step/loss spikes,
  queue stalls) firing registry counters, ``anomaly:`` JSON lines, and
  advisories the tau controller and serve ``/healthz`` consume.
- :mod:`~sparknet_tpu.telemetry.flight` — bounded crash flight
  recorder, dumped next to (and referenced from) ``supervise/records``
  failure records on any crash path.
- :mod:`~sparknet_tpu.telemetry.reqtrace` — per-request tracing for
  the serving tier: an ``X-Sparknet-Trace`` context minted at the
  router, spans at every hop (dispatch/retry, server, batcher wait,
  engine compute, serialize), replica span batches stitched from an
  inline response header into Perfetto-loadable waterfalls
  (``GET /traces``), and exemplar trace ids on the latency histograms.
- :mod:`~sparknet_tpu.telemetry.dash` — the zero-dependency HTML
  dashboard the serve server mounts on ``GET /dash``.

Enable per run with ``--trace OUT.json`` on the apps / ``caffe train``
(or ``SPARKNET_TRACE=OUT.json``); see docs/OBSERVABILITY.md.  With
``--profile-dir`` beside it the loop is not fenced and the profiler's
device plane joins the same file as a ``device`` track.

Everything here is stdlib-only: no jax import, so the supervisor and
forked pipeline workers use it without touching a backend.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

from . import (
    aggregate,
    anomaly,
    dash,
    exporter,
    flight,
    reqtrace,
    timeline,
    trace,
)
from .registry import (
    REGISTRY,
    Counter,
    Gauge,
    LatencyHistogram,
    NamedCounters,
    Registry,
)

__all__ = [
    "REGISTRY",
    "Counter",
    "Gauge",
    "LatencyHistogram",
    "NamedCounters",
    "Registry",
    "aggregate",
    "anomaly",
    "dash",
    "exporter",
    "finish_run",
    "first_batch_lowered",
    "flight",
    "install_for_training",
    "reqtrace",
    "timeline",
    "trace",
]


# install_for_training's SPARKNET_TRACE export, remembered so
# finish_run can restore it (in-process reruns must not inherit a
# stale trace path)
_saved_trace_env: Optional[tuple] = None
# --profile-dir of this run, for finish_run's merge of the device track
_profile_dir: Optional[str] = None


def install_for_training(
    solver, trace_path: Optional[str] = None,
    profile_dir: Optional[str] = None,
):
    """App-side wiring, shared by the image apps, BertApp and the
    ``caffe`` CLI: resolve ``--trace``/``SPARKNET_TRACE``, enable the
    span tracer, and (when tracing or ``SPARKNET_TIMELINE=1``) attach
    an enabled :class:`~sparknet_tpu.telemetry.timeline.Timeline` to
    the solver so its step loop attributes phases.  With
    ``profile_dir`` (``--profile-dir``) the timeline does not fence:
    the device's time comes from the profiler's trace, and the loop
    stays the user's.  The path is
    exported to ``SPARKNET_TRACE`` so supervised children and forked
    workers inherit it (restored by :func:`finish_run`).  Returns the
    resolved trace path (or None)."""
    global _saved_trace_env, _profile_dir
    _profile_dir = profile_dir or None
    path = trace_path or os.environ.get(trace.TRACE_ENV, "").strip() or None
    if path:
        _saved_trace_env = (os.environ.get(trace.TRACE_ENV),)
        os.environ[trace.TRACE_ENV] = path
        trace.enable(path)
    if path or os.environ.get("SPARKNET_TIMELINE", "") not in ("", "0"):
        # the setter makes it the process's current timeline too
        solver.timeline = timeline.Timeline(fence=not profile_dir)
    # arm the crash flight recorder where a postmortem consumer exists
    # (supervised children, or SPARKNET_FLIGHT=1); disabled it stays
    # the allocation-free no-op
    flight.configure_from_env()
    return path


def first_batch_lowered(feed, solver, profile_dir: Optional[str]):
    """With ``--profile-dir``, ``feed`` with the step program of its
    first batch lowered on its way to the loop (``Solver.lower_step``:
    the program that batch's steps dispatch, which :func:`finish_run`
    lays the device's time on, scope by scope); without, ``feed``
    itself.  One lowering before the first step, nothing a step after.
    A step that does not lower from one batch of the feed (``iter_size``
    micro-batches stacked by the loop) costs the run one line, not its
    life."""
    if not profile_dir:
        return feed

    def noting():
        batches = iter(feed)
        first = next(batches)
        try:
            solver.lower_step(first)
        except Exception as e:  # diagnostics must not end a training run
            print(f"trace: the step of the feed's first batch does not "
                  f"lower ({type(e).__name__}: {e}); no time by scope",
                  flush=True)
        yield first
        yield from batches

    return noting()


@contextlib.contextmanager
def training_loop(tl, emit=print):
    """Bracket a training loop: start the timeline's wall clock and the
    periodic ``telemetry:`` flush (``SPARKNET_TELEMETRY_INTERVAL_S``,
    default off), stop both on the way out — exception-safe, so a
    crashed loop still emits its final telemetry line."""
    stop_flush = exporter.maybe_start_periodic(emit=emit)
    tl.start()
    try:
        yield
    finally:
        tl.stop()
        stop_flush()


def _device_track(profile_dir: str, emit=print) -> list:
    """The profiler's executions as Chrome events on the spans' clock,
    and on ``emit`` the anchor's reading and the longest gaps between
    executions of the step program with the phases that cover them.
    Empty, with a line saying why, where the trace holds no device plane
    or the anchor does not hold."""
    from ..utils import profiling  # jax; only when both were asked for

    anchor = profiling.last_anchor()
    planes = profiling.device_modules(profile_dir)
    if not planes or not anchor or anchor["log_dir"] != profile_dir:
        emit(f"trace: no device plane under {profile_dir}; no device track")
        return []
    try:
        # the one plane the anchor ran on: whether the other devices'
        # planes share its clock is not known, so they get no track
        plane, first = next(
            (p, m) for p, m in sorted(planes.items())
            if any(e[0].startswith(profiling.ANCHOR_PROGRAM) for e in m)
        )
        offset_ns, width_ns = trace.anchor_offset(
            first, profiling.ANCHOR_PROGRAM,
            anchor["before_ns"], anchor["after_ns"],
        )
        program = trace.step_program(first, but=profiling.ANCHOR_PROGRAM)
    except (StopIteration, ValueError) as e:
        emit(f"trace: the anchor does not hold ({e}); no device track")
        return []
    events = trace.device_track(first, offset_ns, label=f"device {plane}")
    steps = [
        (n, s + offset_ns, d) for n, s, d in first if n == program
    ]
    phases = [
        (e["name"], 1000 * int(e["ts"]), int(1000 * e["dur"]))
        for e in trace.events() if e.get("cat") == "timeline"
    ]
    beside = [p for p in phases if p[0].startswith(timeline.BACKGROUND_PREFIX)]
    loop = [p for p in phases if not p[0].startswith(timeline.BACKGROUND_PREFIX)]
    emit(
        f"trace: device track of {len(steps)} executions of {program}; "
        f"anchor offset {offset_ns} ns, bracket {width_ns} ns; longest gaps "
        f"between executions:\n"
        + trace.gap_table(trace.longest_gaps(steps, loop, beside))
    )
    return events


def _device_scopes(profile_dir: str, emit=print) -> None:
    """On ``emit``, the device time of the profiled steps by the program's
    own scopes (``utils/profiling.scope``): outermost scope x forward /
    backward / recompute, ms a step and share of the step, then what
    joined no scope, what joined nothing, what lies in fusions over
    several scopes, and the coverage.  From the ``XLA Ops`` of the plane
    that ran the step program and the scope table of the newest Solver's
    lowered step; one line saying why where either is missing."""
    import time

    from ..utils import profiling  # jax; only under --profile-dir

    t0 = time.perf_counter()
    table = profiling.step_scopes()
    if table is None:
        emit(
            "trace: no step program lowered (Solver.lower_step): no scope "
            "table to lay the device's operations on"
        )
        return
    for plane, events in sorted(profiling.device_modules(profile_dir).items()):
        try:
            program = trace.step_program(events, but=profiling.ANCHOR_PROGRAM)
            break
        except ValueError:  # nothing but the anchor ran on this plane
            continue
    else:
        emit(f"trace: no device plane under {profile_dir} ran a program; "
             f"no time by scope")
        return
    seconds, steps = profiling.step_op_seconds(
        events, profiling.device_ops(profile_dir).get(plane, ()), program
    )
    reduced = profiling.by_scope(seconds, table, steps)
    emit(
        f"trace: device time by scope, ms a step over {steps} executions "
        f"of {program} on {plane} (table and sums made in "
        f"{time.perf_counter() - t0:.2f} s):\n  "
        + "\n  ".join(profiling.scope_lines(reduced))
    )


def finish_run() -> None:
    """End-of-run hook (apps' ``finally``): write the merged Chrome
    trace when this process owns one (with the device's track when
    ``--profile-dir`` was on too; with ``--profile-dir``, traced or not,
    the device time by scope is printed first), then reset tracer + current
    timeline (and the SPARKNET_TRACE export) so an in-process rerun
    (tests driving ``main()`` twice) starts clean.  Safe to call when
    telemetry was never enabled."""
    global _saved_trace_env, _profile_dir
    profile_dir, _profile_dir = _profile_dir, None
    if profile_dir:
        _device_scopes(profile_dir)  # needs no --trace
    if trace.enabled():
        try:
            trace.write(
                extra=_device_track(profile_dir) if profile_dir else ()
            )
        finally:
            errs = trace.sidecar_errors()
            if errs:
                # the merge just ran: losses surface here, not only in
                # the registry counter
                print(
                    f"trace: {errs} sidecar merge error(s) — those part "
                    f"files are missing from the merged trace",
                    flush=True,
                )
            trace.disable()
    if _saved_trace_env is not None:
        prev = _saved_trace_env[0]
        _saved_trace_env = None
        if prev is None:
            os.environ.pop(trace.TRACE_ENV, None)
        else:
            os.environ[trace.TRACE_ENV] = prev
    timeline.set_current(None)
