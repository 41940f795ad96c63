"""Tracing / profiling subsystem (SURVEY.md §5).

The reference's only observability is the Spark UI plus Caffe glog
lines; on TPU the equivalents are XLA's profiler (op-level timeline in
TensorBoard format) and step-level throughput/MFU counters, both
exposed here:

- :func:`trace` — context manager around ``jax.profiler.trace``,
  device only, with an *anchor* that ties the device plane's clock to
  the wall clock of the program's own spans; view the dump with
  TensorBoard's profile plugin or xprof, or merged into ``--trace``'s
  Chrome JSON (``telemetry.finish_run``).
- :class:`StepTimer` — windowed step-time / items-per-second / MFU
  meter for app training loops (items = images or tokens).
- :func:`cost_numbers` — FLOPs and bytes of a compiled program from
  XLA cost analysis (``tools/time_net``'s MFU numerator).

This module answers *op-level* questions (what XLA did inside a
dispatch).  Host-side observability — metrics registry, span tracing,
per-step phase attribution, Prometheus export — lives in
:mod:`sparknet_tpu.telemetry` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import contextlib
import glob
import os
import time
from typing import Dict, List, Optional, Tuple

import jax

# bf16 peak FLOP/s per chip by device_kind substring (spec sheets).
PEAK_TFLOPS = [
    ("v6 lite", 918e12),
    ("v6e", 918e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def device_peak_flops(device=None) -> Optional[float]:
    """bf16 peak FLOP/s of one chip.  None on a backend with no peak to
    speak of (CPU); a TPU whose ``device_kind`` is not in the table is
    an error, not a silently missing MFU."""
    device = device or jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in PEAK_TFLOPS:
        if key in kind:
            return peak
    if getattr(device, "platform", "") == "tpu":
        raise ValueError(
            f"no peak FLOP/s known for TPU device_kind "
            f"{device.device_kind!r}: add it to PEAK_TFLOPS "
            f"(utils/profiling.py) with its source"
        )
    return None


def cost_numbers(compiled) -> tuple:
    """(flops, bytes_accessed) of an XLA ``Compiled`` per cost
    analysis — None entries when the backend doesn't report. One home
    for the API's quirks (list-vs-dict return, missing keys)."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        f = float(cost.get("flops", 0.0))
        b = float(cost.get("bytes accessed", 0.0))
        return (f if f > 0 else None, b if b > 0 else None)
    except Exception:
        return (None, None)


def sparknet_anchor(x):
    return x + 1


ANCHOR_PROGRAM = "jit_sparknet_anchor"  # the anchor's name on XLA Modules
_anchor: Optional[dict] = None


def last_anchor() -> Optional[dict]:
    """``{"log_dir", "before_ns", "after_ns"}`` of the newest
    :func:`trace`: the ``time.time_ns()`` readings that bracket the
    anchor program's one execution under the profiler."""
    return _anchor


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """``with trace("/tmp/prof"):`` — no-op when log_dir is falsy.

    Only the device is traced (``host_tracer_level`` and
    ``python_tracer_level`` 0).  With the host tracer at its default a
    633 MB batch takes 2.0 s to reach the device instead of 0.11 s and
    ``stop_trace`` grows past 25 GB (PERF.md section 3): the traced loop
    was not the user's.  What the host does comes from the program's own
    spans (``--trace``).

    So that the two can be laid on one clock, the first thing to run
    under the profiler is the *anchor*: a tiny jitted program, compiled
    beforehand, dispatched and waited for between two ``time.time_ns()``
    readings.  Its ``XLA Modules`` event lies inside that bracket, which
    fixes the offset between the device plane's clock and the wall clock
    to the bracket's width (:func:`sparknet_tpu.telemetry.trace.
    anchor_offset`)."""
    global _anchor
    if not log_dir:
        yield
        return
    import jax.numpy as jnp

    probe, x = jax.jit(sparknet_anchor), jnp.zeros((8, 128), jnp.float32)
    jax.block_until_ready(probe(x))  # compiled before the profiler starts
    device_only = jax.profiler.ProfileOptions()
    device_only.host_tracer_level = 0
    device_only.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=device_only):
        before_ns = time.time_ns()
        jax.block_until_ready(probe(x))
        _anchor = {
            "log_dir": log_dir, "before_ns": before_ns,
            "after_ns": time.time_ns(),
        }
        yield


def device_modules(log_dir: str) -> Dict[str, List[Tuple[str, int, int]]]:
    """``{device plane: [(name, start_ns, duration_ns), ...]}``: the
    ``XLA Modules`` line (one event per execution of a program) of every
    device plane in the newest ``.xplane.pb`` under ``log_dir``.  Empty
    where no device was traced (a CPU run)."""
    from jax.profiler import ProfileData

    found = glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        return {}
    out = {}
    for plane in ProfileData.from_file(max(found, key=os.path.getmtime)).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out[plane.name] = [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                ]
    return out


class StepTimer:
    """Windowed throughput meter for training loops.

    >>> timer = StepTimer(items_per_step=batch_size, flops_per_step=f)
    >>> ... run steps ...
    >>> timer.update(n_steps)  # after a host sync
    >>> timer.format()
    'steps/s=12.3 images/s=1575 mfu=0.31'
    """

    def __init__(
        self,
        items_per_step: float = 0.0,
        flops_per_step: Optional[float] = None,
        unit: str = "items",
        n_chips: int = 1,
    ):
        self.items_per_step = items_per_step
        self.flops_per_step = flops_per_step
        self.unit = unit
        self.peak = device_peak_flops()
        self.n_chips = max(1, n_chips)
        self._t = time.perf_counter()
        self.steps_per_sec = 0.0

    def update(self, n_steps: int) -> "StepTimer":
        now = time.perf_counter()
        dt = max(now - self._t, 1e-9)
        self._t = now
        self.steps_per_sec = n_steps / dt
        return self

    @property
    def items_per_sec(self) -> float:
        return self.steps_per_sec * self.items_per_step

    @property
    def tflops(self) -> Optional[float]:
        if self.flops_per_step is None:
            return None
        return self.steps_per_sec * self.flops_per_step / 1e12

    @property
    def mfu(self) -> Optional[float]:
        t = self.tflops
        if t is None or not self.peak:
            return None
        return t * 1e12 / (self.peak * self.n_chips)

    def format(self) -> str:
        parts = [f"steps/s={self.steps_per_sec:.2f}"]
        if self.items_per_step:
            parts.append(f"{self.unit}/s={self.items_per_sec:.0f}")
        if self.tflops is not None:
            parts.append(f"tflops={self.tflops:.1f}")
        if self.mfu is not None:
            parts.append(f"mfu={self.mfu:.3f}")
        return " ".join(parts)
