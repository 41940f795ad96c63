"""benchmark/run.py: one process, one cell of BENCHMARK.json, one run.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); both are data.  The run builds the cell with the
app's own ``build(args)`` from the argv the two files give, wraps the feed
with ``data.prefetch.maybe_prefetch`` as the app's ``main`` does, and steps
with ``Solver.step(feed, 1)``.  Set-up is everything the system does from
process start to the opening of the window: imports, build, the step
program's compile (or its load from the cache) inside the first warm step,
the warm steps and the one step that primes the loop.  The harness's own
checks are not the system's set-up: the seconds of the reference check on
the first batch are taken off ``setup_s``, and the step program is read
(kernels, memory) after the window.

Timing rule: the host stays one step ahead, as a training loop does.  Step
*i* is dispatched, then the metrics of step *i-1* are waited for
(``block_until_ready``) and the clock is read.  Step times are differences
of those readings; the window opens on the loop's first completion, when
the next step is already in flight, and closes on the first completion
``--seconds`` later.

``--trace 1`` measures no window.  It runs three short parts over fixed
counts of steps from the traffic file: the live loop with the solver's
timeline on and its fence off (what the host needs to enqueue a step, and
the loop's pace), the same with the fence on (where the host's time goes,
phase by phase), and the profiler's trace of the same live loop (device
time, kernels, busy and idle), with the profiler's host tracers off
(``traced_steps``).  Each part first runs the traffic file's uncounted
steps (``lead_steps``, ``skip_steps``) at the live pace: the feed fills
its queues while the harness is between parts, and a part has to read
the steady state, not the draining of that backlog.  Every per-layer
metric is a reader under ``layers/`` that takes what these parts recorded.

Everything but the result goes on ``bench:`` lines; the last line of
stdout is the one JSON object.  Anything but a TPU with the chips the cell
asks for: exit code 2, nothing on stdout.
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()

import argparse
import contextlib
import gc
import importlib
import importlib.util
import io
import itertools
import json
import math
import os
import resource
import shutil
import statistics
import sys
import threading
from typing import Any, Callable, ContextManager, Dict, Iterator, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# jax's PRNGKey and the native loader take 32-bit seeds; the driver's are
# larger.  Distinct seeds up to 2**31 - 2 stay distinct.
SEED_MODULUS = 2**31 - 1


def say(message: str) -> None:
    print(f"bench: {message}", flush=True)


# ------------------------------------------------------------------ the files

def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def _named(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(
        f"BENCHMARK.json names no {what} {name!r}: "
        f"{sorted(e['name'] for e in entries)}"
    )


def load_cell(workload: str, root: str = ROOT) -> Dict[str, Any]:
    """The manifest's entry for ``workload`` with its configuration and
    traffic files read.  The configuration's file is where the manifest
    says; the traffic mix and the per-layer metrics' readers are found by
    name beside it (``<benchmark>/traffic/<traffic>.json``,
    ``<benchmark>/layers/<metric>.py``)."""
    manifest = _load_json(os.path.join(root, "BENCHMARK.json"))
    cell = _named(manifest["workloads"], workload, "workload")
    entry = _named(manifest["configs"], cell["config"], "configuration")
    config_path = os.path.join(root, entry["file"])
    bench_dir = os.path.dirname(os.path.dirname(config_path))
    applies = lambda m: workload in m.get("workloads", [workload])
    return {
        "name": workload,
        "chips": cell["chips"],
        "config": _load_json(config_path),
        "traffic": _load_json(
            os.path.join(bench_dir, "traffic", cell["traffic"] + ".json")
        ),
        "layers_dir": os.path.join(bench_dir, "layers"),
        "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
        "per_layer": [m for m in manifest["per_layer"] if applies(m)],
    }


def resolve(dotted: str) -> Callable:
    """``"module:function"`` from a data file to the function."""
    module, _, name = dotted.partition(":")
    return getattr(importlib.import_module(module), name)


def metric_reader(folder: str, metric: str) -> Callable:
    """``read`` of ``<folder>/<metric>.py``: a per-layer metric's reader."""
    path = os.path.join(folder, metric + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"metric {metric!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(f"_metric_{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ----------------------------------------------------------------- observation

class CompileClock:
    """Counts jax's backend compiles (XLA and Mosaic; on a persistent-cache
    hit the duration is the retrieval) and its cache hits and misses, from
    jax's monitoring events.  Copied from ``chip_smoke.CompileClock``, with
    the count added: a window that saw the count move compiled something.
    jax has no public unregister, so one clock lives for the process and
    callers diff it."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._totals = {
            "compiles": 0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0,
        }
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self._COMPILE:
            with self._lock:
                self._totals["compiles"] += 1
                self._totals["compile_s"] += seconds

    def _event(self, event: str, **_kw) -> None:
        key = self._EVENTS.get(event)
        if key:
            with self._lock:
                self._totals[key] += 1

    def read(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        return {k: v - before[k] for k, v in self.read().items()}


class _Tee(io.TextIOBase):
    """stdout that also keeps what passed through it."""

    def __init__(self, stream):
        self._stream = stream
        self._kept = io.StringIO()

    def write(self, text: str) -> int:
        self._kept.write(text)
        return self._stream.write(text)

    def flush(self) -> None:
        self._stream.flush()

    def getvalue(self) -> str:
        return self._kept.getvalue()


class GcPauses:
    """Seconds of each garbage collection from now to ``stop``: one of the
    things a stalled step is checked against."""

    def __init__(self):
        self._pauses: List[float] = []
        self._started = 0.0
        gc.callbacks.append(self._note)

    def _note(self, phase: str, _info: Dict) -> None:
        if phase == "start":
            self._started = time.perf_counter()
        else:
            self._pauses.append(time.perf_counter() - self._started)

    def stop(self) -> List[float]:
        gc.callbacks.remove(self._note)
        return self._pauses


# ------------------------------------------------------------------- the phases

def build_cell(config: Dict, traffic: Dict, seed: int) -> Dict[str, Any]:
    """The app's own ``build`` on the argv the two files give, and the feed
    wrapped as the app's ``main`` wraps it."""
    from sparknet_tpu.data.prefetch import maybe_prefetch

    app = importlib.import_module(config["builder"])
    argv = [
        *config["argv"], *traffic["argv"], "--seed", str(seed % SEED_MODULUS)
    ]
    say(f"$ {config['builder']}.build {' '.join(argv)}")
    args = app.parser().parse_args(argv)
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        built = app.build(args)
    solver, raw_feed = built[0], built[1]
    return {
        "solver": solver,
        "raw_feed": raw_feed,
        "feed": iter(maybe_prefetch(raw_feed, args, args.parallel)),
        "printed": tee.getvalue(),
    }


def check_feed(built: Dict, traffic: Dict) -> Dict[str, Any]:
    """The feed is the one the traffic file names: its type and, where the
    app prints which feed it resolved to, that line.  A fall-back from the
    native loader to the python feed is a different workload."""
    kind = type(built["raw_feed"]).__name__
    line = next(
        (
            ln[len("train feed: "):]
            for ln in built["printed"].splitlines()
            if ln.startswith("train feed: ")
        ),
        None,
    )
    ok = kind == traffic["feed_type"] and (
        "feed_line" not in traffic or line == traffic["feed_line"]
    )
    return {"type": kind, "line": line, "ok": ok}


def close_feed(built: Dict) -> None:
    """Stop what the feed started: the staging thread of
    ``prefetch_to_device`` (closing its generator), then the feed's own
    workers where it has any (the native loader's threads, a python
    pipeline's processes), as the app's ``main`` does on its way out.  A
    plain generator feed owns nothing, and the staging thread may still be
    inside it."""
    import inspect

    getattr(built["feed"], "close", lambda: None)()
    raw = built["raw_feed"]
    if not inspect.isgenerator(raw):
        getattr(raw, "close", lambda: None)()


def step_program(solver, batch) -> Dict[str, Any]:
    """The program ``Solver.step`` dispatches for ``batch``: the Pallas
    kernels in its lowered text, and the device memory it needs (arguments
    + outputs - aliased + temporaries).  The runtime's
    ``peak_bytes_in_use`` leaves the temporaries out, so it is not read for
    this.  Read after the window: the compile finds in the persistent cache
    what the first ``Solver.step`` put there."""
    lowered = solver.lower_step(batch)
    kernels = lowered.as_text().count("tpu_custom_call")
    memory = lowered.compile().memory_analysis()
    parts = {
        "arguments": int(memory.argument_size_in_bytes),
        "outputs": int(memory.output_size_in_bytes),
        "aliased": int(memory.alias_size_in_bytes),
        "temporaries": int(memory.temp_size_in_bytes),
    }
    total = (
        parts["arguments"] + parts["outputs"] - parts["aliased"]
        + parts["temporaries"]
    )
    return {"tpu_custom_calls": kernels, "bytes": {**parts, "total": total}}


def run_steps(
    solver,
    feed: Iterator,
    loss_key: str,
    seconds: Optional[float] = None,
    count: Optional[int] = None,
    lead: int = 0,
    window: Optional[ContextManager] = None,
) -> Dict[str, Any]:
    """The loop, one step ahead.  After ``lead`` uncounted completions the
    next one opens the window: by then a second step is in flight, as in
    every later reading, so the first step time is like the others
    (opening on the caller's last warm step instead makes the first
    reading wait for two batches).  It closes on the first completion
    ``seconds`` after that, or after ``count`` more.  ``window`` is entered
    as the window opens and left as it closes, before the step in flight
    is waited for: what a traced part switches on for its counted steps.
    ``attempted`` are the steps dispatched inside the window: the
    completed ones and the one in flight at the close, which is waited for
    after it; the steps up to the one that opened it are ``primed``."""
    import jax

    completions: List[float] = []
    cpu: List[float] = []
    cpu_all: List[float] = []
    losses: List[float] = []
    with contextlib.ExitStack() as opened:
        pending = solver.step(feed, 1)
        while True:
            ahead = solver.step(feed, 1)
            jax.block_until_ready(pending)
            now = time.perf_counter(), time.thread_time(), time.process_time()
            losses.append(float(pending[loss_key]))
            pending = ahead
            if len(losses) <= lead:
                continue
            completions.append(now[0])
            cpu.append(now[1])
            cpu_all.append(now[2])
            if len(completions) == 1 and window is not None:
                opened.enter_context(window)
            if count is not None and len(completions) > count:
                break
            if seconds is not None and completions[-1] - completions[0] >= seconds:
                break
    jax.block_until_ready(pending)
    losses.append(float(pending[loss_key]))
    return {
        "opened": completions[0],
        "window_s": completions[-1] - completions[0],
        "completed": len(completions) - 1,
        "attempted": len(completions),
        "primed": lead + 1,
        "losses": losses,
        "step_s": [b - a for a, b in zip(completions, completions[1:])],
        # CPU seconds in each step, of this thread and of the process with
        # the feed's threads: a stall in which neither ran is the machine's
        "step_cpu_s": [b - a for a, b in zip(cpu, cpu[1:])],
        "step_cpu_all_s": [b - a for a, b in zip(cpu_all, cpu_all[1:])],
    }


def timed_phases(
    solver, feed, loss_key: str, count: int, fence: bool, lead: int = 0
) -> Dict:
    """``count`` steps of the live loop with ``Solver.step``'s own timeline
    on: seconds per phase (``input_wait``, ``device_put``,
    ``compiled_step``, the feed's background phases), the calls of the
    step, and the wall time of the same steps, which is the window's.
    With the fence the step waits for the device inside ``compiled_step``;
    without it that phase is the dispatch alone.  The ``lead`` steps
    before them run under a timeline of their own with the same fence, so
    the counted steps follow steps like themselves."""
    from sparknet_tpu.telemetry.timeline import Timeline

    timeline = Timeline(fence=fence)

    @contextlib.contextmanager
    def counted():
        solver.timeline = timeline
        try:
            yield
        finally:
            solver.timeline = uncounted

    before = solver.timeline
    solver.timeline = uncounted = Timeline(fence=fence)
    try:
        log = run_steps(
            solver, feed, loss_key, count=count, lead=lead, window=counted()
        )
    finally:
        solver.timeline = before
    calls = timeline.snapshot()["phases"].get("compiled_step", {}).get("count", 0)
    return {
        **log, "steps": calls, "wall_s": log["window_s"],
        "phases": timeline.phase_seconds(),
    }


def traced_steps(
    solver, feed: Iterator, loss_key: str, skip: int, count: int, trace_dir: str
) -> Dict[str, Any]:
    """The profiler's part: ``skip + count + 2`` steps of the live loop,
    reduced over the ``count`` executions of the step program that follow
    the first ``skip``.  Only the device is traced.  With the profiler's
    host tracer on (its default) a batch of 633 MB takes 2 s to cross to
    the device instead of 0.11 s and every transfer leaves 0.4 GB of events
    in the process, which ``stop_trace`` then multiplies past the machine's
    memory: the trace showed a loop that was not the cell's.  With it off
    the transfer takes its 0.11 s; the host's side of the story comes from
    ``Solver.step``'s own timeline, with the profiler off."""
    import jax

    from benchmark import trace_reduce

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    device_only = jax.profiler.ProfileOptions()
    device_only.host_tracer_level = 0
    device_only.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=device_only):
        log = run_steps(solver, feed, loss_key, count=skip + count)
    path = trace_reduce.find_xplane(trace_dir)
    loaded = trace_reduce.load_xplane(path)
    say(
        f"trace {path} ({os.path.getsize(path)} bytes): "
        f"{sum(len(l['ops']) for l in loaded.values())} device operations "
        f"on {len(loaded)} device(s)"
    )
    return {**log, "trace": trace_reduce.reduce_trace(loaded, skip, count)}


def device_report(devices, program_bytes: int) -> Dict[str, Any]:
    """The device as jax reports it.  ``memory_peak_bytes`` is the larger of
    the runtime's own peak on the fullest chip and what the step program
    needs there: the runtime's figure leaves out the program's temporaries
    (1.7 GB read for a step that needs 14 GB)."""
    runtime_peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        runtime_peak = max(runtime_peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(runtime_peak, program_bytes),
        "runtime_peak_bytes_in_use": runtime_peak,
    }


# completions a reading of the pace averages over: twice what the feed
# holds (4 batches queued, 2 staged, 2 in build), the smallest of 2, 4, 8
# and 16 at which alexnet_live's readings agree from run to run (PERF.md
# section 2), and an even number, so that a loop that completes its steps in
# pairs reads the same in either phase
PACE_RUN = 16


def pace_p90(step_s: List[float], k: int = PACE_RUN) -> float:
    """The 90th percentile, over the window, of the mean time between
    completions over every run of ``k`` consecutive steps, sliding by one:
    the pace a progress line that averages ``k`` steps would show in its
    slow tenth.  A stall, a run of collections or a slow stretch that
    lasts or recurs over a tenth of the window raises it; on which half
    of a short/long alternation a percentile of single steps would land
    does not.  A window of fewer than ``2 k`` steps reads its mean."""
    if len(step_s) < 2 * k:
        return statistics.fmean(step_s)
    ends = [0.0, *itertools.accumulate(step_s)]
    runs = [(b - a) / k for a, b in zip(ends, ends[k:])]
    return statistics.quantiles(runs, n=10, method="inclusive")[8]


def end_to_end(recorded: Dict[str, Any]) -> Dict[str, float]:
    """The end-to-end metrics of an untraced run, which the benchmark takes
    itself: samples completed per second over the span from the window's
    opening to its last completion; the slow tenth of the loop's pace over
    every step of the window (:func:`pace_p90`); the device memory the step
    program needs; and the set-up time, less the harness's own reference
    check."""
    window = recorded["window"]
    return {
        "samples_per_s": (
            window["completed"] * recorded["samples"] / window["window_s"]
        ),
        "pace_ms_p90": 1e3 * pace_p90(window["step_s"]),
        "step_hbm_gb": recorded["program"]["bytes"]["total"] / 1e9,
        "setup_s": (
            window["opened"] - _PROCESS_T0 - recorded["reference"]["seconds"]
        ),
    }


def per_layer(
    folder: str, metrics: List[Dict[str, Any]], recorded: Dict[str, Any]
) -> Dict[str, float]:
    """Each per-layer metric's reader on what the traced run recorded; a
    reader that finds nothing to read returns None and its metric is left
    out."""
    values = {
        m["name"]: metric_reader(folder, m["name"])(recorded) for m in metrics
    }
    return {k: v for k, v in values.items() if v is not None}


# ----------------------------------------------------------------------- a run

def holds(numbers: Dict[str, float]) -> bool:
    """A compared number against the limits beside it (``at_most``,
    ``at_least``, ``equal_to``); a nan holds none."""
    value = numbers["value"]
    return (
        value <= numbers.get("at_most", value)
        and value >= numbers.get("at_least", value)
        and value == numbers.get("equal_to", value)
    )



def set_up(cell: Dict[str, Any], built: Dict[str, Any], clock: CompileClock) -> Dict:
    """From the built cell to the last warm step: the first batch and the
    reference check on it, before any step moves the weights; then the warm
    steps, the first of which compiles the step program or loads it from
    the cache.  Returns what the run recorded so far."""
    import jax

    from benchmark.reference import compare

    config, traffic = cell["config"], cell["traffic"]
    solver, feed = built["solver"], built["feed"]
    stages = {"imports_and_build": time.perf_counter() - _PROCESS_T0}
    mark = time.perf_counter()

    def stage(name: str) -> None:
        nonlocal mark
        now = time.perf_counter()
        stages[name] = now - mark
        mark = now

    batch = next(feed)
    shapes = {name: tuple(value.shape) for name, value in batch.items()}
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(solver.params))
    say(f"first batch {shapes}; {n_params} parameters")
    stage("first_batch")
    check = config["reference"]
    reference = compare(
        solver, batch, resolve(check["forward"]), check["weight_gain"],
        check["abs_tolerance"],
    )
    stage("reference_check")
    reference["seconds"] = stages["reference_check"]  # not the system's set-up
    say(f"reference check {reference}")
    del batch
    for _ in range(traffic["warm_steps"]):
        jax.block_until_ready(solver.step(feed, 1))
    stage("compile_and_warm_steps")
    say(
        "%.2fs since the process started (setup_s leaves the reference "
        "check out): %s; compile events %s" % (
            time.perf_counter() - _PROCESS_T0,
            ", ".join(f"{k} {v:.2f}s" for k, v in stages.items()), clock.read(),
        )
    )
    return {
        "config": config,
        "traffic": traffic,
        "chips": cell["chips"],
        "shapes": shapes,
        "samples": next(iter(shapes.values()))[0],
        "parameters": n_params,
        "reference": reference,
        "flops_per_step": resolve(config["flops"])(config, shapes),
    }


def traced_parts(recorded: Dict, built: Dict, trace_dir: str) -> List[Dict]:
    """The three parts of a traced run, recorded under ``dispatch``,
    ``fenced`` and ``trace``; returns their step logs.  All three run the
    live loop: the first two with ``Solver.step``'s timeline on, the third
    under the profiler.  Each counts its steps after uncounted ones
    (``lead_steps`` of the traffic file's ``trace``, 0 where it names
    none; ``skip_steps`` under the profiler)."""
    solver, feed = built["solver"], built["feed"]
    loss_key = recorded["traffic"].get("loss_key", "loss")
    spec = recorded["traffic"]["trace"]
    lead = spec.get("lead_steps", 0)
    recorded["dispatch"] = timed_phases(
        solver, feed, loss_key, spec["dispatch_steps"], fence=False, lead=lead
    )
    recorded["fenced"] = timed_phases(
        solver, feed, loss_key, spec["fenced_steps"], fence=True, lead=lead
    )
    for name in ("dispatch", "fenced"):
        part = recorded[name]
        say(
            f"timeline, {name}: after {lead} uncounted steps {part['steps']} "
            f"steps in {part['wall_s']:.4f}s, phases {part['phases']}"
        )
    live_step_s = (
        recorded["dispatch"]["window_s"] / recorded["dispatch"]["completed"]
    )
    traced = traced_steps(
        solver, feed, loss_key, spec["skip_steps"], spec["steps"], trace_dir
    )
    recorded["trace"] = trace = traced["trace"]
    say(
        f"traced {trace['steps']} steps of {trace['program']}: busy "
        f"{trace['busy_s']:.6f} s of a window of {trace['window_s']:.6f} s; "
        f"device {trace['device_step_s']} s a step"
    )
    say(
        "a step takes %.6f s under the profiler and %.6f s without it "
        "(host clock, fence off): the profiler must not change the loop" % (
            traced["window_s"] / traced["completed"], live_step_s,
        )
    )
    return [recorded["dispatch"], recorded["fenced"], traced]


def say_window(recorded: Dict, peaks: Dict[str, float]) -> None:
    """n, median, p90 and max of the step times, every step time (a stall
    has a place), and the utilisation, which is a rate times a constant and
    so no metric of its own."""
    window = recorded["window"]
    steps_s = window["step_s"]
    say(
        "window %.3fs, %d completed; step time n=%d median %.3f ms p90 %.3f "
        "ms max %.3f ms (step %d): the single intervals, for diagnosis; the "
        "metric is the pace over runs of %d" % (
            window["window_s"], window["completed"], len(steps_s),
            1e3 * statistics.median(steps_s), 1e3 * pace_p90(steps_s, 1),
            1e3 * max(steps_s), steps_s.index(max(steps_s)), PACE_RUN,
        )
    )
    say("step times, ms: " + " ".join(f"{1e3 * s:.1f}" for s in steps_s))
    longest = steps_s.index(max(steps_s))
    pauses = window["gc_pauses_s"]
    say(
        "longest step %d: %.1f ms; on a CPU in it: this thread %.1f ms, the "
        "process with the feed's threads %.1f ms; garbage collections in "
        "the window: %d, together %.1f ms, longest %.1f ms" % (
            longest, 1e3 * steps_s[longest],
            1e3 * window["step_cpu_s"][longest],
            1e3 * window["step_cpu_all_s"][longest],
            len(pauses), 1e3 * sum(pauses), 1e3 * max(pauses, default=0.0),
        )
    )
    steps_per_s = window["completed"] / window["window_s"]
    say(
        "model FLOPs a step %.6g; end-to-end utilisation %.2f%% of the bf16 "
        "peak (printed, not a metric)" % (
            recorded["flops_per_step"],
            100 * recorded["flops_per_step"] * steps_per_s
            / (peaks["bf16_flops_per_s"] * recorded["chips"]),
        )
    )


def run_cell(
    cell: Dict[str, Any],
    seed: int,
    seconds: float,
    trace: bool,
    clock: CompileClock,
    trace_dir: str,
    peaks: Dict[str, float],
) -> Dict[str, Any]:
    """One run of one cell on the devices jax has; returns the result
    object.  ``main`` refuses anything but a TPU before it gets here and
    hands in that chip's ``peaks``; a test calls this with tiny data files
    on the CPU."""
    import jax

    from benchmark.trace_reduce import top
    from sparknet_tpu.telemetry.timeline import BACKGROUND_PREFIX

    built = build_cell(cell["config"], cell["traffic"], seed)
    try:
        solver = built["solver"]
        feed_check = check_feed(built, cell["traffic"])
        say(f"train feed: {feed_check}")
        recorded = set_up(cell, built, clock)
        recorded["peaks"] = peaks
        compiles_before, iter_before = clock.read(), solver.iter
        if trace:
            logs = traced_parts(recorded, built, trace_dir)
        else:
            pauses = GcPauses()
            recorded["window"] = run_steps(
                solver, built["feed"],
                cell["traffic"].get("loss_key", "loss"), seconds=seconds,
            )
            recorded["window"]["gc_pauses_s"] = pauses.stop()
            logs = [recorded["window"]]
        in_window = clock.since(compiles_before)
        stepped_to = solver.iter
        recorded["program"] = step_program(solver, next(built["feed"]))
        say(f"step program {recorded['program']}")
    finally:
        close_feed(built)

    losses = [x for log in logs for x in log["losses"]]
    attempted = sum(log["attempted"] for log in logs)
    stepped = attempted + sum(log["primed"] for log in logs)
    failed = sum(not math.isfinite(x) for x in losses)
    published = cell["config"].get("parameters", recorded["parameters"])
    # every number that `correct` compares, beside its limit
    program, reference = recorded["program"], recorded["reference"]
    compared = {
        "reference_abs_diff": {
            "value": reference["abs_diff"], "at_most": reference["abs_tolerance"],
        },
        "losses_not_finite": {"value": failed, "at_most": 0},
        "iter_advance": {"value": stepped_to - iter_before, "equal_to": stepped},
        "compiles_in_window": {"value": in_window["compiles"], "at_most": 0},
        "feed_is_the_cells": {"value": int(feed_check["ok"]), "equal_to": 1},
        "tpu_custom_calls": {
            "value": program["tpu_custom_calls"],
            "at_least": cell["config"].get("min_tpu_custom_calls", 0),
        },
        "parameters": {"value": recorded["parameters"], "equal_to": published},
    }
    checks = {name: holds(numbers) for name, numbers in compared.items()}
    say(
        f"{stepped} steps of {recorded['samples']} samples, loss first "
        f"{losses[0]} last {losses[-1]}; compile events in the window "
        f"{in_window}"
    )
    say(f"checks {checks}")
    say(
        "host peak RSS %.2f GB" % (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9
        )
    )

    result: Dict[str, Any] = {
        "correct": all(checks.values()),
        "attempted": attempted,
        "failed": failed,
    }
    device = device_report(
        jax.devices(), recorded["program"]["bytes"]["total"]
    )
    if trace:
        # busy and window are the trace's own, over its counted steps
        reduced, fenced = recorded["trace"], recorded["fenced"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        # with the fence on the device idles whenever the host is not
        # inside compiled_step, and inside it for what is not device time:
        # the dispatch, a batch still on its way to the device, the sync.
        # The feed.* rows are threads beside the loop: they overlap
        # input_wait (feed.produce is worker-seconds) and are no gaps
        idle = {
            name: seconds for name, seconds in fenced["phases"].items()
            if not name.startswith(BACKGROUND_PREFIX)
        }
        idle["compiled_step, not device time"] = max(
            0.0,
            idle.pop("compiled_step", 0.0)
            - fenced["steps"] * sum(reduced["device_step_s"]) / reduced["steps"],
        )
        result["breakdown"] = {
            "device_ops": top(reduced["op_seconds"]),
            "idle_gaps": top(idle),
        }
        values = per_layer(cell["layers_dir"], cell["per_layer"], recorded)
        metrics = cell["per_layer"]
    else:
        say_window(recorded, peaks)
        values = end_to_end(recorded)
        metrics = cell["end_to_end"]
    result["metrics"] = {
        m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
        for m in metrics if m["name"] in values
    }
    result["device"] = device
    result["compared"] = compared  # last on the line
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    try:
        from sparknet_tpu.utils import compile_cache
    except ImportError as e:
        print(
            f"bench: the program is not in this checkout ({e}); the "
            f"benchmark measures sparknet_tpu and runs from its root",
            file=sys.stderr,
        )
        return 3
    cache_dir = compile_cache.enable()  # honours JAX_COMPILATION_CACHE_DIR
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"bench: {args.workload} needs {cell['chips']} TPU chip(s); jax "
            f"has platform={devices[0].platform} count={len(devices)}. "
            f"No CPU fall-back: rehearse with tests/benchmark.",
            file=sys.stderr,
        )
        return 2
    from benchmark import flops

    peaks = flops.peaks(devices[0].device_kind)  # an unknown kind is an error
    clock = CompileClock()
    say(
        f"cell={cell['name']} config={cell['config']['name']} "
        f"traffic={cell['traffic']['name']} chips={cell['chips']} "
        f"seed={args.seed} seconds={args.seconds} trace={args.trace} "
        f"kind={devices[0].device_kind} count={len(devices)} "
        f"compile cache {cache_dir}; imports and the start of the TPU took "
        f"{time.perf_counter() - _PROCESS_T0:.2f}s of imports_and_build"
    )
    result = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), clock,
        trace_dir=os.path.join(ROOT, "runs", "benchmark", cell["name"]),
        peaks=peaks,
    )
    for name, numbers in result["compared"].items():
        print(f"bench: compared {name}: {numbers}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
