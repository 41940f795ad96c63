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
- :func:`scope` / :func:`scope_table` / :func:`by_scope` — device time
  by the program's own scopes: the step names its parts as jax traces
  it, the compiled step's text says which instruction belongs to which
  (and to which pass), and a trace's seconds per operation are summed
  by them (docs/OBSERVABILITY.md, "Device time by scope").

This module answers *op-level* questions (what XLA did inside a
dispatch).  Host-side observability — metrics registry, span tracing,
per-step phase attribution, Prometheus export — lives in
:mod:`sparknet_tpu.telemetry` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import time
import weakref
from collections import Counter, defaultdict
from typing import (
    Dict, Iterable, List, Mapping, NamedTuple, Optional, Sequence, Tuple,
)

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


def _device_lines(
    log_dir: str, line_name: str
) -> Dict[str, List[Tuple[str, int, int]]]:
    """``{device plane: [(name, start_ns, duration_ns), ...]}``: the line
    ``line_name`` of every device plane in the newest ``.xplane.pb``
    under ``log_dir``.  Empty where no device was traced (a CPU run)."""
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
            if line.name == line_name:
                out[plane.name] = [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                ]
    return out


def device_modules(log_dir: str) -> Dict[str, List[Tuple[str, int, int]]]:
    """The ``XLA Modules`` line (one event per execution of a program) of
    every device plane of the newest trace under ``log_dir``."""
    return _device_lines(log_dir, "XLA Modules")


def device_ops(log_dir: str) -> Dict[str, List[Tuple[str, int, int]]]:
    """The ``XLA Ops`` line of the same planes: the operations the core
    ran, each named by its HLO text (``%fusion.261 = ...``), whose first
    token is the instruction's name in the compiled module."""
    return _device_lines(log_dir, "XLA Ops")


# ---------------------------------------------------------------------------
# Device time by the program's own scopes: scope -> instruction -> seconds
# ---------------------------------------------------------------------------

_declared: set = set()


def scope(name: str):
    """``jax.named_scope(name)``, with the name remembered as one of the
    program's own scopes (:func:`declared_scopes`).  It runs while jax
    traces the Python, once a compile: a step pays nothing for it.  The
    name becomes one element of every operation's ``op_name`` path under
    it, so it holds no ``/`` and no parenthesis."""
    if not name or any(c in name for c in "/()"):
        raise ValueError(f"scope name {name!r}: one path element, please")
    _declared.add(name)
    return jax.named_scope(name)


def declared_scopes() -> frozenset:
    """Every name :func:`scope` was given so far in this process."""
    return frozenset(_declared)


class Scoped(NamedTuple):
    """One instruction of a compiled step, as :func:`scope_table` reads it."""
    chain: Tuple[str, ...]  # its declared scopes, outermost first
    pass_: str  # "forward", "backward" or "recompute"
    kernel: bool  # a Pallas kernel (custom call to tpu_custom_call)
    mixed: bool  # a fusion whose body spans several outermost scopes
    lent: bool  # the compiler's own instruction, placed by its neighbours


CONTAINERS = frozenset({"while", "conditional", "call"})
_PLUMBING = frozenset(
    {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
)
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# the computations a container runs: their instructions are device
# operations of their own; a fusion's, a reduction's or a sort's are not
_RUNS = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}"
)
# jvp(x), transpose(jvp(x)), vmap(x): a transformation around a path
# element; jit(x) is a function of that name, not a scope
_WRAPPED = re.compile(r"^(?!p?jit\()\w+\((.*)\)$")


def instruction_name(text: str) -> str:
    """``fusion.261`` of ``%fusion.261 = bf16[...] fusion(...)``: what a
    trace's operation and the compiled module's instruction share."""
    return text.split(" ", 1)[0].lstrip("%")


def opcode_of(text: str) -> Optional[str]:
    """The opcode in an instruction's HLO text (the first lower-case word
    before a parenthesis after the result's type), None where there is no
    text beyond a name."""
    m = _OPCODE.search(text.partition(" = ")[2])
    return m.group(1) if m else None


def scope_chain(op_name: str, declared: Iterable[str]) -> Tuple[str, ...]:
    """The declared scopes on an ``op_name`` path, outermost first; jax's
    own elements (``while``, ``body``, ``closed_call``, ``checkpoint``,
    ``cond``, ``pjit``, primitive names) are skipped and ``jvp(x)`` /
    ``transpose(jvp(x))`` read as ``x``.  Where XLA merged operations it
    joins their paths with ``;``: the first one speaks."""
    chain = []
    for part in op_name.split(";", 1)[0].split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        # a scope closed over by a checkpoint inside it comes twice
        if part in declared and part not in chain[-1:]:
            chain.append(part)
    return tuple(chain)


def pass_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


def _computations(hlo_text: str):
    """``({computation: [(instruction, opcode, rest of its line)]}, the
    entry computation's name)`` of a module's text."""
    found: Dict[str, List[Tuple[str, str, str]]] = {}
    entry, body = None, None
    for line in hlo_text.splitlines():
        if body is not None and line.startswith(" "):
            m = _INSTRUCTION.match(line)
            if m:
                op = _OPCODE.search(" " + m.group(2))
                body.append((m.group(1), op.group(1) if op else "", m.group(2)))
        elif line.endswith("{") and "->" in line and not line.startswith(" "):
            head = line.split("(", 1)[0].split()
            body = found.setdefault(head[-1].lstrip("%"), [])
            if head[0] == "ENTRY":
                entry = head[-1].lstrip("%")
        elif line.startswith("}"):
            body = None
    return found, entry


def _operands(rest: str) -> List[str]:
    """The operands' names in an instruction's text after `` = ``."""
    m = _OPCODE.search(" " + rest)
    if not m:
        return []
    listed = rest[m.end() - 1:].split(")", 1)[0]
    return [
        word.split()[-1].lstrip("%")
        for word in re.sub(r"/\*.*?\*/", "", listed).split(",") if word.strip()
    ]


def scope_table(hlo_text: str, declared: Iterable[str]) -> Dict[str, Scoped]:
    """``{instruction name: Scoped}`` of a *compiled* module's text
    (``compiled.as_text()``): every instruction the device runs as an
    operation of its own — those of the entry computation and of the
    bodies, conditions and branches of its containers — with the chain of
    ``declared`` scopes on its ``op_name`` path and the pass the path
    says.  A fusion takes its own ``op_name``; where it has none, what
    most of its fused computation's instructions carry; and it is marked
    ``mixed`` when those span more than one outermost scope: the size of
    the attribution's error.  What the compiler made itself and gave no
    path of the program's (the grouped products' own kernels, which XLA
    names ``ragged-dot-none``; prefetches and copies between memory
    spaces; buffer allocations) is ``lent`` the scopes that its nearest
    neighbours share — the instructions that use it and those it uses,
    through tuple plumbing and other such instructions — or, where they
    share none, those of what uses it, else of what it uses: a prefetched
    weight belongs to the product it is fetched for.  Containers (``while``,
    ``conditional``, ``call``) get no entry, their children are operations
    of their own; nor do parameters, constants and tuple plumbing, which
    take no time.  A pure function over text."""
    declared = frozenset(declared)
    computations, entry = _computations(hlo_text)
    read = lambda op_name: (scope_chain(op_name, declared), pass_of(op_name))
    table: Dict[str, Scoped] = {}
    uses: Dict[str, List[str]] = {}  # instruction -> its operands
    used_by: Dict[str, List[str]] = defaultdict(list)
    made, passing = set(), set()  # the compiler's own; plumbing to walk through
    seen, queue = set(), [entry]
    while queue:
        name = queue.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        for instruction, opcode, rest in computations[name]:
            if opcode in CONTAINERS:
                for one, several in _RUNS.findall(rest):
                    queue.extend(
                        c.strip().lstrip("%")
                        for c in (several.split(",") if several else [one])
                    )
                continue
            uses[instruction] = _operands(rest)
            for operand in uses[instruction]:
                used_by[operand].append(instruction)
            if opcode in _PLUMBING:
                if opcode not in ("parameter", "constant"):
                    passing.add(instruction)
                continue
            named = _OP_NAME.search(rest)
            path = named.group(1) if named else ""
            chain, pass_ = read(path)
            ours, mixed = "jit(" in path, False  # a path of the program's
            if opcode == "fusion":
                fused = _CALLS.search(rest)
                inner = [
                    read(m.group(1))
                    for _i, op, text in computations.get(
                        fused.group(1) if fused else "", ()
                    )
                    if op not in _PLUMBING
                    for m in [_OP_NAME.search(text)] if m
                ]
                mixed = len({c[0] for c, _p in inner if c}) > 1
                if not path and inner:
                    (chain, pass_), ours = Counter(inner).most_common(1)[0][0], True
            if not ours:
                made.add(instruction)
            table[instruction] = Scoped(
                chain, pass_,
                opcode == "custom-call"
                and 'custom_call_target="tpu_custom_call"' in rest,
                mixed, False,
            )

    def lenders(start: str, edges: Mapping[str, List[str]]) -> List[Tuple]:
        """(chain, pass) of the nearest scoped instructions of the
        program's own along ``edges`` from ``start``."""
        reached, front = {start}, [start]
        for _hop in range(8):
            found = [
                (table[n].chain, table[n].pass_)
                for n in front if n != start and n in table
                and n not in made and table[n].chain
            ]
            if found:
                return found
            front = [
                n for at in front if at == start or at in made or at in passing
                for n in edges.get(at, ()) if n not in reached
            ]
            reached.update(front)
        return []

    for instruction in made:
        after, before = lenders(instruction, used_by), lenders(instruction, uses)
        near = after + before
        if not near:
            continue
        # the scopes all its neighbours share (a product between the
        # activation under moe.experts and the weighting under
        # moe.experts/moe.rows is moe.experts'); where they share none,
        # those of what uses it, or else of what it uses
        shared = os.path.commonprefix([chain for chain, _pass in near])
        chain, pass_ = Counter(after or before).most_common(1)[0][0]
        table[instruction] = table[instruction]._replace(
            chain=shared or chain, pass_=pass_, lent=True
        )
    return table


def by_scope(
    op_seconds: Mapping[str, float], table: Mapping[str, Scoped], steps: int
) -> Dict:
    """A trace's seconds per operation (named by HLO text or by the
    instruction's name alone), summed by the table's scopes, ms a step:
    ``rows`` ``{(chain, pass, kernel): ms}``; ``unscoped``, joined and
    under no declared scope; ``unjoined``, a name the table lacks;
    ``mixed``, the part of the joined time in fusions over several
    outermost scopes, and ``lent``, the part in instructions the compiler
    made, placed by their neighbours: the two sizes of the attribution's
    error; ``containers``, the ``while`` / ``conditional`` /
    ``call`` operations, whose time is their children's over again and is
    in nothing else here; ``total`` = rows + unscoped + unjoined, the
    device time of a step; ``coverage``, the rows' share of it in %."""
    rows: Dict[Tuple, float] = defaultdict(float)
    sums = dict.fromkeys(
        ("unscoped", "unjoined", "mixed", "lent", "containers"), 0.0
    )
    for text, seconds in op_seconds.items():
        ms = 1e3 * seconds / steps
        entry = table.get(instruction_name(text))
        if entry is None:
            container = opcode_of(text) in CONTAINERS
            sums["containers" if container else "unjoined"] += ms
            continue
        if entry.mixed:
            sums["mixed"] += ms
        if entry.lent:
            sums["lent"] += ms
        if entry.chain:
            rows[(entry.chain, entry.pass_, entry.kernel)] += ms
        else:
            sums["unscoped"] += ms
    scoped = sum(rows.values())
    total = scoped + sums["unscoped"] + sums["unjoined"]
    return {
        "rows": dict(rows), **sums, "total": total,
        "coverage": 100.0 * scoped / total if total else 0.0,
    }


PASSES = ("forward", "backward", "recompute")


def scope_lines(reduced: Dict, depth: Optional[int] = 1) -> List[str]:
    """:func:`by_scope`'s result as a table: a row for each chain cut to
    ``depth`` scopes (whole chains where None), ms a step forward,
    backward and recomputed, their sum, its share of the step and the
    part of it in Pallas kernels; then unscoped, unjoined, mixed and the
    coverage."""
    grouped: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for (chain, pass_, kernel), ms in reduced["rows"].items():
        row = grouped["/".join(chain[:depth])]
        row[pass_] += ms
        row["kernels"] += ms if kernel else 0.0
    total = reduced["total"] or 1.0
    width = max([len(k) for k in grouped] + [8])
    lines = [
        f"{'scope':<{width}} {'forward':>9} {'backward':>9} {'recompute':>9} "
        f"{'ms a step':>9} {'share':>7} {'kernels':>9}"
    ]
    ranked = sorted(
        grouped.items(), key=lambda kv: -sum(kv[1][p] for p in PASSES)
    )
    for name, row in ranked:
        ms = sum(row[p] for p in PASSES)
        lines.append(
            f"{name:<{width}} {row['forward']:>9.3f} {row['backward']:>9.3f} "
            f"{row['recompute']:>9.3f} {ms:>9.3f} {100 * ms / total:>6.2f}% "
            f"{row['kernels']:>9.3f}"
        )
    for name in ("unscoped", "unjoined"):
        lines.append(
            f"{name:<{width}} {'':>29} {reduced[name]:>9.3f} "
            f"{100 * reduced[name] / total:>6.2f}%"
        )
    lines.append(
        f"a step {reduced['total']:.3f} ms on the device; under a declared "
        f"scope {reduced['coverage']:.2f}%; in fusions over more than one "
        f"outermost scope (mixed) {reduced['mixed']:.3f} ms, "
        f"{100 * reduced['mixed'] / total:.2f}%; in instructions of the "
        f"compiler's own, placed by their neighbours (lent) "
        f"{reduced['lent']:.3f} ms, {100 * reduced['lent'] / total:.2f}%"
    )
    return lines


def step_op_seconds(
    modules: Sequence[Tuple[str, int, int]],
    ops: Sequence[Tuple[str, int, int]],
    program: str,
) -> Tuple[Dict[str, float], int]:
    """(seconds per operation inside the executions of ``program``, their
    count) from one device plane's two lines."""
    runs = sorted((s, s + d) for name, s, d in modules if name == program)
    starts = [s for s, _e in runs]
    seconds: Dict[str, float] = defaultdict(float)
    for name, start, duration in ops:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < runs[i][1]:
            seconds[name] += duration / 1e9
    return dict(seconds), len(runs)


# the newest Solver: the one whose step program a caller without a solver
# means (a weak reference; None until one is built)
_step_source: Optional[weakref.ref] = None


def publish_step_source(solver) -> None:
    global _step_source
    _step_source = weakref.ref(solver)


def step_scopes() -> Optional[Dict[str, Scoped]]:
    """``Solver.step_scopes()`` of the newest Solver: the scope table of
    the step program it last lowered (``lower_step``).  None where there
    is no solver, or it has lowered nothing yet."""
    solver = _step_source() if _step_source is not None else None
    return solver.step_scopes() if solver is not None else None


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
