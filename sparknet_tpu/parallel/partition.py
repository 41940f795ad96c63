"""Unified sharding compile path: one mesh + regex partition rules.

The strategy zoo this module replaces grew one hand-built ``shard_map``
step builder per parallelism flavour (dp/tp/pp/sp/ep/local-SGD), each
with its own manual collectives.  Following the declarative
dataflow-partitioning design of the TensorFlow paper (PAPERS.md,
arXiv:1605.08695) and jax's mesh/``NamedSharding`` idiom, the unified
path expresses a parallel layout as DATA, not code:

- **Layout** = a mesh shape (``dp``/``tp``/``pp``/``ep`` axes over
  :func:`~sparknet_tpu.parallel.mesh.make_mesh`) plus an ORDERED table
  of regex rules mapping param-tree paths -> ``PartitionSpec``.  First
  match wins; an unmatched leaf gets the explicit replicated fallback;
  ``validate="strict"`` rejects specs whose mesh axes do not divide
  the dims they shard.
- The rule table compiles into per-leaf :class:`NamedSharding` trees
  for params, optimizer slots and the batch, and
  :func:`make_sharded_train_step` jits the ONE generic train step
  (:func:`~sparknet_tpu.solver.trainer.make_train_step`) with
  ``in_shardings``/``out_shardings`` from those trees and
  ``donate_argnums`` on weights + opt state.  The XLA GSPMD
  partitioner inserts (and overlaps) every collective — no
  ``shard_map``, no hand-written ``pmean``/``all_gather``.

Any dp×tp×ep combination is a table entry, not a new trainer: rules
may name axes the current layout does not have (they resolve to
replicated on that dim), so one ruleset serves ``dp=8``, ``dp=2,tp=4``
and ``dp=2,ep=4`` alike.  Numerics: GSPMD partitioning is
semantics-preserving — a sharded step matches the single-device step
to reduction-order (ulp-level) accuracy, and is BITWISE identical to
any hand-built jit with the same shardings (tests/test_partition.py
pins both).

Serialization (``spec_to_str``/``layout_to_json``) lets snapshots
carry per-leaf specs for relayout-on-resume, and
:func:`layout_fingerprint` extends the serve tier's
``net_fingerprint`` so compile caches never alias across layouts.
See docs/PARALLELISM.md.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DP_AXIS, make_mesh

# Mesh axis vocabulary of the framework (mesh.py conventions).  Layouts
# may use any subset; rules may reference any of them and degrade to
# replicated when the layout lacks the axis.
AXES = ("dp", "tp", "pp", "sp", "ep")


# --------------------------------------------------------------------------
# rules
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Rule:
    """One partition rule: ``re.search(pattern, leaf_path)`` against
    the ``/``-joined tree path; ``spec`` entries are mesh-axis names,
    ``None``, or tuples of axis names — exactly ``PartitionSpec``'s
    grammar.  ``align`` anchors a spec shorter than the leaf's rank:
    ``"leading"`` pads ``None`` on the right (PartitionSpec's own
    convention), ``"trailing"`` pads on the left — so one
    ``("tp",) @ trailing`` rule shards the output dim of both a 2-D
    InnerProduct weight and a 4-D conv filter."""

    pattern: str
    spec: Tuple[Any, ...]
    align: str = "leading"

    def __post_init__(self):
        re.compile(self.pattern)  # fail at table-build time, not match time
        if self.align not in ("leading", "trailing"):
            raise ValueError(
                f"rule {self.pattern!r}: align must be leading|trailing, "
                f"got {self.align!r}"
            )
        if not isinstance(self.spec, tuple):
            object.__setattr__(self, "spec", tuple(self.spec))


# Named rule tables.  "tp" covers the prototxt/XLANet families (every
# learned blob is output-dim-trailing); "bert" covers the BertMLM
# family by parameter name (Megatron column/row split + expert stacks).
RULESETS: Dict[str, Tuple[Rule, ...]] = {
    "replicated": (),
    "tp": (
        Rule(r"(^|/)weight$", ("tp",), align="trailing"),
        Rule(r"(^|/)bias$", ("tp",), align="trailing"),
    ),
    "bert": (
        Rule(r"/(q_w|k_w|v_w|ffn_in_w)$", (None, "tp")),
        Rule(r"/(q_b|k_b|v_b|ffn_in_b)$", ("tp",)),
        Rule(r"/(out_w|ffn_out_w)$", ("tp", None)),
        Rule(r"/(w_in|b_in|w_out|b_out)$", ("ep",)),
    ),
}


@dataclasses.dataclass(frozen=True)
class Layout:
    """A parallel layout: ordered mesh axes + the partition rule table.

    ``axes``: ``((name, size), ...)`` major-to-minor; one size may be
    ``-1`` ("all remaining devices", resolved at mesh build).
    ``rules``: ordered :class:`Rule` tuple (first match wins) or a
    :data:`RULESETS` name.  ``validate``: ``"strict"`` (reject specs
    that don't divide the dims they shard) or ``"off"``."""

    axes: Tuple[Tuple[str, int], ...] = ((DP_AXIS, -1),)
    rules: Tuple[Rule, ...] = ()
    name: str = "custom"
    validate: str = "strict"
    batch_axis: str = DP_AXIS

    def __post_init__(self):
        if isinstance(self.rules, str):
            object.__setattr__(self, "rules", RULESETS[self.rules])
        object.__setattr__(
            self, "axes", tuple((str(a), int(s)) for a, s in self.axes)
        )
        if self.validate not in ("strict", "off"):
            raise ValueError(
                f"validate must be strict|off, got {self.validate!r}"
            )
        names = [a for a, _ in self.axes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate mesh axes in {names}")

    def axes_dict(self) -> Dict[str, int]:
        return dict(self.axes)

    def mesh(self, devices=None) -> Mesh:
        axes = self.axes_dict()
        sizes = list(axes.values())
        if devices is None and -1 not in sizes:
            need = 1
            for s in sizes:
                need *= s
            devices = jax.devices()[:need]  # fully-sized layout: take
            # the first N devices rather than demanding an exact count
        return make_mesh(axes, devices)


def parse_axes(spec: str) -> Dict[str, int]:
    """``"dp=2,tp=4"`` -> ``{"dp": 2, "tp": 4}`` (one size may be -1)."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"layout axis {part!r}: want name=size (e.g. dp=2,tp=4)"
            )
        k, _, v = part.partition("=")
        try:
            out[k.strip()] = int(v)
        except ValueError:
            raise ValueError(f"layout axis {part!r}: size must be an int")
    if not out:
        raise ValueError(f"empty layout spec {spec!r}")
    return out


def parse_layout(
    axes: str, rules="replicated", name: Optional[str] = None, **kw
) -> Layout:
    """Build a :class:`Layout` from the apps' flag syntax:
    ``parse_layout("dp=2,tp=2", rules="tp")``."""
    ax = tuple(parse_axes(axes).items())
    rules_t = RULESETS[rules] if isinstance(rules, str) else tuple(rules)
    return Layout(
        axes=ax,
        rules=rules_t,
        name=name or (rules if isinstance(rules, str) else "custom"),
        **kw,
    )


# --------------------------------------------------------------------------
# path naming + rule matching
# --------------------------------------------------------------------------

def _path_str(path) -> str:
    """``/``-joined tree path: dict keys and sequence indices, without
    jax.keystr's bracket noise — ``conv1/weight``, ``m/layer_00/q_w``."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:  # pragma: no cover - future key types
            parts.append(str(p))
    return "/".join(parts)


def tree_paths(tree) -> Tuple[Tuple[str, Any], ...]:
    """Flattened ``(path_str, leaf)`` pairs in tree order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return tuple((_path_str(path), leaf) for path, leaf in flat)


def _entry_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(str(a) for a in entry)
    return (str(entry),)


def _filter_entry(entry, mesh_axes) -> Any:
    """Drop axis names the mesh does not have (rule written for a
    bigger layout) — the dim degrades to replicated there."""
    axes = tuple(a for a in _entry_axes(entry) if a in mesh_axes)
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def match_spec(
    rules: Sequence[Rule],
    path: str,
    leaf,
    mesh: Optional[Mesh] = None,
) -> P:
    """First-match-wins spec for one leaf; replicated fallback.  Scalar
    (0-d / single-element) leaves are never partitioned.  When ``mesh``
    is given, rule axes the mesh lacks resolve to ``None``."""
    ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
    size = getattr(leaf, "size", None)
    if ndim == 0 or size == 1:
        return P()
    mesh_axes = tuple(mesh.shape) if mesh is not None else None
    for rule in rules:
        if re.search(rule.pattern, path) is None:
            continue
        spec = tuple(rule.spec)
        if len(spec) > ndim:
            raise ValueError(
                f"partition rule {rule.pattern!r} has {len(spec)} spec "
                f"entries but {path!r} is rank {ndim}"
            )
        pad = (None,) * (ndim - len(spec))
        spec = pad + spec if rule.align == "trailing" else spec + pad
        if mesh_axes is not None:
            spec = tuple(_filter_entry(e, mesh_axes) for e in spec)
        # trim trailing Nones: P(None, "tp") == P(None, "tp", None)
        while spec and spec[-1] is None:
            spec = spec[:-1]
        return P(*spec)
    return P()  # explicit replicated fallback


def validate_spec(path: str, leaf, spec: P, mesh: Mesh) -> None:
    """Strict mode: every sharded dim must be divisible by the product
    of its mesh axes (XLA would pad silently otherwise, which changes
    memory math and hides layout bugs)."""
    for dim, entry in enumerate(spec):
        factor = 1
        for axis in _entry_axes(entry):
            if axis not in mesh.shape:
                raise ValueError(
                    f"{path}: spec {spec} names mesh axis {axis!r} but the "
                    f"mesh has {tuple(mesh.shape)}"
                )
            factor *= mesh.shape[axis]
        if factor > 1 and leaf.shape[dim] % factor:
            raise ValueError(
                f"{path}: dim {dim} of shape {tuple(leaf.shape)} is not "
                f"divisible by mesh axes {entry!r} (= {factor}); fix the "
                f"rule table or use validate='off'"
            )


def spec_tree(tree, rules: Sequence[Rule], mesh: Mesh, validate: str = "strict"):
    """Same-structure pytree of ``PartitionSpec`` from the rule table."""
    def one(path, leaf):
        spec = match_spec(rules, _path_str(path), leaf, mesh)
        if validate == "strict":
            validate_spec(_path_str(path), leaf, spec, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(one, tree)


def sharding_tree(tree, rules: Sequence[Rule], mesh: Mesh,
                  validate: str = "strict"):
    """Per-leaf :class:`NamedSharding` tree for ``tree``."""
    specs = spec_tree(tree, rules, mesh, validate)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)


# --------------------------------------------------------------------------
# spec serialization (snapshot relayout-on-resume)
# --------------------------------------------------------------------------

def spec_to_str(spec: P) -> str:
    """``P(None, ("dp","tp"))`` -> ``"None,(dp+tp)"`` — stable, eval-free."""
    parts = []
    for entry in spec:
        axes = _entry_axes(entry)
        if not axes:
            parts.append("None")
        elif len(axes) == 1:
            parts.append(axes[0])
        else:
            parts.append("(" + "+".join(axes) + ")")
    return ",".join(parts)


def spec_from_str(s: str) -> P:
    if not s:
        return P()
    entries = []
    for part in s.split(","):
        part = part.strip()
        if part in ("None", ""):
            entries.append(None)
        elif part.startswith("(") and part.endswith(")"):
            entries.append(tuple(part[1:-1].split("+")))
        else:
            entries.append(part)
    return P(*entries)


def specs_record(tree, rules: Sequence[Rule], mesh: Mesh) -> Dict[str, str]:
    """``{leaf_path: spec_str}`` for every leaf — what snapshots carry
    so a resume can detect (and warn about) a relayout."""
    specs = spec_tree(tree, rules, mesh, validate="off")
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    return {
        _path_str(path): spec_to_str(spec)
        for path, spec in flat
    }


def layout_to_json(layout: Layout) -> str:
    return json.dumps(
        {
            "name": layout.name,
            "axes": list(layout.axes),
            "rules": [
                [r.pattern, [list(e) if isinstance(e, tuple) else e
                             for e in r.spec], r.align]
                for r in layout.rules
            ],
            "batch_axis": layout.batch_axis,
        },
        sort_keys=True,
    )


def layout_from_json(doc: str) -> Layout:
    d = json.loads(doc)
    return Layout(
        axes=tuple((a, s) for a, s in d["axes"]),
        rules=tuple(
            Rule(p, tuple(tuple(e) if isinstance(e, list) else e
                          for e in spec), align)
            for p, spec, align in d["rules"]
        ),
        name=d.get("name", "custom"),
        batch_axis=d.get("batch_axis", DP_AXIS),
    )


def layout_fingerprint(layout: Layout) -> str:
    """16-hex content hash of the layout — folded into the serve
    tier's ``net_fingerprint`` so compile caches never alias across
    layouts of the same arch."""
    return hashlib.sha256(layout_to_json(layout).encode()).hexdigest()[:16]


# --------------------------------------------------------------------------
# the compiled-step plan
# --------------------------------------------------------------------------

class Plan:
    """The rule table compiled against concrete trees: per-leaf
    ``NamedSharding`` for params/state, per-slot trees for the
    optimizer state, and the batch shardings — everything
    :func:`make_sharded_train_step` needs, reusable by the solver for
    placement and by the serve engine for inference."""

    def __init__(self, layout: Layout, mesh: Mesh, params, state,
                 opt_keys: Sequence[str] = ()):
        self.layout = layout
        self.mesh = mesh
        for axis, size in layout.axes:
            if size != -1 and mesh.shape.get(axis) != size:
                raise ValueError(
                    f"layout axis {axis}={size} vs mesh "
                    f"{dict(mesh.shape)} — build the mesh from "
                    f"layout.mesh() or pass a matching one"
                )
        self.replicated = NamedSharding(mesh, P())
        self.params_sh = sharding_tree(
            params, layout.rules, mesh, layout.validate
        )
        # net state (BN stats etc.): replicated unless a rule targets it
        self.state_sh = sharding_tree(
            state, layout.rules, mesh, layout.validate
        )
        # solver slots mirror the param tree leaf-for-leaf
        self.opt_sh = {k: self.params_sh for k in opt_keys}
        dp = layout.batch_axis
        self.dp_axis = dp if dp in mesh.shape else None
        self.batch_eval_sh = NamedSharding(mesh, P(dp) if dp in mesh.shape else P())
        self.batch_train_sh = self.batch_eval_sh
        self.specs = specs_record(params, layout.rules, mesh)

    def with_iter_size(self, iter_size: int) -> "Plan":
        """Gradient accumulation stacks micro-batches on a leading
        axis; the batch axis to shard is then axis 1."""
        if iter_size > 1:
            dp = self.layout.batch_axis
            self.batch_train_sh = NamedSharding(
                self.mesh, P(None, dp) if dp in self.mesh.shape else P()
            )
        return self

    # ---- reporting ----------------------------------------------------
    def counts(self) -> Dict[str, int]:
        flat = jax.tree_util.tree_leaves(
            self.params_sh, is_leaf=lambda x: isinstance(x, NamedSharding)
        )
        sharded = sum(1 for s in flat if s.spec != P())
        return {
            "param_leaves": len(flat),
            "sharded": sharded,
            "replicated": len(flat) - sharded,
        }

    def report(self) -> Dict[str, Any]:
        out = {
            "name": self.layout.name,
            "mesh": dict(self.mesh.shape),
            "rules": len(self.layout.rules),
            "fingerprint": layout_fingerprint(self.layout),
        }
        out.update(self.counts())
        return out


def make_plan(
    layout: Layout,
    params,
    state,
    sp=None,
    mesh: Optional[Mesh] = None,
    devices=None,
    iter_size: Optional[int] = None,
) -> Plan:
    """Resolve a layout against concrete trees (and a solver's slot
    keys) into a :class:`Plan`."""
    from ..solver.caffe_solver import opt_state_keys

    mesh = mesh if mesh is not None else layout.mesh(devices)
    keys = opt_state_keys(sp) if sp is not None else ()
    plan = Plan(layout, mesh, params, state, keys)
    if iter_size is None and sp is not None:
        iter_size = sp.iter_size
    return plan.with_iter_size(iter_size or 1)


# --------------------------------------------------------------------------
# the ONE sharded compile path
# --------------------------------------------------------------------------

def jit_sharded_step(fn, in_shardings, out_shardings, donate_argnums=()):
    """The single jit wrapper every sharded program goes through —
    train, eval and the dp wrappers in data_parallel.py all compile
    here, so compiler options and donation policy cannot drift."""
    from ..solver.trainer import step_compile_kw

    return jax.jit(
        fn,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        donate_argnums=donate_argnums,
        **step_compile_kw(),
    )


def make_sharded_train_step(net, sp, plan: Plan, donate: bool = True):
    """``step(params, state, opt_state, batch, it, rng)`` jitted with
    the plan's shardings: params/opt donated, batch dp-sharded, every
    collective inserted by the XLA partitioner.  Works for any object
    satisfying the net protocol (XLANet or a model like BertMLM)."""
    from ..solver.trainer import make_train_step

    repl = plan.replicated
    return jit_sharded_step(
        make_train_step(net, sp),
        in_shardings=(
            plan.params_sh, plan.state_sh, plan.opt_sh,
            plan.batch_train_sh, repl, repl,
        ),
        out_shardings=(plan.params_sh, plan.state_sh, plan.opt_sh, repl),
        donate_argnums=(0, 1, 2) if donate else (),
    )


def make_sharded_eval_step(net, plan: Plan):
    """TEST-phase step over the same sharding trees — serve and eval
    compile through the identical path as training."""
    from ..solver.trainer import make_eval_step

    return jit_sharded_step(
        make_eval_step(net),
        in_shardings=(plan.params_sh, plan.state_sh, plan.batch_eval_sh),
        out_shardings=plan.replicated,
    )


def place(tree, shardings):
    """Device-put a host tree onto its sharding tree (or one broadcast
    sharding) — the layout-aware replacement for ``mesh.replicate``."""
    if isinstance(shardings, (NamedSharding,)):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, shardings), tree
        )
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), tree, shardings
    )


# --------------------------------------------------------------------------
# virtual-mesh + fence guards (test/bench plumbing)
# --------------------------------------------------------------------------

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def backend_initialized() -> bool:
    """Whether this process already holds a jax backend — asked without
    creating one.  The one reach into jax's private bridge: 0.9.0 has no
    public spelling of the question."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def ensure_virtual_devices(n: int) -> bool:
    """Guard for the virtual-CPU mesh: make
    ``XLA_FLAGS=--xla_force_host_platform_device_count=n`` effective
    when the backend is not yet initialized, and a LOUD no-op (warning,
    return False) when it is — instead of the silent 1-device mesh that
    makes every divisibility check downstream fail confusingly.
    Returns True when n devices are (or will be) available."""
    flags = os.environ.get("XLA_FLAGS", "")
    have = re.search(_FORCE_FLAG + r"=(\d+)", flags)
    if backend_initialized():
        ok = len(jax.devices()) >= n
        if not ok:
            warnings.warn(
                f"ensure_virtual_devices({n}): jax backend already "
                f"initialized with {len(jax.devices())} device(s) — set "
                f"XLA_FLAGS={_FORCE_FLAG}={n} before the first device "
                "touch",
                RuntimeWarning,
                stacklevel=2,
            )
        return ok
    if have and int(have.group(1)) >= n:
        return True
    if have:
        flags = re.sub(_FORCE_FLAG + r"=\d+", f"{_FORCE_FLAG}={n}", flags)
    else:
        flags = (flags + f" {_FORCE_FLAG}={n}").strip()
    os.environ["XLA_FLAGS"] = flags
    return True


def fence_once(tree):
    """``block_until_ready`` UNLESS the active telemetry timeline
    already fences the compiled step — the solver's ``compiled_step``
    phase bracket blocks on the step's outputs, so fencing again here
    would put a second device sync inside the timed region and charge
    it to the wrong phase.  Bench arms and smoke scripts use this as
    their one fence."""
    from ..telemetry import timeline as _tl

    if getattr(_tl.current(), "fence", False):
        return tree
    return jax.block_until_ready(tree)


# --------------------------------------------------------------------------
# relayout-on-resume support
# --------------------------------------------------------------------------

def relayout_warning(saved_specs_json: str, current: Dict[str, str],
                     saved_layout: str = "", current_layout: str = "",
                     event: str = "resume") -> str:
    """One aggregated message for a relayout — name the count and the
    two layouts, not a leaf-per-line wall.  The same migration runs on
    two paths, and the wording names which: ``event="resume"`` (a
    snapshot restored under a different layout) or ``event="reshard"``
    (a live in-place migration, parallel/reshard.py)."""
    try:
        saved = json.loads(saved_specs_json)
    except (TypeError, json.JSONDecodeError):
        saved = {}
    changed = [
        k for k in current
        if k in saved and saved[k] != current[k]
    ] + [k for k in current if k not in saved]
    head, src, dst = (
        ("relayout on resume", "snapshot", "run")
        if event == "resume"
        else ("relayout (live reshard)", "old", "new")
    )
    return (
        f"{head}: {len(changed)} of {len(current)} leaves "
        f"re-partitioned ({src} layout {saved_layout or 'unknown'} -> "
        f"{dst} layout {current_layout or 'unknown'}); weights are placed "
        "per the new rule table bitwise-unchanged — numerics of further "
        "training match to reduction order (the same in-place migration "
        "on either path; docs/PARALLELISM.md \"Live resharding\")"
    )
