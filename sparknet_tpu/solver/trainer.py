"""Solver: the training loop, behaviorally Caffe's ``Solver::Step``.

The reference's executor-side loop is ``CaffeNet.train(tau)`` -> native
``Solver::Step(tau)`` (SURVEY.md §3; mount empty). Here the whole
iteration — forward, backward, regularise, update, LR schedule — is a
single jitted function with donated buffers, so stepping ``tau`` times
is ``tau`` XLA executions with zero host round-trips in between (the
reference pays a JNI weight copy per sync; we pay nothing until the
caller explicitly materialises metrics).
"""

from __future__ import annotations

import os
import sys
from collections import deque
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from ..proto import caffe_pb
from ..nets.xlanet import XLANet
from ..telemetry import timeline as _timeline
from ..utils import profiling
from .caffe_solver import init_opt_state, make_update_fn, mults_for_params


def resolve_model_path(path: str, base_dir: str) -> str:
    """Resolve a prototxt-referenced path like Caffe (relative to the
    launch cwd) with relocatable-bundle fallbacks: the solver's own
    directory, then the bare filename inside it."""
    for cand in (
        path,
        os.path.join(base_dir, path),
        os.path.join(base_dir, os.path.basename(path)),
    ):
        if os.path.exists(cand):
            return cand
    return path


def _step_compiler_options() -> Optional[Dict[str, str]]:
    """Per-compile XLA options for the train/eval steps (single-device
    Solver and, via :func:`step_compile_kw`, the dp/local-SGD
    builders).

    ``xla_tpu_scoped_vmem_limit_kib=32768``: more scoped VMEM lets XLA
    form larger fusions. It read faster for AlexNet and BERT and slower
    for ResNet-50 (measured once in round 5 on a set-up that no longer
    exists; not re-measured — ROADMAP S7 re-measures the choice).
    libtpu 0.0.34 accepts the option: every Solver step of
    ``chip_smoke.py`` compiles with it (PR 21). TPU-only (the option
    does not exist on other backends); SPARKNET_SCOPED_VMEM_KIB
    overrides, 0 disables."""
    if jax.default_backend() != "tpu":
        return None
    raw = os.environ.get("SPARKNET_SCOPED_VMEM_KIB", "32768").strip()
    try:
        kib = int(raw or "0")
    except ValueError:
        raise ValueError(
            f"SPARKNET_SCOPED_VMEM_KIB must be an integer KiB count "
            f"(got {raw!r})"
        )
    if kib <= 0:
        return None
    return {"xla_tpu_scoped_vmem_limit_kib": str(kib)}


def step_compile_kw() -> Dict[str, Any]:
    """Splat-ready ``jax.jit`` kwargs carrying the measured step
    compiler options — the ONE place the option dict becomes jit
    kwargs, shared by the single-device Solver and the dp/local-SGD
    step builders.

    (An earlier draft routed through the AOT lower→compile path; AOT
    ``Compiled.__call__`` dispatches in Python, slower than jit's C++
    fast path — jit's own ``compiler_options`` kwarg keeps the fast
    dispatch.)"""
    opts = _step_compiler_options()
    return {"compiler_options": opts} if opts else {}


def make_grad_fn(net: XLANet) -> Callable:
    """``grad_fn(params, state, batch, rng) -> (grads, new_state, metrics)``."""

    def grad_fn(params, state, batch, rng):
        def loss_fn(p):
            blobs, new_state = net.apply(p, state, batch, train=True, rng=rng)
            loss, metrics = net.loss_and_metrics(blobs)
            return loss, (new_state, metrics)

        grads, (new_state, metrics) = jax.grad(loss_fn, has_aux=True)(params)
        return grads, new_state, metrics

    return grad_fn


def accumulate_grads(grad_fn, params, state, micro_stack, rng):
    """Caffe ``iter_size`` gradient accumulation: ``lax.scan`` over the
    leading micro-batch axis, mean of grads and metrics.  Shared by the
    single-device step and the local-SGD round so the semantics cannot
    diverge."""

    def body(carry, micro):
        st, i = carry
        g, st2, m = grad_fn(params, st, micro, jax.random.fold_in(rng, i))
        return (st2, i + 1), (g, m)

    (new_state, _), (gstack, mstack) = jax.lax.scan(body, (state, 0), micro_stack)
    mean0 = lambda t: jax.tree_util.tree_map(lambda x: jnp.mean(x, 0), t)
    return mean0(gstack), new_state, mean0(mstack)


def make_train_step(
    net: XLANet, sp: caffe_pb.SolverParameter, batch_transform=None
) -> Callable:
    """Returns jittable
    ``train_step(params, state, opt_state, batch, it, rng)
       -> (params, state, opt_state, metrics)``.

    ``batch`` may carry a leading micro-batch axis of size
    ``sp.iter_size``: Caffe's gradient accumulation is then a
    ``lax.scan`` over micro-batches inside the same XLA program.

    ``batch_transform`` (e.g. ``Transformer.device_fn()``) runs on the
    batch inside the jitted program before the net sees it — device-side
    augmentation that XLA fuses with the step instead of host python.
    """
    grad_fn = make_grad_fn(net)

    def train_step(params, state, opt_state, batch, it, rng):
        if batch_transform is not None:
            batch = (
                jax.vmap(batch_transform)(batch)
                if sp.iter_size > 1 else batch_transform(batch)
            )
        if sp.iter_size > 1:
            grads, new_state, metrics = accumulate_grads(
                grad_fn, params, state, batch, rng
            )
        else:
            grads, new_state, metrics = grad_fn(params, state, batch, rng)
        specs = net.param_specs()
        lr_m, dec_m = mults_for_params(params, specs)
        update = make_update_fn(sp, lr_m, dec_m)
        with profiling.scope("optimizer"):
            params, opt_state = update(params, grads, opt_state, it)
        return params, new_state, opt_state, metrics

    return train_step


def make_eval_step(net: XLANet) -> Callable:
    def eval_step(params, state, batch):
        blobs, _ = net.apply(params, state, batch, train=False, rng=None)
        _, metrics = net.loss_and_metrics(blobs)
        return metrics

    return eval_step


class Solver:
    """Owns params/state/opt_state and drives jitted steps.

    ``batch_fn`` supplies training batches (dict blob->array);
    ``test_batch_fn`` likewise for the TEST phase net.
    """

    def __init__(
        self,
        solver: caffe_pb.SolverParameter,
        input_shapes: Dict[str, Tuple[int, ...]],
        test_input_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
        net_param: Optional[caffe_pb.NetParameter] = None,
        solver_dir: str = ".",
        compute_dtype: Any = None,
        seed: int = 0,
        model: Any = None,
        remat: bool = False,
        batch_transform: Optional[Callable] = None,
    ):
        """``model``: any object satisfying the net protocol
        (``init/apply/loss_and_metrics/param_specs/input_names/
        blob_shapes``) — e.g. :class:`sparknet_tpu.models.bert.BertMLM` —
        used for both phases in place of a prototxt-compiled XLANet.
        With ``model``, ``compute_dtype`` (if given) overrides the
        model's own; ``net_param``/``test_input_shapes`` don't apply and
        are rejected so a caller can't believe they took effect.
        """
        self.sp = solver
        # device-side augmentation hook, train phase only (TEST center
        # crop is cheap on host and the eval cadence is rare)
        self.batch_transform = batch_transform
        if model is not None:
            if net_param is not None or test_input_shapes is not None:
                raise ValueError(
                    "Solver(model=...) is exclusive with net_param/"
                    "test_input_shapes — the model defines its own net"
                )
            if compute_dtype is not None:
                model.compute_dtype = compute_dtype
            self.net_param = getattr(model, "net_param", None)
            self.train_net = self.test_net = model
            self._finish_init(solver, seed)
            return
        compute_dtype = jnp.float32 if compute_dtype is None else compute_dtype
        if net_param is None:
            if solver.net_param is not None:
                net_param = solver.net_param
            else:
                net_path = solver.net or solver.train_net
                if net_path is None:
                    raise ValueError(
                        "solver specifies no net (no net/train_net path, no "
                        "inline net_param, and none passed to Solver)"
                    )
                net_param = caffe_pb.load_net(
                    resolve_model_path(net_path, solver_dir)
                )
        self.net_param = net_param
        # remat applies to the train net only: eval keeps no backward
        self.train_net = XLANet(
            net_param, "TRAIN", input_shapes, compute_dtype, remat=remat
        )
        self.test_net = XLANet(
            net_param, "TEST", test_input_shapes or input_shapes, compute_dtype
        )
        self._finish_init(solver, seed)

    def _finish_init(self, solver: caffe_pb.SolverParameter, seed: int) -> None:
        seed = solver.random_seed if solver.random_seed >= 0 else seed
        self.rng = jax.random.PRNGKey(seed)
        self.rng, init_rng = jax.random.split(self.rng)
        self.params, self.state = self.train_net.init(init_rng)
        self.opt_state = init_opt_state(solver, self.params)
        self.iter = 0
        # solverstate on-disk format; apps override from --snapshot-format
        from .snapshot import NPZ_SUFFIX

        self.snapshot_suffix = NPZ_SUFFIX
        # environment facts that affect the data/RNG stream (e.g. which
        # loader feeds training); saved into the solverstate so a resume
        # in a changed environment warns instead of silently switching
        # shuffle/augmentation streams
        self.env_meta: Dict[str, Any] = {}
        # cooperative stop for preemption handling: step() returns at
        # the next iteration boundary once set (see apps' train_loop)
        self.stop_requested = False
        # supervision plumbing: register as the process's progress
        # source (one weakref store — the step path is untouched) so a
        # crash handler (multihost._die, the apps' crash-record path)
        # can name the last completed iteration without parsing
        # snapshots
        from ..supervise import records

        records.publish_progress(self)
        # the newest step's metrics, readable through the telemetry
        # registry (source "train_step"); floats are made on read only
        from ..telemetry.registry import REGISTRY, LastStep

        self.last_step = LastStep()
        REGISTRY.register_source("train_step", self.last_step)
        # per-iteration phase attribution (telemetry/timeline.py): the
        # apps swap in an enabled Timeline under --trace /
        # SPARKNET_TIMELINE=1; the default NULL costs one falsy test
        # per phase boundary.  Not through the setter: building a second
        # solver must not take the current timeline from the first
        self._timeline = _timeline.NULL
        # average_loss display smoothing; deque(maxlen) evicts itself
        self._loss_window = deque(maxlen=max(1, solver.average_loss))
        # ONE compiled program per iteration: the train step plus the
        # host's per-iteration work (rng split, counter increment), so
        # an iteration is one dispatch and nothing crosses from the
        # host but the batch.  The traced function's name is the XLA
        # module's (``jit_fused``): the benchmark's trace reduction and
        # the persistent compile cache both key on it.  The compiler
        # options are read here, once.
        kw = step_compile_kw()
        train_step = make_train_step(
            self.train_net, solver, self.batch_transform
        )

        def fused(params, state, opt_state, batch, it, rng):
            with profiling.scope("rng"):
                rng, step_rng = jax.random.split(rng)
            params, state, opt_state, metrics = train_step(
                params, state, opt_state, batch, it, step_rng
            )
            return params, state, opt_state, it + 1, rng, metrics

        self._step_program = jax.jit(
            fused, donate_argnums=(0, 1, 2, 4, 5), **kw
        )
        self._eval_step = jax.jit(make_eval_step(self.test_net), **kw)
        # the iteration counter as the program carries it on the device;
        # None until the first dispatch and after a restore
        self._it_dev = None
        # what lower_step last made, and its scope table once asked for
        # (step_scopes); a caller without a solver means the newest one's
        self._lowered = None
        self._scopes = None
        profiling.publish_step_source(self)

    @property
    def timeline(self):
        return self._timeline

    @timeline.setter
    def timeline(self, tl) -> None:
        """Assigning a timeline also makes it the process's current one,
        so call sites without a solver (``multihost.put_global``, the
        feed's staging thread) report to the loop that is stepping."""
        self._timeline = tl
        _timeline.set_current(tl)

    def step(self, batches: Iterator[Dict[str, Any]], n: int = 1, log_fn=None):
        """Run ``n`` iterations (the reference's ``Solver::Step(n)``).

        Displayed losses honour Caffe's ``average_loss``: the value
        handed to ``log_fn`` is smoothed over the last N iterations
        (device arrays are held lazily; the float() sync happens only
        at display boundaries)."""
        metrics = {}
        tl = self.timeline
        for _ in range(n):
            if self.stop_requested:
                break
            # phase boundaries (telemetry/timeline.py): host blocked on
            # the feed -> placement/global assembly -> the compiled
            # step.  With the NULL timeline each bracket is a no-op
            # context manager; an enabled one accumulates exclusive
            # per-phase time and (fence=True) block_until_ready-fences
            # the step so async dispatch can't smear compute into the
            # next iteration's input_wait.
            with tl.phase("input_wait"):
                if self.sp.iter_size > 1:
                    micro = [next(batches) for _ in range(self.sp.iter_size)]
                    batch = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *micro
                    )
                else:
                    batch = next(batches)
            with tl.phase("device_put"):
                batch = self._put_batch(batch)
            with tl.phase("compiled_step"):
                metrics = self._dispatch(batch)
                if tl.fence:
                    jax.block_until_ready(metrics)
            self.last_step.metrics = metrics
            self.iter += 1
            if log_fn and self.sp.display:
                self._push_loss(metrics)
                if self.iter % self.sp.display == 0:
                    log_fn(self.iter, self._smoothed(metrics))
        return metrics

    def _dispatch(self, batch):
        """Advance the solver by one iteration on a placed ``batch``
        and return its metrics, not waited for: the one place that
        replaces ``params/state/opt_state/rng`` and the device's
        counter.  ParallelSolver's sync mode overrides it with its
        mesh program."""
        if self._it_dev is None:
            self._it_dev = jnp.asarray(self.iter, jnp.int32)
        (
            self.params, self.state, self.opt_state,
            self._it_dev, self.rng, metrics,
        ) = self._step_program(
            self.params, self.state, self.opt_state,
            batch, self._it_dev, self.rng,
        )
        return metrics

    def lower_step(self, batch):
        """``jax.stages.Lowered`` of the program :meth:`step` dispatches
        for ``batch`` — what a caller reads (``.as_text()``) to check
        which kernels the compiled step holds, e.g. that attention
        lowered to the Pallas ``tpu_custom_call`` and not to the
        reference path.  The newest one is kept: it is the program
        :meth:`step_scopes` speaks of."""
        self._lowered = self._step_program.lower(
            self.params, self.state, self.opt_state,
            self._put_batch(batch), jnp.asarray(self.iter, jnp.int32),
            self.rng,
        )
        return self._lowered

    def step_scopes(self):
        """``{instruction name: profiling.Scoped}`` of the step program
        :meth:`lower_step` last lowered: which of the program's own
        scopes (``profiling.scope``) and which pass each instruction of
        the *compiled* step belongs to, for a device trace's operations
        to be summed by (``profiling.by_scope``).  Compiles the kept
        ``Lowered`` (it keeps its executable: a second ``compile()`` is
        free, a first one finds the step in the compile cache) and is
        memoised for it.  None before any ``lower_step``."""
        lowered = self._lowered
        if lowered is None:
            return None
        if self._scopes is None or self._scopes[0] is not lowered:
            self._scopes = (lowered, profiling.scope_table(
                lowered.compile().as_text(), profiling.declared_scopes()
            ))
        return self._scopes[1]

    def _push_loss(self, metrics) -> None:
        """Record this iteration's loss for ``average_loss`` smoothing
        (device array held lazily; synced only at display time)."""
        if self._loss_window.maxlen > 1 and "loss" in metrics:
            self._loss_window.append(metrics["loss"])

    def _smoothed(self, metrics) -> Dict[str, float]:
        """Metrics as floats, with ``loss`` averaged over the window.
        Window entries are converted to host floats on first read and
        cached, so repeated displays don't re-fetch old scalars."""
        out = {k: float(v) for k, v in metrics.items()}
        if self._loss_window:
            for i, x in enumerate(self._loss_window):
                if not isinstance(x, float):
                    self._loss_window[i] = float(x)
            out["loss"] = sum(self._loss_window) / len(self._loss_window)
        return out

    # -- snapshot / restore (Caffe .solverstate parity) ------------------
    def save(self, path: str) -> None:
        """Full solver state: params + net state (BN stats) + optimizer
        slots + iteration + PRNG key — enough to resume bit-identically
        (Caffe's ``.solverstate``, SURVEY.md §5)."""
        from . import snapshot

        snapshot.save_state(path, **self._snapshot_trees())

    def save_or_skip(self, path: str, prefix: str = "") -> bool:
        """:meth:`save` with the disk-full degradation policy
        (:func:`snapshot.save_state_or_skip`): on ENOSPC prune the
        chain one deeper and retry once, else skip with a counter and
        keep training.  Returns True when the snapshot landed."""
        from . import snapshot

        return snapshot.save_state_or_skip(
            path, prefix=prefix, **self._snapshot_trees()
        )

    def _snapshot_trees(self) -> dict:
        return dict(
            params=self.params,
            state=self.state,
            opt_state=self.opt_state,
            it=self.iter,
            rng=self.rng,
            env=dict(self.env_meta),
        )

    def restore(self, path: str, feed=None, weights_only: bool = False) -> None:
        """Load a ``.solverstate.npz``; with ``feed`` given, also align
        the data stream (see :meth:`align_feed`).

        ``weights_only`` (the supervisor's elastic resume,
        ``SPARKNET_ELASTIC_RESUME=1``): restore params/net state/
        iteration/PRNG but re-initialize the optimizer slots — the
        snapshot's slots may be sharded for a dp width the degraded
        relaunch no longer has.  τ-local SGD averaging permits the
        width change by construction; losing optimizer history costs a
        few iterations of momentum re-warmup (documented tradeoff,
        docs/MULTIHOST.md)."""
        from . import snapshot

        st = snapshot.load_state(path)
        saved_env = st.get("env") or {}
        # the full saved env, for drift hooks that need sibling keys
        # (the parallel solver reads the snapshot's per-leaf specs when
        # wording its relayout warning)
        self._restored_env = saved_env
        for key, saved in saved_env.items():
            cur = self.env_meta.get(key)
            if cur is not None and cur != saved and jax.process_index() == 0:
                msg = self._env_drift_message(key, saved, cur)
                if msg:
                    print(f"WARNING: {msg}", file=sys.stderr, flush=True)
        self.iter = int(st["it"])
        self._it_dev = None  # the device's counter follows self.iter
        self.rng = jnp.asarray(st["rng"])
        self._loss_window.clear()  # a restarted Caffe starts empty
        if weights_only:
            self.params, self.state, _ = self._place_restored(
                st["params"], st["state"], {}
            )
            self.opt_state = self._reinit_opt_state()
        else:
            self.params, self.state, self.opt_state = self._place_restored(
                st["params"], st["state"], st["opt_state"]
            )
        if feed is not None:
            self.align_feed(feed)

    def load_weights(self, path: str) -> None:
        """Caffe's ``--weights`` finetuning path: overlay each listed
        artifact's blobs (comma-separated like the caffe binary; later
        files win on overlap) onto the initialised params/state;
        optimizer state is untouched.  Accepts ``.caffemodel`` weight
        files or full ``.solverstate.npz``/``.orbax`` snapshots — the
        latter are manifest-verified and contribute only their params +
        net state (BN stats) while iteration/optimizer/PRNG stay fresh
        (the deploy trainer's first generation starts FROM the serving
        baseline this way)."""
        from ..proto import caffemodel as cm
        from . import snapshot

        p = jax.device_get(self.params)
        s = jax.device_get(self.state)
        for one in path.split(","):
            one = one.strip()
            if one.endswith((snapshot.NPZ_SUFFIX, snapshot.ORBAX_SUFFIX)):
                loaded = snapshot.load_state(one)
                imported, st = loaded["params"], loaded.get("state") or {}
            else:
                imported, st = cm.import_caffemodel(one, self.train_net)
            p = cm.merge_into(p, imported)
            s = cm.merge_into(s, st)
        # opt_state untouched: it may be non-addressable (multi-host
        # local mode), and finetuning starts with fresh optimizer slots
        self.params, self.state, _ = self._place_restored(p, s, {})

    def export_weights(self, path: str) -> None:
        """Write current weights as a binary ``.caffemodel``."""
        from ..proto import caffemodel as cm

        cm.export_caffemodel(
            path, self.train_net, jax.device_get(self.params),
            jax.device_get(self.state),
        )

    def align_feed(self, feed) -> None:
        """Advance a deterministic (seeded) feed past the batches a
        restored run already consumed, so resume is bit-identical to the
        uninterrupted run. (Caffe restarts its DB cursor on resume; a
        seeded ShardedDataset feed lets us do better.)  Feeds exposing a
        ``skip(n)`` method get an O(1) fast-forward; plain generators
        replay (and pay for) the skipped host preprocessing."""
        n = self.iter * max(1, self.sp.iter_size)
        skip = getattr(feed, "skip", None)
        if skip is not None:
            skip(n)
        else:
            for _ in range(n):
                next(feed)

    def _env_drift_message(self, key: str, saved, cur) -> str:
        """One warning line for an env_meta key that differs between
        the snapshot and this run; subclasses override per key (the
        parallel solver turns layout drift into a relayout notice).
        Return "" to suppress."""
        return (
            f"resuming a run snapshotted with {key}={saved!r} in an "
            f"environment where {key}={cur!r} — the shuffle/"
            f"augmentation stream will differ from the uninterrupted run"
        )

    def _place_restored(self, params, state, opt_state):
        """Device placement for restored host trees; ParallelSolver
        overrides to re-apply mesh shardings."""
        to_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        return to_dev(params), to_dev(state), to_dev(opt_state)

    def _reinit_opt_state(self):
        """Fresh optimizer slots for the current params — the elastic
        weights-only resume path; ParallelSolver overrides to rebuild
        its mode's slot layout/sharding."""
        return init_opt_state(self.sp, self.params)

    def _put_batch(self, batch, train: bool = True):
        """Placement hook for one iteration's host batch; the base
        solver lets jit place it.  ParallelSolver overrides (mesh
        shardings, multi-host global assembly)."""
        return batch

    def test(self, batches: Iterator[Dict[str, Any]], test_iter: Optional[int] = None):
        """Caffe's TEST phase: ``test_iter`` eval batches, averaged.

        Accumulates the metric sums as device arrays — each iteration
        only ENQUEUES an eval step and an add, so host preprocessing of
        batch i+1 overlaps device eval of batch i — and materialises the
        floats once after the loop (a per-batch ``float(v)`` would fence
        the device every iteration and serialise the whole eval)."""
        n = test_iter or (self.sp.test_iter[0] if self.sp.test_iter else 1)
        acc: Dict[str, Any] = {}
        for _ in range(n):
            batch = self._put_batch(next(batches), train=False)
            m = self._eval_step(self.params, self.state, batch)
            for k, v in m.items():
                acc[k] = v if k not in acc else acc[k] + v
        return {k: float(v) / n for k, v in acc.items()}
