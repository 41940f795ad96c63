"""τ-local SGD with periodic parameter averaging — SparkNet's algorithm.

The reference's central contribution (SparkNet paper, arXiv:1511.06051;
SURVEY.md §1 "core algorithm"; mount empty, no file:line): each worker
runs τ *independent* SGD steps on its own data shard, then the driver
averages the weights — trading gradient staleness for a τ× reduction in
communication rounds.  There, one round is JNI weight copy -> Spark
treeReduce over TCP -> broadcast.  Here a round is at most TWO compiled
XLA programs under ``shard_map``: each device runs its τ steps as a
``lax.scan`` (no host involvement between steps), then the round-end
weight average runs through :mod:`.comm` — bucketed, optionally
compressed (bf16/int8 + error feedback), and dispatched as its own
program so the timeline can attribute the *exposed* reduction time to
the ``grad_allreduce`` phase (``SPARKNET_COMM=monolithic`` restores the
old single-program round with one fused ``lax.pmean``, the A/B
baseline).  Per-worker solver state (momentum etc.) persists across
rounds without averaging, matching the reference where each executor
keeps its native Caffe solver alive between syncs.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nets.xlanet import XLANet
from ..proto.caffe_pb import SolverParameter
from ..solver.caffe_solver import init_opt_state, make_update_fn, mults_for_params
from ..solver.trainer import accumulate_grads, make_grad_fn, step_compile_kw
from . import comm
from .mesh import DP_AXIS

# opt-state key carrying the error-feedback residual stack (leading
# worker axis, like the solver slots); present only when --grad-compress
# is lossy, so lossless opt state stays bit-compatible with pre-comm
# snapshots
RESIDUAL_KEY = "comm_residual"


def init_local_opt_state(sp: SolverParameter, params: Any, num_workers: int):
    """Per-worker solver state: leading axis = dp mesh size (each worker's
    momentum lives on its own device, like each executor's native solver)."""
    single = init_opt_state(sp, params)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (num_workers,) + x.shape), single
    )


def init_local_residual(params: Any, num_workers: int):
    """Per-worker error-feedback residuals (each worker quantizes its
    own delta, so each carries its own error), zeros at start."""
    single = comm.init_residual(params)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (num_workers,) + x.shape), single
    )


def _scan_tau_steps(net, sp, tau, dp_axis):
    """The shared per-worker τ-step scan body: params/state arrive
    replicated, diverge locally; returns the un-averaged end-of-round
    worker values plus τ-mean metrics (pmean'd)."""
    grad_fn = make_grad_fn(net)
    specs = net.param_specs()

    def scan(params, state, opt_state, batches, it, rng):
        # params/state arrive replicated but immediately diverge per
        # worker (local updates): mark them device-varying for shard_map's
        # replication typing so the scan carry has a stable type.
        params = comm.pcast_varying(params, dp_axis)
        state = comm.pcast_varying(state, dp_axis)
        # inside shard_map: opt_state leading worker-axis is local size 1
        opt_local = jax.tree_util.tree_map(lambda x: x[0], opt_state)
        lr_m, dec_m = mults_for_params(params, specs)
        update = make_update_fn(sp, lr_m, dec_m)
        widx = lax.axis_index(dp_axis)
        wrng = jax.random.fold_in(rng, widx)

        def grads_of(p, st, micro, step_rng):
            """One iteration's gradient; Caffe iter_size accumulation
            when the extra micro-batch axis is present."""
            if sp.iter_size > 1:
                return accumulate_grads(grad_fn, p, st, micro, step_rng)
            return grad_fn(p, st, micro, step_rng)

        def body(carry, micro):
            p, st, opt, i = carry
            g, st2, metrics = grads_of(p, st, micro, jax.random.fold_in(wrng, i))
            p2, opt2 = update(p, g, opt, it + i)
            return (p2, st2, opt2, i + 1), metrics

        (p, st, opt_local, _), mstack = lax.scan(
            body, (params, state, opt_local, 0), batches, length=tau
        )
        metrics = lax.pmean(
            jax.tree_util.tree_map(lambda m: jnp.mean(m, 0), mstack), dp_axis
        )
        return p, st, opt_local, metrics

    return scan


def _batch_spec(sp: SolverParameter, dp_axis: str):
    return P(None, None, dp_axis) if sp.iter_size > 1 else P(None, dp_axis)


def make_local_sgd_round(
    net: XLANet,
    sp: SolverParameter,
    mesh: Mesh,
    tau: int,
    dp_axis: str = DP_AXIS,
    donate: bool = True,
) -> Callable:
    """The MONOLITHIC single-dispatch round (the pre-comm baseline and
    the ``SPARKNET_COMM=monolithic`` A/B arm):

    ``round(params, state, opt_state, batches, it, rng)
        -> (params, state, opt_state, metrics)``

    - ``params``/``state``: replicated in, replicated (averaged) out —
      like the reference, worker nets are averaged wholesale at sync
      (state, e.g. BN running stats, is averaged alongside weights).
    - ``opt_state``: from :func:`init_local_opt_state` — leading axis is
      the worker axis, sharded over ``dp``; persists un-averaged.
    - ``batches``: pytree with leaves shaped ``[tau, global_bs, ...]``
      (or ``[tau, iter_size, global_bs, ...]`` when ``sp.iter_size > 1``);
      the global batch axis is sharded over ``dp`` so each worker scans
      over its own ``[tau, local_bs, ...]`` shard.
    - ``it``: int32 global iteration at round start (advances by tau).
    """
    scan = _scan_tau_steps(net, sp, tau, dp_axis)

    def per_worker(params, state, opt_state, batches, it, rng):
        p, st, opt_local, metrics = scan(
            params, state, opt_state, batches, it, rng
        )
        # SparkNet's sync: elementwise average of worker weights — one
        # ICI all-reduce instead of a driver TCP round-trip.
        p = lax.pmean(p, dp_axis)
        st = lax.pmean(st, dp_axis)  # BN running stats etc.
        opt_out = jax.tree_util.tree_map(lambda x: x[None], opt_local)
        return p, st, opt_out, metrics

    fn = jax.shard_map(
        per_worker,
        mesh=mesh,
        in_specs=(P(), P(), P(dp_axis), _batch_spec(sp, dp_axis), P(), P()),
        out_specs=(P(), P(), P(dp_axis), P()),
        check_vma=False,
    )
    return jax.jit(
        fn, donate_argnums=(0, 1, 2) if donate else (), **step_compile_kw()
    )


def make_local_scan(
    net: XLANet,
    sp: SolverParameter,
    mesh: Mesh,
    tau: int,
    dp_axis: str = DP_AXIS,
    donate: bool = True,
) -> Callable:
    """The bucketed round's FIRST program: the τ-step scan only, no
    averaging.

    ``scan(params, state, opt_state, batches, it, rng) ->
        (params, p_stack, st_stack, opt_state, metrics)``

    ``p_stack``/``st_stack`` carry each worker's un-averaged end-of-
    round values (leading worker axis, dp-sharded, same layout as
    ``opt_state``); ``params`` passes the round-start weights through
    untouched — the reduce program's reference point for compressed
    delta reduction (and a live buffer: the inputs are donated)."""
    scan = _scan_tau_steps(net, sp, tau, dp_axis)

    def per_worker(params, state, opt_state, batches, it, rng):
        p, st, opt_local, metrics = scan(
            params, state, opt_state, batches, it, rng
        )
        lift = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return params, lift(p), lift(st), lift(opt_local), metrics

    fn = jax.shard_map(
        per_worker,
        mesh=mesh,
        in_specs=(P(), P(), P(dp_axis), _batch_spec(sp, dp_axis), P(), P()),
        out_specs=(P(), P(dp_axis), P(dp_axis), P(dp_axis), P()),
        check_vma=False,
    )
    return jax.jit(
        fn, donate_argnums=(0, 1, 2) if donate else (), **step_compile_kw()
    )


def make_round_reduce(
    mesh: Mesh,
    config: comm.CommConfig,
    dp_axis: str = DP_AXIS,
    donate: bool = True,
) -> Callable:
    """The bucketed round's SECOND program: SparkNet's weight average
    through the comm layer.

    ``reduce(p_start, p_stack, st_stack, residual_stack) ->
        (params, state, residual_stack)``

    Lossless (``compress="none"``): bucketed ``pmean`` of the worker
    weights directly — bitwise-identical to the monolithic round's
    average (tests/test_comm.py pins it).  Lossy (bf16/int8): each
    worker reduces its round DELTA (``p_end - p_start``) with error
    feedback — the residual rides ``opt_state["comm_residual"]`` and
    re-injects this round's quantization error into the next round.
    Tau-independent: one compile serves every round length."""
    ndp = mesh.shape[dp_axis]

    def per_worker(p_start, p_stack, st_stack, residual):
        drop = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
        lift = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        p_end, st_end = drop(p_stack), drop(st_stack)
        st, _ = comm.reduce_bucketed(
            st_end, dp_axis, ndp, comm.CommConfig(bucket_mb=config.bucket_mb)
        )
        if not config.wants_residual:
            p, _ = comm.reduce_bucketed(p_end, dp_axis, ndp, config)
            return p, st, residual
        delta = jax.tree_util.tree_map(lambda e, s: e - s, p_end, p_start)
        red, new_res = comm.reduce_bucketed(
            delta, dp_axis, ndp, config, residual=drop(residual)
        )
        p = jax.tree_util.tree_map(lambda s, d: s + d, p_start, red)
        return p, st, lift(new_res)

    fn = jax.shard_map(
        per_worker,
        mesh=mesh,
        in_specs=(P(), P(dp_axis), P(dp_axis), P(dp_axis)),
        out_specs=(P(), P(), P(dp_axis)),
        check_vma=False,
    )
    return jax.jit(
        fn, donate_argnums=(0, 1, 2, 3) if donate else (), **step_compile_kw()
    )


# --------------------------------------------------------------------------
# host-side round batch staging
# --------------------------------------------------------------------------

def stack_round_batches(batch_list, buffer: Optional["RoundBuffer"] = None):
    """Stack tau host batches into the ``[tau, global_bs, ...]`` layout.

    Stacks on the host (numpy): the caller's device_put then shards the
    result straight onto the mesh, instead of committing the full round
    batch to device 0 first and re-transferring.  With a
    :class:`RoundBuffer` the destination is a preallocated rotating
    buffer instead of a fresh ``np.stack`` allocation per round."""
    if buffer is not None:
        out = buffer.stack(batch_list)
        if out is not None:
            return out
    import numpy as np

    return jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *batch_list
    )


class RoundBuffer:
    """Preallocated host staging for :func:`stack_round_batches`.

    ``np.stack`` allocates (and the allocator churns) a fresh
    ``[tau, ...]`` round batch every round; this keeps a small rotation
    of destination buffers per ``(key, n, shape, dtype)`` and copies
    into the next one.  Depth 3: a buffer is only rewritten three
    rounds later, past any plausible async-dispatch runahead — round
    N+1's program consumes round N's output params, so device execution
    serializes per round and the host can run at most the dispatch
    queue ahead (the CPU backend may alias a host buffer zero-copy,
    which is why "reuse immediately" would be wrong).

    Saved allocations are counted in the telemetry registry
    (``round_buffer{event=reuse|alloc}``) and surface through
    ``PipelineMetrics`` snapshots."""

    DEPTH = 3

    def __init__(self):
        self._bufs: Dict[tuple, list] = {}
        self._next: Dict[tuple, int] = {}

    def stack(self, batch_list):
        import numpy as np

        first = batch_list[0]
        if not isinstance(first, dict) or not all(
            isinstance(b, dict) and b.keys() == first.keys()
            for b in batch_list
        ):
            return None  # exotic pytree: fall back to np.stack
        from ..telemetry import REGISTRY

        out = {}
        n = len(batch_list)
        for k in first:
            rows = [np.asarray(b[k]) for b in batch_list]
            key = (k, n, rows[0].shape, rows[0].dtype.str)
            ring = self._bufs.get(key)
            if ring is None:
                ring = self._bufs[key] = []
            slot = self._next.get(key, 0)
            if len(ring) < self.DEPTH:
                ring.append(
                    np.empty((n,) + rows[0].shape, dtype=rows[0].dtype)
                )
                buf = ring[-1]
                self._next[key] = len(ring) % self.DEPTH
                REGISTRY.counter("round_buffer", event="alloc").inc()
            else:
                buf = ring[slot]
                self._next[key] = (slot + 1) % self.DEPTH
                REGISTRY.counter("round_buffer", event="reuse").inc()
            for t, r in enumerate(rows):
                if r.shape != rows[0].shape or r.dtype != rows[0].dtype:
                    return None  # ragged round: let np.stack raise/handle
                buf[t] = r
            out[k] = buf
        return out


def round_batch_sharding(
    mesh: Mesh, dp_axis: str = DP_AXIS, iter_size: int = 1
) -> NamedSharding:
    if iter_size > 1:
        return NamedSharding(mesh, P(None, None, dp_axis))
    return NamedSharding(mesh, P(None, dp_axis))
