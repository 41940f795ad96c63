"""Synchronous data parallelism: in-step gradient all-reduce over ICI.

The reference's sync loop is driver-mediated: broadcast weights, run one
round on each executor, ship every weight array back over TCP, sum and
average on the driver JVM (SURVEY.md §1-3; mount empty, no file:line).
The TPU-native replacement keeps params *resident and replicated* on
the chips and shards only the batch.  Two compiled forms:

- **implicit** (the default): under ``jit`` with ``NamedSharding``,
  computing the mean loss over the globally-sharded batch makes XLA
  insert a single fused ``all-reduce`` over the gradients on the ICI
  mesh — the entire driver round-trip collapses into one on-fabric
  collective inside the compiled step.
- **bucketed** (``SPARKNET_COMM=bucketed``, or any ``--grad-compress``):
  an explicit ``shard_map`` program that routes the reduction through
  :mod:`.comm` — size-bounded buckets issued *inside the backward
  pass* (``custom_vjp``; each bucket's ``pmean`` enters the program
  the moment its layers' gradients exist, so XLA can overlap it with
  the remaining backward work), optionally compressed to bf16/int8
  with per-worker error-feedback residuals carried in opt state.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nets.xlanet import XLANet
from ..proto.caffe_pb import SolverParameter
from ..solver.caffe_solver import (
    make_update_fn,
    mults_for_params,
    opt_state_keys,
)
from ..solver.trainer import (
    accumulate_grads,
    make_eval_step,
    make_grad_fn,
    make_train_step,
    step_compile_kw,
)
from . import comm
from .local_sgd import RESIDUAL_KEY
from .mesh import DP_AXIS, batch_sharding, replicated


def make_dp_train_step(
    net: XLANet,
    sp: SolverParameter,
    mesh: Mesh,
    dp_axis: str = DP_AXIS,
    donate: bool = True,
    config: Optional[comm.CommConfig] = None,
) -> Callable:
    """Jit the train step with mesh shardings; ``config`` (a
    :class:`~sparknet_tpu.parallel.comm.CommConfig`) picks the implicit
    or the bucketed program — see the module docstring.

    Implicit form: params/state/opt_state replicated; batch sharded on
    its leading axis over ``dp_axis``.  Gradients of replicated params
    w.r.t. a sharded batch are partial per shard — XLA closes the
    replication by inserting the psum; this is the idiomatic "annotate
    and let XLA place the collective" recipe rather than a hand-written
    reduce.
    """
    config = config or comm.CommConfig()
    if config.for_sync() == "bucketed":
        return make_bucketed_dp_train_step(
            net, sp, mesh, config, dp_axis, donate
        )
    from . import partition

    repl = replicated(mesh)
    if sp.iter_size > 1:
        # gradient accumulation stacks micro-batches on a leading axis
        # (solver/trainer.py): the batch axis to shard is then axis 1.
        bsh = NamedSharding(mesh, P(None, dp_axis))
    else:
        bsh = batch_sharding(mesh, dp_axis)
    # pure dp is the empty rule table: params/state/opt replicated,
    # batch dp-sharded — compiled through the SAME jit wrapper as every
    # rule-table layout (parallel/partition.py), so sync-DP and the
    # unified path cannot drift
    return partition.jit_sharded_step(
        make_train_step(net, sp),
        in_shardings=(repl, repl, repl, bsh, repl, repl),
        out_shardings=(repl, repl, repl, repl),
        donate_argnums=(0, 1, 2) if donate else (),
    )


def make_bucketed_dp_train_step(
    net: XLANet,
    sp: SolverParameter,
    mesh: Mesh,
    config: comm.CommConfig,
    dp_axis: str = DP_AXIS,
    donate: bool = True,
) -> Callable:
    """The explicit comm-layer sync step (same signature/contract as
    the implicit one; ``opt_state`` additionally carries the
    ``comm_residual`` stack — leading worker axis, dp-sharded — when
    compression is lossy).

    Lossless + no accumulation: the reduction rides the backward pass
    (:func:`comm.overlap_reduce_on_backward`) for overlap.  Lossy (or
    ``iter_size > 1``, where in-backward reduction would fire per
    micro-batch): gradients reduce post-backward through
    :func:`comm.reduce_bucketed` with error feedback.  Dropout streams
    are decorrelated per worker (rng folded with the worker index) —
    statistically equivalent to, but not bitwise-comparable with, the
    implicit program's partitioned global mask.
    """
    grad_fn = make_grad_fn(net)
    ndp = mesh.shape[dp_axis]
    overlap = config.compress == "none" and sp.iter_size == 1
    state_cfg = comm.CommConfig(bucket_mb=config.bucket_mb)

    def per_worker(params, state, opt_state, batch, it, rng):
        widx = lax.axis_index(dp_axis)
        wrng = jax.random.fold_in(rng, widx)
        opt_solver = {
            k: v for k, v in opt_state.items() if k != RESIDUAL_KEY
        }
        new_resid = None
        if overlap:
            def loss_fn(p):
                # each bucket's pmean is emitted by ITS cotangent rule,
                # mid-backward — the overlap point of the whole module
                p = comm.overlap_reduce_on_backward(p, dp_axis, config)
                blobs, new_state = net.apply(
                    p, state, batch, train=True, rng=wrng
                )
                loss, metrics = net.loss_and_metrics(blobs)
                return loss, (new_state, metrics)

            grads, (new_state, metrics) = jax.grad(
                loss_fn, has_aux=True
            )(params)
        else:
            if sp.iter_size > 1:
                grads, new_state, metrics = accumulate_grads(
                    grad_fn, params, state, batch, wrng
                )
            else:
                grads, new_state, metrics = grad_fn(
                    params, state, batch, wrng
                )
            if config.wants_residual:
                resid_local = jax.tree_util.tree_map(
                    lambda x: x[0], opt_state[RESIDUAL_KEY]
                )
                grads, nr = comm.reduce_bucketed(
                    grads, dp_axis, ndp, config, residual=resid_local
                )
                new_resid = jax.tree_util.tree_map(lambda x: x[None], nr)
            else:
                grads, _ = comm.reduce_bucketed(grads, dp_axis, ndp, config)
        specs = net.param_specs()
        lr_m, dec_m = mults_for_params(params, specs)
        update = make_update_fn(sp, lr_m, dec_m)
        # grads are reduced -> every worker applies the identical
        # update; params/opt stay replicated without a weight average
        params, opt_out = update(params, grads, opt_solver, it)
        new_state, _ = comm.reduce_bucketed(
            new_state, dp_axis, ndp, state_cfg
        )
        metrics = lax.pmean(metrics, dp_axis)
        if new_resid is not None:
            opt_out = {**opt_out, RESIDUAL_KEY: new_resid}
        return params, new_state, opt_out, metrics

    okeys = opt_state_keys(sp)
    opt_spec: Dict[str, P] = {k: P() for k in okeys}
    if config.wants_residual:
        opt_spec[RESIDUAL_KEY] = P(dp_axis)
    batch_spec = P(None, dp_axis) if sp.iter_size > 1 else P(dp_axis)
    out_opt_spec = dict(opt_spec) if config.wants_residual else {
        k: P() for k in okeys
    }
    fn = jax.shard_map(
        per_worker,
        mesh=mesh,
        in_specs=(P(), P(), opt_spec, batch_spec, P(), P()),
        out_specs=(P(), P(), out_opt_spec, P()),
        check_vma=False,
    )
    return jax.jit(
        fn, donate_argnums=(0, 1, 2) if donate else (), **step_compile_kw()
    )


def make_dp_eval_step(net: XLANet, mesh: Mesh, dp_axis: str = DP_AXIS) -> Callable:
    from . import partition

    repl = replicated(mesh)
    bsh = batch_sharding(mesh, dp_axis)
    return partition.jit_sharded_step(
        make_eval_step(net),
        in_shardings=(repl, repl, bsh),
        out_shardings=repl,
    )
