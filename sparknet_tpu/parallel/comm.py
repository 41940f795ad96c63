"""Communication-efficiency layer: bucketed collectives + compression.

SparkNet's contribution (PAPER.md) is trading gradient staleness for a
τ-fold cut in communication *rounds*; FireCaffe (PAPERS.md,
arXiv:1511.00175) attacks the cost of each round itself — reduction
trees, overlap with backward work, fewer bytes on the wire.  This
module is the one home of that second lever:

- **Bucketing.**  :func:`plan_buckets` groups a gradient/weight pytree
  into size-bounded buckets in *reverse* flatten order (output-side
  layers first — the order backward produces gradients), so the
  reduction becomes several medium-sized collectives instead of one
  monolithic all-reduce or thousands of per-leaf ones.
- **Overlap.**  :func:`overlap_reduce_on_backward` attaches each
  bucket's ``pmean`` to the *backward pass itself* (a per-bucket
  ``custom_vjp`` identity whose cotangent rule reduces): a bucket's
  all-reduce is issued the moment its layers' gradients exist, so XLA's
  scheduler can overlap it with the remaining backward work.
- **Compression.**  :func:`reduce_bucketed` optionally casts each
  bucket to bf16 or quantizes it to int8 (shared per-bucket scale from
  a ``pmax``) before the reduce, with **error-feedback residuals**: the
  quantization error is returned to the caller, persisted in opt state,
  and re-injected into the next round's payload instead of being lost.

Everything here runs *inside* the compiled step (under ``shard_map``);
the host-side knobs are ``SPARKNET_COMM`` (``bucketed``/``monolithic``),
``SPARKNET_GRAD_COMPRESS`` (``none``/``bf16``/``int8``, also the apps'
``--grad-compress``) and ``SPARKNET_COMM_BUCKET_MB``.  See
docs/COMMUNICATION.md.

"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

COMM_ENV = "SPARKNET_COMM"
COMPRESS_ENV = "SPARKNET_GRAD_COMPRESS"
BUCKET_MB_ENV = "SPARKNET_COMM_BUCKET_MB"

COMM_MODES = ("auto", "bucketed", "monolithic")
COMPRESS_MODES = ("none", "bf16", "int8")

# int8 payloads are accumulated in int16 on the wire: with the shared
# per-bucket scale each element is in [-127, 127], so up to 256 workers
# sum without overflow (a dp axis wider than that would need int32).
_INT8_ACC_DTYPE = jnp.int16
_INT8_MAX_WORKERS = 256


def pcast_varying(tree: Any, axis_name: str) -> Any:
    """Mark a replicated tree device-varying along ``axis_name`` for
    shard_map's typing."""
    return jax.tree_util.tree_map(
        lambda x: lax.pcast(x, axis_name, to="varying"), tree
    )


# --------------------------------------------------------------------------
# config
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Resolved communication settings for one solver.

    ``mode="monolithic"`` is the pre-bucketing behavior (one fused
    all-reduce of the whole tree) and the A/B baseline; ``"bucketed"``
    routes through :func:`plan_buckets`/:func:`reduce_bucketed`.
    ``"auto"`` (the default) resolves per training mode — see
    :meth:`for_local` / :meth:`for_sync`.  ``compress`` only applies to
    bucketed reductions."""

    mode: str = "auto"
    compress: str = "none"
    bucket_mb: float = 4.0

    def __post_init__(self):
        if self.mode not in COMM_MODES:
            raise ValueError(
                f"comm mode {self.mode!r} (want {'|'.join(COMM_MODES)})"
            )
        if self.compress not in COMPRESS_MODES:
            raise ValueError(
                f"grad compression {self.compress!r} "
                f"(want {'|'.join(COMPRESS_MODES)})"
            )
        if self.compress != "none" and self.mode == "monolithic":
            raise ValueError(
                "grad compression requires the bucketed comm path "
                f"({COMM_ENV}=bucketed); monolithic has no place to "
                "quantize"
            )
        if self.bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be > 0, got {self.bucket_mb}")

    @property
    def bucket_bytes(self) -> int:
        return int(self.bucket_mb * 1e6)

    def for_local(self) -> str:
        """τ-local SGD rounds default to the bucketed path: the
        lossless bucketed average is bitwise-identical to the
        monolithic one (pinned by test), so bucketing is pure upside
        there."""
        return "bucketed" if self.mode == "auto" else self.mode

    def for_sync(self) -> str:
        """Sync DP defaults to the implicit path (XLA places one fused
        all-reduce from the shardings — the long-standing behavior)
        unless compression forces the explicit bucketed program, or the
        caller asked for it."""
        if self.mode == "auto":
            return "bucketed" if self.compress != "none" else "monolithic"
        return self.mode

    @property
    def wants_residual(self) -> bool:
        """Lossy compression carries an error-feedback residual in opt
        state; ``none`` must leave the opt-state layout untouched so
        pre-change snapshots stay bit-compatible."""
        return self.compress in ("bf16", "int8")


def resolve_config(
    compress: Optional[str] = None,
    mode: Optional[str] = None,
    bucket_mb: Optional[float] = None,
) -> CommConfig:
    """Explicit args win; the environment fills the rest
    (``SPARKNET_COMM`` / ``SPARKNET_GRAD_COMPRESS`` /
    ``SPARKNET_COMM_BUCKET_MB``)."""
    mode = mode or os.environ.get(COMM_ENV, "").strip() or "auto"
    compress = compress or os.environ.get(COMPRESS_ENV, "").strip() or "none"
    if bucket_mb is None:
        raw = os.environ.get(BUCKET_MB_ENV, "").strip()
        try:
            bucket_mb = float(raw) if raw else 4.0
        except ValueError:
            raise ValueError(
                f"{BUCKET_MB_ENV} must be a float MB count, got {raw!r}"
            ) from None
    return CommConfig(mode=mode, compress=compress, bucket_mb=bucket_mb)


# --------------------------------------------------------------------------
# bucket planning
# --------------------------------------------------------------------------

def plan_buckets(
    leaves: Sequence[Any], bucket_bytes: int
) -> Tuple[Tuple[int, ...], ...]:
    """Greedy size-bounded grouping of flattened leaves, in REVERSE
    flatten order.

    Backward produces gradients output-side-first, so reverse flatten
    order (the param tree flattens input→output) approximates the order
    buckets become ready — the first bucket's reduce can be issued
    while earlier layers are still differentiating.  A leaf larger than
    the bound gets its own bucket; dtypes never mix inside a bucket
    (the payload is one concatenated buffer)."""
    plan: List[Tuple[int, ...]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i in reversed(range(len(leaves))):
        leaf = leaves[i]
        nbytes = int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
        if cur and (
            cur_bytes + nbytes > bucket_bytes
            or jnp.dtype(leaf.dtype) != cur_dtype
        ):
            plan.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = jnp.dtype(leaf.dtype)
    if cur:
        plan.append(tuple(cur))
    return tuple(plan)


def bucket_histogram(
    plan: Sequence[Sequence[int]], leaves: Sequence[Any]
) -> dict:
    """Bucket-size distribution for the ``comm:`` record: how well the
    bound packs this model's tree."""
    sizes = [
        sum(int(leaves[i].size) * jnp.dtype(leaves[i].dtype).itemsize
            for i in bucket)
        for bucket in plan
    ]
    if not sizes:
        return {"buckets": 0}
    return {
        "buckets": len(sizes),
        "leaves": sum(len(b) for b in plan),
        "min_bytes": min(sizes),
        "max_bytes": max(sizes),
        "mean_bytes": int(sum(sizes) / len(sizes)),
        "total_bytes": sum(sizes),
        "bytes": sizes,
    }


def wire_bytes(
    plan: Sequence[Sequence[int]],
    leaves: Sequence[Any],
    compress: str = "none",
) -> int:
    """Estimated payload bytes ONE worker contributes to one reduction
    (per ring hop; multiply by the topology factor for totals):
    ``none`` moves the native dtype, ``bf16`` two bytes/element,
    ``int8`` the int16 accumulation type plus a float32 scale per
    bucket.  An estimate of the algorithm's traffic, not a measurement
    of XLA's wire format."""
    total = 0
    for bucket in plan:
        n = sum(int(leaves[i].size) for i in bucket)
        if compress == "bf16":
            total += 2 * n
        elif compress == "int8":
            total += jnp.dtype(_INT8_ACC_DTYPE).itemsize * n + 4
        else:
            total += sum(
                int(leaves[i].size) * jnp.dtype(leaves[i].dtype).itemsize
                for i in bucket
            )
    return total


# --------------------------------------------------------------------------
# bucket payload packing
# --------------------------------------------------------------------------

def _concat_bucket(leaves: Sequence[Any], bucket: Sequence[int]):
    if len(bucket) == 1:
        return leaves[bucket[0]].reshape(-1)
    return jnp.concatenate([leaves[i].reshape(-1) for i in bucket])


def _split_bucket(flat, leaves: Sequence[Any], bucket: Sequence[int], out):
    off = 0
    for i in bucket:
        n = int(leaves[i].size)
        out[i] = flat[off:off + n].reshape(leaves[i].shape)
        off += n


# --------------------------------------------------------------------------
# in-step reduction (call inside shard_map)
# --------------------------------------------------------------------------

def _reduce_payload(flat, axis_name: str, compress: str, axis_size: int):
    """One bucket's mean-reduce over ``axis_name`` with the configured
    wire format; returns ``(reduced_f32like, dequantized_local)`` where
    the second term is what THIS worker's peers received from it (for
    the error-feedback residual; equals ``flat`` when lossless)."""
    if compress == "bf16":
        # bf16 on the wire, float32 accumulation: reducing IN bf16
        # would add summation error the error-feedback residual cannot
        # see (it only measures local quantization), leaving a
        # persistent bias — with a wide accumulator EF converges
        q = flat.astype(jnp.bfloat16)
        red = lax.pmean(q.astype(flat.dtype), axis_name)
        return red, q.astype(flat.dtype)
    if compress == "int8":
        if axis_size > _INT8_MAX_WORKERS:
            raise ValueError(
                f"int8 gradient compression accumulates in int16 and "
                f"supports at most {_INT8_MAX_WORKERS} workers, got "
                f"{axis_size}"
            )
        # shared scale: every worker quantizes against the same bound,
        # so the summed int payloads dequantize with one multiply
        absmax = lax.pmax(jnp.max(jnp.abs(flat)), axis_name)
        scale = jnp.maximum(absmax, 1e-30) / 127.0
        q = jnp.clip(jnp.round(flat / scale), -127.0, 127.0)
        acc = lax.psum(q.astype(_INT8_ACC_DTYPE), axis_name)
        red = (acc.astype(flat.dtype) * scale) / float(axis_size)
        return red, q.astype(flat.dtype) * scale
    return lax.pmean(flat, axis_name), flat


def reduce_bucketed(
    tree: Any,
    axis_name: str,
    axis_size: int,
    config: CommConfig,
    residual: Optional[Any] = None,
):
    """Mean-reduce a pytree over ``axis_name``, bucket by bucket, with
    the configured compression.  Call inside ``shard_map``.

    Returns ``(reduced_tree, new_residual)``.  With a lossy ``compress``
    the caller passes last round's residual tree (zeros to start): the
    payload becomes ``value + residual`` and the new residual is the
    part quantization dropped — re-injected next round, so compression
    error accumulates to zero instead of biasing training.  With
    ``compress="none"`` the residual is passed through untouched
    (``None`` in, ``None`` out) and the math is exactly the per-leaf
    ``pmean`` it replaces, one concatenated buffer at a time."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree, residual
    plan = plan_buckets(leaves, config.bucket_bytes)
    out: List[Any] = [None] * len(leaves)
    if not config.wants_residual:
        for bucket in plan:
            flat = _concat_bucket(leaves, bucket)
            red, _ = _reduce_payload(flat, axis_name, "none", axis_size)
            _split_bucket(red, leaves, bucket, out)
        return jax.tree_util.tree_unflatten(treedef, out), residual
    res_leaves = jax.tree_util.tree_leaves(residual)
    if len(res_leaves) != len(leaves):
        raise ValueError(
            f"error-feedback residual has {len(res_leaves)} leaves, "
            f"tree has {len(leaves)} — opt state out of sync with "
            f"--grad-compress (see docs/COMMUNICATION.md)"
        )
    new_res: List[Any] = [None] * len(leaves)
    for bucket in plan:
        flat = _concat_bucket(leaves, bucket)
        res = _concat_bucket(res_leaves, bucket).astype(flat.dtype)
        payload = flat + res
        red, sent = _reduce_payload(
            payload, axis_name, config.compress, axis_size
        )
        _split_bucket(red, leaves, bucket, out)
        # residuals stay float32 regardless of the payload dtype, so
        # the opt-state layout (and jit signature) is round-stable
        _split_bucket(
            (payload - sent).astype(jnp.float32), leaves, bucket, new_res
        )
    return (
        jax.tree_util.tree_unflatten(treedef, out),
        jax.tree_util.tree_unflatten(treedef, new_res),
    )


def init_residual(tree: Any) -> Any:
    """Zero error-feedback residuals shaped like ``tree`` (one per
    communicated leaf), float32 — quantization error is small and must
    accumulate without itself rounding away."""
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), tree
    )


# --------------------------------------------------------------------------
# overlapped in-backward reduction (sync DP)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pmean_on_backward(axis_name: str, leaves: Tuple[Any, ...]):
    """Identity forward; the backward rule mean-reduces the bucket's
    cotangents over ``axis_name`` as ONE concatenated buffer.  Because
    autodiff emits a bucket's rule the moment its last cotangent
    exists, each bucket's all-reduce enters the program mid-backward —
    the overlap FireCaffe gets from interleaving reduction trees with
    remaining backprop work."""
    return leaves


def _pmean_on_backward_fwd(axis_name, leaves):
    return leaves, None


def _pmean_on_backward_bwd(axis_name, _, g):
    g = tuple(g)
    bucket = tuple(range(len(g)))
    flat = _concat_bucket(g, bucket)
    red = lax.pmean(flat, axis_name)
    out: List[Any] = [None] * len(g)
    _split_bucket(red, g, bucket, out)
    return (tuple(out),)


_pmean_on_backward.defvjp(_pmean_on_backward_fwd, _pmean_on_backward_bwd)


def overlap_reduce_on_backward(
    params: Any, axis_name: str, config: CommConfig
) -> Any:
    """Wrap a params pytree so its gradients come back bucket-mean-
    reduced over ``axis_name``, each bucket's collective issued inside
    the backward pass.  Use on the loss function's input params, inside
    ``shard_map``; lossless only (lossy compression needs the residual
    state that :func:`reduce_bucketed` threads)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if not leaves:
        return params
    plan = plan_buckets(leaves, config.bucket_bytes)
    out = list(leaves)
    for bucket in plan:
        synced = _pmean_on_backward(
            axis_name, tuple(leaves[i] for i in bucket)
        )
        for j, i in enumerate(bucket):
            out[i] = synced[j]
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# host-side accounting
# --------------------------------------------------------------------------

def count_reduction(config: CommConfig, tree: Any, path: str) -> int:
    """Record one reduction's estimated traffic in the telemetry
    registry (``comm_bytes{path=...}`` counter + a bucket gauge);
    returns the byte estimate.  Host-side, once per compiled-program
    build or round — never in the per-step hot path."""
    from ..telemetry import REGISTRY

    leaves = jax.tree_util.tree_leaves(tree)
    if config.mode == "bucketed":
        plan = plan_buckets(leaves, config.bucket_bytes)
    else:
        plan = (tuple(range(len(leaves))),) if leaves else ()
    est = wire_bytes(plan, leaves, config.compress)
    REGISTRY.counter("comm_bytes", path=path).inc(est)
    REGISTRY.gauge("comm_buckets", path=path).set(len(plan))
    return est
