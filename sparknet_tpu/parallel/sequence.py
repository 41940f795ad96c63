"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference is data-parallel only (SURVEY.md §2: "no TP/PP/SP/EP/CP" —
long-context parallelism is a task-spec obligation, designed TPU-native
here rather than ported). Both strategies run *inside* ``shard_map``
over an ``"sp"`` mesh axis, with sequence-sharded q/k/v ``(B, H, S/n,
D)`` per device:

- :func:`ring_attention` — k/v shards rotate around the ring via
  ``lax.ppermute`` (ICI neighbor exchange) while each device folds every
  incoming block into a running online-softmax accumulator ``(o, m, l)``
  — flash attention's recurrence at shard granularity, so no device ever
  materialises more than one ``(S/n, S/n)`` logit block. Memory is
  O(S/n), communication is the bandwidth-optimal ring.
- :func:`ulysses_attention` — ``lax.all_to_all`` re-shards sequence ->
  heads, runs *full-sequence* attention locally on H/n heads (the Pallas
  flash kernel on TPU), then re-shards back. Cheaper compute plumbing
  when H divides the axis and S fits per-device; ring wins at extreme S.

Both differentiate through the collectives (``ppermute``/``all_to_all``
have transpose rules), so the same code path trains.
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import NEG_INF, attention


def _block_logits(q, k, scale, kv_mask_blk, causal, q_off, kv_off):
    """(B,H,Sq,Sk) masked logits for one ring block."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    sq, sk = q.shape[2], k.shape[2]
    valid = jnp.ones((1, 1, sq, sk), bool)
    if causal:
        qi = jnp.arange(sq)[:, None] + q_off
        ki = jnp.arange(sk)[None, :] + kv_off
        valid = valid & (ki <= qi)[None, None]
    if kv_mask_blk is not None:
        valid = valid & kv_mask_blk[:, None, None, :].astype(bool)
    return jnp.where(valid, s, NEG_INF)


def ring_engine(s_local: int) -> str:
    """The block engine :func:`ring_attention` uses by default for a
    shard of ``s_local`` positions: ``SPARKNET_RING_IMPL`` when set,
    else the Pallas flash kernels on a TPU for lane-aligned shards
    (``s_local % 128 == 0``) and the einsum ring everywhere else.  The
    app prints it, so a run cannot mistake one engine for the other."""
    impl = os.environ.get("SPARKNET_RING_IMPL")
    if impl:
        return impl
    if jax.default_backend() == "tpu" and s_local % 128 == 0:
        return "flash"
    return "einsum"


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    kv_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """Ring attention over sequence shards. Call inside ``shard_map``.

    q/k/v: local shards (B, H, S_local, D); kv_mask: local (B, S_local),
    True/1 = valid key. Returns the local output shard (B, H, S_local, D).

    Two block engines, same ring:

    - **flash** (TPU default for lane-aligned shards): each ring step
      runs the streamed Pallas flash kernel on (q_local × kv shard) with
      per-step q/kv offsets, merging the per-block ``(out, lse)`` pairs
      with logaddexp weights; backward re-runs the ring calling the
      flash backward kernels per block with the GLOBAL merged lse
      (exact), accumulating dk/dv in carries that rotate with their kv
      shard so every contribution lands home. HBM per step is O(S_local
      * D) — the (S_local, S_local) logit block never materialises.
    - **einsum** (fallback/oracle): materialises one f32 logit block per
      step with an explicit online-softmax merge.

    ``impl`` forces "flash"/"einsum" (env ``SPARKNET_RING_IMPL``
    overrides the default); ``interpret`` runs the flash kernels in
    Pallas interpret mode (CPU tests).

    Attention-probability dropout drops entries of the *unnormalised*
    online-softmax numerator p per ring step (keyed by the source shard
    so the mask is well-defined per (query, key) pair); the denominator
    keeps the undropped sum, matching the reference path's
    ``p/sum(p)``-then-drop semantics in expectation.
    """
    b, h, s_loc, d = q.shape
    impl = impl or ring_engine(s_loc)
    if impl not in ("flash", "einsum"):
        raise ValueError(
            f"ring impl {impl!r}: want 'flash' or 'einsum' "
            f"(check SPARKNET_RING_IMPL)"
        )
    if impl == "flash":
        scale_v = (1.0 / math.sqrt(d)) if scale is None else scale
        mask = (
            jnp.ones((b, s_loc), jnp.int8)
            if kv_mask is None
            else kv_mask.astype(jnp.int8)
        )
        if dropout_rate > 0.0 and dropout_rng is not None:
            from ..ops.attention import seed_from_rng

            seed = seed_from_rng(dropout_rng)
        else:
            dropout_rate = 0.0
            seed = jnp.asarray(0, jnp.int32)
        return _ring_flash(
            q, k, v, mask, seed, axis_name, causal, float(scale_v),
            float(dropout_rate), interpret,
        )
    return _ring_einsum(
        q, k, v, axis_name=axis_name, causal=causal, kv_mask=kv_mask,
        scale=scale, dropout_rate=dropout_rate, dropout_rng=dropout_rng,
    )


def _ring_flash_steps(q, k, v, kv_mask, seed, axis_name, causal, scale,
                      dropout_rate, interpret):
    """Forward ring: one flash-fwd kernel call per kv shard, partials
    merged by logaddexp weights. Returns (out f32, merged lse)."""
    from ..ops.attention import flash_block_fwd

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    q_off = idx * s_loc
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        o, lse_acc, k_cur, v_cur, mask_cur, src = carry
        o_s, lse_s = flash_block_fwd(
            q, k_cur, v_cur, mask_cur,
            q_offset=q_off, kv_offset=src * s_loc,
            # decorrelate masks per (q shard, kv shard) — the kernel's
            # own PRNG only sees block-local coordinates
            seed=seed + src * jnp.int32(-1640531527)
            + idx * jnp.int32(40503),
            causal=causal, scale=scale, interpret=interpret,
            dropout_rate=dropout_rate,
        )
        # NEG_INF is finite (-1e30), so dead rows merge NaN-free: their
        # weights underflow to 0 and their o stays 0
        lse_new = jnp.logaddexp(lse_acc, lse_s)
        w1 = jnp.exp(lse_acc - lse_new)
        w2 = jnp.exp(lse_s - lse_new)
        o = o * w1[..., None] + o_s.astype(jnp.float32) * w2[..., None]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = lax.ppermute(mask_cur, axis_name, perm)
        return (o, lse_new, k_nxt, v_nxt, mask_nxt, (src - 1) % n), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    (o, lse, *_), _ = lax.scan(
        step, (o0, lse0, k, v, kv_mask, idx), None, length=n
    )
    return o, lse


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _ring_flash(q, k, v, kv_mask, seed, axis_name, causal, scale,
                dropout_rate, interpret):
    o, _ = _ring_flash_steps(
        q, k, v, kv_mask, seed, axis_name, causal, scale, dropout_rate,
        interpret,
    )
    return o.astype(q.dtype)


def _ring_flash_fwd(q, k, v, kv_mask, seed, axis_name, causal, scale,
                    dropout_rate, interpret):
    o, lse = _ring_flash_steps(
        q, k, v, kv_mask, seed, axis_name, causal, scale, dropout_rate,
        interpret,
    )
    out = o.astype(q.dtype)
    return out, (q, k, v, kv_mask, seed, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, dropout_rate, interpret,
                    res, do):
    from ..ops.attention import flash_block_bwd

    q, k, v, kv_mask, seed, out, lse = res
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    q_off = idx * s_loc
    perm = [(i, (i + 1) % n) for i in range(n)]
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (B, H, S_local)

    def step(carry, _):
        dq, dk_acc, dv_acc, k_cur, v_cur, mask_cur, src = carry
        dq_s, dk_s, dv_s = flash_block_bwd(
            q, k_cur, v_cur, mask_cur, do, lse, delta,
            q_offset=q_off, kv_offset=src * s_loc,
            seed=seed + src * jnp.int32(-1640531527)
            + idx * jnp.int32(40503),
            causal=causal, scale=scale, interpret=interpret,
            dropout_rate=dropout_rate,
        )
        dq = dq + dq_s.astype(jnp.float32)
        # dk/dv accumulators travel WITH their kv shard: add this
        # device's contribution, then rotate both together — after the
        # full circle every shard is home with its total gradient
        dk_nxt = lax.ppermute(
            dk_acc + dk_s.astype(jnp.float32), axis_name, perm
        )
        dv_nxt = lax.ppermute(
            dv_acc + dv_s.astype(jnp.float32), axis_name, perm
        )
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = lax.ppermute(mask_cur, axis_name, perm)
        return (
            dq, dk_nxt, dv_nxt, k_nxt, v_nxt, mask_nxt, (src - 1) % n
        ), None

    z = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (dq, dk, dv, *_), _ = lax.scan(
        step, (z, z, z, k, v, kv_mask, idx), None, length=n
    )
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
        None, None,
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _ring_einsum(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    kv_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jax.Array:
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    q_off = idx * s_loc
    if kv_mask is None:
        kv_mask = jnp.ones((b, s_loc), jnp.int32)
    dropping = dropout_rate > 0.0 and dropout_rng is not None

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        o, m, l, k_cur, v_cur, mask_cur, src = carry
        kv_off = src * s_loc
        s_blk = _block_logits(
            q, k_cur, scale, mask_cur, causal, q_off, kv_off
        )
        m_blk = jnp.max(s_blk, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # guard: rows with nothing valid yet keep exp(NEG_INF-NEG_INF)
        # from turning into 1
        p = jnp.where(
            s_blk <= NEG_INF * 0.5, 0.0, jnp.exp(s_blk - m_new[..., None])
        )
        alpha = jnp.where(
            m <= NEG_INF * 0.5, 0.0, jnp.exp(m - m_new)
        )
        l = alpha * l + jnp.sum(p, axis=-1)
        p_v = p
        if dropping:
            # mask keyed by (q shard, kv shard origin), independent of
            # ring scheduling; numerator-only so l stays the softmax sum
            blk_rng = jax.random.fold_in(
                jax.random.fold_in(dropout_rng, idx), src
            )
            keep = jax.random.bernoulli(blk_rng, 1.0 - dropout_rate, p.shape)
            p_v = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p_v, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = lax.ppermute(mask_cur, axis_name, perm)
        src = (src - 1) % n
        return (o, m_new, l, k_nxt, v_nxt, mask_nxt, src), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    (o, m, l, *_), _ = lax.scan(
        step, (o0, m0, l0, k, v, kv_mask, idx), None, length=n
    )
    dead = m <= NEG_INF * 0.5
    out = jnp.where(
        dead[..., None], 0.0, o / jnp.maximum(l, 1e-30)[..., None]
    )
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    kv_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    force: Optional[str] = None,
) -> jax.Array:
    """Ulysses SP: all-to-all seq->heads, local full-seq attention
    (flash on TPU), all-to-all heads->seq. Call inside ``shard_map``.

    Heads must be divisible by the axis size. Attention dropout is
    delegated to the local attention dispatcher (each rank holds
    distinct heads, so per-rank rng decorrelation is handled by folding
    in the axis index).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    if h % n:
        raise ValueError(f"ulysses: heads ({h}) not divisible by axis ({n})")
    # (B, H, S/n, D) -> (B, H/n, S, D)
    a2a = partial(
        lax.all_to_all, axis_name=axis_name, split_axis=1, concat_axis=2,
        tiled=True,
    )
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    mask_g = (
        lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
        if kv_mask is not None
        else None
    )
    if dropout_rng is not None:
        dropout_rng = jax.random.fold_in(dropout_rng, idx)
    ctx = attention(
        qg, kg, vg, causal=causal, kv_mask=mask_g, scale=scale,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng, force=force,
    )
    # (B, H/n, S, D) -> (B, H, S/n, D)
    return lax.all_to_all(
        ctx, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )


# ---------------------------------------------------------------------------
# Sequence-parallel BERT training step
# ---------------------------------------------------------------------------

def make_sp_train_step(model, sp, mesh, dp_axis: str = "dp", sp_axis: str = "sp"):
    """Jitted ``step(params, opt_state, batch, it, rng) -> (params,
    opt_state, metrics)`` training a token-loss BERT over a 2-D
    ``(dp, sp)`` mesh: batch rows sharded over ``dp``, sequence sharded
    over ``sp`` (ring/ulysses attention inside the model), params
    replicated, gradient all-reduce over both axes.

    ``model`` must be a BertMLM built with ``attention_impl`` in
    {"ring", "ulysses"}; ``batch`` blobs are (B, S) token-level arrays
    (``mlm_labels``/``mlm_weights`` per token, plus ``position_ids``).
    """
    from jax.sharding import PartitionSpec as P

    from ..solver.caffe_solver import make_update_fn, mults_for_params

    if model.attention_impl not in ("ring", "ulysses"):
        raise ValueError(
            "make_sp_train_step needs a model built with attention_impl="
            f"'ring' or 'ulysses' (got {model.attention_impl!r}) — plain "
            "attention would silently attend within each shard only"
        )
    if model.sp_axis != sp_axis:
        raise ValueError(
            f"model.sp_axis ({model.sp_axis!r}) != sp_axis ({sp_axis!r})"
        )

    def local_step(params, opt_state, batch, it, rng):
        # decorrelate dropout across mesh positions
        rng = jax.random.fold_in(rng, lax.axis_index(dp_axis))
        rng = jax.random.fold_in(rng, lax.axis_index(sp_axis))

        def loss_fn(p):
            nll, w, corr = model.token_loss_sums(
                p, {}, batch, train=True, rng=rng
            )
            w_tot = lax.psum(w, (dp_axis, sp_axis))
            loss_local = nll / jnp.maximum(w_tot, 1.0)
            return loss_local, (nll, w_tot, corr)

        grads, (nll, w_tot, corr) = jax.grad(loss_fn, has_aux=True)(params)
        grads = lax.psum(grads, (dp_axis, sp_axis))
        lr_m, dec_m = mults_for_params(params, model.param_specs())
        update = make_update_fn(sp, lr_m, dec_m)
        params, opt_state = update(params, grads, opt_state, it)
        loss = lax.psum(nll, (dp_axis, sp_axis)) / jnp.maximum(w_tot, 1.0)
        acc = lax.psum(corr, (dp_axis, sp_axis)) / jnp.maximum(w_tot, 1.0)
        return params, opt_state, {"loss": loss, "mlm_acc": acc}

    batch_spec = {
        "input_ids": P(dp_axis, sp_axis),
        "token_type_ids": P(dp_axis, sp_axis),
        "attention_mask": P(dp_axis, sp_axis),
        "position_ids": P(dp_axis, sp_axis),
        "mlm_labels": P(dp_axis, sp_axis),
        "mlm_weights": P(dp_axis, sp_axis),
    }
    step = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec, P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(step, donate_argnums=(0, 1))
