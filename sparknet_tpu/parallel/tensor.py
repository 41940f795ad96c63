"""Tensor (model) parallelism for the BERT family — Megatron-style.

No reference counterpart (SURVEY.md §2: data parallelism only). Layer
weights shard over a ``"tp"`` mesh axis: qkv and ffn_in are
column-parallel (output-dim sharded — each rank owns a contiguous block
of heads / ffn neurons), out and ffn_out are row-parallel (input-dim
sharded, partial products ``psum``-reduced inside
:meth:`BertMLM.encode`). Embeddings, LayerNorms and the MLM head stay
replicated — they are a small fraction of parameters and keeping them
replicated avoids a vocab-sharded softmax.

The train step composes with the other axes: batch rows shard over
``dp``, sequence over ``sp`` (ring attention on the local heads), and
gradients reduce over exactly the axes each parameter is *replicated*
on — sharded leaves reduce over dp/sp only.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..solver.caffe_solver import make_update_fn, mults_for_params


def bert_param_pspecs(model, tp_axis: str = "tp") -> Dict[str, Dict[str, P]]:
    """PartitionSpec tree matching ``BertMLM`` params: column-parallel
    qkv/ffn_in, row-parallel out/ffn_out, everything else replicated."""
    col_w = P(None, tp_axis)
    col_b = P(tp_axis)
    row_w = P(tp_axis, None)
    rep = P()
    specs: Dict[str, Dict[str, P]] = {
        "embeddings": {
            "word": rep, "position": rep, "token_type": rep,
            "ln_scale": rep, "ln_bias": rep,
        },
        "mlm_head": {
            "dense_w": rep, "dense_b": rep, "ln_scale": rep,
            "ln_bias": rep, "output_bias": rep,
        },
    }
    for li in range(model.cfg.num_layers):
        specs[f"layer_{li:02d}"] = {
            "q_w": col_w, "q_b": col_b,
            "k_w": col_w, "k_b": col_b,
            "v_w": col_w, "v_b": col_b,
            "out_w": row_w, "out_b": rep,
            "attn_ln_scale": rep, "attn_ln_bias": rep,
            "ffn_in_w": col_w, "ffn_in_b": col_b,
            "ffn_out_w": row_w, "ffn_out_b": rep,
            "ffn_ln_scale": rep, "ffn_ln_bias": rep,
        }
    return specs


def _grad_reduce(grads, data_axes):
    """Gradients reduce over the data axes only. No tp reduction is
    needed anywhere: sharded leaves own their shard's grad outright, and
    replicated leaves already see the full gradient on every tp rank
    because the model's ``_tp_copy`` (Megatron "f") psums the
    column-parallel input cotangents in backward."""
    if not data_axes:
        return grads
    return jax.tree_util.tree_map(lambda g: lax.psum(g, data_axes), grads)


def make_tp_train_step(
    model,
    sp,
    mesh,
    dp_axis: Optional[str] = "dp",
    tp_axis: str = "tp",
    sp_axis: Optional[str] = None,
):
    """Jitted ``step(params, opt_state, batch, it, rng)`` over a
    dp×tp(×sp) mesh with token-level MLM loss.

    ``model`` must be built with ``tp_axis=tp_axis`` (and, when
    ``sp_axis`` is given, ``attention_impl="ring"`` — ulysses shards
    heads and composes poorly with head-sharding tp). ``batch`` is the
    token-level layout of
    :func:`sparknet_tpu.data.text.mlm_feed_tokens`.
    """
    ntp = mesh.shape[tp_axis]
    cfg = model.cfg
    if cfg.num_heads % ntp or cfg.intermediate_size % ntp:
        raise ValueError(
            f"tp={ntp} must divide num_heads ({cfg.num_heads}) and "
            f"intermediate_size ({cfg.intermediate_size})"
        )
    # a model without the matching tp hook would silently skip the
    # row-parallel psum and train on partial activations
    if model.tp_axis != tp_axis:
        raise ValueError(
            f"model.tp_axis ({model.tp_axis!r}) != tp_axis ({tp_axis!r}): "
            "build the model with BertMLM(..., tp_axis=tp_axis)"
        )
    if sp_axis is not None and model.attention_impl != "ring":
        raise ValueError(
            "sp_axis with tensor parallelism requires attention_impl="
            f"'ring' (got {model.attention_impl!r}); ulysses re-shards "
            "heads and conflicts with tp head sharding"
        )
    pspecs = bert_param_pspecs(model, tp_axis)
    data_axes = tuple(a for a in (dp_axis, sp_axis) if a is not None)

    def local_step(params, opt_state, batch, it, rng):
        # dropout: identical across tp ranks (activations are
        # replicated there), distinct across data axes
        for a in data_axes:
            rng = jax.random.fold_in(rng, lax.axis_index(a))

        def loss_fn(p):
            nll, w, corr = model.token_loss_sums(
                p, {}, batch, train=True, rng=rng
            )
            w_tot = lax.psum(w, data_axes) if data_axes else w
            loss_local = nll / jnp.maximum(w_tot, 1.0)
            return loss_local, (nll, w_tot, corr)

        grads, (nll, w_tot, corr) = jax.grad(loss_fn, has_aux=True)(params)
        grads = _grad_reduce(grads, data_axes)
        lr_m, dec_m = mults_for_params(params, model.param_specs())
        update = make_update_fn(sp, lr_m, dec_m)
        params, opt_state = update(params, grads, opt_state, it)
        nll_tot = lax.psum(nll, data_axes) if data_axes else nll
        corr_tot = lax.psum(corr, data_axes) if data_axes else corr
        denom = jnp.maximum(w_tot, 1.0)
        return params, opt_state, {
            "loss": nll_tot / denom, "mlm_acc": corr_tot / denom,
        }

    batch_axes = P(dp_axis, sp_axis)
    batch_spec = {
        "input_ids": batch_axes,
        "token_type_ids": batch_axes,
        "attention_mask": batch_axes,
        "position_ids": batch_axes,
        "mlm_labels": batch_axes,
        "mlm_weights": batch_axes,
    }
    # opt_state's outer keys depend on the solver type ("m"/"v" for
    # AdamW, "momentum" for SGD, ...), so its spec tree is resolved at
    # first call and the shard_map cached per key set
    compiled = {}

    def stepper(params, opt_state, batch, it, rng):
        key = tuple(sorted(opt_state))
        if key not in compiled:
            ospec = {k: pspecs for k in opt_state}
            compiled[key] = jax.jit(
                jax.shard_map(
                    local_step,
                    mesh=mesh,
                    in_specs=(pspecs, ospec, batch_spec, P(), P()),
                    out_specs=(pspecs, ospec, P()),
                    check_vma=False,
                ),
                donate_argnums=(0, 1),
            )
        return compiled[key](params, opt_state, batch, it, rng)

    return stepper