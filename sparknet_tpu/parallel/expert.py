"""Expert parallelism for the BERT-MoE family.

No reference counterpart (SURVEY.md §2: data parallelism only; EP is a
task-spec obligation). The expert stacks of every MoE layer shard on
their leading (expert) dim over an ``"ep"`` mesh axis; tokens stay
replicated across ``ep`` (each rank routes the full local batch) and
``lax.all_to_all`` inside :func:`~sparknet_tpu.parallel.moe.moe_ffn`
carries each expert's token groups to its owner.  Composes with ``dp``:
batch rows shard over ``dp``, expert weights over ``ep``, and gradient
reduction follows each leaf's replication — dp for expert shards,
dp×ep for everything else.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..solver.caffe_solver import make_update_fn, mults_for_params
from .moe import moe_pspecs


def bert_moe_pspecs(model, ep_axis: str = "ep") -> Dict[str, Dict[str, P]]:
    """PartitionSpec tree for a MoE ``BertMLM``: expert stacks sharded
    on ``ep``, all other params replicated."""
    rep = P()
    moe = moe_pspecs(ep_axis)
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
            "q_w": rep, "q_b": rep, "k_w": rep, "k_b": rep,
            "v_w": rep, "v_b": rep, "out_w": rep, "out_b": rep,
            "attn_ln_scale": rep, "attn_ln_bias": rep,
            "ffn_ln_scale": rep, "ffn_ln_bias": rep,
            **moe,
        }
    return specs


def make_ep_train_step(
    model,
    sp,
    mesh,
    dp_axis: Optional[str] = "dp",
    ep_axis: str = "ep",
):
    """Jitted ``step(params, opt_state, batch, it, rng)`` over a
    dp×ep mesh with token-level MLM loss (+ router aux loss).

    ``model`` must be built with ``ep_axis=ep_axis`` and a MoE config
    whose expert count divides the mesh's ep size. ``batch`` is the
    token-level layout of
    :func:`sparknet_tpu.data.text.mlm_feed_tokens`.
    """
    cfg = model.cfg
    nep = mesh.shape[ep_axis]
    if cfg.moe_num_experts <= 0:
        raise ValueError("make_ep_train_step needs a MoE config")
    if cfg.moe_num_experts % nep:
        raise ValueError(
            f"ep={nep} must divide moe_num_experts ({cfg.moe_num_experts})"
        )
    if model.ep_axis != ep_axis:
        raise ValueError(
            f"model.ep_axis ({model.ep_axis!r}) != ep_axis ({ep_axis!r}): "
            "build the model with BertMLM(..., ep_axis=ep_axis)"
        )
    pspecs = bert_moe_pspecs(model, ep_axis)
    ndp = mesh.shape[dp_axis] if dp_axis else 1

    def local_step(params, opt_state, batch, it, rng):
        # dropout: identical across ep ranks (tokens are replicated
        # there — divergent masks would desynchronise routing inputs),
        # distinct across dp shards
        if dp_axis:
            rng = jax.random.fold_in(rng, lax.axis_index(dp_axis))

        def loss_fn(p):
            nll, w, corr, aux = model.token_loss_sums_with_aux(
                p, {}, batch, train=True, rng=rng
            )
            w_tot = lax.psum(w, dp_axis) if dp_axis else w
            # aux is already pmean'd over ep inside moe_ffn; /ndp makes
            # the dp-psum'd gradients carry its dp-mean
            loss_local = (
                nll / jnp.maximum(w_tot, 1.0)
                + cfg.moe_aux_weight * aux / ndp
            )
            return loss_local, (nll, w_tot, corr, aux)

        grads, (nll, w_tot, corr, aux) = jax.grad(loss_fn, has_aux=True)(params)
        # tokens are REPLICATED over ep: every ep rank computes the same
        # local loss, and the all_to_all transpose accumulates one
        # cotangent copy per rank into each expert shard — so expert
        # leaves come back scaled by nep; normalise them
        grads = {
            layer: {
                name: g / nep if ep_axis in pspecs[layer][name] else g
                for name, g in entry.items()
            }
            for layer, entry in grads.items()
        }
        if dp_axis:
            # replicated leaves see identical grads on every ep rank (no
            # ep reduction needed); every leaf still reduces over dp
            grads = jax.tree_util.tree_map(
                lambda g: lax.psum(g, dp_axis), grads
            )
        lr_m, dec_m = mults_for_params(params, model.param_specs())
        update = make_update_fn(sp, lr_m, dec_m)
        params, opt_state = update(params, grads, opt_state, it)
        nll_tot = lax.psum(nll, dp_axis) if dp_axis else nll
        corr_tot = lax.psum(corr, dp_axis) if dp_axis else corr
        aux_mean = lax.pmean(aux, dp_axis) if dp_axis else aux
        denom = jnp.maximum(w_tot, 1.0)
        return params, opt_state, {
            "loss": nll_tot / denom + cfg.moe_aux_weight * aux_mean,
            "mlm_acc": corr_tot / denom,
        }

    rows = P(dp_axis)  # replicated over ep
    batch_spec = {
        "input_ids": rows,
        "token_type_ids": rows,
        "attention_mask": rows,
        "position_ids": rows,
        "mlm_labels": rows,
        "mlm_weights": rows,
    }
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
