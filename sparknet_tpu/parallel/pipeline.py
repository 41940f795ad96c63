"""Pipeline parallelism: GPipe-style microbatch pipelining over "pp".

No reference counterpart (SURVEY.md §2: data parallelism only). The
encoder layer stack shards over the ``"pp"`` mesh axis — rank ``r`` owns
layers ``[r*L/npp, (r+1)*L/npp)`` as *stacked* arrays (leading layer
axis, ``lax.scan`` inside the stage: one compiled layer body regardless
of depth). Microbatches march through stages with a neighbor
``ppermute`` per tick — the classic ``n_micro + npp - 1`` tick schedule
with bubble ticks at the ends. Embeddings and the MLM head are
replicated (computed on every rank; only stage 0's embedding output and
the last stage's loss carry gradients, so the pp-psum of grads is exact,
not double-counted).

Autodiff runs through the whole schedule: ``ppermute`` transposes to the
inverse permutation, giving the reverse-order backward pipeline for
free.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..solver.caffe_solver import make_update_fn, mults_for_params


def stack_layer_params(params: Dict[str, Dict[str, jax.Array]], num_layers: int):
    """Split BertMLM params into (stacked_layers, rest): the per-layer
    dicts become one dict of arrays with a leading layer axis."""
    layer_keys = [f"layer_{li:02d}" for li in range(num_layers)]
    names = params[layer_keys[0]].keys()
    stacked = {
        n: jnp.stack([params[k][n] for k in layer_keys]) for n in names
    }
    rest = {k: v for k, v in params.items() if k not in layer_keys}
    return stacked, rest


def unstack_layer_params(stacked, rest, num_layers: int):
    out = dict(rest)
    for li in range(num_layers):
        out[f"layer_{li:02d}"] = {n: v[li] for n, v in stacked.items()}
    return out


# expert stacks: leading layer axis shards over pp, the (now second)
# expert axis over ep
_EXPERT_NAMES = frozenset({"w_in", "b_in", "w_out", "b_out"})


def bert_pp_pspecs(model, pp_axis: str = "pp", ep_axis=None):
    """(stacked_spec, rest_spec): layer stack sharded on its leading
    axis over pp, everything else replicated. For a MoE config the
    layer dict holds expert stacks instead of dense FFN weights; with
    ``ep_axis`` those additionally shard their expert dim."""
    if getattr(model.cfg, "moe_num_experts", 0) > 0:
        ffn_names = ["router_w", "w_in", "b_in", "w_out", "b_out"]
    else:
        ffn_names = ["ffn_in_w", "ffn_in_b", "ffn_out_w", "ffn_out_b"]
    names = [
        "q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "out_w", "out_b",
        "attn_ln_scale", "attn_ln_bias", *ffn_names,
        "ffn_ln_scale", "ffn_ln_bias",
    ]
    stacked_spec = {
        n: (
            P(pp_axis, ep_axis)
            if ep_axis and n in _EXPERT_NAMES
            else P(pp_axis)
        )
        for n in names
    }
    rest_spec = {
        "embeddings": {
            "word": P(), "position": P(), "token_type": P(),
            "ln_scale": P(), "ln_bias": P(),
        },
        "mlm_head": {
            "dense_w": P(), "dense_b": P(), "ln_scale": P(),
            "ln_bias": P(), "output_bias": P(),
        },
    }
    return stacked_spec, rest_spec


def _stage_apply(model, stacked_local, x, kv_mask, rng, train, stage, l_loc,
                 micro_idx):
    """Scan this rank's layers over x; returns (y, moe_aux_sum). rng
    folds in the *global* layer index (decorrelates across stages) and
    the microbatch index (decorrelates dropout across microbatches,
    matching the unpipelined baseline where every batch row draws
    independent mask values)."""

    def body(carry, layer_params):
        x, li, aux = carry
        lrng = None
        if rng is not None:
            lrng = jax.random.fold_in(
                jax.random.fold_in(rng, stage * l_loc + li), micro_idx
            )
        y, a = model.layer_apply_with_aux(
            layer_params, x, kv_mask, lrng, train
        )
        return (y, li + 1, aux + a), None

    (y, _, aux), _ = lax.scan(
        body, (x, 0, jnp.asarray(0.0, jnp.float32)), stacked_local
    )
    return y, aux


def make_pp_train_step(
    model,
    sp,
    mesh,
    n_micro: int,
    dp_axis: Optional[str] = None,
    pp_axis: str = "pp",
    ep_axis: Optional[str] = None,
):
    """Jitted ``step(params, opt_state, batch, it, rng)`` with the layer
    stack pipelined over ``pp`` (optionally composed with ``dp`` and,
    for MoE configs, ``ep``).

    ``params``/``opt_state`` use the *stacked* layout:
    ``{"layers": stacked, "rest": rest}`` from
    :func:`stack_layer_params`. ``batch`` is token-level
    (:func:`sparknet_tpu.data.text.mlm_feed_tokens`); its leading batch
    dim must divide ``n_micro`` (× dp).

    MoE composition: each stage scans its stacked expert layers; the
    router aux loss is accumulated per (stage, live microbatch) through
    the tick scan — the pipelined objective adds
    ``moe_aux_weight * mean_over_microbatches(sum_over_layers(aux))``,
    the microbatch-granular analogue of the unpipelined loss. With
    ``ep_axis`` the expert stacks shard their expert dim and tokens
    reach their expert's owner via the ``all_to_all`` inside
    :func:`~sparknet_tpu.parallel.moe.moe_ffn`, exactly as in
    :func:`~sparknet_tpu.parallel.expert.make_ep_train_step`.
    """
    cfg = model.cfg
    moe = getattr(cfg, "moe_num_experts", 0) > 0
    if ep_axis and not moe:
        raise ValueError("ep_axis given but the config has no MoE experts")
    if moe and model.ep_axis != ep_axis:
        raise ValueError(
            f"model.ep_axis ({model.ep_axis!r}) != ep_axis ({ep_axis!r}): "
            "build the model with BertMLM(..., ep_axis=ep_axis)"
        )
    nep = mesh.shape[ep_axis] if ep_axis else 1
    if moe and cfg.moe_num_experts % nep:
        raise ValueError(
            f"ep={nep} must divide moe_num_experts ({cfg.moe_num_experts})"
        )
    npp = mesh.shape[pp_axis]
    L = model.cfg.num_layers
    if L % npp:
        raise ValueError(f"pp={npp} must divide num_layers ({L})")
    l_loc = L // npp
    ndp = mesh.shape[dp_axis] if dp_axis else 1
    data_axes = (dp_axis,) if dp_axis else ()
    stacked_spec, rest_spec = bert_pp_pspecs(
        model, pp_axis, ep_axis if moe else None
    )
    pspec = {"layers": stacked_spec, "rest": rest_spec}

    # layer lr/decay multipliers, stacked layout: identical per layer
    l_specs = model.param_specs()["layer_00"]
    mult_tree = {
        "layers": {n: l_specs[n][0] for n in stacked_spec},
        "rest": {
            k: {n: s[0] for n, s in model.param_specs()[k].items()}
            for k in ("embeddings", "mlm_head")
        },
    }
    decay_tree = {
        "layers": {n: l_specs[n][1] for n in stacked_spec},
        "rest": {
            k: {n: s[1] for n, s in model.param_specs()[k].items()}
            for k in ("embeddings", "mlm_head")
        },
    }

    def local_step(params, opt_state, batch, it, rng):
        stage = lax.axis_index(pp_axis)
        if dp_axis:
            rng = jax.random.fold_in(rng, lax.axis_index(dp_axis))
        is_first = stage == 0
        is_last = stage == npp - 1
        perm = [(i, i + 1) for i in range(npp - 1)]

        def loss_fn(p):
            stacked, rest = p["layers"], p["rest"]
            x0, kv_mask, rng2 = model.embed(
                rest, batch, train=True, rng=rng
            )
            b = x0.shape[0]
            if b % n_micro:
                raise ValueError(f"batch {b} not divisible by {n_micro} micro")
            mb = b // n_micro
            s, h = x0.shape[1], x0.shape[2]
            x_micro = x0.reshape(n_micro, mb, s, h)
            mask_micro = kv_mask.reshape(n_micro, mb, s)
            ticks = n_micro + npp - 1

            def tick(carry, t):
                recv, outs, aux_acc = carry
                mi_in = jnp.clip(t, 0, n_micro - 1)
                inject = jnp.where(
                    is_first,
                    x_micro[mi_in].astype(jnp.float32),
                    recv.astype(jnp.float32),
                ).astype(x0.dtype)
                # each tick, stage s processes microbatch t - s; mask
                # for that microbatch (clamped during bubbles)
                mi_here = jnp.clip(t - stage, 0, n_micro - 1)
                y, aux = _stage_apply(
                    model, stacked, inject, mask_micro[mi_here], rng2,
                    True, stage, l_loc, mi_here,
                )
                # bubble ticks process clamped garbage whose outputs are
                # never consumed — their aux must not be either
                live_tick = jnp.logical_and(t >= stage, t - stage < n_micro)
                aux_acc = aux_acc + jnp.where(live_tick, aux, 0.0)
                recv_next = lax.ppermute(y, pp_axis, perm)
                # last stage emits microbatch t - (npp - 1)
                mi_out = t - (npp - 1)
                outs = jnp.where(
                    jnp.logical_and(is_last, mi_out >= 0)[..., None],
                    lax.dynamic_update_index_in_dim(
                        outs, y, jnp.clip(mi_out, 0, n_micro - 1), 0
                    ),
                    outs,
                )
                return (recv_next, outs, aux_acc), None

            outs0 = jnp.zeros((n_micro, mb, s, h), x0.dtype)
            recv0 = jnp.zeros((mb, s, h), x0.dtype)
            aux0 = jnp.asarray(0.0, jnp.float32)
            (_, outs, aux_acc), _ = lax.scan(
                tick, (recv0, outs0, aux0), jnp.arange(ticks)
            )
            xf = outs.reshape(b, s, h)
            nll, w, corr = model.token_loss_from_hidden(
                rest, xf, batch["mlm_labels"], batch["mlm_weights"]
            )
            # only the last stage's head output is real
            live = is_last.astype(jnp.float32)
            nll, corr = nll * live, corr * live
            w_tot = lax.psum(
                batch["mlm_weights"].astype(jnp.float32).sum(), data_axes
            ) if data_axes else batch["mlm_weights"].astype(jnp.float32).sum()
            # this stage's aux (already ep-pmean'd inside moe_ffn), mean
            # over microbatches; /ndp so the dp-psum of grads carries
            # the dp-mean (cf. make_ep_train_step)
            aux_mean = aux_acc / n_micro
            loss_local = nll / jnp.maximum(w_tot, 1.0)
            if moe:
                loss_local = (
                    loss_local + cfg.moe_aux_weight * aux_mean / ndp
                )
            return loss_local, (nll, w_tot, corr, aux_mean)

        grads, (nll, w_tot, corr, aux_mean) = jax.grad(
            loss_fn, has_aux=True
        )(params)
        if moe and ep_axis:
            # tokens are replicated over ep: the all_to_all transpose
            # accumulates one cotangent copy per ep rank into each
            # expert shard — normalise them (cf. make_ep_train_step);
            # non-expert leaves see identical grads on every ep rank
            grads = {
                "layers": {
                    n: g / nep if n in _EXPERT_NAMES else g
                    for n, g in grads["layers"].items()
                },
                "rest": grads["rest"],
            }
        # pp reduction: replicated leaves ("rest") have grads only on the
        # stage that used them (embed on 0 unless... actually embed runs
        # on every rank but only stage 0's output enters the pipeline, so
        # cotangents vanish elsewhere) -> psum over pp completes them.
        # stacked layers are pp-sharded: psum over data axes only.
        grads = {
            "layers": jax.tree_util.tree_map(
                (lambda g: lax.psum(g, data_axes)) if data_axes else (lambda g: g),
                grads["layers"],
            ),
            "rest": jax.tree_util.tree_map(
                lambda g: lax.psum(g, data_axes + (pp_axis,)),
                grads["rest"],
            ),
        }
        update = make_update_fn(sp, mult_tree, decay_tree)
        params, opt_state = update(params, grads, opt_state, it)
        red = lambda z: lax.psum(z, data_axes + (pp_axis,))
        denom = jnp.maximum(w_tot, 1.0)
        metrics = {"loss": red(nll) / denom, "mlm_acc": red(corr) / denom}
        if moe:
            # stages hold disjoint layers: psum over pp completes the
            # layer sum; dp shards see different tokens: mean
            aux_all = lax.psum(aux_mean, pp_axis)
            if data_axes:
                aux_all = lax.pmean(aux_all, data_axes)
            metrics["loss"] = metrics["loss"] + cfg.moe_aux_weight * aux_all
            metrics["moe_aux"] = aux_all
        return params, opt_state, metrics

    batch_axes = P(dp_axis) if dp_axis else P()
    batch_spec = {
        k: batch_axes
        for k in (
            "input_ids", "token_type_ids", "attention_mask",
            "position_ids", "mlm_labels", "mlm_weights",
        )
    }
    compiled = {}

    def stepper(params, opt_state, batch, it, rng):
        key = tuple(sorted(opt_state))
        if key not in compiled:
            ospec = {k: pspec for k in opt_state}
            compiled[key] = jax.jit(
                jax.shard_map(
                    local_step,
                    mesh=mesh,
                    in_specs=(pspec, ospec, batch_spec, P(), P()),
                    out_specs=(pspec, ospec, P()),
                    check_vma=False,
                ),
                donate_argnums=(0, 1),
            )
        return compiled[key](params, opt_state, batch, it, rng)

    return stepper