"""BERT-base MLM — the pure-JAX transformer family (no prototxt path).

BASELINE.json config #5: "BERT-base MLM (new — drop Caffe layer-lib,
pure-JAX transformer stretch)". The reference has nothing comparable
(SURVEY.md §2 — SparkNet predates transformers), so this is designed
TPU-first rather than ported: bf16-friendly matmul shapes, attention via
:mod:`sparknet_tpu.ops.attention` (Pallas flash on TPU), params in the
same two-level ``WeightCollection`` layout the Caffe solver update fns
consume, and the :class:`~sparknet_tpu.solver.trainer.Solver` protocol
(``init/apply/loss_and_metrics/param_specs/input_names/blob_shapes``) so
every training path — single chip, sync DP, τ-local SGD — works on BERT
unchanged.

Batch blobs:
- ``input_ids``     (B, S) int32
- ``token_type_ids``(B, S) int32
- ``attention_mask``(B, S) int32 — 1 = real token
- ``mlm_positions`` (B, M) int32 — indices into S
- ``mlm_labels``    (B, M) int32
- ``mlm_weights``   (B, M) float — 0 pads unused prediction slots
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.matmul import mxu_dot
from ..utils.profiling import scope


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    # Mixture-of-Experts FFN (0 experts = dense FFN). Routed through
    # parallel/moe.py; aux (load-balance + z) loss joins the MLM loss
    # with weight moe_aux_weight.
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_z_loss: float = 1e-3
    moe_aux_weight: float = 0.01
    moe_dispatch: str = "dense"
    # rematerialise each encoder layer (trade FLOPs for activation
    # memory — the long-context knob)
    remat: bool = False

    @classmethod
    def bert_base(cls) -> "BertConfig":
        return cls()

    @classmethod
    def bert_small(cls) -> "BertConfig":
        return cls(hidden_size=256, num_layers=4, num_heads=4,
                   intermediate_size=1024)

    @classmethod
    def bert_tiny(cls, vocab_size: int = 1024) -> "BertConfig":
        return cls(vocab_size=vocab_size, hidden_size=128, num_layers=2,
                   num_heads=2, intermediate_size=512, max_position=128)


def _layer_norm(x, scale, bias, eps):
    with scope("norm"):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * scale + bias).astype(x.dtype)


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_copy(x, axis):
    """Megatron's "f" operator: identity forward, psum backward. Placed
    where a replicated activation enters column-parallel matmuls, it
    reduces the partial per-rank input-cotangents so every upstream
    (replicated) parameter sees the full gradient on every tp rank —
    which is what lets the train step skip tp gradient all-reduces for
    replicated params entirely."""
    return x


def _tp_copy_fwd(x, axis):
    return x, None


def _tp_copy_bwd(axis, _, g):
    return (jax.lax.psum(g, axis),)


_tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_reduce(x, axis):
    """Megatron's "g" operator: psum forward, identity backward. Raw
    ``lax.psum`` transposes to another psum under shard_map, which would
    scale the (already tp-identical) cotangent by the axis size; the
    correct adjoint of sum-then-replicate is identity per rank."""
    return jax.lax.psum(x, axis)


def _tp_reduce_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _tp_reduce_bwd(axis, _, g):
    return (g,)


_tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


def _dropout(x, rate, rng, train):
    if not train or rate <= 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


class BertMLM:
    """Functional BERT encoder + tied-embedding MLM head."""

    def __init__(
        self,
        config: BertConfig,
        input_shapes: Dict[str, Tuple[int, ...]],
        compute_dtype: Any = jnp.float32,
        attention_impl: Optional[str] = None,
        # None=auto, "flash", "reference", or — inside shard_map over a
        # sequence-sharded mesh axis — "ring" / "ulysses"
        sp_axis: str = "sp",
        # set inside shard_map over a tensor-parallel axis: layer weights
        # arrive sharded (column-parallel qkv/ffn_in, row-parallel
        # out/ffn_out) and row-parallel projections psum over this axis
        tp_axis: Optional[str] = None,
        # set inside shard_map over an expert-parallel axis: MoE expert
        # stacks arrive sharded on their leading (expert) dim
        ep_axis: Optional[str] = None,
    ):
        self.cfg = config
        self.compute_dtype = compute_dtype
        self.attention_impl = attention_impl
        self.sp_axis = sp_axis
        self.tp_axis = tp_axis
        self.ep_axis = ep_axis
        if config.moe_num_experts > 0:
            if tp_axis is not None or attention_impl in ("ring", "ulysses"):
                raise NotImplementedError(
                    "MoE FFN composes with dp/ep; tp and sequence-parallel "
                    "attention are not wired to the expert path yet"
                )
        if "input_ids" not in input_shapes:
            raise ValueError("input_shapes must provide 'input_ids' (B, S)")
        b, s = input_shapes["input_ids"]
        m = input_shapes.get("mlm_positions", (b, max(1, s // 8)))[1]
        self.batch, self.seq_len, self.num_preds = b, s, m
        if s > config.max_position:
            raise ValueError(f"seq {s} > max_position {config.max_position}")
        if config.hidden_size % config.num_heads:
            raise ValueError(
                f"num_heads ({config.num_heads}) must divide hidden_size "
                f"({config.hidden_size})"
            )
        self.input_names: List[str] = [
            "input_ids", "token_type_ids", "attention_mask",
            "mlm_positions", "mlm_labels", "mlm_weights",
        ]
        self.blob_shapes: Dict[str, Tuple[int, ...]] = {
            "input_ids": (b, s),
            "token_type_ids": (b, s),
            "attention_mask": (b, s),
            "mlm_positions": (b, m),
            "mlm_labels": (b, m),
            "mlm_weights": (b, m),
            "loss": (),
            "mlm_acc": (),
        }

    # -- init ----------------------------------------------------------------
    def init(self, rng: jax.Array):
        cfg = self.cfg
        h, i_sz, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
        std = cfg.initializer_range
        keys = iter(jax.random.split(rng, 16 + 16 * cfg.num_layers))

        def trunc(key, shape):
            return (
                jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
                * std
            )

        params: Dict[str, Dict[str, jax.Array]] = {
            "embeddings": {
                "word": trunc(next(keys), (v, h)),
                "position": trunc(next(keys), (cfg.max_position, h)),
                "token_type": trunc(next(keys), (cfg.type_vocab_size, h)),
                "ln_scale": jnp.ones((h,), jnp.float32),
                "ln_bias": jnp.zeros((h,), jnp.float32),
            }
        }
        for li in range(cfg.num_layers):
            layer = {
                "q_w": trunc(next(keys), (h, h)),
                "q_b": jnp.zeros((h,), jnp.float32),
                "k_w": trunc(next(keys), (h, h)),
                "k_b": jnp.zeros((h,), jnp.float32),
                "v_w": trunc(next(keys), (h, h)),
                "v_b": jnp.zeros((h,), jnp.float32),
                "out_w": trunc(next(keys), (h, h)),
                "out_b": jnp.zeros((h,), jnp.float32),
                "attn_ln_scale": jnp.ones((h,), jnp.float32),
                "attn_ln_bias": jnp.zeros((h,), jnp.float32),
                "ffn_ln_scale": jnp.ones((h,), jnp.float32),
                "ffn_ln_bias": jnp.zeros((h,), jnp.float32),
            }
            if cfg.moe_num_experts > 0:
                from ..parallel.moe import init_moe_params

                layer.update(
                    init_moe_params(
                        next(keys), h, i_sz, cfg.moe_num_experts,
                        std=cfg.initializer_range,
                    )
                )
            else:
                layer.update(
                    {
                        "ffn_in_w": trunc(next(keys), (h, i_sz)),
                        "ffn_in_b": jnp.zeros((i_sz,), jnp.float32),
                        "ffn_out_w": trunc(next(keys), (i_sz, h)),
                        "ffn_out_b": jnp.zeros((h,), jnp.float32),
                    }
                )
            params[f"layer_{li:02d}"] = layer
        params["mlm_head"] = {
            "dense_w": trunc(next(keys), (h, h)),
            "dense_b": jnp.zeros((h,), jnp.float32),
            "ln_scale": jnp.ones((h,), jnp.float32),
            "ln_bias": jnp.zeros((h,), jnp.float32),
            # decoder weight is tied to embeddings["word"]
            "output_bias": jnp.zeros((v,), jnp.float32),
        }
        return params, {}

    # -- encoder -------------------------------------------------------------
    def embed(self, params, batch, *, train: bool, rng):
        """Embedding sum + LN + dropout (the encoder prologue). Returns
        (x, kv_mask, rng') — split out so pipeline stages can run it
        outside the layer loop."""
        cfg = self.cfg
        ids = batch["input_ids"]
        s = ids.shape[1]
        emb = params["embeddings"]
        # position_ids lets sequence-sharded callers pass each shard's
        # global positions (they shard along S with the rest of the batch)
        pos_ids = batch.get("position_ids")
        with scope("embed"):
            pos_emb = (
                emb["position"][jnp.arange(s)][None, :, :]
                if pos_ids is None
                else emb["position"][pos_ids]
            )
            x = emb["word"][ids] + pos_emb + emb["token_type"][batch["token_type_ids"]]
            x = _layer_norm(x, emb["ln_scale"], emb["ln_bias"], cfg.layer_norm_eps)
            if rng is not None:
                rng_emb, rng = jax.random.split(rng)
                x = _dropout(x, cfg.hidden_dropout, rng_emb, train)
            x = x.astype(self.compute_dtype)
        kv_mask = batch["attention_mask"].astype(jnp.int32)
        return x, kv_mask, rng

    def encode(self, params, batch, *, train: bool, rng):
        x, _ = self.encode_with_aux(params, batch, train=train, rng=rng)
        return x

    def encode_with_aux(self, params, batch, *, train: bool, rng):
        """(hidden states, aux loss): aux is the summed MoE router loss
        (0.0 for dense-FFN configs)."""
        cfg = self.cfg
        x, kv_mask, rng = self.embed(params, batch, train=train, rng=rng)

        def apply_one(lp, h, mask, lrng):
            # train stays a Python bool (dropout branches on it), so it
            # is closed over rather than passed through jax.checkpoint
            return self.layer_apply_with_aux(lp, h, mask, lrng, train)

        if cfg.remat:
            apply_one = jax.checkpoint(apply_one)
        aux_total = jnp.asarray(0.0, jnp.float32)
        for li in range(cfg.num_layers):
            lp = params[f"layer_{li:02d}"]
            with scope("rng"):
                lrng = jax.random.fold_in(rng, li) if rng is not None else None
            x, aux = apply_one(lp, x, kv_mask, lrng)
            aux_total = aux_total + aux
        return x, aux_total

    def layer_apply_with_aux(self, lp, x, kv_mask, rng=None, train=False):
        """One encoder layer (attention + FFN with post-LN residuals),
        returning (x, moe_aux).

        Factored out of :meth:`encode` so pipeline parallelism can scan
        a stage's stacked layer params through the identical math.
        """
        cfg = self.cfg
        cdt = self.compute_dtype
        b, s, _ = x.shape
        hd = cfg.hidden_size // cfg.num_heads
        tp = self.tp_axis

        def proj(w, b_, t):
            y = mxu_dot(t, w.astype(cdt)) + b_
            return y.astype(cdt)

        def row_proj(w, b_, t):
            """Row-parallel projection: local partial matmul, f/g-correct
            psum over tp (if sharded), replicated bias."""
            y = mxu_dot(t, w.astype(cdt))
            if tp is not None:
                y = _tp_reduce(y, tp)
            return (y + b_).astype(cdt)

        with scope("attn"):
            # column-parallel under tp: q_w is (h, h/ntp), so the local
            # head count falls out of the weight shape
            nh = lp["q_w"].shape[-1] // hd
            x_in = _tp_copy(x, tp) if tp is not None else x
            # one fused (h, 3h) matmul instead of three: a bigger MXU op
            # with identical math — y = x@[q|k|v] column-blocks exactly
            # equals the three separate products (params stay separate, so
            # checkpoints and tp sharding are unchanged)
            with scope("attn.proj"):
                qkv = proj(
                    jnp.concatenate([lp["q_w"], lp["k_w"], lp["v_w"]], axis=1),
                    jnp.concatenate([lp["q_b"], lp["k_b"], lp["v_b"]]),
                    x_in,
                )
            local_h = nh * hd
            q, k, v = (
                t.reshape(b, s, nh, hd)
                for t in jnp.split(qkv, (local_h, 2 * local_h), axis=-1)
            )
            # (B,S,H,D) -> (B,H,S,D)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            if rng is not None and train and cfg.attention_dropout > 0:
                rng, attn_rng = jax.random.split(rng)
            else:
                attn_rng = None
            impl = self.attention_impl
            if impl in ("ring", "ulysses"):
                from ..parallel.sequence import ring_attention, ulysses_attention

                sp_fn = ring_attention if impl == "ring" else ulysses_attention
                ctx = sp_fn(
                    q, k, v, axis_name=self.sp_axis, kv_mask=kv_mask,
                    dropout_rate=cfg.attention_dropout if train else 0.0,
                    dropout_rng=attn_rng,
                )
            else:
                ctx = attention(
                    q, k, v, kv_mask=kv_mask, force=impl,
                    dropout_rate=cfg.attention_dropout if train else 0.0,
                    dropout_rng=attn_rng,
                )
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
            with scope("attn.proj"):
                attn_out = row_proj(lp["out_w"], lp["out_b"], ctx)
            if rng is not None:
                k1, k2 = jax.random.split(rng)
                attn_out = _dropout(attn_out, cfg.hidden_dropout, k1, train)
            else:
                k2 = None
        with scope("residual"):
            x = x + attn_out
        x = _layer_norm(
            x, lp["attn_ln_scale"], lp["attn_ln_bias"], cfg.layer_norm_eps
        ).astype(cdt)
        aux = jnp.asarray(0.0, jnp.float32)
        if "router_w" in lp:  # MoE FFN (dropped tokens ride the residual)
            from ..parallel.moe import moe_ffn

            moe_params = {
                k: lp[k]
                for k in ("router_w", "w_in", "b_in", "w_out", "b_out")
            }
            ff, aux = moe_ffn(
                x, moe_params, ep_axis=self.ep_axis,
                capacity_factor=cfg.moe_capacity_factor,
                top_k=cfg.moe_top_k, z_loss_weight=cfg.moe_z_loss,
                dispatch=cfg.moe_dispatch, compute_dtype=cdt,
            )
            ff = _dropout(ff, cfg.hidden_dropout, k2, train)
        else:
            with scope("mlp.dense"):
                ff_in = _tp_copy(x, tp) if tp is not None else x
                ff = jax.nn.gelu(
                    proj(lp["ffn_in_w"], lp["ffn_in_b"], ff_in),
                    approximate=True,
                )
                ff = row_proj(lp["ffn_out_w"], lp["ffn_out_b"], ff)
                ff = _dropout(ff, cfg.hidden_dropout, k2, train)
        with scope("residual"):
            out = x + ff
        out = _layer_norm(
            out, lp["ffn_ln_scale"], lp["ffn_ln_bias"], cfg.layer_norm_eps
        ).astype(cdt)
        return out, aux

    # -- Solver protocol -----------------------------------------------------
    def apply(self, params, state, batch, *, train=None, rng=None):
        cfg = self.cfg
        train = bool(train)
        x, moe_aux = self.encode_with_aux(
            params, batch, train=train, rng=rng if train else None
        )
        b, s, h = x.shape
        with scope("loss"):  # the MLM head and the cross-entropy
            pos = batch["mlm_positions"]  # (B, M)
            gathered = jnp.take_along_axis(x, pos[:, :, None], axis=1)  # (B,M,H)
            head = params["mlm_head"]
            t = jax.nn.gelu(
                mxu_dot(gathered, head["dense_w"].astype(x.dtype))
                + head["dense_b"],
                approximate=True,
            )
            t = _layer_norm(t, head["ln_scale"], head["ln_bias"], cfg.layer_norm_eps)
            logits = (
                mxu_dot(
                    t.astype(self.compute_dtype),
                    params["embeddings"]["word"].T.astype(self.compute_dtype),
                )
                + head["output_bias"]
            )  # (B, M, V) f32
            labels = batch["mlm_labels"]
            weights = batch["mlm_weights"].astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[:, :, None], axis=-1)[..., 0]
            denom = jnp.maximum(jnp.sum(weights), 1.0)
            loss = jnp.sum(nll * weights) / denom
            if cfg.moe_num_experts > 0:
                loss = loss + cfg.moe_aux_weight * moe_aux
            acc = jnp.sum(
                (jnp.argmax(logits, -1) == labels).astype(jnp.float32) * weights
            ) / denom
        return {"loss": loss, "mlm_acc": acc}, state

    def token_loss_sums(self, params, state, batch, *, train=False, rng=None):
        """Token-level MLM loss pieces for sequence-sharded training.

        Unlike :meth:`apply` (which gathers ``mlm_positions`` — a global
        -index gather that cannot run on a sequence shard), this scores
        *every* local position and weights by ``mlm_weights`` of shape
        (B, S_local). Returns local partial sums
        ``(nll_sum, weight_sum, correct_sum)`` for the caller (the SP
        train step) to ``psum`` over the mesh.
        """
        nll, w, corr, _ = self.token_loss_sums_with_aux(
            params, state, batch, train=train, rng=rng
        )
        return nll, w, corr

    def token_loss_sums_with_aux(
        self, params, state, batch, *, train=False, rng=None
    ):
        """:meth:`token_loss_sums` plus the MoE router aux loss (0.0 for
        dense configs) — the expert-parallel train step consumes it."""
        x, aux = self.encode_with_aux(
            params, batch, train=bool(train), rng=rng
        )
        return (
            *self.token_loss_from_hidden(
                params, x, batch["mlm_labels"], batch["mlm_weights"]
            ),
            aux,
        )

    def token_loss_from_hidden(self, params, x, labels, weights):
        """MLM head + per-token NLL over hidden states ``x`` (B, S, H).
        Returns local partial sums (nll_sum, weight_sum, correct_sum)."""
        cfg = self.cfg
        with scope("loss"):
            head = params["mlm_head"]
            t = jax.nn.gelu(
                mxu_dot(x, head["dense_w"].astype(x.dtype)) + head["dense_b"],
                approximate=True,
            )
            t = _layer_norm(t, head["ln_scale"], head["ln_bias"], cfg.layer_norm_eps)
            logits = (
                mxu_dot(
                    t.astype(self.compute_dtype),
                    params["embeddings"]["word"].T.astype(self.compute_dtype),
                )
                + head["output_bias"]
            )  # (B, S_local, V)
            weights = weights.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
            return (
                jnp.sum(nll * weights),
                jnp.sum(weights),
                jnp.sum(correct * weights),
            )

    def loss_and_metrics(self, blobs):
        return blobs["loss"], {"loss": blobs["loss"], "mlm_acc": blobs["mlm_acc"]}

    def param_specs(self):
        """BERT convention: no weight decay on biases/LayerNorm params,
        expressed through Caffe decay_mult semantics."""

        def spec_for(name: str) -> Tuple[float, float]:
            nodecay = (
                name.endswith("_b")
                or name.endswith("_bias")
                or name.startswith("b_")  # MoE expert biases b_in/b_out
                or "ln_" in name
                or name in ("output_bias",)
            )
            return (1.0, 0.0 if nodecay else 1.0)

        names = {
            "embeddings": ["word", "position", "token_type", "ln_scale", "ln_bias"],
            "mlm_head": ["dense_w", "dense_b", "ln_scale", "ln_bias", "output_bias"],
        }
        if self.cfg.moe_num_experts > 0:
            ffn_names = ["router_w", "w_in", "b_in", "w_out", "b_out"]
        else:
            ffn_names = ["ffn_in_w", "ffn_in_b", "ffn_out_w", "ffn_out_b"]
        for li in range(self.cfg.num_layers):
            names[f"layer_{li:02d}"] = [
                "q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "out_w", "out_b",
                "attn_ln_scale", "attn_ln_bias",
                *ffn_names,
                "ffn_ln_scale", "ffn_ln_bias",
            ]
        return {layer: {n: spec_for(n) for n in ns} for layer, ns in names.items()}

    def dummy_batch(self):
        b, s, m = self.batch, self.seq_len, self.num_preds
        return {
            "input_ids": jnp.zeros((b, s), jnp.int32),
            "token_type_ids": jnp.zeros((b, s), jnp.int32),
            "attention_mask": jnp.ones((b, s), jnp.int32),
            "mlm_positions": jnp.zeros((b, m), jnp.int32),
            "mlm_labels": jnp.zeros((b, m), jnp.int32),
            "mlm_weights": jnp.ones((b, m), jnp.float32),
        }

    def num_params(self, params) -> int:
        import numpy as np

        return sum(
            int(np.prod(v.shape)) for lp in params.values() for v in lp.values()
        )
