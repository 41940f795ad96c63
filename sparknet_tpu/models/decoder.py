"""Causal decoder LMs — the pure-JAX decoder family, beside :mod:`.bert`:
:class:`DecoderLM` mixes window and full softmax attention over sparse
experts; :class:`HybridLM` (further down, with its own header) mixes gated
delta-rule linear attention (KDA) and latent attention (MLA);
:class:`MambaHybridLM` (with its own header) mixes Mamba-2 state-space
mixers and attention without positions over dense layers, on packed
documents; :class:`ConvHybridLM` (last, with its own header) mixes gated
short convolutions and attention with q/k norms over experts routed by
sigmoid with a selection bias.  They share DecoderLM's frame: the residual
layout, SwiGLU, the chunked loss, the counters and the Solver protocol.

DecoderLM is built from a published ``config.json``'s own keys
(:meth:`DecoderConfig.from_published`): ``layer_types`` (``full_attention``
or ``sliding_attention`` per layer), ``num_attention_heads_per_layer``,
``mlp_layer_types`` (``dense`` or ``sparse``), ``rope_parameters`` per
layer type.  Per layer, pre-norm: ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``; a final RMSNorm and an untied head; the loss is the mean
next-token cross-entropy over every position — or, on a batch of packed
documents (below), over the positions whose next token belongs to the same
document.

- **Attention**: grouped-query (``num_key_value_heads`` under a per-layer
  head count), no bias, rotary embeddings by layer type — ``default``
  (all of the head, or its first ``partial_rotary_factor``) or ``yarn``
  (the Hugging Face convention: blended inverse frequencies,
  ``attention_factor`` on cos and sin) — causal, with ``sliding_window``
  on sliding layers, through :func:`sparknet_tpu.ops.attention.attention`
  (the Pallas flash kernels on a TPU; they skip key blocks outside the
  window).
- **FFN**: SwiGLU. Dense layers at ``intermediate_size``; sparse layers
  are :func:`sparknet_tpu.parallel.moe.held_experts_ffn` — this chip's
  ``experts_held`` of the router's ``num_experts``, no token dropped —
  plus a shared expert with weight 1 where the file gives one a width.
  The router is the configuration's ``scoring_func``: ``sigmoid``
  (``route_sigmoid``, with ``moe_routed_scaling_factor``) or ``softmax``
  (``route_softmax``; a file that says ``norm_topk_prob: false`` is refused).
- **Packed documents**: a batch that also carries ``segment_ids`` and
  ``positions`` (B, S) (``data.text.packed_feed``) rotates by the position
  inside the document, attends inside documents only (the flash kernels
  skip key blocks that hold only other documents), and counts the loss
  where ``labels >= 0``.  Its counters beside the expert layer's:
  ``DOC_COUNTERS``.
- A published ``gating`` flag is not modelled: no equation comes with it.

It satisfies the :class:`~sparknet_tpu.solver.trainer.Solver` net protocol
as :class:`~.bert.BertMLM` does: float32 weights in the two-level layout,
``compute_dtype`` activations and matmul inputs, float32 norms, softmax,
router and loss.  Batch blobs: ``input_ids`` (B, S) and ``labels`` (B, S)
int32, the next token at every position (-100 where a packed batch has none).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import attention, document_spans, flash_tiles_documents
from ..ops.kda import KDA_MIN_LOG_DECAY, kda_chunks, kda_scan, uses_kernels
from ..ops.matmul import mxu_dot
from ..ops.rope import rope_tables, rope_to_heads, uses_rope_kernel
from ..ops.ssd import document_starts, ssd_chunks, ssd_scan
from ..ops.ssd import uses_kernels as ssd_uses_kernels
from ..utils.profiling import scope
from ..parallel.moe import (
    held_experts_ffn, init_held_experts_params, route_grouped, route_sigmoid,
    route_softmax,
)

FULL, SLIDING = "full_attention", "sliding_attention"
KDA, MLA = "kda", "mla"
MAMBA, ATTENTION = "mamba", "attention"
CONV = "conv"
COUNTERS = (
    "moe_slots_held", "moe_slots_in_kernel", "moe_load_max_over_mean",
    "moe_slots_dropped", "moe_slots_in_gmm",
)
# rows of q and k (B * S * (heads + KV heads), summed over the layers) that
# ops.rope.rope_to_heads rotated in the newest step: all of them where the
# kernel runs, 0 on apply_rope's path
ROPE_COUNTERS = ("rope_rows_in_kernel",)
KDA_COUNTERS = ("kda_chunks", "kda_chunks_in_kernel", "kda_decay_min")
# the Mamba-2 scan's: the chunks it walks one after another for a sequence;
# of them those walked inside its Pallas kernels (all of them where
# ops.ssd.uses_kernels says so, none on the jax.numpy path); the document
# starts inside a sequence at which the carried state was dropped; the
# smallest decay exp(delta A) of the step
SSD_COUNTERS = (
    "ssd_chunks", "ssd_chunks_in_kernel", "ssd_state_resets", "ssd_decay_min",
)
# the gated short convolution's: the document starts inside a sequence at
# which its taps stopped reading back, summed over the conv layers; and the
# share of slots whose expert the router's selection bias changed against an
# unbiased top-k, mean over the sparse layers
CONV_COUNTERS = ("short_conv_resets", "moe_bias_rerouted")
# of a batch of packed documents, newest step: the documents in it; the
# positions that bear a loss; the keys every token sees, summed over the
# batch, in one layer of a kind (times heads and head size: a product's
# multiply-adds); the score tiles a batch-head of such a layer executes in
# the flash forward and dq kernels (ops.attention.flash_tiles_documents)
DOC_COUNTERS = (
    "doc_count", "loss_positions", "attn_pairs_full", "attn_pairs_window",
    "flash_tiles_docs_full", "flash_tiles_docs_window",
)
# a counter over the layers that report it: the mean of the slots held (and
# of those whose rows the moe_combine kernel read, and of those whose
# grouped products the ops.gmm kernels computed: as many, or none), the
# worst load ratio, every slot dropped; the chunks a sequence (the same in
# every layer), the smallest decay anywhere, the resets of every layer
_REDUCE = {
    "moe_slots_held": jnp.mean, "moe_slots_in_kernel": jnp.mean,
    "moe_slots_in_gmm": jnp.mean,
    "moe_load_max_over_mean": jnp.max, "moe_slots_dropped": jnp.sum,
    "rope_rows_in_kernel": jnp.sum, "kda_chunks": jnp.max,
    "kda_chunks_in_kernel": jnp.max, "kda_decay_min": jnp.min,
    "ssd_chunks": jnp.max, "ssd_chunks_in_kernel": jnp.max,
    "ssd_state_resets": jnp.sum, "ssd_decay_min": jnp.min,
    "short_conv_resets": jnp.sum, "moe_bias_rerouted": jnp.mean,
    **dict.fromkeys(DOC_COUNTERS, jnp.max),  # of the batch: reported once
}


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_key_value_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_attention_heads_per_layer: Tuple[int, ...]
    rope_parameters: Mapping[str, Mapping[str, Any]]
    sliding_window: int
    # the router's width, and which of its experts live here
    num_experts: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    moe_routed_scaling_factor: float = 1.0
    # the router: "sigmoid" scores times the factor above, or "softmax"
    # probabilities; both normalised over the chosen
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # recompute each layer in the backward pass; tokens per chunk of the
    # loss (the logits of one chunk are all that exist at a time)
    remat: bool = False
    loss_chunk: int = 4096

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def from_published(cls, published: Mapping[str, Any], **overrides):
        """From a ``config.json`` (or a cut of one: ``num_hidden_layers``
        takes the first so many entries of the per-layer lists;
        ``num_experts`` counts the experts held here, from
        ``deployment.experts_first`` on, of the router's
        ``deployment.num_experts_routed`` — both default to all)."""
        if not published.get("norm_topk_prob", True):
            raise ValueError(
                "norm_topk_prob false: both routers normalise over the chosen"
            )
        n = published["num_hidden_layers"]
        heads = published.get("num_attention_heads_per_layer") or (
            [published["num_attention_heads"]] * n
        )
        deployment = published.get("deployment", {})
        held = published.get("num_experts", 0)
        fields = dict(
            vocab_size=published["vocab_size"],
            hidden_size=published["hidden_size"],
            intermediate_size=published["intermediate_size"],
            num_key_value_heads=published["num_key_value_heads"],
            head_dim=published["head_dim"],
            layer_types=tuple(published["layer_types"][:n]),
            mlp_layer_types=tuple(published["mlp_layer_types"][:n]),
            num_attention_heads_per_layer=tuple(heads[:n]),
            rope_parameters=published["rope_parameters"],
            sliding_window=published["sliding_window"],
            num_experts=deployment.get("num_experts_routed", held),
            experts_held=(deployment.get("experts_first", 0), held),
            num_experts_per_tok=published.get("num_experts_per_tok", 1),
            moe_intermediate_size=published.get("moe_intermediate_size", 0),
            shared_expert_intermediate_size=published.get(
                "shared_expert_intermediate_size", 0
            ),
            moe_routed_scaling_factor=published.get(
                "moe_routed_scaling_factor", 1.0
            ),
            scoring_func=published.get("scoring_func", "sigmoid"),
            rms_norm_eps=published["rms_norm_eps"],
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def tiny(cls, **overrides) -> "DecoderConfig":
        """Every kind of layer at a size for CPU tests: dense + sliding +
        full, head counts that differ, 16 experts with 4 held, top-2,
        window 8, yarn on half the head of full layers."""
        fields = dict(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_key_value_heads=2, head_dim=16,
            layer_types=(FULL, SLIDING, SLIDING, FULL),
            mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
            num_attention_heads_per_layer=(4, 6, 6, 4),
            rope_parameters={
                FULL: {
                    "rope_type": "yarn", "rope_theta": 500000, "factor": 8,
                    "original_max_position_embeddings": 16, "beta_slow": 1,
                    "beta_fast": 4, "attention_factor": 1.2,
                    "partial_rotary_factor": 0.5,
                },
                SLIDING: {
                    "rope_type": "default", "rope_theta": 10000,
                    "partial_rotary_factor": 1,
                },
            },
            sliding_window=8, num_experts=16, experts_held=(4, 4),
            num_experts_per_tok=2, moe_intermediate_size=16,
            shared_expert_intermediate_size=16,
            moe_routed_scaling_factor=2.5, loss_chunk=32,
        )
        fields.update(overrides)
        return cls(**fields)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    with scope("norm"):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * scale).astype(x.dtype)


def rope_inv_freq(rope: Mapping[str, Any], head_dim: int) -> Tuple[jax.Array, float]:
    """(inverse frequencies (rot/2,), factor on cos and sin) of one layer
    type's ``rope_parameters`` entry, as transformers'
    ``ROPE_INIT_FUNCTIONS`` compute them."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base = float(rope["rope_theta"])
    pos_freqs = base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return 1.0 / pos_freqs, 1.0
    if kind != "yarn":
        raise NotImplementedError(f"rope_type {kind!r}")
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (
            rot * math.log(original / (rotations * 2 * math.pi))
            / (2 * math.log(base))
        )

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(rot // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    extrapolation = 1.0 - ramp
    inv_freq = (
        (1.0 / (factor * pos_freqs)) * (1.0 - extrapolation)
        + (1.0 / pos_freqs) * extrapolation
    )
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv_freq, float(scale)


def apply_rope(x, positions, inv_freq, scale):
    """Rotate the first ``2 * len(inv_freq)`` dims of each head of ``x``
    (B, S, H, D), in float32, by the rotate-half convention; ``positions``
    (S,), or (B, S) where they differ by sequence (packed documents)."""
    rot = 2 * inv_freq.shape[0]
    angles = jnp.atleast_2d(positions).astype(jnp.float32)[:, :, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)  # (B or 1, S, rot)
    cos = (jnp.cos(angles) * scale)[:, :, None, :]
    sin = (jnp.sin(angles) * scale)[:, :, None, :]
    xf = x.astype(jnp.float32)
    xr, rest = xf[..., :rot], xf[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., : rot // 2]], axis=-1)
    return jnp.concatenate([xr * cos + half * sin, rest], axis=-1)


def swiglu(u, gate_w, up_w, down_w):
    """``(silu(u gate) * (u up)) down``; ``u`` in the compute type, the
    activation in float32, float32 out."""
    cdt = u.dtype
    act = jax.nn.silu(mxu_dot(u, gate_w.astype(cdt))) * mxu_dot(
        u, up_w.astype(cdt)
    )
    return mxu_dot(act.astype(cdt), down_w.astype(cdt))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class DecoderLM:
    """Functional decoder + untied head; see the module docstring."""

    counters = COUNTERS + ROPE_COUNTERS
    doc_counters = DOC_COUNTERS  # beside them on a packed batch

    def __init__(
        self,
        config: DecoderConfig,
        input_shapes: Dict[str, Tuple[int, ...]],
        compute_dtype: Any = jnp.float32,
        attention_impl: Optional[str] = None,  # None=auto, flash, reference
    ):
        cfg = self.cfg = config
        self.compute_dtype = compute_dtype
        self.attention_impl = attention_impl
        if "input_ids" not in input_shapes:
            raise ValueError("input_shapes must provide 'input_ids' (B, S)")
        if len(cfg.mlp_layer_types) != cfg.num_layers:
            raise ValueError("the per-layer lists differ in length")
        self._check_layers()
        b, s = input_shapes["input_ids"]
        self.batch, self.seq_len = b, s
        self.input_names: List[str] = ["input_ids", "labels"]
        # packed documents: the batch carries these two blobs as well
        self.packed = "segment_ids" in input_shapes
        if self.packed:
            self._check_packed()
            self.input_names += ["segment_ids", "positions"]
            self.counters = self.counters + self.doc_counters
        self.blob_shapes: Dict[str, Tuple[int, ...]] = {
            **{name: (b, s) for name in self.input_names}, "loss": (),
            "token_acc": (), **{name: () for name in self.counters},
        }

    def _check_packed(self) -> None:
        pass  # every layer of this model keeps to a document's bounds

    def _check_layers(self) -> None:
        cfg = self.cfg
        if len(cfg.num_attention_heads_per_layer) != cfg.num_layers:
            raise ValueError("the per-layer lists differ in length")
        for kind in set(cfg.layer_types):
            if kind not in (FULL, SLIDING) or kind not in cfg.rope_parameters:
                raise ValueError(f"layer type {kind!r}")
        for heads in cfg.num_attention_heads_per_layer:
            if heads % cfg.num_key_value_heads:
                raise ValueError(
                    f"{heads} heads over {cfg.num_key_value_heads} KV heads"
                )
        if cfg.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func {cfg.scoring_func!r}")

    # -- init ----------------------------------------------------------------
    def init(self, rng: jax.Array):
        cfg = self.cfg
        h, d, kv = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
        keys = iter(jax.random.split(rng, 4 + 12 * cfg.num_layers))

        def trunc(shape):
            return cfg.initializer_range * jax.random.truncated_normal(
                next(keys), -2.0, 2.0, shape, jnp.float32
            )

        ones = lambda: jnp.ones((h,), jnp.float32)
        params: Dict[str, Dict[str, jax.Array]] = {
            "embed": {"tokens": trunc((cfg.vocab_size, h))}
        }
        for li in range(cfg.num_layers):
            heads = cfg.num_attention_heads_per_layer[li]
            layer = {
                "attn_norm": ones(),
                "q_w": trunc((h, heads * d)),
                "k_w": trunc((h, kv * d)),
                "v_w": trunc((h, kv * d)),
                "o_w": trunc((heads * d, h)),
                "ffn_norm": ones(),
            }
            layer.update(self._init_ffn(li, trunc, keys))
            params[f"layer_{li:02d}"] = layer
        params["head"] = {
            "norm": ones(), "lm_w": trunc((h, cfg.vocab_size)),
        }
        return params, {}

    def _init_ffn(self, li: int, trunc, keys):
        """Layer ``li``'s FFN weights: the dense SwiGLU, or the held experts
        with their router and, where the configuration gives it a width,
        the shared expert."""
        cfg = self.cfg
        h, ffn = cfg.hidden_size, {}
        if cfg.mlp_layer_types[li] == "sparse":
            ffn.update(init_held_experts_params(
                next(keys), h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.experts_held[1], std=cfg.initializer_range,
            ))
            width = cfg.shared_expert_intermediate_size
            prefix = "shared_"
            if not width:
                return ffn
        else:
            width, prefix = cfg.intermediate_size, ""
        ffn.update({
            prefix + "gate_w": trunc((h, width)),
            prefix + "up_w": trunc((h, width)),
            prefix + "down_w": trunc((width, h)),
        })
        return ffn

    # -- layers --------------------------------------------------------------
    def _attention(self, li: int, lp, u, docs=None):
        """(float32 output, the layer's rotary counter).  ``docs``:
        (segment_ids, positions) of a packed batch, else the sequence is
        one document.  q and k go from their projection's float32 product
        to the flash kernels' head-major layout through
        :func:`sparknet_tpu.ops.rope.rope_to_heads` where
        :func:`~sparknet_tpu.ops.rope.uses_rope_kernel` says so, else
        through :func:`apply_rope`, a cast and a transpose."""
        cfg, cdt = self.cfg, self.compute_dtype
        b, s, _ = u.shape
        kind = cfg.layer_types[li]
        heads = cfg.num_attention_heads_per_layer[li]
        kv, d = cfg.num_key_value_heads, cfg.head_dim
        inv_freq, factor = rope_inv_freq(cfg.rope_parameters[kind], d)
        segment_ids, positions = docs or (None, jnp.arange(s))
        rot = 2 * inv_freq.shape[0]
        kernel = uses_rope_kernel(s, d, rot, self.attention_impl)
        if kernel:
            with scope("attn.rope"):
                tables = rope_tables(positions, inv_freq, factor, d)

        def project(name, n, rotate):
            with scope("attn.proj"):
                t = mxu_dot(u, lp[name + "_w"].astype(cdt))
            if rotate:
                t = self._qk_norm(lp, name, t)
            if rotate and kernel:
                with scope("attn.rope"):
                    return rope_to_heads(t, *tables, rot, cdt)
            t = t.reshape(b, s, n, d)
            if rotate:
                with scope("attn.rope"):
                    t = apply_rope(t, positions, inv_freq, factor)
            return t.astype(cdt).transpose(0, 2, 1, 3)  # (B, n, S, D)

        out = attention(
            project("q", heads, True),
            project("k", kv, True),
            project("v", kv, False),
            causal=True,
            window=cfg.sliding_window if kind == SLIDING else None,
            segment_ids=segment_ids, force=self.attention_impl,
        )
        out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
        with scope("attn.proj"):
            out = mxu_dot(out, lp["o_w"].astype(cdt))
        rows = b * s * (heads + kv) if kernel else 0
        return out, {"rope_rows_in_kernel": jnp.asarray(rows, jnp.float32)}

    def _qk_norm(self, lp, name, t):
        """Hook on q's or k's float32 projection ``t`` (B, S, heads * d)
        before rotary (``name`` "q" or "k"): none here."""
        return t

    def _router(self, xt, lp):
        """(weights, experts) a token, by the configuration's scoring."""
        cfg = self.cfg
        if cfg.scoring_func == "softmax":
            return route_softmax(xt, lp["router_w"], cfg.num_experts_per_tok)
        return route_sigmoid(
            xt, lp["router_w"], cfg.num_experts_per_tok,
            cfg.moe_routed_scaling_factor,
        )

    def _ffn(self, li: int, lp, u):
        """(float32 FFN output, this layer's counters)."""
        cfg = self.cfg
        if cfg.mlp_layer_types[li] != "sparse":
            with scope("mlp.dense"):
                return swiglu(u, lp["gate_w"], lp["up_w"], lp["down_w"]), {}
        routed, counters = held_experts_ffn(
            u, lp, experts_held=cfg.experts_held,
            top_k=cfg.num_experts_per_tok,
            compute_dtype=self.compute_dtype, router=self._router,
            force=self.attention_impl,
        )
        if not cfg.shared_expert_intermediate_size:
            return routed.astype(jnp.float32), counters
        with scope("moe.shared"):
            shared = swiglu(
                u, lp["shared_gate_w"], lp["shared_up_w"], lp["shared_down_w"]
            )
        return shared + routed.astype(jnp.float32), counters

    def _mix(self, li: int, lp, u, docs=None):
        """The layer's token mixer on the normed ``u``: (float32 output,
        its counters), under the layer kind's scope."""
        kind = "attn.window" if self.cfg.layer_types[li] == SLIDING else "attn.full"
        with scope(kind):
            return self._attention(li, lp, u, docs)

    def layer_apply(self, li: int, lp, x, docs=None):
        """One layer on ``x`` (B, S, h): (x, the layer's counters)."""
        cfg, cdt = self.cfg, self.compute_dtype
        mixed, counters = self._mix(
            li, lp, rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps), docs
        )
        with scope("residual"):
            x = (x.astype(jnp.float32) + mixed).astype(cdt)
        fed, ffn_counters = self._ffn(
            li, lp, rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
        )
        with scope("residual"):
            x = (x.astype(jnp.float32) + fed).astype(cdt)
        return x, {**counters, **ffn_counters}

    def hidden(self, params, input_ids, docs=None):
        """The final-layer hidden states (before the head's norm) and the
        layers' counters, one dict a layer."""
        cfg = self.cfg
        with scope("embed"):
            x = self._embed(params["embed"]["tokens"], input_ids)
        counted = []
        for li in range(cfg.num_layers):
            fn = lambda lp, x, li=li: self.layer_apply(li, lp, x, docs)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            x, counters = fn(params[f"layer_{li:02d}"], x)
            counted.append(counters)
        return x, counted

    def _embed(self, table, input_ids):
        """The rows of the embedding ``table`` for ``input_ids``, in the
        compute type."""
        return table[input_ids].astype(self.compute_dtype)

    def _head_weight(self, params):
        """The head's (hidden, vocabulary) matrix in the compute type."""
        return params["head"]["lm_w"].astype(self.compute_dtype)

    def _logits(self, xc, lm_w):
        return mxu_dot(xc, lm_w)  # (chunk, V) f32

    def _loss(self, params, x, labels):
        """(mean next-token NLL, accuracy) over every position — of a
        packed batch, over the positions that bear a label (``labels >=
        0``) — the logits made ``loss_chunk`` tokens at a time and not
        kept."""
        cfg = self.cfg
        x = rms_norm(x, params["head"]["norm"], cfg.rms_norm_eps)
        tokens = labels.size
        chunk = min(cfg.loss_chunk, tokens)
        if tokens % chunk:
            raise ValueError(
                f"loss_chunk {cfg.loss_chunk} does not divide {tokens} tokens"
            )
        xs = x.reshape(tokens // chunk, chunk, x.shape[-1])
        ys = labels.reshape(tokens // chunk, chunk)
        lm_w = self._head_weight(params)

        @jax.checkpoint
        def one(xc, yc):
            logits = self._logits(xc, lm_w)
            with scope("loss"):  # inside lm_head: the head's product is not
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                if self.packed:
                    borne = yc >= 0
                    picked = jnp.take_along_axis(
                        logits, jnp.maximum(yc, 0)[:, None], axis=-1
                    )[:, 0]
                    hit = borne & (jnp.argmax(logits, -1) == yc)
                    return (
                        jnp.sum(jnp.where(borne, lse - picked, 0.0)),
                        jnp.sum(hit.astype(jnp.float32)),
                    )
                picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
                hit = jnp.argmax(logits, -1) == yc
                return jnp.sum(lse - picked), jnp.sum(hit.astype(jnp.float32))

        def body(carry, xy):
            nll, hit = one(*xy)
            return (carry[0] + nll, carry[1] + hit), None

        (nll, hit), _ = jax.lax.scan(body, (0.0, 0.0), (xs, ys))
        if self.packed:
            tokens = jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)
        return nll / tokens, hit / tokens

    def _doc_counters(self, batch):
        """``doc_counters`` of a packed batch: a few vector operations on
        its ``segment_ids`` and ``labels`` (the window's two where the
        model has a window)."""
        window = getattr(self.cfg, "sliding_window", None)
        seg = batch["segment_ids"]
        at = jnp.arange(seg.shape[1], dtype=jnp.int32)
        start, _ = document_spans(seg)
        seen = at - start + 1  # the keys a token sees in a full layer
        total = lambda x: jnp.sum(x).astype(jnp.float32)
        counted = {
            "doc_count": total(at == start),
            "loss_positions": total(batch["labels"] >= 0),
            "attn_pairs_full": total(seen),
        }
        if window is not None:
            counted["attn_pairs_window"] = total(jnp.minimum(seen, window))
        counted["flash_tiles_docs_full"] = flash_tiles_documents(seg)
        if window is not None:
            counted["flash_tiles_docs_window"] = flash_tiles_documents(
                seg, window=window
            )
        return counted

    # -- Solver protocol -----------------------------------------------------
    def apply(self, params, state, batch, *, train=None, rng=None):
        docs = (batch["segment_ids"], batch["positions"]) if self.packed else None
        x, counted = self.hidden(params, batch["input_ids"], docs)
        with scope("lm_head"):
            loss, acc = self._loss(params, x, batch["labels"])
        blobs = {"loss": loss, "token_acc": acc}
        with scope("counters"):
            if self.packed:
                counted = counted + [self._doc_counters(batch)]
            for name in self.counters:
                seen = [c[name] for c in counted if name in c]
                blobs[name] = (
                    _REDUCE[name](jnp.stack(seen)) if seen
                    else jnp.zeros((), jnp.float32)
                )
        return blobs, state

    def loss_and_metrics(self, blobs):
        return blobs["loss"], {
            k: blobs[k] for k in ("loss", "token_acc", *self.counters)
        }

    _no_decay = ()  # leaves without weight decay beside the norm scales
    _buffers = ()  # leaves no step moves

    def param_specs(self):
        """(lr_mult, decay_mult) a leaf: no weight decay on the vectors
        (norm scales, Caffe decay_mult 0, and ``_no_decay``); ``_buffers``
        do not move."""
        params, _ = jax.eval_shape(self.init, jax.random.PRNGKey(0))

        def spec(name):
            if name in self._buffers:
                return (0.0, 0.0)
            still = "norm" in name or name in self._no_decay
            return (1.0, 0.0 if still else 1.0)

        return {
            layer: {n: spec(n) for n in leaves}
            for layer, leaves in params.items()
        }

    def dummy_batch(self):
        zeros = jnp.zeros((self.batch, self.seq_len), jnp.int32)
        return {name: zeros for name in self.input_names}

    def num_params(self, params) -> int:
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# The hybrid: gated delta-rule linear attention (KDA) and latent attention
# (MLA) mixed, over experts the router selects by groups
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """A ``bailing_hybrid`` ``config.json``, as :class:`HybridLM` needs it.
    The names DecoderConfig has mean the same here."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    head_dim: int  # a KDA head's keys and values
    layer_types: Tuple[str, ...]  # KDA or MLA
    mlp_layer_types: Tuple[str, ...]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = KDA_MIN_LOG_DECAY
    num_experts: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    num_experts_per_tok: int = 1
    n_group: int = 1
    topk_group: int = 1
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    moe_routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    remat: bool = False
    loss_chunk: int = 4096
    kda_chunk: int = 64  # tokens a chunk of ops.kda.kda_scan
    kda_segment: int = 1024  # tokens a checkpointed segment of a KDA layer

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def from_published(cls, published: Mapping[str, Any], **overrides):
        """From the published keys (or a cut: ``deployment.layers_kept``
        lists the published indices of the ``num_hidden_layers`` layers
        held, default the first so many; ``num_experts`` counts the experts
        held of ``deployment.num_experts_routed``).  Published layer *i* is
        MLA if ``(i + 1) % layer_group_size == 0``, else KDA; it has the
        dense FFN if ``i < first_k_dense_replace``."""
        n = published["num_hidden_layers"]
        deployment = published.get("deployment", {})
        kept = list(deployment.get("layers_kept", range(n)))
        if len(kept) != n:
            raise ValueError(f"layers_kept {kept} against {n} layers")
        if published["kda_lower_bound"] < KDA_MIN_LOG_DECAY:
            raise ValueError(
                f"kda_lower_bound {published['kda_lower_bound']} is under "
                f"what ops.kda.kda_scan keeps finite ({KDA_MIN_LOG_DECAY})"
            )
        group = published["layer_group_size"]
        held = published["num_experts"]
        fields = dict(
            vocab_size=published["vocab_size"],
            hidden_size=published["hidden_size"],
            intermediate_size=published["intermediate_size"],
            num_attention_heads=published["num_attention_heads"],
            head_dim=published["head_dim"],
            layer_types=tuple(
                MLA if (i + 1) % group == 0 else KDA for i in kept
            ),
            mlp_layer_types=tuple(
                "dense" if i < published["first_k_dense_replace"] else "sparse"
                for i in kept
            ),
            kv_lora_rank=published["kv_lora_rank"],
            qk_nope_head_dim=published["qk_nope_head_dim"],
            qk_rope_head_dim=published["qk_rope_head_dim"],
            v_head_dim=published["v_head_dim"],
            rope_theta=published["rope_theta"],
            short_conv_kernel_size=published["short_conv_kernel_size"],
            kda_lower_bound=published["kda_lower_bound"],
            num_experts=deployment.get("num_experts_routed", held),
            experts_held=(deployment.get("experts_first", 0), held),
            num_experts_per_tok=published["num_experts_per_tok"],
            n_group=published["n_group"],
            topk_group=published["topk_group"],
            moe_intermediate_size=published["moe_intermediate_size"],
            shared_expert_intermediate_size=(
                published["moe_shared_expert_intermediate_size"]
                * published["num_shared_experts"]
            ),
            moe_routed_scaling_factor=published["routed_scaling_factor"],
            rms_norm_eps=published["rms_norm_eps"],
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def tiny(cls, **overrides) -> "HybridConfig":
        """Both kinds of layer and both FFNs at a size for CPU tests: a
        period of three (KDA dense, KDA, MLA), 16 experts in 4 groups with
        4 held, 2 of the groups and 3 experts a token."""
        fields = dict(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_attention_heads=4, head_dim=8,
            layer_types=(KDA, KDA, MLA), mlp_layer_types=("dense", "sparse", "sparse"),
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, rope_theta=10000.0, num_experts=16,
            experts_held=(4, 4), num_experts_per_tok=3, n_group=4,
            topk_group=2, moe_intermediate_size=16,
            shared_expert_intermediate_size=16,
            moe_routed_scaling_factor=2.5, loss_chunk=32, kda_chunk=16,
            kda_segment=32,
        )
        fields.update(overrides)
        return cls(**fields)


def causal_conv(x, w, history=None, bias=None, segment_ids=None, history_ids=None):
    """Depthwise causal convolution: ``y_t = sum_j w[j] * x_{t-(K-1)+j}``
    (``w[K-1]`` meets the current token), plus ``bias`` (C,) where given.
    ``x``: (B, S, C); ``w``: (K, C); ``history`` (B, K-1, C): the positions
    before the first, zeros where None.  With ``segment_ids`` (B, S) a tap
    reads 0 where its token lies in another document than the current
    token; ``history_ids`` (B, K-1) are the history's ids (None: no
    document of this call's)."""
    taps, s = w.shape[0], x.shape[1]
    if history is None:
        history = jnp.zeros((x.shape[0], taps - 1, x.shape[2]), x.dtype)
    padded = jnp.concatenate([history, x], axis=1)
    if segment_ids is None:
        out = sum(padded[:, j:j + s] * w[j] for j in range(taps))
    else:
        if history_ids is None:
            history_ids = jnp.full((x.shape[0], taps - 1), -1, segment_ids.dtype)
        ids = jnp.concatenate([history_ids, segment_ids], axis=1)
        out = sum(
            jnp.where((ids[:, j:j + s] == segment_ids)[..., None], padded[:, j:j + s], 0.0)
            * w[j] for j in range(taps)
        )
    return out if bias is None else out + bias


def rope_interleaved(x, positions, theta):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis of ``x``
    (B, S, H, R) by ``position * theta ** (-2i / R)``, in float32."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], r // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


class HybridLM(DecoderLM):
    """A decoder whose layers are KDA (Kimi Delta Attention: short causal
    convolutions, L2-normed q and k, a bounded per-channel decay gate, the
    gated delta-rule scan of :mod:`sparknet_tpu.ops.kda`, a gated per-head
    RMSNorm, no rotary) or MLA (latent attention in its expanded form:
    keys and values rebuilt from a ``kv_lora_rank``-wide latent, one rotary
    key shared by all heads, through the flash kernels at head sizes
    ``qk_nope + qk_rope`` for q and k and ``v_head_dim`` for v); the sparse
    FFN's router selects by groups on biased scores
    (:func:`sparknet_tpu.parallel.moe.route_grouped`; the bias is a buffer
    that starts at 0 and no step moves).  The frame is DecoderLM's.

    Counters beside DecoderLM's: ``kda_chunks`` (the chunks the scan walks
    one after another for a sequence), ``kda_chunks_in_kernel`` (those of
    them walked inside the Pallas kernels of ``ops/kda.py``: all where
    ``kda_scan`` takes the kernels, 0 on its ``jax.numpy`` path; decided
    with the scan's own rule) and ``kda_decay_min`` (the smallest
    decay ``alpha`` of the step, over every KDA layer, token and channel: a
    gate that underflows shows here)."""

    counters = COUNTERS + KDA_COUNTERS
    _no_decay = ("A_log", "dt_bias")  # the decay gate's vectors
    _buffers = ("router_bias",)  # the selection bias: it starts at 0 and stays

    def _check_packed(self) -> None:
        raise NotImplementedError(
            "packed documents through KDA layers: the scan's state and the "
            "convolutions' history would have to reset at a boundary"
        )

    def _check_layers(self) -> None:
        cfg = self.cfg
        for kind in set(cfg.layer_types):
            if kind not in (KDA, MLA):
                raise ValueError(f"layer type {kind!r}")
        if cfg.num_experts % max(cfg.n_group, 1):
            raise ValueError(f"{cfg.num_experts} experts in {cfg.n_group} groups")

    # -- init ----------------------------------------------------------------
    def init(self, rng: jax.Array):
        cfg = self.cfg
        h, heads, d = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        keys = iter(jax.random.split(rng, 4 + 20 * cfg.num_layers))

        def trunc(shape):
            return cfg.initializer_range * jax.random.truncated_normal(
                next(keys), -2.0, 2.0, shape, jnp.float32
            )

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        ones = lambda n=h: jnp.ones((n,), jnp.float32)
        params: Dict[str, Dict[str, jax.Array]] = {
            "embed": {"tokens": trunc((cfg.vocab_size, h))}
        }
        for li in range(cfg.num_layers):
            layer = {"attn_norm": ones(), "ffn_norm": ones()}
            if cfg.layer_types[li] == KDA:
                taps = cfg.short_conv_kernel_size
                bound = taps ** -0.5
                # the decay a token starts from: dt log-uniform in
                # [1e-3, 1e-1], dt_bias its inverse softplus
                dt = jnp.exp(uniform((heads * d,), math.log(1e-3), math.log(1e-1)))
                layer.update({
                    "q_w": trunc((h, heads * d)), "k_w": trunc((h, heads * d)),
                    "v_w": trunc((h, heads * d)), "f_w": trunc((h, heads * d)),
                    "g_w": trunc((h, heads * d)), "beta_w": trunc((h, heads)),
                    "o_w": trunc((heads * d, h)),
                    "q_conv": uniform((taps, heads * d), -bound, bound),
                    "k_conv": uniform((taps, heads * d), -bound, bound),
                    "v_conv": uniform((taps, heads * d), -bound, bound),
                    "A_log": jnp.log(uniform((heads,), 1.0, 4.0)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "o_norm": ones(d),
                })
            else:
                qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                layer.update({
                    "q_w": trunc((h, heads * qk)),
                    "kv_a_w": trunc((h, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
                    "kv_a_norm": ones(cfg.kv_lora_rank),
                    "kv_b_w": trunc((
                        cfg.kv_lora_rank,
                        heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                    )),
                    "o_w": trunc((heads * cfg.v_head_dim, h)),
                })
            layer.update(self._init_ffn(li, trunc, keys))
            if cfg.mlp_layer_types[li] == "sparse":
                layer["router_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
            params[f"layer_{li:02d}"] = layer
        params["head"] = {
            "norm": ones(), "lm_w": trunc((h, cfg.vocab_size)),
        }
        return params, {}

    # -- layers --------------------------------------------------------------
    def _router(self, xt, lp):
        cfg = self.cfg
        return route_grouped(
            xt, lp["router_w"], lp["router_bias"], cfg.num_experts_per_tok,
            cfg.moe_routed_scaling_factor, cfg.n_group, cfg.topk_group,
        )

    def _mix(self, li: int, lp, u, docs=None):
        if self.cfg.layer_types[li] == KDA:
            with scope("attn.kda"):
                return self._kda(lp, u)
        with scope("attn.mla"):
            return self._mla(lp, u), {}

    def _kda(self, lp, u):
        """The KDA mixer, ``kda_segment`` tokens at a time (the whole of a
        sequence shorter than that): a ``lax.scan`` over segments of the
        sequence carries the scan's state and the convolutions' last
        inputs, and each segment is a checkpoint.  What lives at once
        between a projection and the output product (some twenty float32
        tensors of tokens x 4096, and what ``kda_scan`` keeps for its
        backward pass: every chunk's entering state and ``T`` where it
        takes the Pallas kernels, 40 MB a segment of 1024 tokens and 32
        heads; its chunk matrices on the ``jax.numpy`` path) is then one
        segment's; at 16 384 tokens the sequence's would be several GB.
        A longer sequence is whole segments: there is no fallback that
        would give the bound up.  ``attention_impl`` governs the scan's
        kernels as it governs the flash kernels.  (Under ``remat`` the
        layer's checkpoint runs the mixer a second time and a segment's a
        third.  Leaving the layer's off a KDA layer would save that pass,
        and the step would need 15.62 GB in place of 14.88: PERF.md
        section 7.)"""
        cfg, cdt = self.cfg, self.compute_dtype
        b, s, hidden = u.shape
        heads, d = cfg.num_attention_heads, cfg.head_dim
        taps = cfg.short_conv_kernel_size
        seg = min(cfg.kda_segment, s)
        if s % seg:
            raise ValueError(
                f"{s} tokens a sequence are not whole KDA segments of "
                f"{cfg.kda_segment} (HybridConfig.kda_segment)"
            )
        by_head = lambda x: x.reshape(b, seg, heads, d).transpose(0, 2, 1, 3)

        @jax.checkpoint
        def segment(carry, u_s):
            state, history = carry
            def project(name):  # float32
                with scope("attn.proj"):
                    return mxu_dot(u_s, lp[name].astype(cdt))

            mixed, latest = {}, {}
            for name in ("q", "k", "v"):
                pre = project(name + "_w")
                mixed[name] = by_head(jax.nn.silu(
                    causal_conv(pre, lp[name + "_conv"], history[name])
                ))
                latest[name] = jnp.concatenate(
                    [history[name], pre], axis=1
                )[:, -(taps - 1):]
            unit = lambda x: x * jax.lax.rsqrt(
                jnp.sum(x * x, -1, keepdims=True) + 1e-6
            )
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(lp["A_log"])[:, None, None]
                * by_head(project("f_w") + lp["dt_bias"])
            )
            beta = jax.nn.sigmoid(project("beta_w")).transpose(0, 2, 1)
            out, state = kda_scan(
                (unit(mixed["q"]) * d ** -0.5).astype(cdt),
                unit(mixed["k"]).astype(cdt), mixed["v"].astype(cdt), g, beta,
                chunk=cfg.kda_chunk, initial_state=state, return_state=True,
                force=self.attention_impl,
            )  # (B, H, seg, d) float32
            out = rms_norm(out.transpose(0, 2, 1, 3), lp["o_norm"], cfg.rms_norm_eps)
            out = out.reshape(b, seg, heads * d) * jax.nn.sigmoid(project("g_w"))
            with scope("attn.proj"):
                y = mxu_dot(out.astype(cdt), lp["o_w"].astype(cdt))
            return (state, latest), (y, jnp.min(g))

        zeros = lambda *shape: jnp.zeros(shape, jnp.float32)
        start = (
            zeros(b, heads, d, d),
            {name: zeros(b, taps - 1, heads * d) for name in ("q", "k", "v")},
        )
        _, (y, least) = jax.lax.scan(
            segment, start,
            jnp.moveaxis(u.reshape(b, s // seg, seg, hidden), 1, 0),
        )
        chunks = s // seg * kda_chunks(seg, cfg.kda_chunk)
        in_kernel = uses_kernels(
            (b, heads, seg, d), (b, heads, seg, d), cfg.kda_chunk,
            self.attention_impl,
        )
        counters = {
            "kda_chunks": jnp.asarray(chunks, jnp.float32),
            "kda_chunks_in_kernel": jnp.asarray(
                chunks if in_kernel else 0, jnp.float32
            ),
            "kda_decay_min": jnp.exp(jnp.min(least)),
        }
        return jnp.moveaxis(y, 0, 1).reshape(b, s, hidden), counters

    def _mla(self, lp, u):
        cfg, cdt = self.cfg, self.compute_dtype
        b, s, _ = u.shape
        heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        positions = jnp.arange(s)
        by_head = lambda x: x.astype(cdt).transpose(0, 2, 1, 3)

        # checkpoints, as in _kda: the float32 tensors between a projection
        # and what the kernels take are made again in the backward pass
        @jax.checkpoint
        def queries(w):
            with scope("attn.proj"):
                q = mxu_dot(u, w.astype(cdt)).reshape(b, s, heads, nope + rope)
            with scope("attn.rope"):
                rotated = rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
            return by_head(jnp.concatenate([q[..., :nope], rotated], axis=-1))

        @jax.checkpoint
        def keys_values(kv_a_w, kv_a_norm, kv_b_w):
            with scope("attn.proj"):
                kv_a = mxu_dot(u, kv_a_w.astype(cdt))  # latent | rotary key
            latent = rms_norm(kv_a[..., :rank], kv_a_norm, cfg.rms_norm_eps)
            with scope("attn.proj"):
                kv = mxu_dot(latent.astype(cdt), kv_b_w.astype(cdt)).reshape(
                    b, s, heads, nope + cfg.v_head_dim
                )
            with scope("attn.rope"):
                k_rot = rope_interleaved(
                    kv_a[..., None, rank:], positions, cfg.rope_theta
                )
            k = jnp.concatenate([
                kv[..., :nope], jnp.broadcast_to(k_rot, (b, s, heads, rope)),
            ], axis=-1)
            return by_head(k), by_head(kv[..., nope:])

        out = attention(
            queries(lp["q_w"]),
            *keys_values(lp["kv_a_w"], lp["kv_a_norm"], lp["kv_b_w"]),
            causal=True, scale=(nope + rope) ** -0.5, force=self.attention_impl,
        )
        out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * cfg.v_head_dim)
        with scope("attn.proj"):
            return mxu_dot(out, lp["o_w"].astype(cdt))


# ---------------------------------------------------------------------------
# The state-space hybrid: Mamba-2 mixers and NoPE grouped-query attention
# over dense SwiGLU layers, on packed documents
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MambaHybridConfig:
    """A ``granitemoehybrid`` ``config.json`` with no experts, as
    :class:`MambaHybridLM` needs it; the published keys keep their names."""
    vocab_size: int
    hidden_size: int
    shared_intermediate_size: int  # the dense SwiGLU of every layer
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: Tuple[str, ...]  # MAMBA or ATTENTION
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    attention_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    remat: bool = False
    loss_chunk: int = 4096
    ssm_segment: int = 2048  # tokens a checkpointed segment of a Mamba mixer

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def mlp_layer_types(self) -> Tuple[str, ...]:
        return ("dense",) * self.num_layers  # no experts

    @property
    def head_dim(self) -> int:  # of an attention head
        return self.hidden_size // self.num_attention_heads

    @property
    def ssm_width(self) -> int:  # x, z and y: the heads side by side
        return self.mamba_n_heads * self.mamba_d_head

    @classmethod
    def from_published(cls, published: Mapping[str, Any], **overrides):
        """From the published keys (or a cut: the first
        ``num_hidden_layers`` of ``layer_types``).  What this model does not
        compute is refused, not ignored: experts, rotary positions, a bias
        on a projection, an untied head, more than one group of B and C, an
        expansion the heads do not fill."""
        refused = {
            "num_local_experts": published.get("num_local_experts", 0) != 0,
            "position_embedding_type": published.get("position_embedding_type") != "nope",
            "attention_bias": published.get("attention_bias", False),
            "mamba_proj_bias": published.get("mamba_proj_bias", False),
            "mamba_conv_bias": not published.get("mamba_conv_bias", True),
            "tie_word_embeddings": not published.get("tie_word_embeddings", True),
            "mamba_n_groups": published.get("mamba_n_groups", 1) != 1,
            "mamba_expand": published["mamba_expand"] * published["hidden_size"]
            != published["mamba_n_heads"] * published["mamba_d_head"],
        }
        if any(refused.values()):
            raise ValueError(
                "not modelled: " + ", ".join(k for k, v in refused.items() if v)
            )
        n = published["num_hidden_layers"]
        fields = dict(
            vocab_size=published["vocab_size"],
            hidden_size=published["hidden_size"],
            shared_intermediate_size=published["shared_intermediate_size"],
            num_attention_heads=published["num_attention_heads"],
            num_key_value_heads=published["num_key_value_heads"],
            layer_types=tuple(published["layer_types"][:n]),
            **{key: published[key] for key in (
                "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
                "mamba_chunk_size", "attention_multiplier",
                "embedding_multiplier", "residual_multiplier", "logits_scaling",
                "rms_norm_eps",
            )},
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def tiny(cls, **overrides) -> "MambaHybridConfig":
        """Both kinds of layer at a size for CPU tests: two Mamba layers
        round an attention layer, 4 heads of 16 in the scan with a state of
        8, 4 query heads over 2 KV heads of 8 scaled by an eighth of 8^-1/2
        (as the published 0.015625 is of 64^-1/2), chunks of 8 in segments
        of 32."""
        fields = dict(
            vocab_size=96, hidden_size=32, shared_intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2,
            layer_types=(MAMBA, ATTENTION, MAMBA), mamba_n_heads=4,
            mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
            attention_multiplier=8 ** -0.5 / 8, embedding_multiplier=12.0,
            residual_multiplier=0.22, logits_scaling=2.0, loss_chunk=32,
            ssm_segment=32,
        )
        fields.update(overrides)
        return cls(**fields)


class MambaHybridLM(DecoderLM):
    """A decoder whose layers are Mamba-2 mixers or grouped-query attention
    without positions (IBM Granite 4.0-H), each followed by a dense SwiGLU,
    with a tied embedding and head; DecoderLM's frame otherwise.  On ``h``,
    per layer: ``a = h + r Mix(RMSNorm(h))``, ``h' = a + r MLP(RMSNorm(a))``
    (``r`` = ``residual_multiplier``); ``h_0 = embedding_multiplier E[ids]``
    and ``logits = RMSNorm(h_L) E^T / logits_scaling``.

    - **Mamba-2 mixer**: ``[z | xBC | dt] = u W_in``; ``xBC <- SiLU(conv(xBC)
      + b)``, a depthwise causal convolution of ``mamba_d_conv`` taps whose
      taps read 0 before the token's document; ``x | B | C``, ``x`` in
      ``mamba_n_heads`` heads; ``delta = softplus(dt + dt_bias)``, ``A =
      -exp(A_log)``; :func:`sparknet_tpu.ops.ssd.ssd_scan` with the ``D``
      skip, its state reset at every document's first token; ``y <-
      RMSNorm(y * SiLU(z)) w`` over all the heads; ``y W_out``.  The mixer
      runs ``ssm_segment`` tokens at a time, each a checkpoint, with the
      scan's state, the convolution's last inputs and their document ids
      carried between them (``HybridLM._kda``'s bound on what lives at
      once).
    - **Attention**: ``num_attention_heads`` over ``num_key_value_heads`` of
      ``hidden / heads``, no rotary, scores scaled by
      ``attention_multiplier``, causal and inside documents, through the
      flash kernels.

    Counters beside the packed batch's: ``SSD_COUNTERS``."""

    counters = SSD_COUNTERS
    doc_counters = tuple(c for c in DOC_COUNTERS if "window" not in c)
    _no_decay = ("A_log", "dt_bias", "D", "conv_b")

    def _check_layers(self) -> None:
        for kind in set(self.cfg.layer_types):
            if kind not in (MAMBA, ATTENTION):
                raise ValueError(f"layer type {kind!r}")

    # -- init ----------------------------------------------------------------
    def init(self, rng: jax.Array):
        """Mamba-2's initialisation: ``A_log = log(1 .. heads)``, ``D = 1``,
        ``dt_bias`` the inverse softplus of a ``dt`` log-uniform in [1e-3,
        1e-1] a head, the convolution as a depthwise ``Conv1d``'s default
        (uniform in +-1/sqrt(taps), bias likewise); matrices truncated
        normal ``initializer_range``, norm scales 1."""
        cfg = self.cfg
        h = cfg.hidden_size
        heads, width = cfg.mamba_n_heads, cfg.ssm_width
        channels = width + 2 * cfg.mamba_d_state  # x, B, C
        d = cfg.head_dim
        keys = iter(jax.random.split(rng, 2 + 12 * cfg.num_layers))

        def trunc(shape):
            return cfg.initializer_range * jax.random.truncated_normal(
                next(keys), -2.0, 2.0, shape, jnp.float32
            )

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        ones = lambda n=h: jnp.ones((n,), jnp.float32)
        bound = cfg.mamba_d_conv ** -0.5
        params: Dict[str, Dict[str, jax.Array]] = {
            "embed": {"tokens": trunc((cfg.vocab_size, h))}
        }
        for li, kind in enumerate(cfg.layer_types):
            layer = {"attn_norm": ones(), "ffn_norm": ones()}
            if kind == MAMBA:
                dt = jnp.exp(uniform((heads,), math.log(1e-3), math.log(1e-1)))
                layer.update({
                    "in_proj": trunc((h, 2 * width + 2 * cfg.mamba_d_state + heads)),
                    "conv_w": uniform((cfg.mamba_d_conv, channels), -bound, bound),
                    "conv_b": uniform((channels,), -bound, bound),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                    "D": ones(heads),
                    "ssm_norm": ones(width),
                    "out_proj": trunc((width, h)),
                })
            else:
                kv = cfg.num_key_value_heads
                layer.update({
                    "q_w": trunc((h, cfg.num_attention_heads * d)),
                    "k_w": trunc((h, kv * d)), "v_w": trunc((h, kv * d)),
                    "o_w": trunc((cfg.num_attention_heads * d, h)),
                })
            layer.update({
                "mlp_in": trunc((h, 2 * cfg.shared_intermediate_size)),
                "mlp_out": trunc((cfg.shared_intermediate_size, h)),
            })
            params[f"layer_{li:02d}"] = layer
        params["head"] = {"norm": ones()}  # the matrix is the embedding's
        return params, {}

    # -- the frame's hooks ---------------------------------------------------
    def _embed(self, table, input_ids):
        return (table[input_ids] * self.cfg.embedding_multiplier).astype(
            self.compute_dtype
        )

    def _head_weight(self, params):
        return params["embed"]["tokens"].astype(self.compute_dtype).T

    def _logits(self, xc, lm_w):
        return mxu_dot(xc, lm_w) / self.cfg.logits_scaling

    def layer_apply(self, li: int, lp, x, docs=None):
        cfg, cdt = self.cfg, self.compute_dtype
        r = cfg.residual_multiplier
        mixed, counters = self._mix(
            li, lp, rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps), docs
        )
        with scope("residual"):
            x = (x.astype(jnp.float32) + r * mixed).astype(cdt)
        fed, _ = self._ffn(li, lp, rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps))
        with scope("residual"):
            x = (x.astype(jnp.float32) + r * fed).astype(cdt)
        return x, counters

    def _ffn(self, li: int, lp, u):
        """The dense SwiGLU, its gate and up in one matrix (gate first)."""
        cdt, width = u.dtype, self.cfg.shared_intermediate_size
        with scope("mlp.dense"):
            both = mxu_dot(u, lp["mlp_in"].astype(cdt))
            act = jax.nn.silu(both[..., :width]) * both[..., width:]
            return mxu_dot(act.astype(cdt), lp["mlp_out"].astype(cdt)), {}

    def _mix(self, li: int, lp, u, docs=None):
        if self.cfg.layer_types[li] == MAMBA:
            with scope("attn.ssm"):
                return self._ssm(lp, u, docs)
        with scope("attn.full"):
            return self._attention(li, lp, u, docs), {}

    def _attention(self, li: int, lp, u, docs=None):
        cfg, cdt = self.cfg, self.compute_dtype
        b, s, _ = u.shape
        heads, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

        def project(w, n):  # (B, n, S, d)
            with scope("attn.proj"):
                t = mxu_dot(u, w.astype(cdt))
            return t.reshape(b, s, n, d).astype(cdt).transpose(0, 2, 1, 3)

        out = attention(
            project(lp["q_w"], heads), project(lp["k_w"], kv),
            project(lp["v_w"], kv), causal=True,
            segment_ids=None if docs is None else docs[0],
            scale=cfg.attention_multiplier, force=self.attention_impl,
        )
        out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
        with scope("attn.proj"):
            return mxu_dot(out, lp["o_w"].astype(cdt))

    def _gated_norm(self, lp, y, z):
        """``RMSNorm(y * SiLU(z)) w`` over all the heads' channels."""
        return rms_norm(y * jax.nn.silu(z), lp["ssm_norm"], self.cfg.rms_norm_eps)

    def _ssm(self, lp, u, docs=None):
        """The Mamba-2 mixer (class header), ``ssm_segment`` tokens at a
        time: (float32 output, the layer's counters)."""
        cfg, cdt = self.cfg, self.compute_dtype
        b, s, hidden = u.shape
        heads, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        width, taps = cfg.ssm_width, cfg.mamba_d_conv
        seg = min(cfg.ssm_segment, s)
        if s % seg:
            raise ValueError(
                f"{s} tokens a sequence are not whole Mamba segments of "
                f"{cfg.ssm_segment} (MambaHybridConfig.ssm_segment)"
            )
        ids = None if docs is None else docs[0]
        a = -jnp.exp(lp["A_log"])

        @jax.checkpoint
        def segment(carry, inputs):
            state, history, history_ids, state_segment = carry
            u_s, ids_s = inputs
            with scope("attn.proj"):
                zxbcdt = mxu_dot(u_s, lp["in_proj"].astype(cdt))  # float32
            z, xbc, dt = jnp.split(zxbcdt, [width, 2 * width + 2 * n], axis=-1)
            with scope("ssm.conv"):
                xbc_act = jax.nn.silu(causal_conv(
                    xbc, lp["conv_w"], history, bias=lp["conv_b"],
                    segment_ids=ids_s, history_ids=history_ids,
                ))
            x, bm, cm = jnp.split(xbc_act, [width, width + n], axis=-1)
            delta = jax.nn.softplus(dt + lp["dt_bias"])
            y, state = ssd_scan(
                x.reshape(b, seg, heads, p).astype(cdt), delta, a, bm.astype(cdt),
                cm.astype(cdt), lp["D"], chunk=cfg.mamba_chunk_size,
                segment_ids=ids_s, state_segment=state_segment,
                initial_state=state, return_state=True, force=self.attention_impl,
            )
            y = self._gated_norm(lp, y.reshape(b, seg, width), z)
            with scope("attn.proj"):
                out = mxu_dot(y.astype(cdt), lp["out_proj"].astype(cdt))
            latest = jnp.concatenate([history, xbc], axis=1)[:, -(taps - 1):]
            carry = (state, latest, None, None)
            if ids_s is not None:
                carry = (
                    state, latest,
                    jnp.concatenate([history_ids, ids_s], axis=1)[:, -(taps - 1):],
                    ids_s[:, -1],
                )
            return carry, (out, jnp.min(delta * a))

        by_segment = lambda t: jnp.moveaxis(t.reshape(b, s // seg, seg, *t.shape[2:]), 1, 0)
        start = (
            jnp.zeros((b, heads, p, n), jnp.float32),
            jnp.zeros((b, taps - 1, width + 2 * n), jnp.float32),
            None if ids is None else jnp.full((b, taps - 1), -1, ids.dtype),
            None if ids is None else ids[:, 0],
        )
        _, (y, least) = jax.lax.scan(
            segment, start, (by_segment(u), None if ids is None else by_segment(ids))
        )
        resets = 0.0 if ids is None else jnp.sum(document_starts(ids)[:, 1:])
        chunks = s // seg * ssd_chunks(seg, cfg.mamba_chunk_size)
        in_kernel = ssd_uses_kernels(
            (b, seg, heads, p), n, cfg.mamba_chunk_size, self.attention_impl
        )
        counters = {
            "ssd_chunks": jnp.asarray(chunks, jnp.float32),
            "ssd_chunks_in_kernel": jnp.asarray(chunks if in_kernel else 0, jnp.float32),
            "ssd_state_resets": jnp.asarray(resets, jnp.float32),
            "ssd_decay_min": jnp.exp(jnp.min(least)),
        }
        return jnp.moveaxis(y, 0, 1).reshape(b, s, hidden), counters


# ---------------------------------------------------------------------------
# The convolutional hybrid: gated short convolutions and grouped-query
# attention with q/k norms, over experts routed by sigmoid with a selection
# bias, on packed documents
# ---------------------------------------------------------------------------

# added to the chosen scores' sum before the weights are normalised by it, as
# transformers' Lfm2MoeSparseMoeBlock does
ROUTER_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class ConvHybridConfig:
    """A ``lfm2_moe`` ``config.json``, as :class:`ConvHybridLM` needs it.
    The names DecoderConfig has mean the same here (``rms_norm_eps`` is the
    published ``norm_eps``, ``moe_routed_scaling_factor`` its
    ``routed_scaling_factor``)."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int  # the leading dense layers' SwiGLU
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: Tuple[str, ...]  # CONV or FULL
    mlp_layer_types: Tuple[str, ...]
    conv_L_cache: int = 3  # taps of the short convolution
    rope_theta: float = 1e6
    num_experts: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 0
    moe_routed_scaling_factor: float = 1.0
    # the selection bias is drawn as this times a standard normal (0: zeros,
    # as a fresh published model starts)
    expert_bias_std: float = 0.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    remat: bool = False
    loss_chunk: int = 4096

    shared_expert_intermediate_size = 0  # no shared expert

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_attention_heads_per_layer(self) -> Tuple[int, ...]:
        return (self.num_attention_heads,) * self.num_layers

    @property
    def rope_parameters(self) -> Mapping[str, Mapping[str, Any]]:
        return {FULL: {"rope_type": "default", "rope_theta": self.rope_theta}}

    @classmethod
    def from_published(cls, published: Mapping[str, Any], **overrides):
        """From the published keys (or a cut: ``deployment.layers_kept``
        lists the published indices of the ``num_hidden_layers`` layers
        held, default the first so many; ``num_experts`` counts the experts
        held of ``deployment.num_experts_routed``).  Published layer *i* has
        the dense FFN if ``i < num_dense_layers``.  What this model does not
        compute is refused, not ignored: biases on the convolution's
        projections, unnormalised routing weights, no selection bias, an
        untied head."""
        refused = {
            "conv_bias": published.get("conv_bias", False),
            "norm_topk_prob": not published.get("norm_topk_prob", True),
            "use_expert_bias": not published.get("use_expert_bias", True),
            "tie_word_embeddings": not published.get("tie_word_embeddings", True),
        }
        if any(refused.values()):
            raise ValueError(
                "not modelled: " + ", ".join(k for k, v in refused.items() if v)
            )
        n = published["num_hidden_layers"]
        deployment = published.get("deployment", {})
        kept = list(deployment.get("layers_kept", range(n)))
        if len(kept) != n:
            raise ValueError(f"layers_kept {kept} against {n} layers")
        held = published["num_experts"]
        fields = dict(
            vocab_size=published["vocab_size"],
            hidden_size=published["hidden_size"],
            intermediate_size=published["intermediate_size"],
            num_attention_heads=published["num_attention_heads"],
            num_key_value_heads=published["num_key_value_heads"],
            layer_types=tuple(published["layer_types"][i] for i in kept),
            mlp_layer_types=tuple(
                "dense" if i < published["num_dense_layers"] else "sparse"
                for i in kept
            ),
            conv_L_cache=published["conv_L_cache"],
            rope_theta=published["rope_theta"],
            num_experts=deployment.get("num_experts_routed", held),
            experts_held=(deployment.get("experts_first", 0), held),
            num_experts_per_tok=published["num_experts_per_tok"],
            moe_intermediate_size=published["moe_intermediate_size"],
            moe_routed_scaling_factor=published["routed_scaling_factor"],
            expert_bias_std=published.get("expert_bias_std", 0.0),
            rms_norm_eps=published["norm_eps"],
        )
        fields.update(overrides)
        return cls(**fields)

    @classmethod
    def tiny(cls, **overrides) -> "ConvHybridConfig":
        """Both kinds of layer and both FFNs at a size for CPU tests: two
        conv layers round an attention layer, the first dense, 4 query
        heads over 2 KV heads of 8, 8 experts with 2 held (experts 2 and 3),
        2 a token times 2.5, a selection bias that moves some of them."""
        fields = dict(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_attention_heads=4, num_key_value_heads=2,
            layer_types=(CONV, FULL, CONV), mlp_layer_types=("dense", "sparse", "sparse"),
            rope_theta=10000.0, num_experts=8, experts_held=(2, 2),
            num_experts_per_tok=2, moe_intermediate_size=16,
            moe_routed_scaling_factor=2.5, expert_bias_std=0.05, loss_chunk=32,
        )
        fields.update(overrides)
        return cls(**fields)


class ConvHybridLM(DecoderLM):
    """A decoder whose layers are gated short convolutions or grouped-query
    attention with q/k norms (Liquid AI's LFM2), the leading ones with a
    dense SwiGLU and the rest sparse, with a tied embedding and head;
    DecoderLM's frame otherwise (pre-norm residuals, a final RMSNorm).

    - **Gated short convolution** (scope ``attn.conv``): ``[B | C | x] = u
      W_in``; ``v = B * x``; ``z_t = sum_j w_j v_(t-L+1+j)``, a depthwise
      causal convolution of ``conv_L_cache`` taps whose taps read 0 before
      the token's document (scope ``conv.short``, with the two gates);
      ``y = C * z``; ``y W_out``.  No activation, no bias.
    - **Attention** (``attn.full``): DecoderLM's, with q and k each normed
      per head (an RMSNorm over the head's ``head_dim`` channels with its
      own scale, ``q_norm`` / ``k_norm``) before rotary (``default``, the
      whole head, ``rope_theta``, by position in the document).
    - **Sparse FFN**: :func:`sparknet_tpu.parallel.moe.route_sigmoid` with
      the layer's ``router_bias`` (a buffer no step moves) steering the
      selection of the ``num_experts_per_tok`` experts only, and the weights
      ``s / (sum(s) + ROUTER_EPS)`` times the scaling factor; this chip's
      ``experts_held``; no shared expert.

    Counters beside DecoderLM's and the packed batch's: ``CONV_COUNTERS``."""

    counters = COUNTERS + ROPE_COUNTERS + CONV_COUNTERS
    doc_counters = tuple(c for c in DOC_COUNTERS if "window" not in c)
    _buffers = ("router_bias",)

    def _check_layers(self) -> None:
        cfg = self.cfg
        for kind in set(cfg.layer_types):
            if kind not in (CONV, FULL):
                raise ValueError(f"layer type {kind!r}")
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(
                f"{cfg.num_attention_heads} heads over {cfg.num_key_value_heads} KV heads"
            )

    # -- init ----------------------------------------------------------------
    def init(self, rng: jax.Array):
        """Matrices truncated normal ``initializer_range``, the taps as a
        depthwise ``Conv1d``'s default (uniform in +-1/sqrt(taps)), norm
        scales 1, the selection bias ``expert_bias_std`` x a normal."""
        cfg = self.cfg
        h, d = cfg.hidden_size, cfg.head_dim
        heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        keys = iter(jax.random.split(rng, 2 + 12 * cfg.num_layers))

        def trunc(shape):
            return cfg.initializer_range * jax.random.truncated_normal(
                next(keys), -2.0, 2.0, shape, jnp.float32
            )

        ones = lambda n=h: jnp.ones((n,), jnp.float32)
        bound = cfg.conv_L_cache ** -0.5
        params: Dict[str, Dict[str, jax.Array]] = {
            "embed": {"tokens": trunc((cfg.vocab_size, h))}
        }
        for li, kind in enumerate(cfg.layer_types):
            layer = {"attn_norm": ones(), "ffn_norm": ones()}
            if kind == CONV:
                layer.update({
                    "in_proj": trunc((h, 3 * h)),
                    "conv_w": jax.random.uniform(
                        next(keys), (cfg.conv_L_cache, h), jnp.float32, -bound, bound
                    ),
                    "out_proj": trunc((h, h)),
                })
            else:
                layer.update({
                    "q_w": trunc((h, heads * d)), "k_w": trunc((h, kv * d)),
                    "v_w": trunc((h, kv * d)), "o_w": trunc((heads * d, h)),
                    "q_norm": ones(d), "k_norm": ones(d),
                })
            layer.update(self._init_ffn(li, trunc, keys))
            if cfg.mlp_layer_types[li] == "sparse":
                layer["router_bias"] = cfg.expert_bias_std * jax.random.normal(
                    next(keys), (cfg.num_experts,), jnp.float32
                )
            params[f"layer_{li:02d}"] = layer
        params["head"] = {"norm": ones()}  # the matrix is the embedding's
        return params, {}

    # -- the frame's hooks ---------------------------------------------------
    def _head_weight(self, params):
        return params["embed"]["tokens"].astype(self.compute_dtype).T

    def _qk_norm(self, lp, name, t):
        b, s, width = t.shape
        d = self.cfg.head_dim
        heads = t.reshape(b, s, width // d, d)
        return rms_norm(heads, lp[name + "_norm"], self.cfg.rms_norm_eps).reshape(b, s, width)

    def _mix(self, li: int, lp, u, docs=None):
        if self.cfg.layer_types[li] == CONV:
            with scope("attn.conv"):
                return self._short_conv(lp, u, docs)
        with scope("attn.full"):
            return self._attention(li, lp, u, docs)

    def _short_conv(self, lp, u, docs=None):
        """The gated short convolution (class header): (float32 output, the
        layer's counters).  The gates and the taps in float32."""
        cdt = self.compute_dtype
        ids = None if docs is None else docs[0]
        with scope("attn.proj"):
            bcx = mxu_dot(u, lp["in_proj"].astype(cdt))  # float32
        with scope("conv.short"):
            y = self._gated_conv(lp, bcx, ids)
        with scope("attn.proj"):
            out = mxu_dot(y.astype(cdt), lp["out_proj"].astype(cdt))
        resets = 0.0 if ids is None else jnp.sum(document_starts(ids)[:, 1:])
        return out, {"short_conv_resets": jnp.asarray(resets, jnp.float32)}

    def _gated_conv(self, lp, bcx, ids):
        """``C * conv(B * x)`` of ``bcx = [B | C | x]``, the taps masked by
        the document ``ids`` (B, S) where given."""
        b_gate, c_gate, x = jnp.split(bcx, 3, axis=-1)
        return c_gate * causal_conv(b_gate * x, lp["conv_w"], segment_ids=ids)

    def _router(self, xt, lp):
        cfg = self.cfg
        return route_sigmoid(
            xt, lp["router_w"], cfg.num_experts_per_tok,
            cfg.moe_routed_scaling_factor, bias=lp["router_bias"], eps=ROUTER_EPS,
        )

    def _ffn(self, li: int, lp, u):
        """DecoderLM's, and on a sparse layer the counter
        ``moe_bias_rerouted``: of the slots, the share whose expert is not
        among the unbiased top-k of the same token."""
        cfg = self.cfg
        if cfg.mlp_layer_types[li] != "sparse":
            return super()._ffn(li, lp, u)
        rerouted = []

        def router(xt, lp):
            weights, experts = self._router(xt, lp)
            with scope("counters"):
                logits = jnp.dot(
                    xt.astype(jnp.float32), lp["router_w"],
                    preferred_element_type=jnp.float32,
                )
                _, plain = jax.lax.top_k(
                    jax.lax.stop_gradient(logits), cfg.num_experts_per_tok
                )
                moved = jnp.all(experts[..., None] != plain[:, None, :], axis=-1)
                rerouted.append(jnp.mean(moved.astype(jnp.float32)))
            return weights, experts

        routed, counters = held_experts_ffn(
            u, lp, experts_held=cfg.experts_held, top_k=cfg.num_experts_per_tok,
            compute_dtype=self.compute_dtype, router=router,
            force=self.attention_impl,
        )
        counters["moe_bias_rerouted"] = rerouted[0]
        with scope("moe.experts"):  # the cast is the layer's, not unscoped glue
            return routed.astype(jnp.float32), counters
