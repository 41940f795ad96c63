"""A causal decoder LM that mixes window and full attention over sparse
experts — the pure-JAX decoder family, beside :mod:`.bert`.

Built from a published ``config.json``'s own keys
(:meth:`DecoderConfig.from_published`): ``layer_types`` (``full_attention``
or ``sliding_attention`` per layer), ``num_attention_heads_per_layer``,
``mlp_layer_types`` (``dense`` or ``sparse``), ``rope_parameters`` per
layer type.  Per layer, pre-norm: ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``; a final RMSNorm and an untied head; the loss is the mean
next-token cross-entropy over every position.

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
  plus a shared expert with weight 1.
- A published ``gating`` flag is not modelled: no equation comes with it.

It satisfies the :class:`~sparknet_tpu.solver.trainer.Solver` net protocol
as :class:`~.bert.BertMLM` does: float32 weights in the two-level layout,
``compute_dtype`` activations and matmul inputs, float32 norms, softmax,
router and loss.  Batch blobs: ``input_ids`` (B, S) and ``labels`` (B, S)
int32, the next token at every position.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.matmul import mxu_dot
from ..parallel.moe import held_experts_ffn, init_held_experts_params

FULL, SLIDING = "full_attention", "sliding_attention"
COUNTERS = ("moe_slots_held", "moe_load_max_over_mean", "moe_slots_dropped")


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
    (B, S, H, D), in float32, by the rotate-half convention."""
    rot = 2 * inv_freq.shape[0]
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)  # (S, rot)
    cos = (jnp.cos(angles) * scale)[None, :, None, :]
    sin = (jnp.sin(angles) * scale)[None, :, None, :]
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
        n = cfg.num_layers
        if not (
            len(cfg.mlp_layer_types) == n
            and len(cfg.num_attention_heads_per_layer) == n
        ):
            raise ValueError("the per-layer lists differ in length")
        for kind in set(cfg.layer_types):
            if kind not in (FULL, SLIDING) or kind not in cfg.rope_parameters:
                raise ValueError(f"layer type {kind!r}")
        for heads in cfg.num_attention_heads_per_layer:
            if heads % cfg.num_key_value_heads:
                raise ValueError(
                    f"{heads} heads over {cfg.num_key_value_heads} KV heads"
                )
        b, s = input_shapes["input_ids"]
        self.batch, self.seq_len = b, s
        self.input_names: List[str] = ["input_ids", "labels"]
        self.blob_shapes: Dict[str, Tuple[int, ...]] = {
            "input_ids": (b, s), "labels": (b, s), "loss": (),
            "token_acc": (), **{name: () for name in COUNTERS},
        }

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
            if cfg.mlp_layer_types[li] == "sparse":
                layer.update(init_held_experts_params(
                    next(keys), h, cfg.moe_intermediate_size, cfg.num_experts,
                    cfg.experts_held[1], std=cfg.initializer_range,
                ))
                width = cfg.shared_expert_intermediate_size
                prefix = "shared_"
            else:
                width, prefix = cfg.intermediate_size, ""
            layer.update({
                prefix + "gate_w": trunc((h, width)),
                prefix + "up_w": trunc((h, width)),
                prefix + "down_w": trunc((width, h)),
            })
            params[f"layer_{li:02d}"] = layer
        params["head"] = {
            "norm": ones(), "lm_w": trunc((h, cfg.vocab_size)),
        }
        return params, {}

    # -- layers --------------------------------------------------------------
    def _attention(self, li: int, lp, u):
        cfg, cdt = self.cfg, self.compute_dtype
        b, s, _ = u.shape
        kind = cfg.layer_types[li]
        heads = cfg.num_attention_heads_per_layer[li]
        kv, d = cfg.num_key_value_heads, cfg.head_dim
        inv_freq, factor = rope_inv_freq(cfg.rope_parameters[kind], d)
        positions = jnp.arange(s)

        def project(w, n, rotate):
            t = mxu_dot(u, w.astype(cdt)).reshape(b, s, n, d)
            if rotate:
                t = apply_rope(t, positions, inv_freq, factor)
            return t.astype(cdt).transpose(0, 2, 1, 3)  # (B, n, S, D)

        out = attention(
            project(lp["q_w"], heads, True),
            project(lp["k_w"], kv, True),
            project(lp["v_w"], kv, False),
            causal=True,
            window=cfg.sliding_window if kind == SLIDING else None,
            force=self.attention_impl,
        )
        out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
        return mxu_dot(out, lp["o_w"].astype(cdt))

    def _ffn(self, li: int, lp, u):
        """(float32 FFN output, this layer's counters or None)."""
        cfg = self.cfg
        if cfg.mlp_layer_types[li] != "sparse":
            return swiglu(u, lp["gate_w"], lp["up_w"], lp["down_w"]), None
        routed, counters = held_experts_ffn(
            u, lp, experts_held=cfg.experts_held,
            top_k=cfg.num_experts_per_tok,
            routed_scale=cfg.moe_routed_scaling_factor,
            compute_dtype=self.compute_dtype,
        )
        with jax.named_scope("moe.shared"):
            shared = swiglu(
                u, lp["shared_gate_w"], lp["shared_up_w"], lp["shared_down_w"]
            )
        return shared + routed.astype(jnp.float32), counters

    def layer_apply(self, li: int, lp, x):
        """One layer on ``x`` (B, S, h): (x, counters or None)."""
        cfg, cdt = self.cfg, self.compute_dtype
        scope = "attn.window" if cfg.layer_types[li] == SLIDING else "attn.full"
        with jax.named_scope(scope):
            attended = self._attention(
                li, lp, rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            )
        x = (x.astype(jnp.float32) + attended).astype(cdt)
        fed, counters = self._ffn(
            li, lp, rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
        )
        return (x.astype(jnp.float32) + fed).astype(cdt), counters

    def hidden(self, params, input_ids):
        """The final-layer hidden states (before the head's norm) and the
        sparse layers' counters, stacked."""
        cfg = self.cfg
        x = params["embed"]["tokens"][input_ids].astype(self.compute_dtype)
        counted = []
        for li in range(cfg.num_layers):
            fn = lambda lp, x, li=li: self.layer_apply(li, lp, x)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            x, counters = fn(params[f"layer_{li:02d}"], x)
            if counters is not None:
                counted.append(counters)
        return x, counted

    def _loss(self, head, x, labels):
        """(mean next-token NLL, accuracy) over every position, the
        logits made ``loss_chunk`` tokens at a time and not kept."""
        cfg, cdt = self.cfg, self.compute_dtype
        x = rms_norm(x, head["norm"], cfg.rms_norm_eps)
        tokens = labels.size
        chunk = min(cfg.loss_chunk, tokens)
        if tokens % chunk:
            raise ValueError(
                f"loss_chunk {cfg.loss_chunk} does not divide {tokens} tokens"
            )
        xs = x.reshape(tokens // chunk, chunk, x.shape[-1])
        ys = labels.reshape(tokens // chunk, chunk)
        lm_w = head["lm_w"].astype(cdt)

        @jax.checkpoint
        def one(xc, yc):
            logits = mxu_dot(xc, lm_w)  # (chunk, V) f32
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
            hit = jnp.argmax(logits, -1) == yc
            return jnp.sum(lse - picked), jnp.sum(hit.astype(jnp.float32))

        def body(carry, xy):
            nll, hit = one(*xy)
            return (carry[0] + nll, carry[1] + hit), None

        (nll, hit), _ = jax.lax.scan(body, (0.0, 0.0), (xs, ys))
        return nll / tokens, hit / tokens

    # -- Solver protocol -----------------------------------------------------
    def apply(self, params, state, batch, *, train=None, rng=None):
        x, counted = self.hidden(params, batch["input_ids"])
        with jax.named_scope("lm_head"):
            loss, acc = self._loss(params["head"], x, batch["labels"])
        blobs = {"loss": loss, "token_acc": acc}
        # per sparse layer: the mean of the slots held, the worst load
        # ratio, every slot dropped
        reduce = dict(zip(COUNTERS, (jnp.mean, jnp.max, jnp.sum)))
        for name in COUNTERS:
            blobs[name] = (
                reduce[name](jnp.stack([c[name] for c in counted]))
                if counted else jnp.zeros((), jnp.float32)
            )
        return blobs, state

    def loss_and_metrics(self, blobs):
        return blobs["loss"], {
            k: blobs[k] for k in ("loss", "token_acc", *COUNTERS)
        }

    def param_specs(self):
        """No weight decay on the norm scales (Caffe decay_mult 0)."""
        params, _ = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return {
            layer: {n: (1.0, 0.0 if "norm" in n else 1.0) for n in leaves}
            for layer, leaves in params.items()
        }

    def dummy_batch(self):
        zeros = jnp.zeros((self.batch, self.seq_len), jnp.int32)
        return {"input_ids": zeros, "labels": zeros}

    def num_params(self, params) -> int:
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
