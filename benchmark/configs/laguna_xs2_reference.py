"""Laguna-XS.2's forward pass and next-token loss in plain ``jax.numpy``,
float32, for one chip's share of the model as ``laguna_xs2.json`` beside
this file cuts it (``make_loss`` takes any such configuration; a test
hands it a tiny one, and an uncut one for the add-up test).

Per layer, on tokens ``x``: ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``; after the last layer a final RMSNorm and the untied head;
the loss is the mean cross-entropy of every position's next token over the
vocabulary slice.

- Attention: ``H_l = num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``, no bias; query head h
  reads KV head ``h // (H_l / KV)``.  Rotary by the layer's type
  (``rope_parameters``): on ``full_attention`` layers the first
  ``partial_rotary_factor`` of each head, YaRN as transformers computes it
  (inverse frequencies blended between ``1/f`` and ``1/(factor f)`` by a
  linear ramp over the truncated correction range of ``beta_fast`` and
  ``beta_slow``, ``attention_factor`` multiplying cos and sin); on
  ``sliding_attention`` layers the whole head, plain.  Scores ``q.k /
  sqrt(head_dim)``, mask ``j <= i`` (and ``i - j < sliding_window`` on
  sliding layers), softmax, ``concat(heads) W_o``.  Computed a block of
  queries at a time, so that no (H, S, S) array exists whole.
- Sparse FFN: ``s = sigmoid(u W_r)`` over all ``num_experts_routed``
  experts, the ``num_experts_per_tok`` largest, ``w =
  moe_routed_scaling_factor * s / sum(s)`` over all chosen; ``FFN(u) =
  E_shared(u) + sum over the chosen experts HELD HERE of w_k E_k(u)``,
  ``E(u) = (silu(u W_g) * (u W_u)) W_d``.  What the absent experts would
  add is left out.  Every held expert is run on every token and masked:
  plain, not fast.
- Dense FFN: the same SwiGLU at ``intermediate_size``.

Departures from the publication (``assumed`` in the JSON): the router's
scoring is the convention its scaling factor comes from; pre-norm; the
config's ``gating`` flag is not modelled (no equation is published).  It
shares no code with ``sparknet_tpu``; it takes the program's parameter tree
by its names (``experts_gate_up`` holds gate in its first half of columns).
"""

import json
import math
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(__file__), "laguna_xs2.json")) as _fh:
    CONFIG = json.load(_fh)
_HIGH = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGH)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(u, gate, up, down):
    return _mm(jax.nn.silu(_mm(u, gate)) * _mm(u, up), down)


def _rotary_tables(rope, head_dim, length):
    """cos and sin, (length, rot), for the first ``rot`` dims of a head."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = rope["rope_theta"]
    freqs = [theta ** (2 * i / rot) for i in range(rot // 2)]
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor, original = rope["factor"], rope["original_max_position_embeddings"]

        def dim_of(rotations):  # the dim that makes this many turns over `original`
            return rot * math.log(original / (rotations * 2 * math.pi)) / (
                2 * math.log(theta)
            )

        low = max(math.floor(dim_of(rope["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
        if low == high:
            high += 0.001
        inv = []
        for i, f in enumerate(freqs):
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            # ramp 0: the published frequency; ramp 1: stretched by `factor`
            inv.append((1 - ramp) / f + ramp / (factor * f))
        scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    elif rope.get("rope_type", "default") == "default":
        inv = [1.0 / f for f in freqs]
    else:
        raise NotImplementedError(rope["rope_type"])
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv + inv, jnp.float32
    )[None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(x, cos, sin):
    """x: (B, S, H, D); the first cos.shape[-1] dims of each head turn."""
    rot = cos.shape[-1]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    turned = jnp.concatenate([-b, a], -1)
    head = x[..., :rot] * cos[None, :, None, :] + turned * sin[None, :, None, :]
    return jnp.concatenate([head, x[..., rot:]], -1)


def _attention(config, kind, heads, p, u):
    b, s, _ = u.shape
    kv, d = config["num_key_value_heads"], config["head_dim"]
    group = heads // kv
    cos, sin = _rotary_tables(config["rope_parameters"][kind], d, s)
    q = _rotate(_mm(u, p["q_w"]).reshape(b, s, heads, d), cos, sin)
    k = _rotate(_mm(u, p["k_w"]).reshape(b, s, kv, d), cos, sin)
    v = _mm(u, p["v_w"]).reshape(b, s, kv, d)
    window = config["sliding_window"] if kind == "sliding_attention" else s
    block = math.gcd(s, QUERY_BLOCK)
    q = q.reshape(b, s // block, block, kv, group, d).transpose(1, 0, 2, 3, 4, 5)
    keys_at = jnp.arange(s)

    def one_block(args):
        qb, start = args  # (B, block, KV, G, D)
        at = start + jnp.arange(block)
        seen = (keys_at[None, :] <= at[:, None]) & (
            at[:, None] - keys_at[None, :] < window
        )
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k, precision=_HIGH)
        scores = jnp.where(seen, scores / math.sqrt(d), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=_HIGH)

    out = jax.lax.map(one_block, (q, jnp.arange(0, s, block)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, heads * d)
    return _mm(out, p["o_w"])


def _sparse_ffn(config, p, u):
    first = config.get("deployment", {}).get("experts_first", 0)
    held = config["num_experts"]
    width = config["moe_intermediate_size"]
    scores = jax.nn.sigmoid(_mm(u, p["router_w"]))
    top, chosen = jax.lax.top_k(scores, config["num_experts_per_tok"])
    weights = config["moe_routed_scaling_factor"] * top / top.sum(-1, keepdims=True)

    def add_expert(total, expert):
        e, gate_up, down = expert
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        y = _swiglu(u, gate_up[:, :width], gate_up[:, width:], down)
        return total + mine[..., None] * y, None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.arange(held), p["experts_gate_up"], p["experts_down"]),
    )
    shared = _swiglu(u, p["shared_gate_w"], p["shared_up_w"], p["shared_down_w"])
    return shared + routed


def layer(config, index, p, x):
    """Layer ``index`` of the configuration on ``x`` (B, S, hidden)."""
    eps = config["rms_norm_eps"]
    heads = config["num_attention_heads_per_layer"][index]
    kind = config["layer_types"][index]
    h = x + _attention(config, kind, heads, p, _rms_norm(x, p["attn_norm"], eps))
    u = _rms_norm(h, p["ffn_norm"], eps)
    if config["mlp_layer_types"][index] == "sparse":
        return h + _sparse_ffn(config, p, u)
    return h + _swiglu(u, p["gate_w"], p["up_w"], p["down_w"])


def make_loss(config):
    """``loss(params, batch)`` of a configuration in ``laguna_xs2.json``'s
    form."""

    def loss(params, batch):
        x = params["embed"]["tokens"][batch["input_ids"]]
        for index in range(config["num_hidden_layers"]):
            x = layer(config, index, params[f"layer_{index:02d}"], x)
        x = _rms_norm(x, params["head"]["norm"], config["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(x, params["head"]["lm_w"]), axis=-1)
        picked = jnp.take_along_axis(logp, batch["labels"][..., None], -1)
        return -jnp.mean(picked)

    return loss


loss = make_loss(CONFIG)
