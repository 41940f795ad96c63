"""Ling-3.0-flash's forward pass and next-token loss in plain ``jax.numpy``,
float32, for one chip's share of the model as ``ling3_flash.json`` beside
this file cuts it (``make_loss`` takes any such configuration; a test hands
it a tiny one, and an uncut one for the add-up test).

Per layer, on tokens ``x``: ``h = x + Mix(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``; after the last layer a final RMSNorm and the untied head;
the loss is the mean cross-entropy of every position's next token over the
vocabulary slice.  Published layer ``i`` (``deployment.layers_kept`` lists
the indices held) mixes by MLA if ``(i + 1) % layer_group_size == 0``, else
by KDA, and has the dense FFN if ``i < first_k_dense_replace``.

- KDA (Kimi Delta Attention), ``H = num_attention_heads`` heads of
  ``head_dim``: ``q, k, v = SiLU(conv(u W))`` with a depthwise causal
  convolution over ``short_conv_kernel_size`` positions (``y_t = sum_j w[j]
  x_(t-K+1+j)``); q and k L2-normed per head (``1e-6`` under the root), q
  times ``head_dim ** -0.5``; the decay ``alpha_t = exp(kda_lower_bound *
  sigmoid(exp(A_log_h) * (u_t W_f + dt_bias)))`` per channel, ``beta_t =
  sigmoid(u_t W_beta)`` per head; the state, **token by token** (a
  ``lax.scan`` over positions): ``S <- Diag(alpha_t) S``, ``S <- S + beta_t
  k_t (v_t - S^T k_t)^T``, ``o_t = S^T q_t``; out ``[RMSNorm_head(o_t) *
  sigmoid(u_t W_g)] W_o``.  No rotary.
- MLA: ``q = u W_q`` in heads of ``qk_nope_head_dim + qk_rope_head_dim``;
  ``[c | k_r] = u W_kva``; ``[k_nope | v] = RMSNorm(c) W_kvb`` in heads of
  ``qk_nope_head_dim + v_head_dim``; the rotary parts turn in interleaved
  pairs ``(x[2i], x[2i+1])`` by ``t * rope_theta ** (-2i / R)``, ``k_r``
  shared by every head; scores ``q.k / sqrt(nope + rope)``, mask ``j <= i``,
  softmax, ``concat(heads) W_o``.  A block of queries at a time.
- Sparse FFN: ``s = sigmoid(u W_r)`` over all ``num_experts_routed``; on ``s
  + b`` the experts in ``n_group`` groups, the ``topk_group`` groups with
  the largest sum of their two best, the ``num_experts_per_tok`` best inside
  them, **by sorting** (stable: ties to the lower index); ``w =
  routed_scaling_factor * s / sum(s)`` over all chosen, from the scores
  without ``b``; ``FFN(u) = E_shared(u) + sum over the chosen experts HELD
  HERE of w_e E_e(u)``, ``E(u) = (silu(u W_g) * (u W_u)) W_d``.  What the
  absent experts would add is left out.  Every held expert is run on every
  token and masked: plain, not fast.
- Dense FFN: the same SwiGLU at ``intermediate_size``.

Departures from the publication are ``assumed`` in the JSON.  It shares no
code with ``sparknet_tpu``; it takes the program's parameter tree by its
names (``experts_gate_up`` holds gate in its first half of columns).
"""

import json
import math
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(__file__), "ling3_flash.json")) as _fh:
    CONFIG = json.load(_fh)
_HIGH = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGH)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(u, gate, up, down):
    return _mm(jax.nn.silu(_mm(u, gate)) * _mm(u, up), down)


def _conv(x, w):
    """y_t = sum_j w[j] x_(t-K+1+j) per channel; x (B, S, C), w (K, C)."""
    taps, length = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :length - back]], axis=1
        )
        out = out + w[j] * shifted
    return out


def _kda(config, p, u):
    b, s, _ = u.shape
    heads, d = config["num_attention_heads"], config["head_dim"]
    split = lambda x: x.reshape(b, s, heads, d)
    q = split(jax.nn.silu(_conv(_mm(u, p["q_w"]), p["q_conv"])))
    k = split(jax.nn.silu(_conv(_mm(u, p["k_w"]), p["k_conv"])))
    v = split(jax.nn.silu(_conv(_mm(u, p["v_w"]), p["v_conv"])))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(d)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    rate = jnp.exp(p["A_log"])[None, None, :, None]
    alpha = jnp.exp(config["kda_lower_bound"] * jax.nn.sigmoid(
        rate * split(_mm(u, p["f_w"]) + p["dt_bias"])
    ))
    beta = jax.nn.sigmoid(_mm(u, p["beta_w"]))  # (B, S, H)

    def token(state, at):  # state (B, H, d_k, d_v)
        q_t, k_t, v_t, a_t, b_t = at
        state = a_t[..., :, None] * state
        seen = jnp.sum(state * k_t[..., :, None], axis=-2)
        state = state + k_t[..., :, None] * (b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)

    by_time = lambda x: jnp.swapaxes(x, 0, 1)
    _, out = jax.lax.scan(
        token, jnp.zeros((b, heads, d, d), jnp.float32),
        tuple(map(by_time, (q, k, v, alpha, beta))),
    )
    out = _rms_norm(by_time(out), p["o_norm"], config["rms_norm_eps"])
    gated = out.reshape(b, s, heads * d) * jax.nn.sigmoid(_mm(u, p["g_w"]))
    return _mm(gated, p["o_w"])


def _turn(x, theta):
    """Interleaved rotary on the last axis of x (B, S, H, R)."""
    length, r = x.shape[1], x.shape[-1]
    inv = jnp.asarray([theta ** (-2 * i / r) for i in range(r // 2)], jnp.float32)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def _mla(config, p, u):
    b, s, _ = u.shape
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, theta = config["v_head_dim"], config["rope_theta"]
    q = _mm(u, p["q_w"]).reshape(b, s, heads, nope + rope)
    kv_a = _mm(u, p["kv_a_w"])
    latent = _rms_norm(kv_a[..., :rank], p["kv_a_norm"], config["rms_norm_eps"])
    kv = _mm(latent, p["kv_b_w"]).reshape(b, s, heads, nope + dv)
    k_rot = _turn(kv_a[..., rank:][:, :, None, :], theta)
    q = jnp.concatenate([q[..., :nope], _turn(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rot, (b, s, heads, rope))], -1
    )
    v = kv[..., nope:]
    block = math.gcd(s, QUERY_BLOCK)
    q = q.reshape(b, s // block, block, heads, nope + rope).transpose(1, 0, 2, 3, 4)
    keys_at = jnp.arange(s)

    def one_block(args):
        qb, start = args  # (B, block, H, D)
        at = start + jnp.arange(block)
        scores = jnp.einsum("bqhd,bshd->bhqs", qb, k, precision=_HIGH)
        scores = jnp.where(
            keys_at[None, :] <= at[:, None], scores / math.sqrt(nope + rope),
            -jnp.inf,
        )
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v, precision=_HIGH)

    out = jax.lax.map(one_block, (q, jnp.arange(0, s, block)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, heads * dv)
    return _mm(out, p["o_w"])


def route(config, p, u):
    """(weights, chosen), both (..., K): the router by sorting."""
    routed = p["router_w"].shape[-1]
    groups, keep = config["n_group"], config["topk_group"]
    scores = jax.nn.sigmoid(_mm(u, p["router_w"]))
    biased = scores + p["router_bias"]
    grouped = biased.reshape(*biased.shape[:-1], groups, routed // groups)
    best_two = -jnp.sort(-grouped, axis=-1)[..., :2]
    group_order = jnp.argsort(-best_two.sum(-1), axis=-1, stable=True)
    kept = jnp.any(
        group_order[..., :keep, None] == jnp.arange(groups), axis=-2
    )  # (..., groups)
    inside = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(biased.shape)
    chosen = jnp.argsort(-inside, axis=-1, stable=True)[
        ..., :config["num_experts_per_tok"]
    ]
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = config["routed_scaling_factor"] * top / top.sum(-1, keepdims=True)
    return weights, chosen


def _sparse_ffn(config, p, u):
    first = config.get("deployment", {}).get("experts_first", 0)
    held = config["num_experts"]
    width = config["moe_intermediate_size"]
    weights, chosen = route(config, p, u)

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


def layers_kept(config):
    n = config["num_hidden_layers"]
    return list(config.get("deployment", {}).get("layers_kept", range(n)))


def layer(config, published_index, p, x):
    """Published layer ``published_index`` on ``x`` (B, S, hidden)."""
    eps = config["rms_norm_eps"]
    u = _rms_norm(x, p["attn_norm"], eps)
    if (published_index + 1) % config["layer_group_size"] == 0:
        h = x + _mla(config, p, u)
    else:
        h = x + _kda(config, p, u)
    u = _rms_norm(h, p["ffn_norm"], eps)
    if published_index >= config["first_k_dense_replace"]:
        return h + _sparse_ffn(config, p, u)
    return h + _swiglu(u, p["gate_w"], p["up_w"], p["down_w"])


def make_loss(config):
    """``loss(params, batch)`` of a configuration in ``ling3_flash.json``'s
    form."""

    def loss(params, batch):
        x = params["embed"]["tokens"][batch["input_ids"]]
        for held, published_index in enumerate(layers_kept(config)):
            x = layer(config, published_index, params[f"layer_{held:02d}"], x)
        x = _rms_norm(x, params["head"]["norm"], config["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(x, params["head"]["lm_w"]), axis=-1)
        picked = jnp.take_along_axis(logp, batch["labels"][..., None], -1)
        return -jnp.mean(picked)

    return loss


loss = make_loss(CONFIG)
