"""Mellum2's forward pass and loss on packed documents in plain ``jax.numpy``,
float32, for one chip's share of the model as ``mellum2.json`` beside this
file cuts it (``make_loss`` takes any such configuration; a test hands it a
tiny one, and an uncut one for the add-up test).

Per layer, on tokens ``x``: ``h = x + Attn(RMSNorm(x))``, ``y = h +
MoE(RMSNorm(h))``; after the last layer a final RMSNorm and the untied head.

- A batch is sequences into which documents were packed back to back:
  ``segment_ids`` (B, S) names each token's document, and does not decrease
  along a sequence.  Everything here is derived from those ids: a token's
  position is its index less the index of the first token that carries its
  id (the batch's ``positions`` blob is not read); token i sees key j iff
  ``segment_ids[j] == segment_ids[i]``, ``j <= i`` and, on
  ``sliding_attention`` layers, ``i - j < sliding_window`` — one dense (S, S)
  mask a sequence and kind of layer.
- Attention: ``num_attention_heads`` query heads over ``num_key_value_heads``
  KV heads of ``head_dim``, no bias; query head h reads KV head ``h // (H /
  KV)``.  Rotary on the whole head by the layer's type (``rope_parameters``),
  rotate-half: ``default`` on sliding layers; on full layers YaRN as
  transformers computes it (inverse frequencies blended between ``1/f`` and
  ``1/(factor f)`` by a linear ramp over the truncated correction range of
  ``beta_fast`` and ``beta_slow``, ``attention_factor`` on cos and sin).
  Scores ``q.k / sqrt(head_dim)``, the mask, softmax, ``concat(heads) W_o``;
  a block of queries at a time, so that no (H, S, S) array exists whole.
- Sparse FFN, every layer: ``p = softmax(u W_r)`` over all
  ``num_experts_routed`` experts, the ``num_experts_per_tok`` largest, ``w =
  p / sum(p)`` over all chosen (``norm_topk_prob``); ``MoE(u) = sum over the
  chosen experts HELD HERE of w_k E_k(u)``, ``E(u) = (silu(u W_g) * (u W_u))
  W_d``; no shared expert.  What the absent experts would add is left out.
  Every held expert is run on every token and masked: plain, not fast.
- Loss: the mean cross-entropy, over the vocabulary slice, of the next token
  at every position whose next token carries the same id; a document's last
  token and a sequence's last position bear none.  It is taken from the ids,
  and the batch's ``labels`` are read only for the token (a ``labels`` of
  -100 where this file finds a loss would read as a wrong answer, not as no
  answer).

Departures from the publication (``assumed`` in the JSON): the router's
softmax and its normalisation are the ``norm_topk_prob`` convention; pre-norm;
the described MTP head is not modelled (no key, no equation).  It shares no
code with ``sparknet_tpu``; it takes the program's parameter tree by its
names (``experts_gate_up`` holds gate in its first half of columns).
"""

import json
import math
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(__file__), "mellum2.json")) as _fh:
    CONFIG = json.load(_fh)
_HIGH = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGH)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def document_positions(segment_ids):
    """(B, S): each token's index inside its document."""
    s = segment_ids.shape[1]
    at = jnp.arange(s)
    same = segment_ids[:, :, None] == segment_ids[:, None, :]  # (B, i, j)
    first = jnp.argmax(same, axis=-1)  # the first j that carries i's id
    return at[None, :] - first


def seen_mask(segment_ids, window):
    """(B, S, S) bool: query i (rows) sees key j (columns)."""
    s = segment_ids.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    band = (j <= i) & (i - j < window)
    return band[None] & (segment_ids[:, :, None] == segment_ids[:, None, :])


def _inverse_frequencies(rope, head_dim):
    """(the head's rot/2 inverse frequencies, the factor on cos and sin)."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = rope["rope_theta"]
    freqs = [theta ** (2 * i / rot) for i in range(rot // 2)]
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return [1.0 / f for f in freqs], 1.0
    if kind != "yarn":
        raise NotImplementedError(kind)
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
    return inv, rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0


def _rotate(x, positions, rope):
    """x: (B, S, H, D) turned by each token's position (B, S)."""
    inv, scale = _inverse_frequencies(rope, x.shape[-1])
    rot = 2 * len(inv)
    angles = positions.astype(jnp.float32)[:, :, None] * jnp.asarray(
        inv + inv, jnp.float32
    )
    cos = (jnp.cos(angles) * scale)[:, :, None, :]
    sin = (jnp.sin(angles) * scale)[:, :, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    head = x[..., :rot] * cos + jnp.concatenate([-b, a], -1) * sin
    return jnp.concatenate([head, x[..., rot:]], -1)


def _attention(config, kind, p, u, segment_ids):
    b, s, _ = u.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, group = config["head_dim"], heads // kv
    rope = config["rope_parameters"][kind]
    positions = document_positions(segment_ids)
    q = _rotate(_mm(u, p["q_w"]).reshape(b, s, heads, d), positions, rope)
    k = _rotate(_mm(u, p["k_w"]).reshape(b, s, kv, d), positions, rope)
    v = _mm(u, p["v_w"]).reshape(b, s, kv, d)
    window = config["sliding_window"] if kind == "sliding_attention" else s
    seen = seen_mask(segment_ids, window)  # (B, S, S)
    block = math.gcd(s, QUERY_BLOCK)
    in_blocks = lambda x: jnp.moveaxis(
        x.reshape(b, s // block, block, *x.shape[2:]), 1, 0
    )

    def one_block(args):
        qb, rows = args  # (B, block, H, D), (B, block, S)
        qb = qb.reshape(b, block, kv, group, d)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k, precision=_HIGH)
        scores = jnp.where(
            rows[:, None, None], scores / math.sqrt(d), -jnp.inf
        )
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=_HIGH)
        return out.reshape(b, block, heads * d)

    out = jax.lax.map(one_block, (in_blocks(q), in_blocks(seen)))
    return _mm(jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d), p["o_w"])


def _sparse_ffn(config, p, u):
    first = config.get("deployment", {}).get("experts_first", 0)
    held = config["num_experts"]
    width = config["moe_intermediate_size"]
    probs = jax.nn.softmax(_mm(u, p["router_w"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])
    weights = top / top.sum(-1, keepdims=True)

    def add_expert(total, expert):
        e, gate_up, down = expert
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        gate, up = _mm(u, gate_up[:, :width]), _mm(u, gate_up[:, width:])
        return total + mine[..., None] * _mm(jax.nn.silu(gate) * up, down), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.arange(held), p["experts_gate_up"], p["experts_down"]),
    )
    return routed


def layer(config, index, p, x, segment_ids):
    """Layer ``index`` of the configuration on ``x`` (B, S, hidden)."""
    eps = config["rms_norm_eps"]
    kind = config["layer_types"][index]
    h = x + _attention(
        config, kind, p, _rms_norm(x, p["attn_norm"], eps), segment_ids
    )
    return h + _sparse_ffn(config, p, _rms_norm(h, p["ffn_norm"], eps))


def make_loss(config):
    """``loss(params, batch)`` of a configuration in ``mellum2.json``'s
    form, on a batch with ``input_ids``, ``labels`` and ``segment_ids``."""

    def loss(params, batch):
        ids = batch["segment_ids"]
        x = params["embed"]["tokens"][batch["input_ids"]]
        for index in range(config["num_hidden_layers"]):
            x = layer(config, index, params[f"layer_{index:02d}"], x, ids)
        x = _rms_norm(x, params["head"]["norm"], config["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(x, params["head"]["lm_w"]), axis=-1)
        picked = jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
        # a position bears a loss iff the next token carries its id
        borne = jnp.concatenate(
            [ids[:, 1:] == ids[:, :-1], jnp.zeros_like(ids[:, :1], bool)], 1
        )
        return -jnp.sum(jnp.where(borne, picked, 0.0)) / jnp.sum(borne)

    return loss


loss = make_loss(CONFIG)
