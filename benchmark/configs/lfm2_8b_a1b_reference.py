"""LFM2-8B-A1B's forward pass and loss on packed documents in plain
``jax.numpy``, float32, for one chip's share of the model as
``lfm2_8b_a1b.json`` beside this file cuts it (``make_loss`` takes any such
configuration; a test hands it a tiny one, and an uncut one for the add-up
test).

``E`` is the tied embedding.  ``x_0 = E[ids]``; per layer ``h = x +
Mix(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``; ``logits = RMSNorm(x_L)
E^T``.  The layers are the published ones ``deployment.layers_kept`` names
(default the first ``num_hidden_layers``), each of the kind its published
index has in ``layer_types``.

- A batch is sequences into which documents were packed back to back:
  ``segment_ids`` (B, S) names each token's document and does not decrease
  along a sequence.  Everything here is derived from those ids: a token's
  position in its document, which taps and keys it reads, where a loss is
  (the batch's ``positions`` blob is not read).
- Gated short convolution (``conv``): ``[B | C | x] = u W_in`` (that order
  by columns); ``v = B * x``; ``z_t = sum_j w_j v_(t-L+1+j)`` over the
  ``conv_L_cache`` = L taps, a tap before the token's document reading 0, a
  loop over the taps; ``y = C * z``; ``y W_out``.  No bias, no activation.
- Attention (``full_attention``): ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads of ``hidden / heads``, no bias; q and k
  of each head normed by an RMSNorm over the head (``q_norm``, ``k_norm``:
  one scale a channel of a head, shared by the heads), then rotated by the
  token's position in its document (rotate-half over the whole head, the
  default inverse frequencies of ``rope_theta``); scores ``q.k /
  sqrt(head)``; token i sees key j iff both carry one id and ``j <= i``;
  ``concat(heads) W_o``; a block of queries at a time, so that no (H, S, S)
  array exists whole.
- FFN: a published layer below ``num_dense_layers`` has the dense SwiGLU
  ``(silu(u W_g) * (u W_u)) W_d`` of ``intermediate_size``; the others are
  sparse: ``s = sigmoid(u W_r)`` over all ``num_experts_routed`` experts;
  the ``num_experts_per_tok`` largest of ``s + bias`` (``router_bias``)
  chosen; ``w = s / (sum(s) + 1e-6) * routed_scaling_factor`` over all the
  chosen; ``MoE(u) = sum over the chosen experts HELD HERE of w E(u)``, the
  experts SwiGLUs of ``moe_intermediate_size``, each held expert run on
  every token and masked, one after another.  What the absent experts would
  add is left out.
- Loss: the mean cross-entropy, over the vocabulary slice, of the next token
  at every position whose next token carries the same id (the batch's
  ``labels`` read only for the token).  A batch without ``segment_ids`` is
  one document a row with a label at every position, as a batch that is
  not packed is.

Departures from the published model are the configuration's cut: the
vocabulary is a slice of the tied embedding's rows and the logits, the ids
and the loss are over it; the experts held here are a share of the routed
ones.  The selection bias is a value drawn from the seed, not a trained one
(``assumed`` in the JSON).  It shares no code with ``sparknet_tpu``; it
takes the program's parameter tree by its names (``experts_gate_up`` holds
gate in its first half of columns).
"""

import json
import math
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(__file__), "lfm2_8b_a1b.json")) as _fh:
    CONFIG = json.load(_fh)
_HIGH = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
ROUTER_EPS = 1e-6


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGH)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def document_positions(segment_ids):
    """(B, S): each token's index inside its document."""
    same = segment_ids[:, :, None] == segment_ids[:, None, :]  # (B, i, j)
    return jnp.arange(segment_ids.shape[1])[None, :] - jnp.argmax(same, axis=-1)


def kept_layers(config):
    """The published indices of the layers held, in order."""
    n = config["num_hidden_layers"]
    return list(config.get("deployment", {}).get("layers_kept", range(n)))


def _short_conv(config, p, u, positions):
    hidden = u.shape[-1]
    bcx = _mm(u, p["in_proj"])
    b_gate, c_gate, x = bcx[..., :hidden], bcx[..., hidden:2 * hidden], bcx[..., 2 * hidden:]
    v = b_gate * x
    taps = config["conv_L_cache"]
    s = v.shape[1]
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    z = jnp.zeros_like(v)
    for j in range(taps):
        lag = taps - 1 - j
        inside = (positions >= lag)[..., None]
        z = z + jnp.where(inside, padded[:, j:j + s], 0.0) * p["conv_w"][j]
    return _mm(c_gate * z, p["out_proj"])


def _rotate(x, positions, theta):
    """x: (B, S, H, D) turned by each token's position (B, S)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, :, None] * inv
    angles = jnp.concatenate([angles, angles], -1)[:, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angles) + half * jnp.sin(angles)


def _attention(config, p, u, segment_ids, positions):
    b, s, hidden = u.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, group = hidden // heads, heads // kv
    eps, theta = config["norm_eps"], config["rope_theta"]
    q = _mm(u, p["q_w"]).reshape(b, s, heads, d)
    k = _mm(u, p["k_w"]).reshape(b, s, kv, d)
    v = _mm(u, p["v_w"]).reshape(b, s, kv, d)
    q = _rotate(_rms_norm(q, p["q_norm"], eps), positions, theta)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), positions, theta)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i)[None] & (segment_ids[:, :, None] == segment_ids[:, None, :])
    block = math.gcd(s, QUERY_BLOCK)
    in_blocks = lambda x: jnp.moveaxis(x.reshape(b, s // block, block, *x.shape[2:]), 1, 0)

    def one_block(args):
        qb, rows = args  # (B, block, H, d), (B, block, S)
        qb = qb.reshape(b, block, kv, group, d)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k, precision=_HIGH)
        scores = jnp.where(rows[:, None, None], scores / math.sqrt(d), -jnp.inf)
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", jax.nn.softmax(scores, axis=-1), v, precision=_HIGH
        )
        return out.reshape(b, block, heads * d)

    out = jax.lax.map(one_block, (in_blocks(q), in_blocks(seen)))
    return _mm(jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d), p["o_w"])


def _swiglu(u, gate, up, down):
    return _mm(jax.nn.silu(_mm(u, gate)) * _mm(u, up), down)


def sparse_ffn(config, p, u):
    """The held experts' part of a sparse layer on ``u`` (B, S, hidden)."""
    deployment = config.get("deployment", {})
    first = deployment.get("experts_first", 0)
    width = config["moe_intermediate_size"]
    scores = jax.nn.sigmoid(_mm(u, p["router_w"]))
    _, chosen = jax.lax.top_k(scores + p["router_bias"], config["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, -1)
    weights = top / (top.sum(-1, keepdims=True) + ROUTER_EPS) * config["routed_scaling_factor"]

    def add_expert(total, expert):
        e, gate_up, down = expert
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = _swiglu(u, gate_up[:, :width], gate_up[:, width:], down)
        return total + mine[..., None] * out, None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.arange(config["num_experts"]), p["experts_gate_up"], p["experts_down"]),
    )
    return routed


def layer(config, published, p, x, segment_ids):
    """The layer of published index ``published`` on ``x`` (B, S, hidden)."""
    eps = config["norm_eps"]
    positions = document_positions(segment_ids)
    u = _rms_norm(x, p["attn_norm"], eps)
    if config["layer_types"][published] == "conv":
        h = x + _short_conv(config, p, u, positions)
    else:
        h = x + _attention(config, p, u, segment_ids, positions)
    u = _rms_norm(h, p["ffn_norm"], eps)
    if published < config["num_dense_layers"]:
        return h + _swiglu(u, p["gate_w"], p["up_w"], p["down_w"])
    return h + sparse_ffn(config, p, u)


def make_loss(config):
    """``loss(params, batch)`` of a configuration in ``lfm2_8b_a1b.json``'s
    form, on a batch with ``input_ids``, ``labels`` and ``segment_ids``."""

    def loss(params, batch):
        ids = batch.get("segment_ids")
        if ids is None:  # one document a row, a label at every position
            ids = jnp.zeros_like(batch["input_ids"])
            borne = jnp.ones(ids.shape, bool)
        else:  # a position bears a loss iff the next token carries its id
            borne = jnp.concatenate(
                [ids[:, 1:] == ids[:, :-1], jnp.zeros_like(ids[:, :1], bool)], 1
            )
        table = params["embed"]["tokens"]
        x = table[batch["input_ids"]]
        for index, published in enumerate(kept_layers(config)):
            x = layer(config, published, params[f"layer_{index:02d}"], x, ids)
        x = _rms_norm(x, params["head"]["norm"], config["norm_eps"])
        logp = jax.nn.log_softmax(_mm(x, table.T), axis=-1)
        picked = jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
        return -jnp.sum(jnp.where(borne, picked, 0.0)) / jnp.sum(borne)

    return loss


loss = make_loss(CONFIG)
