"""IBM Granite 4.0-H Micro's forward pass and loss on packed documents in
plain ``jax.numpy``, float32, for one chip's share of the model as
``granite4_h_micro.json`` beside this file cuts it (``make_loss`` takes any
such configuration; a test hands it a tiny one).

``h`` is the residual stream, ``E`` the tied embedding.  ``h_0 =
embedding_multiplier * E[ids]``; per layer ``a = h + r Mix(RMSNorm(h))``,
``h' = a + r MLP(RMSNorm(a))`` with ``r = residual_multiplier``; ``logits =
RMSNorm(h_L) E^T / logits_scaling``.  ``MLP(u) = (silu(u W_in[:, :F]) * (u
W_in[:, F:])) W_out``, ``F = shared_intermediate_size``.

- A batch is sequences into which documents were packed back to back:
  ``segment_ids`` (B, S) names each token's document and does not decrease
  along a sequence.  Everything here is derived from those ids: a token's
  position in its document, where a document begins, which keys a token
  sees, where a loss is (the batch's ``positions`` blob is not read).
- Mamba-2 mixer (``layer_types`` ``mamba``), token by token, exactly the
  recurrence: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(sum_j w_j
  xBC_(t-3+j) + b)``, a tap before the token's document reading 0; ``x | B |
  C``, ``x`` in ``mamba_n_heads`` heads of ``mamba_d_head``; ``delta =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = a_t
  S_(t-1) + delta_t x_t B_t^T`` with ``a_t = exp(delta_t A)``, or 0 at a
  document's first token, ``S_0 = 0``; ``y_t = S_t C_t + D x_t``; ``y <-
  RMSNorm(y * silu(z)) w`` over all the heads' channels; ``y W_out``.  A
  ``lax.scan`` over the tokens: a different algorithm from the chunked scan
  it checks.
- Attention (``attention``): ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads of ``hidden / heads``, no bias, **no
  positions** (NoPE); scores ``q.k * attention_multiplier``; token i sees key
  j iff both carry one id and ``j <= i``; ``concat(heads) W_o``; a block of
  queries at a time, so that no (H, S, S) array exists whole.
- Loss: the mean cross-entropy, over the vocabulary slice, of the next token
  at every position whose next token carries the same id (the batch's
  ``labels`` read only for the token).

Its one departure from the published model is the configuration's:
the vocabulary is a slice of the tied embedding's rows (``reduced``), and
the logits, the ids and the loss are over it.  It shares no code with
``sparknet_tpu``; it takes the program's parameter tree by its names
(``in_proj`` is ``[z | xBC | dt]`` by columns, ``mlp_in`` gate first).
"""

import json
import math
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(__file__), "granite4_h_micro.json")) as _fh:
    CONFIG = json.load(_fh)
_HIGH = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGH)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def document_positions(segment_ids):
    """(B, S): each token's index inside its document."""
    same = segment_ids[:, :, None] == segment_ids[:, None, :]  # (B, i, j)
    return jnp.arange(segment_ids.shape[1])[None, :] - jnp.argmax(same, axis=-1)


def _conv(config, p, xbc, positions):
    """The depthwise causal convolution with bias, taps before the token's
    document reading 0, then silu."""
    taps = config["mamba_d_conv"]
    s = xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    out = p["conv_b"]
    for j in range(taps):
        lag = taps - 1 - j
        inside = (positions >= lag)[..., None]
        out = out + jnp.where(inside, padded[:, j:j + s], 0.0) * p["conv_w"][j]
    return jax.nn.silu(out)


def _mamba(config, p, u, positions):
    b, s, _ = u.shape
    heads, hp, n = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    width = heads * hp
    proj = _mm(u, p["in_proj"])
    z, xbc, dt = proj[..., :width], proj[..., width:2 * width + 2 * n], proj[..., 2 * width + 2 * n:]
    xbc = _conv(config, p, xbc, positions)
    x = xbc[..., :width].reshape(b, s, heads, hp)
    bm, cm = xbc[..., width:width + n], xbc[..., width + n:]
    delta = jax.nn.softplus(dt + p["dt_bias"])  # (B, S, H)
    a = -jnp.exp(p["A_log"])

    def token(state, inputs):
        x_t, delta_t, b_t, c_t, first = inputs
        decay = jnp.where(first[:, None], 0.0, jnp.exp(delta_t * a))
        state = decay[..., None, None] * state + jnp.einsum(
            "bhp,bn->bhpn", delta_t[..., None] * x_t, b_t, precision=_HIGH
        )
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t, precision=_HIGH)

    over_time = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(
        token, jnp.zeros((b, heads, hp, n), jnp.float32),
        tuple(map(over_time, (x, delta, bm, cm, positions == 0))),
    )
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x
    y = y.reshape(b, s, width) * jax.nn.silu(z)
    return _mm(_rms_norm(y, p["ssm_norm"], config["rms_norm_eps"]), p["out_proj"])


def _attention(config, p, u, segment_ids):
    b, s, hidden = u.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, group = hidden // heads, heads // kv
    q = _mm(u, p["q_w"]).reshape(b, s, heads, d)
    k = _mm(u, p["k_w"]).reshape(b, s, kv, d)
    v = _mm(u, p["v_w"]).reshape(b, s, kv, d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i)[None] & (segment_ids[:, :, None] == segment_ids[:, None, :])
    block = math.gcd(s, QUERY_BLOCK)
    in_blocks = lambda x: jnp.moveaxis(x.reshape(b, s // block, block, *x.shape[2:]), 1, 0)

    def one_block(args):
        qb, rows = args  # (B, block, H, d), (B, block, S)
        qb = qb.reshape(b, block, kv, group, d)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k, precision=_HIGH)
        scores = jnp.where(
            rows[:, None, None], scores * config["attention_multiplier"], -jnp.inf
        )
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", jax.nn.softmax(scores, axis=-1), v, precision=_HIGH
        )
        return out.reshape(b, block, heads * d)

    out = jax.lax.map(one_block, (in_blocks(q), in_blocks(seen)))
    return _mm(jnp.moveaxis(out, 0, 1).reshape(b, s, heads * d), p["o_w"])


def _mlp(config, p, u):
    width = config["shared_intermediate_size"]
    both = _mm(u, p["mlp_in"])
    return _mm(jax.nn.silu(both[..., :width]) * both[..., width:], p["mlp_out"])


def layer(config, index, p, h, segment_ids):
    """Layer ``index`` of the configuration on ``h`` (B, S, hidden)."""
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    u = _rms_norm(h, p["attn_norm"], eps)
    if config["layer_types"][index] == "mamba":
        mixed = _mamba(config, p, u, document_positions(segment_ids))
    else:
        mixed = _attention(config, p, u, segment_ids)
    a = h + r * mixed
    return a + r * _mlp(config, p, _rms_norm(a, p["ffn_norm"], eps))


def make_loss(config):
    """``loss(params, batch)`` of a configuration in
    ``granite4_h_micro.json``'s form, on a batch with ``input_ids``,
    ``labels`` and ``segment_ids``."""

    def loss(params, batch):
        ids = batch["segment_ids"]
        table = params["embed"]["tokens"]
        h = config["embedding_multiplier"] * table[batch["input_ids"]]
        for index in range(config["num_hidden_layers"]):
            h = layer(config, index, params[f"layer_{index:02d}"], h, ids)
        h = _rms_norm(h, params["head"]["norm"], config["rms_norm_eps"])
        logits = _mm(h, table.T) / config["logits_scaling"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
        # a position bears a loss iff the next token carries its id
        borne = jnp.concatenate(
            [ids[:, 1:] == ids[:, :-1], jnp.zeros_like(ids[:, :1], bool)], 1
        )
        return -jnp.sum(jnp.where(borne, picked, 0.0)) / jnp.sum(borne)

    return loss


loss = make_loss(CONFIG)
