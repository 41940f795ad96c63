"""BERT's forward pass and masked-language-model loss in plain
``jax.numpy``, float32.

Follows Devlin et al. 2018 and the original implementation
(google-research/bert ``modeling.py``): summed word, position and segment
embeddings, layer norm; per layer multi-head scaled dot-product attention
with padded keys masked, output projection, residual and layer norm, a
feed-forward block with GELU in the tanh form the original code uses,
residual and layer norm; the MLM head gathers the predicted positions,
applies dense + GELU + layer norm and decodes with the word embeddings'
transpose plus a bias; the loss is the cross-entropy averaged over the
predicted positions that carry weight.  Dropout is off (the evaluation
forward).  Departure from the publication: no pooler and no next-sentence
loss, as the program trains the MLM objective alone.  It shares no code
with ``sparknet_tpu``; it takes the program's parameter tree by its names,
and the head size from ``bert_base.json`` beside this file.
"""

import json
import math
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(__file__), "bert_base.json")) as _fh:
    _CONFIG = json.load(_fh)
HEAD_SIZE = _CONFIG["hidden_size"] // _CONFIG["num_attention_heads"]
EPS = _CONFIG["layer_norm_eps"]
_HIGH = jax.lax.Precision.HIGHEST


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * scale + bias


def _gelu(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3))
    )


def _dense(x, w, b):
    return jnp.dot(x, w, precision=_HIGH) + b


def _layer(p, x, key_bias):
    b, s, h = x.shape
    heads = h // HEAD_SIZE
    split = lambda t: t.reshape(b, s, heads, HEAD_SIZE)
    q = split(_dense(x, p["q_w"], p["q_b"]))
    k = split(_dense(x, p["k_w"], p["k_b"]))
    v = split(_dense(x, p["v_w"], p["v_b"]))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HIGH)
    probs = jax.nn.softmax(scores / math.sqrt(HEAD_SIZE) + key_bias, axis=-1)
    context = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HIGH)
    attended = _dense(context.reshape(b, s, h), p["out_w"], p["out_b"])
    x = _layer_norm(x + attended, p["attn_ln_scale"], p["attn_ln_bias"])
    hidden = _gelu(_dense(x, p["ffn_in_w"], p["ffn_in_b"]))
    fed = _dense(hidden, p["ffn_out_w"], p["ffn_out_b"])
    return _layer_norm(x + fed, p["ffn_ln_scale"], p["ffn_ln_bias"])


def loss(params, batch):
    emb = params["embeddings"]
    ids = batch["input_ids"]
    positions = batch.get("position_ids")
    if positions is None:
        positions = jnp.arange(ids.shape[1])[None, :]
    x = (
        emb["word"][ids] + emb["position"][positions]
        + emb["token_type"][batch["token_type_ids"]]
    )
    x = _layer_norm(x, emb["ln_scale"], emb["ln_bias"])
    key_bias = jnp.where(batch["attention_mask"] > 0, 0.0, -1e9)[:, None, None, :]
    for name in sorted(k for k in params if k.startswith("layer_")):
        x = _layer(params[name], x, key_bias)
    head = params["mlm_head"]
    picked = jnp.take_along_axis(x, batch["mlm_positions"][:, :, None], axis=1)
    t = _gelu(_dense(picked, head["dense_w"], head["dense_b"]))
    t = _layer_norm(t, head["ln_scale"], head["ln_bias"])
    logits = jnp.dot(t, emb["word"].T, precision=_HIGH) + head["output_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["mlm_labels"][:, :, None], axis=-1)[..., 0]
    weights = batch["mlm_weights"].astype(jnp.float32)
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
