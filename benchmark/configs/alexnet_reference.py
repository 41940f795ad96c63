"""AlexNet's forward pass and loss in plain ``jax.numpy``, float32.

Follows Krizhevsky et al. 2012 as BVLC Caffe's ``bvlc_alexnet`` states it,
from the layer table in ``alexnet.json`` beside this file: convolution
(grouped where the paper split the model over two GPUs), ReLU, local
response normalisation across channels, 3x3 max pooling with stride 2, three
fully connected layers, softmax cross-entropy averaged over the batch.
Dropout is off (the evaluation forward).  It shares no code with
``sparknet_tpu``; it takes the program's parameter tree as it is laid out:
``params[layer]["weight"]`` as HWIO for a convolution (I = inputs of one
group) and ``(inputs, outputs)`` for a fully connected layer, whose inputs
are the NHWC feature map flattened in that order.
"""

import json
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(__file__), "alexnet.json")) as _fh:
    LAYERS = json.load(_fh)["layers"]


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )


def _grouped_conv(x, w, stride, pad, groups):
    cin, cout = x.shape[-1] // groups, w.shape[-1] // groups
    return jnp.concatenate(
        [
            _conv(
                x[..., g * cin:(g + 1) * cin],
                w[..., g * cout:(g + 1) * cout], stride, pad,
            )
            for g in range(groups)
        ],
        axis=-1,
    )


def _lrn(x, size, alpha, beta):
    half = size // 2
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (half, half)))
    window = sum(sq[..., j:j + x.shape[-1]] for j in range(size))
    return x / (1.0 + alpha / size * window) ** beta


def _max_pool(x, kernel, stride):
    out = (x.shape[1] - kernel) // stride + 1  # exact for 55, 27 and 13
    span = stride * (out - 1) + 1
    return jnp.max(
        jnp.stack([
            x[:, i:i + span:stride, j:j + span:stride, :]
            for i in range(kernel) for j in range(kernel)
        ]),
        axis=0,
    )


def loss(params, batch):
    x = batch["data"].astype(jnp.float32)
    last = [lp["name"] for lp in LAYERS if lp["kind"] == "fc"][-1]
    for lp in LAYERS:
        kind = lp["kind"]
        if kind == "conv":
            p = params[lp["name"]]
            x = _grouped_conv(
                x, p["weight"], lp["stride"], lp["pad"], lp["groups"]
            )
            x = jnp.maximum(x + p["bias"], 0.0)
        elif kind == "lrn":
            x = _lrn(x, lp["local_size"], lp["alpha"], lp["beta"])
        elif kind == "pool":
            x = _max_pool(x, lp["kernel"], lp["stride"])
        elif kind == "fc":
            p = params[lp["name"]]
            x = jnp.dot(
                x.reshape(x.shape[0], -1), p["weight"],
                precision=jax.lax.Precision.HIGHEST,
            ) + p["bias"]
            if lp["name"] != last:
                x = jnp.maximum(x, 0.0)
    logp = jax.nn.log_softmax(x, axis=-1)
    picked = jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
    return -jnp.mean(picked)
