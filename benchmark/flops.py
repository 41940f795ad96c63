"""Model FLOPs of one training step, computed from shapes, and the chip peaks.

Model FLOPs are the multiply-adds of the matrix multiplications and
convolutions the architecture requires, times two: forward once, backward
twice (gradient of the input and of the weights).  A layer fed by the data
needs no input gradient and counts twice, not three times.  Elementwise work
(ReLU, LRN, pooling, layer norm, softmax, the optimizer) and anything
recomputed count nothing, so the figure does not change when the program
does.  A configuration file names its function as ``"flops":
"module:function"``; the function takes the configuration (its JSON) and the
shapes of one batch, and returns the FLOPs of one step.
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Dict, Mapping, Sequence

Shapes = Mapping[str, Sequence[int]]

_PEAKS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks")


def conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def pool_out(size: int, kernel: int, stride: int, pad: int = 0) -> int:
    """Caffe rounds pooling windows up."""
    return math.ceil((size + 2 * pad - kernel) / stride) + 1


def convnet_layer_macs(
    layers: Sequence[Mapping], size: int, channels: int
) -> Dict[str, int]:
    """Multiply-adds per image of every conv and fc layer of a feed-forward
    stack, walking the spatial size and the channels from the input."""
    macs: Dict[str, int] = {}
    features = None  # set once the first fc flattens the map
    for lp in layers:
        kind = lp["kind"]
        if kind == "conv":
            size = conv_out(size, lp["kernel"], lp["stride"], lp["pad"])
            macs[lp["name"]] = (
                size * size * lp["out"]
                * lp["kernel"] ** 2 * (channels // lp["groups"])
            )
            channels = lp["out"]
        elif kind == "pool":
            size = pool_out(size, lp["kernel"], lp["stride"], lp.get("pad", 0))
        elif kind == "fc":
            if features is None:
                features = size * size * channels
            macs[lp["name"]] = features * lp["out"]
            features = lp["out"]
    return macs


def convnet(config: Mapping, shapes: Shapes) -> float:
    """Training FLOPs of one step of a Caffe-style stack (``layers`` in the
    configuration file) on a batch ``data: (N, H, W, C)``."""
    n, height, _width, channels = shapes["data"]
    macs = convnet_layer_macs(config["layers"], height, channels)
    first = next(iter(macs))
    per_image = sum(
        2 * m * (2 if name == first else 3) for name, m in macs.items()
    )
    return float(n * per_image)


def bert_mlm(config: Mapping, shapes: Shapes) -> float:
    """Training FLOPs of one BERT MLM step on ``input_ids: (B, S)`` with
    ``mlm_positions: (B, M)`` predicted positions: per layer the four
    attention projections, the two feed-forward matrices and the two
    attention products (scores and context, S x S per head, all heads
    together 2*S*S*hidden multiply-adds a sequence); in the head the dense
    layer and the tied decoder over the vocabulary, at the predicted
    positions only.  The embedding look-ups multiply nothing."""
    b, s = shapes["input_ids"]
    m = shapes["mlm_positions"][1]
    h = config["hidden_size"]
    layers = config["num_hidden_layers"]
    per_token = 4 * h * h + 2 * h * config["intermediate_size"]
    encoder = layers * (b * s * per_token + b * 2 * s * s * h)
    head = b * m * (h * h + h * config["vocab_size"])
    return float(3 * 2 * (encoder + head))


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of this kind.  A kind with no file
    under ``peaks/`` is an error, never a default."""
    path = os.path.join(
        _PEAKS_DIR, re.sub(r"[^A-Za-z0-9.-]", "_", device_kind) + ".json"
    )
    if not os.path.isfile(path):
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add {path} with "
            f"the chip's published numbers and their source"
        )
    with open(path) as fh:
        table = json.load(fh)
    if table["device_kind"] != device_kind:
        raise KeyError(f"{path} describes {table['device_kind']!r}")
    return table
