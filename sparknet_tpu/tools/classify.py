"""classify — deploy-prototxt inference over a .caffemodel.

The reference era's ``classification.cpp`` / ``classify.py`` workflow:
load a deploy NetParameter, overlay trained weights, preprocess images
(resize, BGR, mean subtract) and report top-k classes.

Inference routes through ``serve.InferenceEngine`` — the ONE compile
path shared with the serving subsystem and extract_features, so the
one-shot tool and the persistent server cannot drift.

    python -m sparknet_tpu.tools.classify \
        --model deploy.prototxt --weights model.caffemodel \
        [--mean mean.binaryproto] [--labels synset_words.txt] img.jpg...
"""

from __future__ import annotations

import argparse
from typing import List, Optional

import numpy as np

import jax


def load_model(model: str, weights: Optional[str] = None, batch: int = 1):
    from ..nets.xlanet import XLANet
    from ..proto import caffe_pb

    net_param = caffe_pb.load_net(model)
    net = XLANet(net_param, "TEST")
    params, state = net.init(jax.random.PRNGKey(0))
    if weights:
        from ..serve.engine import load_weights_any

        params, state = load_weights_any(net, params, state, weights)
    return net, params, state


def preprocess(
    paths: List[str], size: int, mean_hwc: Optional[np.ndarray]
) -> np.ndarray:
    from PIL import Image

    out = []
    for p in paths:
        img = Image.open(p).convert("RGB").resize((size, size), Image.BILINEAR)
        arr = np.asarray(img, np.float32)[:, :, ::-1]  # BGR, Caffe order
        if mean_hwc is not None:
            arr = arr - mean_hwc
        out.append(arr)
    return np.stack(out)


def make_engine(net, params, state, buckets=(1, 8, 32)):
    """The resident engine main() classifies through — shared compile
    path with ``tools/serve`` and ``extract_features``."""
    from ..serve.engine import InferenceEngine

    return InferenceEngine(net, params, state, buckets=buckets)


def classify(
    net, params, state, batch_hwc: np.ndarray, top_k: int = 5, engine=None
):
    """-> (indices (N, top_k), probs (N, top_k)) from the net's final
    blob (softmaxed by the engine if the deploy net ends in logits).
    One-shot callers get a single-bucket engine sized to the batch (no
    padding); pass ``engine`` to reuse compiled executables."""
    if engine is None:
        engine = make_engine(net, params, state, buckets=(len(batch_hwc),))
    return engine.topk(batch_hwc, top_k)


def main(argv=None):
    ap = argparse.ArgumentParser(description="deploy-net image classification")
    ap.add_argument("--model", required=True, help="deploy .prototxt")
    ap.add_argument("--weights", default=None, help=".caffemodel")
    ap.add_argument("--mean", default=None, help="mean .binaryproto")
    ap.add_argument("--labels", default=None, help="one label per line")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("images", nargs="+")
    args = ap.parse_args(argv)

    net, params, state = load_model(args.model, args.weights)
    name = net.input_names[0] if net.input_names else "data"
    size = net.blob_shapes[name][1]
    mean = None
    if args.mean:
        from ..proto.caffemodel import load_binaryproto_mean

        mean = load_binaryproto_mean(args.mean)
    labels = None
    if args.labels:
        labels = [l.strip() for l in open(args.labels)]

    batch = preprocess(args.images, size, mean)
    engine = make_engine(net, params, state, buckets=(len(batch),))
    idx, probs = classify(net, params, state, batch, args.top_k, engine=engine)
    for img, row_i, row_p in zip(args.images, idx, probs):
        print(f"{img}:")
        for i, p in zip(row_i, row_p):
            label = labels[i] if labels and i < len(labels) else str(i)
            print(f"  {p:.4f} {label}")
    return idx, probs


if __name__ == "__main__":
    main()
