"""extract_features — dump intermediate blob activations to an LMDB.

Twin of Caffe's ``tools/extract_features.cpp``: run a net's data layer
for N batches and write the named blob's per-sample features as float
Datums (channels = feature length), the format downstream Caffe-era
pipelines (SVM training, retrieval indexes) consume.

    python -m sparknet_tpu.tools.extract_features \
        --model net.prototxt [--weights w.caffemodel|.npz] \
        --blob ip1 --out feats_lmdb [--iterations 10] [--phase TEST]
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

import numpy as np

import jax


def extract(
    model: str,
    blob: str,
    out: str,
    weights: Optional[str] = None,
    iterations: int = 10,
    phase: str = "TEST",
) -> int:
    from ..data.caffe_layers import encode_datum
    from ..data.lmdb_io import write_lmdb
    from ..proto import caffe_pb
    from ._common import batch_transform_fn, build_phase_net, load_weights

    net_param = caffe_pb.load_net(model)
    model_dir = os.path.dirname(os.path.abspath(model))
    net, ds, tf, bs = build_phase_net(net_param, model_dir, phase)
    if net is None:
        raise SystemExit(
            f"extract_features: no on-disk data source in phase {phase}"
        )
    if blob not in net.blob_shapes:
        raise SystemExit(
            f"extract_features: blob {blob!r} not in net "
            f"(have: {sorted(net.blob_shapes)})"
        )
    params, state = net.init(jax.random.PRNGKey(0))
    if weights:
        params, state = load_weights(net, params, state, weights)

    # the serving engine is the one compile path for all inference
    # tools: one bucket, exactly the data layer's batch size
    from ..serve.engine import InferenceEngine

    engine = InferenceEngine(net, params, state, output=blob, buckets=(bs,))

    feed = ds.batches(
        bs, shuffle=False, seed=0, transform=batch_transform_fn(tf)
    )
    items = []
    for it in range(iterations):
        batch = next(feed)
        feats = np.asarray(engine.infer(batch), np.float32)
        flat = feats.reshape(len(feats), -1)
        for j, f in enumerate(flat):
            # Caffe stores features as channels=D, h=1, w=1 Datums;
            # encode_datum takes (H, W, C)
            items.append(
                (
                    f"{it * bs + j:010d}".encode(),
                    encode_datum(f.reshape(1, 1, -1), int(batch["label"][j])),
                )
            )
    os.makedirs(out, exist_ok=True)
    write_lmdb(out, items)
    return len(items)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="extract_features")
    ap.add_argument("--model", required=True)
    ap.add_argument("--weights", default=None)
    ap.add_argument("--blob", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--iterations", type=int, default=10)
    ap.add_argument("--phase", default="TEST", choices=("TRAIN", "TEST"))
    args = ap.parse_args(argv)
    n = extract(
        args.model, args.blob, args.out,
        weights=args.weights, iterations=args.iterations, phase=args.phase,
    )
    print(f"extracted {n} {args.blob} features to {args.out}")
    return n


if __name__ == "__main__":
    main()
