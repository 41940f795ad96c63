"""Synthetic convergence smokes for the big zoo nets.

The env ships no datasets (SURVEY.md §0), so these memorize a small
deterministic batch cycle — the same oracle tau_sweep.py uses: a net
whose loss falls markedly on memorisable data has working forward,
backward, and update paths end-to-end. GoogLeNet additionally pins the
train_val's three-head loss weighting (aux heads 0.3 + main 1.0);
ResNet-50 checks BatchNorm's moving stats stay sane while training.

Both are CPU-minutes heavy -> @slow (the nightly tier; `-m "not slow"`
skips them).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparknet_tpu.proto import caffe_pb
from sparknet_tpu.solver.trainer import Solver

ZOO = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "sparknet_tpu", "models", "prototxt",
)


def _memorisable_feed(bs, size, classes, n_distinct=2, seed=0):
    rng = np.random.default_rng(seed)
    batches = [
        {
            "data": rng.normal(size=(bs, size, size, 3)).astype(np.float32),
            "label": rng.integers(0, classes, bs).astype(np.int32),
        }
        for _ in range(n_distinct)
    ]
    while True:
        yield from batches


def _smoke_solver(proto, size, bs, iters, lr=0.01):
    sp = caffe_pb.load_solver(os.path.join(ZOO, proto))
    sp.base_lr = lr
    sp.lr_policy = "fixed"
    sp.max_iter = iters + 10
    sp.display = 0
    sp.snapshot = 0
    sp.test_interval = 0
    shapes = {"data": (bs, size, size, 3), "label": (bs,)}
    return Solver(sp, shapes, solver_dir=ZOO)


@pytest.mark.slow
def test_googlenet_synthetic_convergence():
    solver = _smoke_solver("bvlc_googlenet_quick_solver.prototxt", 224,
                           bs=4, iters=24)
    # the 1,310-line train_val's three loss heads, aux-weighted 0.3
    heads = {
        lp.top[0]: (lp.loss_weight[0] if lp.loss_weight else 1.0)
        for lp in solver.train_net.layers
        if lp.type == "SoftmaxWithLoss"
    }
    assert heads == {
        "loss1/loss": pytest.approx(0.3),
        "loss2/loss": pytest.approx(0.3),
        "loss3/loss": pytest.approx(1.0),
    }

    feed = _memorisable_feed(4, 224, classes=8)
    m0 = solver.step(feed, 2)
    first = {k: float(v) for k, v in m0.items() if "loss" in k}
    m1 = solver.step(feed, 22)
    last = {k: float(v) for k, v in m1.items() if "loss" in k}
    # every head must be finite and the main head clearly descending
    assert all(np.isfinite(v) for v in last.values()), last
    assert last["loss3/loss"] < first["loss3/loss"] * 0.85, (first, last)


@pytest.mark.slow
def test_resnet50_synthetic_convergence_and_bn_stats():
    solver = _smoke_solver("resnet50_solver.prototxt", 224, bs=2, iters=16)
    feed = _memorisable_feed(2, 224, classes=4, seed=1)
    m0 = solver.step(feed, 2)
    l0 = float(next(v for k, v in m0.items() if "loss" in k))
    m1 = solver.step(feed, 14)
    l1 = float(next(v for k, v in m1.items() if "loss" in k))
    assert np.isfinite(l1) and l1 < l0 * 0.9, (l0, l1)

    # BatchNorm moving stats: finite everywhere, variances positive
    bn_layers = 0
    for name, st in jax.device_get(solver.state).items():
        if not isinstance(st, dict) or "mean" not in st:
            continue
        bn_layers += 1
        assert np.all(np.isfinite(st["mean"])), name
        assert np.all(np.isfinite(st["var"])), name
        assert np.all(np.asarray(st["var"]) >= 0.0), name
    assert bn_layers >= 49, f"ResNet-50 should carry >=49 BN layers, saw {bn_layers}"
