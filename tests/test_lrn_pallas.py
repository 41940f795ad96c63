"""The Pallas LRN (``ops/lrn.py``) against the ``jax.numpy`` oracle, forward
and gradient, in interpret mode, in both orientations; the rule that picks
the kernels; the backward's residuals.

The ``jax.numpy`` form in nets/layers.py is torch-verified (test_layers);
the kernels must match it closely in float32, including through jax.grad."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sparknet_tpu.nets import layers as L
from sparknet_tpu.ops.lrn import lrn_nhwc, orientation, uses_lrn_kernel
from sparknet_tpu.proto.caffe_pb import LayerParameter
from sparknet_tpu.proto.textformat import parse


def _oracle(x, size, alpha, beta, k):
    lp = LayerParameter.from_message(parse(
        f'name: "n" type: "LRN" lrn_param {{ local_size: {size} '
        f"alpha: {alpha} beta: {beta} k: {k} }}"
    ))
    (y,), _ = L.LRN.apply(lp, {}, None, [x], None)
    return y


CASES = [
    # (shape, size, alpha, beta, k)
    ((2, 5, 5, 96), 5, 1e-4, 0.75, 1.0),   # AlexNet norm1 geometry
    ((2, 4, 4, 256), 5, 1e-4, 0.75, 1.0),  # AlexNet norm2 channels
    ((1, 3, 3, 64), 5, 1e-4, 0.75, 2.0),   # GoogLeNet-style k=2
    ((2, 3, 3, 32), 3, 5e-5, 0.5, 1.0),    # dyadic beta=0.5
    ((1, 2, 2, 16), 4, 1e-4, 0.9, 1.0),    # even window + general beta
    # the batch on the lanes (C no whole lane tile, N one)
    ((128, 3, 3, 96), 5, 1e-4, 0.75, 1.0),  # norm1's channels
    ((128, 2, 3, 64), 5, 1e-4, 0.75, 2.0),  # GoogLeNet's, k=2
    ((128, 2, 2, 96), 5, 1.0, 0.75, 1.0),   # a window that bites
    ((128, 2, 2, 32), 4, 0.5, 0.9, 1.0),    # even window + general beta
    # the channels on the lanes, a window that bites
    ((2, 3, 3, 256), 5, 1.0, 0.75, 1.0),
]


@pytest.mark.parametrize("shape,size,alpha,beta,k", CASES)
def test_forward_matches_oracle(shape, size, alpha, beta, k):
    x = jnp.asarray(
        np.random.default_rng(0).normal(0, 2, shape), jnp.float32
    )
    y_ref = _oracle(x, size, alpha, beta, k)
    y = lrn_nhwc(
        x, size=size, alpha=alpha, beta=beta, k=k, interpret=True
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-6)


@pytest.mark.parametrize("shape,size,alpha,beta,k", CASES)
def test_grad_matches_oracle(shape, size, alpha, beta, k):
    x = jnp.asarray(
        np.random.default_rng(1).normal(0, 2, shape), jnp.float32
    )
    g = jnp.asarray(np.random.default_rng(2).normal(0, 1, shape), jnp.float32)

    def loss_ref(x):
        return jnp.sum(_oracle(x, size, alpha, beta, k) * g)

    def loss_ker(x):
        return jnp.sum(
            lrn_nhwc(x, size=size, alpha=alpha, beta=beta, k=k,
                     interpret=True) * g
        )

    dx_ref = jax.grad(loss_ref)(x)
    dx = jax.grad(loss_ker)(x)
    np.testing.assert_allclose(
        np.asarray(dx), np.asarray(dx_ref), atol=3e-6
    )


def test_bf16_io_keeps_f32_internals():
    x = jnp.asarray(
        np.random.default_rng(3).normal(0, 2, (2, 4, 4, 96)), jnp.bfloat16
    )
    y = lrn_nhwc(x, size=5, alpha=1e-4, beta=0.75, k=1.0, interpret=True)
    assert y.dtype == jnp.bfloat16
    y_ref = _oracle(x, 5, 1e-4, 0.75, 1.0)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), atol=2e-2
    )


def test_row_padding_roundtrip():
    """H*W not a whole number of the channels form's blocks: the last
    block's positions past the end are never written back."""
    x = jnp.asarray(  # 5775 positions of (3, 32); a block holds 5461
        np.random.default_rng(4).normal(0, 1, (3, 77, 75, 32)), jnp.float32
    )
    y = lrn_nhwc(x, size=5, alpha=1e-4, beta=0.75, k=1.0, interpret=True)
    y_ref = _oracle(x, 5, 1e-4, 0.75, 1.0)
    assert y.shape == x.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-6)


def test_batch_blocks_past_the_end_roundtrip():
    """H*W not a whole number of the batch form's blocks: the last block's
    positions past the end are never written back."""
    shape = (128, 9, 5, 96)  # 45 positions of (96, 128); a block holds 42
    assert orientation(shape) == "batch"
    x = jnp.asarray(np.random.default_rng(5).normal(0, 2, shape), jnp.float32)
    y = lrn_nhwc(x, size=5, alpha=1.0, beta=0.75, k=1.0, interpret=True)
    y_ref = _oracle(x, 5, 1.0, 0.75, 1.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-6)


@pytest.mark.parametrize("shape,region,expected", [
    ((1024, 55, 55, 96), "ACROSS_CHANNELS", "batch"),      # alexnet_live norm1
    ((1024, 27, 27, 256), "ACROSS_CHANNELS", "channels"),  # alexnet_live norm2
    ((128, 56, 56, 64), "ACROSS_CHANNELS", "batch"),       # GoogLeNet's norm1
    ((128, 56, 56, 192), "ACROSS_CHANNELS", "batch"),      # and norm2
    ((10, 55, 55, 96), "ACROSS_CHANNELS", None),           # a serving batch
    ((10, 27, 27, 256), "ACROSS_CHANNELS", None),          # its norm2
    ((16, 27, 27, 256), "ACROSS_CHANNELS", "channels"),    # whole 16-row tiles
    ((1024, 55, 55, 96), "WITHIN_CHANNEL", None),
    ((1024, 6, 6, 1024), "ACROSS_CHANNELS", None),         # wider than the band
    ((1024, 5, 5, 20), "ACROSS_CHANNELS", None),           # no whole 8-row tile
], ids=["norm1", "norm2", "googlenet_c64", "googlenet_c192", "serving_norm1",
        "serving_norm2", "batch16_norm2", "within_channel", "c1024", "c20"])
def test_the_rule_picks_an_orientation_from_the_shape(shape, region, expected):
    assert orientation(shape, region) == expected
    assert uses_lrn_kernel(shape, region, force="flash") == (expected is not None)
    assert not uses_lrn_kernel(shape, region, force="reference")
    # on this backend the layer takes the jax.numpy form
    assert not uses_lrn_kernel(shape, region)


def test_the_backward_keeps_x_alone():
    """The only residual of the kernels' VJP is x, in x's dtype: no float32
    tensor of x's size is kept for the backward pass."""
    x = jnp.asarray(
        np.random.default_rng(6).normal(0, 2, (128, 3, 3, 96)), jnp.bfloat16
    )
    _, pullback = jax.vjp(
        lambda t: lrn_nhwc(t, size=5, alpha=1e-4, beta=0.75, k=1.0,
                           interpret=True), x
    )
    kept = [a for a in jax.tree_util.tree_leaves(pullback)
            if hasattr(a, "dtype") and a.size >= x.size]
    assert [(a.dtype, a.size) for a in kept] == [(jnp.bfloat16, x.size)]


def test_lrn_elems_in_kernel_counts_what_the_rule_gives_the_kernels(monkeypatch):
    """``imagenet_app``'s start-up counter: Σ N·H·W·C over the LRN layers
    the kernels take, as the registry's gauge — 0 on this backend, and
    alexnet_live's two inputs at batch 1024 where the kernels run."""
    import functools
    import os

    from sparknet_tpu.apps import imagenet_app
    from sparknet_tpu.nets.xlanet import XLANet
    from sparknet_tpu.ops import lrn
    from sparknet_tpu.proto.caffe_pb import load_net
    from sparknet_tpu.telemetry.registry import REGISTRY

    net = XLANet(
        load_net(os.path.join(imagenet_app.ZOO, "bvlc_alexnet_train_val.prototxt")),
        "TRAIN", {"data": (1024, 227, 227, 3), "label": (1024,)},
    )
    assert imagenet_app.lrn_elems_in_kernel(net) == 0
    assert REGISTRY.gauge("lrn_elems_in_kernel").value == 0
    monkeypatch.setattr(L, "uses_lrn_kernel", functools.partial(lrn.uses_lrn_kernel, force="flash"))
    assert imagenet_app.lrn_elems_in_kernel(net) == 488472576  # 297369600 + 191102976
    assert REGISTRY.gauge("lrn_elems_in_kernel").value == 488472576


@pytest.mark.parametrize("shape", [(512, 3, 3, 96), (64, 3, 3, 256)], ids=["batch", "channels"])
def test_a_partitioned_program_takes_the_plain_form_and_a_shard_map_the_kernel(shape):
    """A Mosaic kernel cannot be split by XLA's partitioner: under ``jit``
    with the batch sharded over four devices (as the data-parallel solvers
    build their steps) the layer lowers to its ``jax.numpy`` form, inside a
    ``shard_map`` to the kernel on each shard; both as the oracle, forward
    and backward."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    sharded = NamedSharding(mesh, P("dp"))
    x = jnp.asarray(np.random.default_rng(7).normal(0, 2, shape), jnp.float32)
    g = jnp.asarray(np.random.default_rng(8).normal(0, 1, shape), jnp.float32)

    def both(lrn):
        def run(t, u):
            y, pull = jax.vjp(lrn, t)
            return y, pull(u)[0]
        return run

    kernel = both(lambda t: lrn_nhwc(t, size=5, alpha=1.0, beta=0.75, k=1.0, interpret=True))
    y_ref, dx_ref = both(lambda t: _oracle(t, 5, 1.0, 0.75, 1.0))(x, g)
    programs = {
        "jit": jax.jit(kernel, in_shardings=(sharded, sharded), out_shardings=(sharded, sharded)),
        # (the interpreter's loops carry no varying axes: vma unchecked)
        "shard_map": jax.jit(jax.shard_map(
            kernel, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False
        )),
    }
    for form, program in programs.items():
        text = program.lower(x, g).compile().as_text()
        assert ("reduce-window" in text) == (form == "jit"), form
        y, dx = program(jax.device_put(x, sharded), jax.device_put(g, sharded))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-6)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref), atol=3e-6)
