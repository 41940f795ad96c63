"""Pallas LRN (ACROSS_CHANNELS): one pass forward, one pass backward.

Caffe's LRN is AlexNet's and GoogLeNet's normalization across channels:

    d(c) = k + (alpha/size) * sum_{c' in [c-a, c+b]} x(c')^2
    y(c) = x(c) * d(c)^-beta          (a = size//2, b = size-1-a)

It is a 5-tap stencil along the channel axis, a power and a product, so it
is bound by HBM bandwidth.  The forward kernel reads x and writes y; the
backward kernel (``custom_vjp``) reads x and the cotangent g and writes

    dx = g*d^-beta - 2*(alpha/size)*beta * x * W^T(g * x * d^(-beta-1))

with d formed again from x in VMEM: the only residual is x itself, which
the layer's input already is.  ``W^T`` is the window with (a, b) swapped.
Inside a tile everything is float32 but the window, which is one product
with a (C, C) 0/1 band on the MXU, its operands in the input's dtype and
its sums in float32 (on a v5e at AlexNet's sizes the kernels take about
1.3 x their bytes' time so; rolled copies on the vector units took 2.2-3 x).

**Orientation.**  A ``pallas_call`` takes its operands row-major, and the
layers around an LRN leave its tensor in a layout of XLA's choosing.  The
kernel reads the logical transpose whose row-major form is that layout, so
the transpose in and out lowers to a bitcast and not to a copy of the
tensor.  In AlexNet's step norm1's ``[1024,55,55,96]`` is batch-minor
(physically H, W, C, N) from conv1 to pool1: no copy.  norm2's
``[1024,27,27,256]`` leaves conv2 batch-minor and enters pool2
channel-minor (H, W, N, C), so one copy each way stays in either
orientation; the channels form keeps the step's memory lower (the
``jax.numpy`` form's step holds three such copies).

- ``"channels"``: C a whole number of 128-lane tiles; the NHWC -> HWNC
  transpose viewed as (H*W, N, C), the window along the lanes;
- ``"batch"``: otherwise, N a whole number of lane tiles; the NHWC -> HWCN
  transpose viewed as (H*W, C, N), the window along the sublanes.

:func:`uses_lrn_kernel` decides from the input's shape and the backend
(``nets/layers.py`` ``LRN.apply`` asks it); elsewhere the layer's
``jax.numpy`` form runs, which is also the oracle of
``tests/test_lrn_pallas.py``.  :func:`lrn_nhwc` itself takes any NHWC
shape (a shape that fits neither orientation goes through the channels
form).  A program that XLA partitions over several devices cannot hold a
Mosaic kernel: there the same oriented view lowers to the ``jax.numpy``
form (:func:`_whole_program`), inside a ``shard_map`` to the kernels.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import mlir

from .attention import uses_flash

_LANES = 128
_MAX_CHANNELS = 512
_BLOCK_BYTES = 1 << 21  # of x a grid step reads
_TILE_ELEMS = 1 << 17  # float32 elements of a tile the body works on at once


def orientation(shape, region: str = "ACROSS_CHANNELS") -> Optional[str]:
    """``"channels"``, ``"batch"`` or None (module docstring) for an NHWC
    ``shape``: channels on the lanes where C is whole lane tiles (and N
    whole 16-row tiles), else the batch on the lanes where N is whole lane
    tiles (and C whole 8-row tiles); never more than 512 channels, never
    WITHIN_CHANNEL."""
    if region != "ACROSS_CHANNELS" or len(shape) != 4:
        return None
    n, _, _, c = shape
    if c > _MAX_CHANNELS:
        return None
    if c % _LANES == 0 and n % 16 == 0:
        return "channels"
    if n % _LANES == 0 and c % 8 == 0:
        return "batch"
    return None


def uses_lrn_kernel(
    shape, region: str = "ACROSS_CHANNELS", force: Optional[str] = None
) -> bool:
    """Whether an LRN over an input of ``shape`` runs as the kernels
    (``force`` as :func:`sparknet_tpu.ops.attention.attention` has it:
    "flash" the kernels where the shape fits an orientation, "reference"
    never, None the kernels on a TPU)."""
    return orientation(shape, region) is not None and uses_flash(force)


def _inv_beta(d, t, beta: float):
    """d^-beta, given t = rsqrt(d); rsqrt/sqrt chains for the usual betas."""
    if beta == 0.75:
        return jnp.sqrt(t * t * t)
    if beta == 0.5:
        return t
    if beta == 1.0:
        return t * t
    return jnp.exp(jnp.log(d) * -beta)


def _band(n: int, axis: int, lo: int, hi: int, dtype):
    """The (n, n) 0/1 band that sums ``t[c+lo .. c+hi]`` along ``axis`` of a
    2-D tile ``t`` as one product (:func:`_window`)."""
    src = lax.broadcasted_iota(jnp.int32, (n, n), 1 - axis)
    out = lax.broadcasted_iota(jnp.int32, (n, n), axis)
    return ((src - out >= lo) & (src - out <= hi)).astype(dtype)


def _window(t, band, axis: int):
    """``sum_{o=lo..hi} t[c+o]`` along ``axis``, zero past either edge, on
    the MXU: operands in the band's dtype, float32 accumulation."""
    precision = lax.Precision.HIGHEST if band.dtype == jnp.float32 else None
    pair = (t.astype(band.dtype), band) if axis else (band, t.astype(band.dtype))
    return jnp.dot(*pair, precision=precision, preferred_element_type=jnp.float32)


def _tile_rows(c: int) -> int:
    """Images a channels-form tile holds: ``_TILE_ELEMS`` float32 at most."""
    return max(16, _TILE_ELEMS // c // 16 * 16)


def _fwd_kernel(x_ref, y_ref, *, orient, a, b, scale, k, beta):
    """A block of positions, a 2-D tile each (rows independent)."""
    axis = 0 if orient == "batch" else 1
    band = _band(x_ref.shape[1 + axis], axis, -a, b, x_ref.dtype)

    def tile(j, carry):
        x = x_ref[j].astype(jnp.float32)
        d = k + scale * _window(x * x, band, axis)
        y_ref[j] = (x * _inv_beta(d, lax.rsqrt(d), beta)).astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, x_ref.shape[0], tile, 0)


def _bwd_kernel(x_ref, g_ref, dx_ref, *, orient, a, b, scale, k, beta):
    axis = 0 if orient == "batch" else 1
    c = x_ref.shape[1 + axis]
    band = _band(c, axis, -a, b, x_ref.dtype)
    adjoint = _band(c, axis, -b, a, x_ref.dtype)

    def tile(j, carry):
        x = x_ref[j].astype(jnp.float32)
        g = g_ref[j].astype(jnp.float32)
        d = k + scale * _window(x * x, band, axis)
        t = lax.rsqrt(d)
        inv = _inv_beta(d, t, beta)
        u = g * x * (inv * (t * t))  # g * x * d^(-beta-1)
        wt = _window(u, adjoint, axis)
        dx_ref[j] = (g * inv - (2.0 * scale * beta) * x * wt).astype(dx_ref.dtype)
        return carry

    lax.fori_loop(0, x_ref.shape[0], tile, 0)


def _blocks(shape, itemsize: int, orient: str):
    """(grid, block shape) over the oriented view (H*W, C, N) or
    (H*W, N, C): a 2-D tile of at most ``_TILE_ELEMS`` a position, about
    ``_BLOCK_BYTES`` of x a block; a last block that runs past the end is
    read as padding and not written back."""
    hw, p, q = shape
    if orient == "batch":
        tile = (p, next(
            (t for t in (1024, 512, 256) if q % t == 0 and p * t <= _TILE_ELEMS),
            _LANES if q % _LANES == 0 else q,
        ))
    else:
        tile = (min(p, _tile_rows(q)), q)
    rows = max(1, min(hw, _BLOCK_BYTES // (tile[0] * tile[1] * itemsize)))
    return (pl.cdiv(hw, rows), pl.cdiv(p, tile[0]), pl.cdiv(q, tile[1])), (rows, *tile)


def _pallas(kernel, name, orient, geometry, interpret, *operands):
    """One kernel over an oriented view; the output is shaped as operand 0.
    ``geometry``: the kernel's a, b, scale, k and beta."""
    shape = operands[0].shape
    grid, block = _blocks(shape, operands[0].dtype.itemsize, orient)
    spec = pl.BlockSpec(block, lambda i, j, l: (i, j, l))
    return pl.pallas_call(
        functools.partial(kernel, orient=orient, **geometry),
        name=name,
        grid=grid,
        in_specs=[spec] * len(operands),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(  # varying as x over a shard_map's axes
            shape, operands[0].dtype, vma=jax.typeof(operands[0]).vma
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=32 * 1024 * 1024,
        ),
        interpret=interpret,
    )(*operands)


def _reference(xo, orient, size, alpha, beta, k):
    """The layer's ``jax.numpy`` form on an oriented view: a windowed sum
    along C, temps in x's dtype."""
    axis = 1 if orient == "batch" else 2
    window, pad = [1, 1, 1], [(0, 0)] * 3
    window[axis], pad[axis] = size, (size // 2, size - 1 - size // 2)
    ssum = lax.reduce_window(jnp.square(xo), 0.0, lax.add, window, (1, 1, 1), pad)
    return (xo * jnp.power(k + alpha / size * ssum, -beta)).astype(xo.dtype)


def _reference_vjp(xo, g, **lrn):
    return jax.vjp(functools.partial(_reference, **lrn), xo)[1](g)[0]


# A Mosaic kernel runs on the shapes it is given: XLA's partitioner cannot
# split one.  So the choice between a kernel and its ``jax.numpy`` form is
# made where the program is lowered, from what the lowering knows: one
# device, or every axis of a ``shard_map`` manual, runs the kernel; a
# program XLA partitions over several devices (``jit`` with shardings, as
# the data-parallel solvers build theirs) runs the plain form.
_per_program = Primitive("lrn")
_per_program.def_impl(lambda *operands, kernel, plain: kernel(*operands))
_per_program.def_abstract_eval(lambda *avals, kernel, plain: avals[0])


def _whole_program(axis_context) -> bool:
    """Whether a Mosaic kernel may be lowered here: the condition of jax's
    own lowering of one (``tpu_custom_call``), which raises otherwise."""
    manual = getattr(axis_context, "manual_axes", None)
    if manual is not None:  # inside a shard_map
        every = set(manual) | set(axis_context.mesh.manual_axes)
        return not manual or every == set(axis_context.mesh.axis_names)
    return getattr(axis_context, "num_devices", 1) == 1


def _lower(ctx, *operands, kernel, plain):
    fn = kernel if _whole_program(ctx.module_context.axis_context) else plain
    return mlir.lower_fun(fn, multiple_results=False)(ctx, *operands)


mlir.register_lowering(_per_program, _lower)


def _geometry(size, alpha, beta, k):
    return dict(a=size // 2, b=size - 1 - size // 2, scale=alpha / size, k=k, beta=beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _lrn(xo, orient, size, alpha, beta, k, interpret):
    """LRN over the oriented view ``xo``: (H*W, C, N) for ``"batch"``,
    (H*W, N, C) for ``"channels"``."""
    geometry = _geometry(size, alpha, beta, k)
    return _per_program.bind(
        xo,
        kernel=functools.partial(_pallas, _fwd_kernel, "lrn_fwd", orient, geometry, interpret),
        plain=functools.partial(_reference, orient=orient, size=size, alpha=alpha, beta=beta, k=k),
    )


def _lrn_fwd(xo, orient, size, alpha, beta, k, interpret):
    return _lrn(xo, orient, size, alpha, beta, k, interpret), xo


def _lrn_bwd(orient, size, alpha, beta, k, interpret, xo, g):
    geometry = _geometry(size, alpha, beta, k)
    dx = _per_program.bind(
        xo, g.astype(xo.dtype),
        kernel=functools.partial(_pallas, _bwd_kernel, "lrn_bwd", orient, geometry, interpret),
        plain=functools.partial(_reference_vjp, orient=orient, size=size, alpha=alpha, beta=beta, k=k),
    )
    return (dx,)


_lrn.defvjp(_lrn_fwd, _lrn_bwd)


def lrn_nhwc(x, *, size, alpha, beta, k, interpret=False):
    """ACROSS_CHANNELS LRN of an NHWC tensor through the kernels, in the
    orientation :func:`orientation` gives its shape (the channels form
    where it gives none); differentiable."""
    n, h, w, c = x.shape
    if orientation(x.shape) == "batch":
        xo = jnp.transpose(x, (1, 2, 3, 0)).reshape(h * w, c, n)
        y = _lrn(xo, "batch", size, alpha, beta, k, interpret)
        return jnp.transpose(y.reshape(h, w, c, n), (3, 0, 1, 2))
    xo = jnp.transpose(x, (1, 2, 0, 3)).reshape(h * w, n, c)
    y = _lrn(xo, "channels", size, alpha, beta, k, interpret)
    return jnp.transpose(y.reshape(h, w, n, c), (2, 0, 1, 3))
