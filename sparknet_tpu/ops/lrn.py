"""Pallas LRN (ACROSS_CHANNELS): one fused stencil pass each way.

Caffe's LRN (reference vendored engine, SURVEY.md §2; mount empty) is
AlexNet/GoogLeNet's only non-conv normalization:

    d(c) = k + (alpha/size) * sum_{c' in [c-a, c+b]} x(c')^2
    y(c) = x(c) * d(c)^-beta          (a = size//2, b = size-1-a)

The jnp path (``nets/layers.py``) is numerically fine but XLA
materialises the squared tensor, the windowed sum, the power and its
VJP chain as separate conv-sized HBM temps — cost analysis reports
~5x the activation size in bytes accessed, which makes the two AlexNet
LRNs a candidate slice of the whole train step (ROADMAP S6: not
measured on the current code). LRN is a pure 1-D stencil along the minor
(channel) axis, so one Pallas pass holds the whole window in VMEM:

- forward: read x, write y and the residual d — no squared/windowed
  HBM temps, and d^-beta is built in-register (rsqrt/sqrt chain for
  the dyadic betas — free here precisely because nothing round-trips
  to HBM, unlike the round-4 XLA-level attempt the A/B reverted).
- backward (custom VJP): dx = g*d^-beta - 2*(alpha/size)*beta * x *
  W^T(g * x * d^(-beta-1)); one pass reading g, x, d and writing dx.
  W^T flips the window's (a, b) asymmetry; for the usual odd
  ``local_size`` it equals W.

Rows (N*H*W) are independent, so the grid tiles a flattened (M, C)
view; C rides the 128-lane axis (C < 128 pads — zero lanes contribute
zero to the window sum and d = k > 0 keeps the power finite).

The jnp path remains the oracle and the DEFAULT (the kernel is opt-in
via SPARKNET_LRN_PALLAS=1): inside the AlexNet train step the kernel
read about twice as slow (measured once in round 5 on a set-up that no
longer exists; not re-measured) — XLA assigns the neighbouring convs
exotic layouts (batch-minor {0,3,2,1} activations) and a pallas_call
pins row-major operands, so each LRN pays two conv-sized relayout
copies that dwarf the temp-chain saving. ROADMAP D3 deletes this
module. The kernel wins only where the operand is already
row-major (standalone use); equivalence incl. grads is pinned in
tests/test_lrn_pallas.py (interpret mode on CPU).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _band(c: int, a: int, b: int) -> jax.Array:
    """(C, C) 0/1 band: (t @ band)[c] = sum t[c-a .. c+b].

    The channel stencil as a matmul: lane-shifted slices are the slow
    path on the VPU (measured 2x worse than the jnp fallback end to
    end), while a (rows, C) x (C, C) dot rides the MXU for free — the
    band lives in VMEM for the whole grid (1 MB at the C=512 cap the
    layer gate enforces, alongside ~6 MB of double-buffered row
    tiles)."""
    i = jnp.arange(c)[:, None]  # source channel
    j = jnp.arange(c)[None, :]  # output channel
    return ((j - a <= i) & (i <= j + b)).astype(jnp.float32)


def _inv_beta(d: jax.Array, beta: float) -> jax.Array:
    """d^-beta in registers; rsqrt/sqrt chains for the dyadic betas."""
    if beta == 0.75:
        t = jax.lax.rsqrt(d)  # d^-0.5
        return jnp.sqrt(t * t * t)  # (d^-1.5)^0.5
    if beta == 0.5:
        return jax.lax.rsqrt(d)
    if beta == 1.0:
        return 1.0 / d
    return jnp.exp(jnp.log(d) * -beta)


def _fwd_kernel(x_ref, w_ref, y_ref, d_ref, *, scale, k, beta):
    x = x_ref[...].astype(jnp.float32)
    acc = jnp.dot(x * x, w_ref[...], preferred_element_type=jnp.float32)
    d = k + scale * acc
    y_ref[...] = (x * _inv_beta(d, beta)).astype(y_ref.dtype)
    d_ref[...] = d


def _fwd_only_kernel(x_ref, w_ref, y_ref, *, scale, k, beta):
    # primal-only variant: no d residual, so inference pays no extra
    # f32 HBM write (pallas outputs are opaque to XLA's DCE)
    x = x_ref[...].astype(jnp.float32)
    acc = jnp.dot(x * x, w_ref[...], preferred_element_type=jnp.float32)
    y_ref[...] = (x * _inv_beta(k + scale * acc, beta)).astype(y_ref.dtype)


def _bwd_kernel(g_ref, x_ref, d_ref, w_ref, dx_ref, *, scale, beta):
    g = g_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    d = d_ref[...]
    inv = _inv_beta(d, beta)
    u = g * x * inv / d  # g * x * d^(-beta-1)
    # adjoint window = the band transposed (identical for odd sizes)
    wt = jnp.dot(u, w_ref[...].T, preferred_element_type=jnp.float32)
    dx_ref[...] = (g * inv - (2.0 * scale * beta) * x * wt).astype(
        dx_ref.dtype
    )


def _tiles(m: int, c: int, block_rows: int) -> Tuple[int, int]:
    """(padded_rows, block): rows padded up to a whole number of
    sublane-aligned blocks; the pad rows are dead weight (<1 block).

    The row block shrinks with C to bound VMEM: ~1 MB per f32
    (block, C) tile keeps x/y/d plus the (C, C) band and Mosaic's
    double-buffering comfortably inside a v5e's ~16 MB."""
    vmem_rows = max(8, ((1 << 18) // max(c, 1)) & ~7)  # 256K f32 ≈ 1 MB
    block = max(8, min(block_rows, vmem_rows, m + (-m % 8)))
    block += -block % 8
    return m + (-m % block), block


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6)
)
def lrn_pallas(x, size, alpha, beta, k, block_rows=1024, interpret=False):
    """LRN over the last axis of 2-D ``x`` (rows independent).

    Callers flatten NHWC to (N*H*W, C); use :func:`lrn_nhwc` for the
    4-D convenience wrapper. Differentiable via the fused backward;
    the primal (inference) call runs a no-residual kernel."""
    m, c = x.shape
    a, b = size // 2, size - 1 - size // 2
    pm, block = _tiles(m, c, block_rows)
    if pm != m:
        x = jnp.pad(x, ((0, pm - m), (0, 0)))
    kern = functools.partial(
        _fwd_only_kernel, scale=alpha / size, k=k, beta=beta
    )
    y = pl.pallas_call(
        kern,
        grid=(pm // block,),
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((c, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pm, c), x.dtype),
        interpret=interpret,
    )(x, _band(c, a, b))
    return y[:m]


def _lrn_fwd_impl(x, size, alpha, beta, k, block_rows, interpret):
    m, c = x.shape
    a, b = size // 2, size - 1 - size // 2
    scale = alpha / size
    pm, block = _tiles(m, c, block_rows)
    if pm != m:
        x = jnp.pad(x, ((0, pm - m), (0, 0)))
    kern = functools.partial(_fwd_kernel, scale=scale, k=k, beta=beta)
    y, d = pl.pallas_call(
        kern,
        grid=(pm // block,),
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((c, c), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((block, c), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pm, c), x.dtype),
            jax.ShapeDtypeStruct((pm, c), jnp.float32),
        ],
        interpret=interpret,
    )(x, _band(c, a, b))
    return y[:m], (x, d)


def _lrn_bwd_impl(size, alpha, beta, k, block_rows, interpret, res, g):
    xp, d = res  # xp is already row-padded; d matches it
    pm, c = xp.shape
    m = g.shape[0]  # true (unpadded) row count, from the cotangent
    a, b = size // 2, size - 1 - size // 2
    scale = alpha / size
    _, block = _tiles(m, c, block_rows)
    if m != pm:
        g = jnp.pad(g, ((0, pm - m), (0, 0)))
    kern = functools.partial(_bwd_kernel, scale=scale, beta=beta)
    dx = pl.pallas_call(
        kern,
        grid=(pm // block,),
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((c, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pm, c), xp.dtype),
        interpret=interpret,
    )(g, xp, d, _band(c, a, b))
    return (dx[:m],)


lrn_pallas.defvjp(_lrn_fwd_impl, _lrn_bwd_impl)


def lrn_nhwc(x, *, size, alpha, beta, k, interpret=False):
    """ACROSS_CHANNELS LRN on an NHWC tensor via the fused kernel."""
    n, h, w, c = x.shape
    flat = x.reshape(n * h * w, c)
    y = lrn_pallas(flat, size, alpha, beta, k, 1024, interpret)
    return y.reshape(n, h, w, c)
