"""MXU-rate matmul with guaranteed f32 accumulation, fwd AND bwd.

The TPU MXU's native mode for bf16 operands is bf16 multiplies into
f32 accumulators; ``jnp.dot(x, w, preferred_element_type=f32)`` asks
for exactly that. But JAX's *default transpose rule* then feeds the
f32 cotangent of the f32 output straight into the two backward dots —
f32×bf16 operands promote to pure-f32 matmuls, which run the MXU in
multi-pass f32 mode at a fraction of bf16 throughput. Measured on the
AlexNet train step HLO: every forward conv/dot was bf16, every FC
backward dot was f32 (the convolution path does not have the problem
because its output stays bf16, so its cotangents are bf16 already).

:func:`mxu_dot` is the shared fix: the forward dot is unchanged
(bf16 in, f32 accumulate/out); the custom VJP rounds the cotangent to
the operand dtype before the two backward dots, so dgrad and wgrad run
at bf16 MXU rate with the same f32 accumulation. This is the same
"backward signal at compute dtype" convention the conv layers already
follow, now applied uniformly. With f32 operands (CPU tests, f32
training) every cast is a no-op and the math is identical to the
default rule.

Used by the InnerProduct/LSTM/RNN layers (nets/layers.py) and the BERT
dense projections + MLM head (models/bert.py).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

# The custom VJP read slightly faster than the default transpose rule at
# AlexNet bs512 (measured once in round 5 on a set-up that no longer
# exists; not re-measured), so it is the default. SPARKNET_MXU_VJP=0
# drops to a plain dot (still bf16 operands + f32 accumulation forward)
# so the comparison stays re-runnable (ROADMAP D3).
_USE_VJP = os.environ.get("SPARKNET_MXU_VJP", "1") not in ("", "0")


@jax.custom_vjp
def mxu_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """``dot(x, w)`` contracting x's last axis with 2-D w's first;
    f32 output, backward at operand (compute) dtype."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _fwd(x, w):
    return mxu_dot(x, w), (x, w)


def _bwd(res, g):
    x, w = res
    gl = g.astype(w.dtype)  # round the cotangent once: bf16-rate bwd
    dx = jnp.dot(gl, w.T, preferred_element_type=jnp.float32).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1])
    g2 = gl.reshape(-1, gl.shape[-1])
    dw = jnp.dot(x2.T, g2, preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw


mxu_dot.defvjp(_fwd, _bwd)

if not _USE_VJP:
    def mxu_dot(x, w):  # noqa: F811 — measured fallback, see header
        return jnp.dot(x, w, preferred_element_type=jnp.float32)


@jax.custom_vjp
def mxu_bmm(x: jax.Array, w: jax.Array) -> jax.Array:
    """Batched ``(B, I, J) @ (B, J, K) -> (B, I, K)`` with the same
    contract as :func:`mxu_dot`: f32 accumulation forward, cotangent
    rounded to operand dtype so both backward contractions run at bf16
    MXU rate. Used for the MoE per-expert FFN matmuls (the largest
    matmuls in an expert-parallel step)."""
    return jnp.einsum("bij,bjk->bik", x, w,
                      preferred_element_type=jnp.float32)


def _bmm_fwd(x, w):
    return mxu_bmm(x, w), (x, w)


def _bmm_bwd(res, g):
    x, w = res
    gl = g.astype(w.dtype)
    dx = jnp.einsum(
        "bik,bjk->bij", gl, w, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    dw = jnp.einsum(
        "bij,bik->bjk", x, gl, preferred_element_type=jnp.float32
    ).astype(w.dtype)
    return dx, dw


mxu_bmm.defvjp(_bmm_fwd, _bmm_bwd)

if not _USE_VJP:
    def mxu_bmm(x, w):  # noqa: F811 — measured fallback, see header
        return jnp.einsum(
            "bij,bjk->bik", x, w, preferred_element_type=jnp.float32
        )
