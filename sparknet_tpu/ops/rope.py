"""Rotary embedding and the move to the flash kernels' layout in one pass.

A decoder's q and k leave their projection as a float32 product
``(B, S, n * D)``, token-major; the flash kernels read ``(B, n, S, D)`` in
the compute type.  Between the two lie the rotation (in float32, by the
rotate-half convention, of the first ``rot`` lanes of each head), one
rounding and a transpose.  As ``jax.numpy`` (``models.decoder.apply_rope``,
the plain form and the reference of the tests) the halves are cut and
concatenated at lane offsets that are no multiple of 128, and every cut is
a relayout copy of the float32 tensor.  :func:`rope_to_heads` is the same
arithmetic as one Pallas kernel: a block of rows of a few heads comes in,
``x * cos + swap(x) * sin`` is formed in float32, rounded once and written
where the head's rows belong.  ``swap`` exchanges the two halves of the
first ``rot`` lanes: lane rotations and a select; the tables carry the rest
of the convention (:func:`rope_tables`: sin negated on the first half, cos 1
and sin 0 past ``rot``, the YaRN factor folded in), so a head that rotates
whole and one that rotates its first half are one kernel.  The backward
pass is the same kernel the other way, with sin negated: the angles of the
two halves are equal, so the transposed rotation of a cotangent ``g`` is
``g * cos - swap(g) * sin``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import uses_flash

_ROW_BLOCKS = (512, 256, 128, 64, 32, 16)  # rows a grid step, the first that divides S
_HEADS_A_STEP = 8  # at most: 4 KB a row of a float32 block at D = 128


def _row_block(seq_len: int) -> Optional[int]:
    return next((r for r in _ROW_BLOCKS if seq_len % r == 0), None)


def uses_rope_kernel(
    seq_len: int, head_dim: int, rot: int, force: Optional[str] = None
) -> bool:
    """Whether q and k are rotated by :func:`rope_to_heads` (``force`` as
    :func:`sparknet_tpu.ops.attention.attention` has it: "flash" the kernel
    where the shapes fit it, "reference" never, None the kernel on a TPU):
    heads of whole 128-lane tiles, an even rotated width inside the head,
    sequences of whole row blocks."""
    fits = (
        head_dim % 128 == 0 and rot % 2 == 0 and 0 < rot <= head_dim
        and _row_block(seq_len) is not None
    )
    return fits and uses_flash(force)


def rope_tables(positions, inv_freq, scale, head_dim: int):
    """(cos, signed sin), both (1 or B, S, head_dim) float32, of
    ``positions`` (S,) or (B, S): over the first ``2 * len(inv_freq)`` lanes
    what ``apply_rope`` multiplies by, the sin negated on their first half
    (rotate-half's sign); past them cos 1 and sin 0."""
    angles = jnp.atleast_2d(positions).astype(jnp.float32)[:, :, None] * inv_freq
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    rest = angles.shape[:2] + (head_dim - 2 * angles.shape[2],)
    return (
        jnp.concatenate([cos, cos, jnp.ones(rest, jnp.float32)], axis=-1),
        jnp.concatenate([-sin, sin, jnp.zeros(rest, jnp.float32)], axis=-1),
    )


def _rotate_kernel(cos_ref, sin_ref, x_ref, out_ref, *, rot, to_heads):
    """One block of rows of a few heads.  ``to_heads``: ``x_ref`` is
    (1, rows, heads * D) and ``out_ref`` (1, heads, rows, D), the forward
    rotation; else the other way round and the transposed rotation."""
    cos = cos_ref[0]
    sin = sin_ref[0] if to_heads else -sin_ref[0]
    rows, d = cos.shape
    heads = out_ref.shape[1] if to_heads else x_ref.shape[1]
    if rot < d:
        first = lax.broadcasted_iota(jnp.int32, (rows, d), 1) < rot // 2
    for j in range(heads):
        lanes = slice(j * d, (j + 1) * d)
        x = (x_ref[0, :, lanes] if to_heads else x_ref[0, j]).astype(jnp.float32)
        # swap(x)[l] = x[l + rot/2] on the first half, x[l - rot/2] on the
        # second; past rot sin is 0, whatever lands there
        swapped = pltpu.roll(x, rot // 2, 1)
        if rot < d:
            swapped = jnp.where(first, pltpu.roll(x, d - rot // 2, 1), swapped)
        y = (x * cos + swapped * sin).astype(out_ref.dtype)
        if to_heads:
            out_ref[0, j] = y
        else:
            out_ref[0, :, lanes] = y


@functools.partial(
    jax.jit, static_argnames=("rot", "dtype", "to_heads", "interpret")
)
def _rotate(x, cos, sin, *, rot, dtype, to_heads, interpret):
    """The one ``pallas_call`` of both directions: ``x`` (B, S, n * D) to
    (B, n, S, D) (``to_heads``) or back, in ``dtype``."""
    d = cos.shape[-1]
    if to_heads:
        b, s, n = x.shape[0], x.shape[1], x.shape[2] // d
    else:
        b, n, s, _ = x.shape
    rows = _row_block(s)
    step = next(h for h in range(min(n, _HEADS_A_STEP), 0, -1) if n % h == 0)
    # heads innermost: a table's block index does not move with them, so
    # it is fetched once for all the heads of its rows
    table = pl.BlockSpec(
        (1, rows, d),
        (lambda bi, i, h: (bi, i, 0)) if cos.shape[0] > 1
        else (lambda bi, i, h: (0, i, 0)),
    )
    tokens = pl.BlockSpec((1, rows, step * d), lambda bi, i, h: (bi, i, h))
    by_head = pl.BlockSpec((1, step, rows, d), lambda bi, i, h: (bi, h, i, 0))
    return pl.pallas_call(
        functools.partial(_rotate_kernel, rot=rot, to_heads=to_heads),
        name="rope_to_heads" if to_heads else "rope_from_heads",
        grid=(b, s // rows, n // step),
        in_specs=[table, table, tokens if to_heads else by_head],
        out_specs=by_head if to_heads else tokens,
        out_shape=jax.ShapeDtypeStruct(
            (b, n, s, d) if to_heads else (b, s, n * d), dtype
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
    )(cos, sin, x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def rope_to_heads(x, cos, sin, rot: int, dtype, interpret: bool = False):
    """``x`` (B, S, n * D) float32, a projection's product, rotated in
    float32 by :func:`rope_tables`' ``cos`` and ``sin`` (D their last
    extent, ``rot`` the lanes of a head that rotate), rounded once to
    ``dtype`` and returned head-major, (B, n, S, D): what
    ``apply_rope(x.reshape(B, S, n, D), ...).astype(dtype).transpose(0, 2,
    1, 3)`` gives, in one pass over the tensor.  For the shapes
    :func:`uses_rope_kernel` admits.  The cotangent comes back the same
    way, rounded to ``dtype`` at the kernel's store as ``mxu_dot``'s
    backward rounds it first thing; the tables get none.  ``interpret``
    runs the kernels in Pallas's interpreter, for tests off a TPU."""
    return _rotate(
        x, cos, sin, rot=rot, dtype=dtype, to_heads=True, interpret=interpret
    )


def _rope_fwd(x, cos, sin, rot, dtype, interpret):
    return rope_to_heads(x, cos, sin, rot, dtype, interpret), (cos, sin)


def _rope_bwd(rot, dtype, interpret, tables, g):
    cos, sin = tables
    dx = _rotate(
        g, cos, sin, rot=rot, dtype=g.dtype, to_heads=False, interpret=interpret
    )
    return dx.astype(jnp.float32), None, None


rope_to_heads.defvjp(_rope_fwd, _rope_bwd)
