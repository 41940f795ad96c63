"""Grouped matrix products over rows sorted by group, as Pallas kernels that
visit only the rows that belong to a group.

Rows ``0 .. sum(sizes)`` of ``x`` (R, k) come in ``len(sizes)`` consecutive
groups; group ``g`` takes ``sizes[g]`` of them.  :func:`gmm` gives
``x[r] @ w[g(r)]`` (or ``@ w[g(r)].T``) and :func:`tgmm` gives ``sum over
the rows r of g of x[r].T @ y[r]`` for every group: the products of a
grouped ``lax.ragged_dot`` and of its two gradients.  Both walk a list of
*visits* (:func:`group_visits`, a scalar prefetch): a visit is one group
on one tile of ``tm`` rows, in the order of the groups, so a tile that an
expert boundary cuts is visited once for each group it holds, with a row
mask, and the tiles past ``sum(sizes)`` are not visited at all.  The
grid's length is the number of visits, a traced value.

- :func:`gmm`: the grid is (lane tiles of the output, visits); the whole
  contraction is one block, so a group's weights stay in VMEM while its
  row tiles pass (the block's index does not change, so it is fetched
  once a group).  A visit writes its rows of the output and keeps
  the others of the tile as the visit before it wrote them.  Rows of no
  group are neither read into a product nor written: whatever the
  output's buffer held is left there.
- :func:`tgmm`: the grid is (tiles of k, tiles of n, visits); the float32
  output block of a group starts as the sum it is given (the output is
  written over it) on its first visit and is summed into on each, from
  both operands masked to the group's rows (a row of no group may hold
  anything, NaN included).  An empty group is visited once, and its block
  written back as it was.

Tiles come from the shapes by one rule (:func:`row_tile`,
:func:`lane_tiles`): row tiles of 512, 256 or 128 rows, the largest that
divides the rows and whose straddled tiles cost no more than an eighth of
them; lane tiles the largest multiples of 128 that divide the widths and
keep the blocks, double-buffered, inside ``_VMEM_BUDGET``.  The kernels'
instructions are named ``ragged-dot-gmm`` and ``ragged-dot-tgmm``: the
grouped products of the program, whatever computes them.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROW_TILES = (512, 256, 128)
_VMEM_BUDGET = 32 * 1024 * 1024  # the blocks of one kernel, double-buffered
_VMEM_LIMIT = 64 * 1024 * 1024  # of v5e's 128 MiB


def row_tile(rows: int, groups: int) -> int:
    """Rows of a tile: the largest of ``_ROW_TILES`` that divides ``rows``
    and whose at most ``groups - 1`` straddled tiles, each visited twice,
    add no more than an eighth of ``rows``; else the smallest that divides
    them, or 0 where none does."""
    fits = [t for t in _ROW_TILES if rows % t == 0]
    return next((t for t in fits if (groups - 1) * t <= rows // 8), fits[-1] if fits else 0)


def _divisors_of_lanes(width: int):
    return [t for t in range(width, 0, -128) if width % t == 0 and t % 128 == 0]


def lane_tiles(
    tm: int, k: int, n: int, in_bytes: int, grouped_k: bool
) -> Tuple[int, int]:
    """``(tk, tn)`` for a product ``(tm, k) x (k, n)`` with operands of
    ``in_bytes`` and a float32 output block: :func:`gmm` takes the whole of
    k (``grouped_k`` False) and the widest tn whose blocks fit; :func:`tgmm`
    (True) the largest ``tk * tn`` that fits, the wider tn among equals."""
    def footprint(tk, tn):
        blocks = tm * tk * in_bytes + tk * tn * in_bytes + tm * tn * 4
        if grouped_k:  # x and y tiles and the group's (tk, tn) sum in, the sum out
            blocks = tm * tk * in_bytes + tm * tn * in_bytes + 2 * tk * tn * 4
        return 2 * blocks + 4 * (tk * tn if grouped_k else tm * tn)

    pairs = [
        (tk, tn) for tk in (_divisors_of_lanes(k) if grouped_k else [k])
        for tn in _divisors_of_lanes(n) if footprint(tk, tn) <= _VMEM_BUDGET
    ]
    if not pairs:
        return (128 if grouped_k else k), 128
    return max(pairs, key=lambda p: (p[0] * p[1], p[1]))


def group_visits(sizes: jax.Array, rows: int, tm: int, empty: bool):
    """The visits of ``rows`` rows in tiles of ``tm`` by groups of
    ``sizes`` (G,): ``(group, tile)`` of each, both (rows // tm + G - 1,)
    int32, in the order of the groups and, inside one, of the tiles; the
    groups' row offsets (G + 1,); and the number of visits.  A group takes
    every tile that holds one of its rows; an empty one none, or (``empty``)
    one, the tile where it would begin.  Entries past the count repeat the
    last group and are never visited.  Comparisons over (visits, G), no
    gather."""
    g = sizes.shape[0]
    tiles = rows // tm
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles - 1)
    count = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, int(empty))
    after = jnp.cumsum(count)
    v = lax.iota(jnp.int32, tiles + g - 1)
    group = jnp.minimum(jnp.sum(after[None, :] <= v[:, None], axis=1), g - 1)
    mine = group[:, None] == jnp.arange(g)
    tile = v + jnp.sum(jnp.where(mine, first - (after - count), 0), axis=1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (
        group.astype(jnp.int32), jnp.clip(tile, 0, tiles - 1).astype(jnp.int32),
        offsets, after[-1],
    )


def _rows_of(group_ref, tile_ref, offs_ref, v, tm):
    """The visit's first row, its group's rows ``[lo, hi)``, and whether the
    tile lies inside them whole."""
    g = group_ref[v]
    top, lo, hi = tile_ref[v] * tm, offs_ref[g], offs_ref[g + 1]
    return top, lo, hi, (lo <= top) & (top + tm <= hi)


def _in_group(shape, top, lo, hi):
    row = top + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo) & (row < hi)


def _gmm_kernel(
    group_ref, tile_ref, offs_ref, x_ref, w_ref, o_ref, *, tm, dims, cdt
):
    top, lo, hi, whole = _rows_of(group_ref, tile_ref, offs_ref, pl.program_id(1), tm)
    y = lax.dot_general(
        x_ref[...].astype(cdt), w_ref[...].astype(cdt), dims,
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)

    @pl.when(whole)
    def _():
        o_ref[...] = y

    @pl.when(jnp.logical_not(whole))
    def _():
        o_ref[...] = jnp.where(_in_group(y.shape, top, lo, hi), y, o_ref[...])


def _widest(*arrays) -> int:
    return max(a.dtype.itemsize for a in arrays)


@functools.partial(
    jax.jit, static_argnames=("transpose_rhs", "out_dtype", "interpret")
)
def gmm(
    x: jax.Array, w: jax.Array, sizes: jax.Array, *,
    transpose_rhs: bool = False, out_dtype=jnp.float32, interpret: bool = False,
) -> jax.Array:
    """``out[r] = x[r] @ w[g(r)]`` (``transpose_rhs``: ``@ w[g(r)].T``), (R,
    n) ``out_dtype``, for the rows of the groups of ``sizes`` (G,); ``x`` (R,
    k), ``w`` (G, k, n) or (G, n, k), multiplied in ``w``'s dtype (a wider
    ``x`` is rounded to it in VMEM, as a cast before the call would) with
    float32 accumulation, rounded once at the store.  Rows past
    ``sum(sizes)`` are not computed (module header)."""
    rows, k = x.shape
    groups = w.shape[0]
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tm = row_tile(rows, groups)
    _, tn = lane_tiles(tm, k, n, _widest(x, w), grouped_k=False)
    group, tile, offsets, visits = group_visits(sizes, rows, tm, empty=False)
    if transpose_rhs:
        w_spec = pl.BlockSpec((None, tn, k), lambda j, v, g, t, o: (g[v], j, 0))
        dims = (((1,), (1,)), ((), ()))
    else:
        w_spec = pl.BlockSpec((None, k, tn), lambda j, v, g, t, o: (g[v], 0, j))
        dims = (((1,), (0,)), ((), ()))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, dims=dims, cdt=w.dtype),
        name="ragged-dot-gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, g, t, o: (t[v], 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, g, t, o: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(n // tn) * rows * k * x.dtype.itemsize
            + w.size * w.dtype.itemsize + jnp.dtype(out_dtype).itemsize * rows * n,
        ),
        interpret=interpret,
    )(group, tile, offsets, x, w)


def _tgmm_kernel(
    group_ref, tile_ref, offs_ref, x_ref, y_ref, acc_ref, o_ref, *, tm, cdt
):
    v = pl.program_id(2)
    top, lo, hi, whole = _rows_of(group_ref, tile_ref, offs_ref, v, tm)

    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != group_ref[v]))
    def _():
        o_ref[...] = acc_ref[...]

    def add(x, y):
        o_ref[...] += lax.dot_general(
            x.astype(cdt), y.astype(cdt), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(whole)
    def _():
        add(x_ref[...], y_ref[...])

    @pl.when(jnp.logical_not(whole) & (hi > lo))
    def _():
        x, y = x_ref[...], y_ref[...]
        add(
            jnp.where(_in_group(x.shape, top, lo, hi), x, jnp.zeros_like(x)),
            jnp.where(_in_group(y.shape, top, lo, hi), y, jnp.zeros_like(y)),
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def tgmm(
    x: jax.Array, y: jax.Array, sizes: jax.Array, acc: jax.Array, *,
    interpret: bool = False,
) -> jax.Array:
    """``acc[g] + sum over the rows r of group g of x[r].T @ y[r]``, (G, k,
    n) float32, for ``x`` (R, k) and ``y`` (R, n), multiplied in ``x``'s
    dtype (a wider ``y`` is rounded to it in VMEM) with float32
    accumulation, written over ``acc`` (G, k, n) float32 (an empty group's
    block as it was): a sum over chunks of rows keeps one buffer.  Rows past
    ``sum(sizes)`` are not read into a product (module header)."""
    rows, k = x.shape
    n = y.shape[1]
    groups = sizes.shape[0]
    tm = row_tile(rows, groups)
    tk, tn = lane_tiles(tm, k, n, _widest(x, y), grouped_k=True)
    group, tile, offsets, visits = group_visits(sizes, rows, tm, empty=True)
    block = pl.BlockSpec((None, tk, tn), lambda i, j, v, g, t, o: (g[v], i, j))
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, cdt=x.dtype),
        name="ragged-dot-tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(k // tk, n // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, v, g, t, o: (t[v], i)),
                pl.BlockSpec((tm, tn), lambda i, j, v, g, t, o: (t[v], j)),
                block,
            ],
            out_specs=block,
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(n // tn) * rows * k * x.dtype.itemsize
            + (k // tk) * rows * n * y.dtype.itemsize
            + 8 * groups * k * n,
        ),
        interpret=interpret,
    )(group, tile, offsets, x, y, acc.astype(jnp.float32))
