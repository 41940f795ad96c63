"""The gated delta-rule scan of Kimi Delta Attention (KDA, arXiv:2510.26692):
linear attention whose per-head state is decayed per channel, corrected by
a rank-one delta and read, token by token.

Per head, with ``S`` (d_k, d_v) starting at 0, ``alpha_t = exp(g_t)`` per key
channel (``g_t <= 0``) and ``beta_t`` a scalar::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

- :func:`kda_recurrent` — exactly that, a ``lax.scan`` over positions:
  the numerics oracle and what tests hold the chunked form to.
- :func:`kda_scan` — the same result chunk by chunk, so that matrix
  products do the work (the WY form of the delta rule).  With ``G`` the
  cumulative log decay inside a chunk and ``Gamma = exp(G)``::

      A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])   (s < t)
      P[t, s] =        sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])   (s <= t)
      T = (I + A)^-1
      U = T (beta V) - T (beta K Gamma) S_0          (the corrected values)
      O = (Q Gamma) S_0 + P U
      S_C = Diag(Gamma_C) S_0 + (K Gamma_C / Gamma)^T U

  It comes in two forms, one algorithm, chosen by what the call shows
  (:func:`uses_kernels`: the backend and the shapes, or the caller's
  ``force``, as :func:`sparknet_tpu.ops.attention.attention` chooses):

  * **The Pallas kernels** (a TPU; whole chunks of 64, head sizes in whole
    lane tiles): ``kda_scan_fwd`` walks a call's chunks in order with the
    float32 state of some heads in VMEM scratch and forms each chunk's
    ``G``, ``P``, ``A``, ``T``, ``W``, ``U``, output and next state in
    VMEM from q, k, v, g, beta and the entering state, so HBM sees the
    inputs, the output and the state once a pass.  Under a
    ``jax.custom_vjp`` the forward pass also keeps every chunk's entering
    state and ``T``, and ``kda_scan_bwd`` walks the chunks backwards with
    the state's gradient in scratch: a chunk's gradients are the
    ``jax.vjp`` of the one function both kernels share (:func:`_chunk`),
    with ``dA = -T^T dT T^T`` through the inverse and a running sum up
    the rows through ``G``.  Both kernels take or return the state (or
    its gradient) as ``[B, H, d_k, d_v]``: the benchmark's reader of
    ``kda_scan_ms`` finds the scan's operations by that tensor.
  * **``jax.numpy``** (anything else; the CPU path, and the oracle the
    kernels are held to by ``tests/test_kda_kernel.py``): everything but
    the last three lines is computed for all the chunks of a call at
    once; those three run in a ``lax.scan`` over the chunks, which
    carries ``S`` in float32.  It is differentiable as written
    (``jax.grad`` walks the scan backwards), and one call's chunk
    matrices live to its backward pass.

  How much of a sequence one call takes is the caller's to decide: at
  16 384 tokens and 32 heads of 128 a whole sequence's chunk matrices
  (or kept states) are several GB, so ``models/decoder.py``'s KDA layer
  passes a segment of the sequence at a time, each a ``jax.checkpoint``,
  and the state between them (``initial_state``, ``return_state``).

``exp(G_t - G_s)`` is never formed from ``exp(G_t) * exp(-G_s)`` over a
whole chunk: with decays as strong as ``g = -5`` a token the second factor
overflows float32 after 18 tokens.  Rows are taken ``SUB`` = 16 at a time
and both factors are referred to ``G`` at the first row of their block:
``exp(G_t - ref) <= 1`` on the row side, ``exp(ref - G_s)`` at most
``exp(15 * 5)`` on the column side, and a pair so far apart that a factor
underflows has a true weight below float32's smallest number.  The bound
that makes this safe is the caller's: ``g >= -5`` (``KDA_MIN_LOG_DECAY``).
``T`` comes from forward substitution by rows inside 16 x 16 diagonal
blocks and block merges above them, in float32 (the merges' products at
``HIGHEST``), in both forms: a Neumann series would cancel
catastrophically where neighbouring keys are alike.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.profiling import scope
from .attention import uses_flash

SUB = 16
KDA_MIN_LOG_DECAY = -5.0  # SUB * 5 = 80 < log(float32 max) = 88.7
_HIGHEST = lax.Precision.HIGHEST


def kda_recurrent(q, k, v, g, beta, initial_state=None, return_state=False):
    """The recurrence token by token, in float32.  ``q``, ``k``, ``g``:
    (B, H, S, d_k); ``v``: (B, H, S, d_v); ``beta``: (B, H, S).  Returns
    ``o`` (B, H, S, d_v) float32; the state starts at ``initial_state``
    (zeros where None) and comes back beside ``o`` with ``return_state``."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    b, h, _, dk = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HIGHEST)

    if initial_state is None:
        initial_state = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    over_time = lambda x: jnp.moveaxis(x, 2, 0)
    state, out = lax.scan(
        step, initial_state.astype(f32), tuple(map(over_time, (q, k, v, g, beta))),
    )
    out = jnp.moveaxis(out, 0, 2)
    return (out, state) if return_state else out


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., c, c), c at
    most ``SUB`` or ``SUB`` times a power of two, in ``a``'s type."""
    c = a.shape[-1]
    base = min(c, SUB)
    nb = c // base
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)
    diag = jnp.stack(
        [a[..., i * base:(i + 1) * base, i * base:(i + 1) * base] for i in range(nb)],
        axis=-3,
    )
    # (I + a) t = I by rows: row r of t is e_r less a[r] against the rows above
    t = jnp.broadcast_to(jnp.eye(base, dtype=a.dtype), diag.shape)
    for r in range(1, base):
        row = jnp.einsum("...s,...sj->...j", diag[..., r, :], t, precision=_HIGHEST)
        t = t.at[..., r, :].add(-row)
    size = base
    while size < c:
        below = jnp.stack(
            [
                a[..., (2 * p + 1) * size:(2 * p + 2) * size,
                  2 * p * size:(2 * p + 1) * size]
                for p in range(c // (2 * size))
            ],
            axis=-3,
        )
        t11, t22 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        t21 = -mm(mm(t22, below), t11)
        t = jnp.concatenate([
            jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
            jnp.concatenate([t21, t22], axis=-1),
        ], axis=-2)
        size *= 2
    return t[..., 0, :, :]



# ---------------------------------------------------------------------------
# the Pallas kernels: one call's scan with the chunk matrices and the state
# in VMEM (module header)
# ---------------------------------------------------------------------------

KERNEL_CHUNK = 64  # the chunk the kernels are written for
_EXP_CAP = float(SUB * -KDA_MIN_LOG_DECAY)  # 80: over any exponent a pair keeps
_HEADS_A_STEP = 8  # heads a grid step takes, at most


def _mm(x, y, contract, mmt=None, precision=None):
    """One 2-D product with float32 accumulation; ``contract``: the
    contracted dimension of ``x`` and of ``y``.  Operands are rounded to
    ``mmt`` where given, as ``kda_scan``'s ``dot`` rounds them."""
    if mmt is not None:
        x, y = x.astype(mmt), y.astype(mmt)
    return lax.dot_general(
        x, y, (((contract[0],), (contract[1],)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )


def _grid(n, m):
    """Row and column indices of an (n, m) tile."""
    return (
        lax.broadcasted_iota(jnp.int32, (n, m), 0),
        lax.broadcasted_iota(jnp.int32, (n, m), 1),
    )


def _column(row):
    """A (1, n) row as an (n, 1) column, exactly: the diagonal of the row
    spread over n sublanes, summed along lanes."""
    n = row.shape[1]
    r, c = _grid(n, n)
    return jnp.sum(jnp.where(r == c, row, 0.0), axis=1, keepdims=True)


def _block_diagonal(x, c):
    """Tiles side by side, (c, m c), as the block-diagonal (m c, m c)."""
    tile = lax.broadcasted_iota(jnp.int32, x.shape, 1) // c
    return jnp.concatenate(
        [jnp.where(tile == i, x, 0.0) for i in range(x.shape[1] // c)], axis=0
    )


def _tile_inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular (c, c) float32 tiles, m
    of them side by side as (c, m c), as :func:`_unit_lower_inverse`
    forms it, on whole tiles: the ``SUB``-row diagonal blocks by
    substitution, all of them at once (step ``s`` takes column ``s`` of
    every block out of the rows under it), then the blocks under the
    diagonal by merges of twice the size, each a pair of ``HIGHEST``
    products.  Two tiles side by side fill the MXU's 128 columns: a
    product of the pair costs what one tile's costs."""
    c, width = a.shape
    r, lane = _grid(c, width)
    tile, col = lane // c, lane % c
    base = min(c, SUB)
    diag = jnp.where(r // base == col // base, a, 0.0)
    t = jnp.where(r == col, 1.0, 0.0)
    for s in range(base - 1):
        # a[r, s of r's block] along its tile's lanes; row s of r's block of t
        at = jnp.where(col % base == s, diag, 0.0)
        pivot = 0.0
        for i in range(width // c):
            mine = tile == i
            pivot = pivot + jnp.where(
                mine, jnp.sum(jnp.where(mine, at, 0.0), axis=1, keepdims=True), 0.0
            )
        rows = t.reshape(c // base, base, width)[:, s:s + 1, :]
        rows = jnp.broadcast_to(rows, (c // base, base, width)).reshape(c, width)
        t = t - pivot * rows
    size = base
    while size < c:
        below = jnp.where(
            ((r // size) % 2 == 1) & (col // size == r // size - 1), a, 0.0
        )
        t = t - _mm(
            _mm(t, _block_diagonal(below, c), (1, 0), precision=_HIGHEST),
            _block_diagonal(t, c), (1, 0), precision=_HIGHEST,
        )
        size *= 2
    return t


def _inverse_gradient(t, dt):
    """``dA = -T^T dT T^T`` on the strict lower triangle: what a gradient
    ``dt`` of ``T = (I + A)^-1`` is to ``A``, (c, c) tiles."""
    r, col = _grid(*t.shape)
    da = _mm(
        _mm(t, dt, (0, 0), precision=_HIGHEST), t, (1, 1), precision=_HIGHEST
    )
    return jnp.where(col < r, -da, 0.0)


@jax.custom_vjp
def _known_inverse(a, t):
    """``(I + a)^-1`` where a forward pass kept it: ``t``, with ``a``'s
    gradient through it."""
    return t


_known_inverse.defvjp(
    lambda a, t: (t, t),
    lambda t, dt: (_inverse_gradient(t, dt), jnp.zeros_like(t)),
)


def _running_sum(x, reverse):
    """The inclusive sum down (or up) the rows of a tile by doubling
    shifts: float32 additions, no product."""
    n = x.shape[0]
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < n:
        if reverse:
            x = x + jnp.where(row < n - shift, pltpu.roll(x, n - shift, 0), 0.0)
        else:
            x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


@jax.custom_vjp
def _cumsum(g):
    """``G``: the inclusive sum down the rows of a (c, d) float32 tile
    (Mosaic lowers no ``cumsum``)."""
    return _running_sum(g, reverse=False)


_cumsum.defvjp(
    lambda g: (_running_sum(g, reverse=False), None),
    lambda _, dcum: (_running_sum(dcum, reverse=True),),
)


def _row(x, i):
    """Row ``i`` of a tile as (1, d), by a mask and a sum: a slice's
    transpose is a pad, this one's a broadcast."""
    at = lax.broadcasted_iota(jnp.int32, x.shape, 0) == i
    return jnp.sum(jnp.where(at, x, 0.0), axis=0, keepdims=True)


def _decayed_products(qf, kf, cum, mmt):
    """``P`` and ``A`` before their masks, (c, c) each: ``SUB`` rows at a
    time against every column, both factors referred to ``G`` at the
    block's first row (module header).  A column after the block is
    masked by the caller; its exponent is capped so that it stays finite."""
    c = cum.shape[0]
    sub = min(c, SUB)
    p_rows, a_rows = [], []
    for i in range(c // sub):
        at = slice(i * sub, (i + 1) * sub)
        ref = _row(cum, i * sub)
        own = jnp.exp(cum[at] - ref)
        theirs = kf * jnp.exp(jnp.minimum(ref - cum, _EXP_CAP))
        both = _mm(
            jnp.concatenate([qf[at] * own, kf[at] * own], axis=0), theirs,
            (1, 1), mmt,
        )
        p_rows.append(both[:sub])
        a_rows.append(both[sub:])
    return jnp.concatenate(p_rows, axis=0), jnp.concatenate(a_rows, axis=0)


def _chunk(q, k, v, g, beta, state, mmt, inverses=None):
    """One chunk of some heads on loaded values (the module header's
    equations).  Each argument is a tuple over the heads: ``q``, ``k``
    (c, d_k), ``v`` (c, d_v), ``g`` (c, d_k) float32, ``beta`` (1, c)
    float32, the entering ``state`` (d_k, d_v) float32, and where a
    forward pass kept them the ``inverses`` ``T`` (c, c).  Returns the
    heads' outputs (c, d_v), their states after the chunk and their
    ``T``, float32.  The backward kernel takes ``jax.vjp`` of this, so
    every operation here has a transpose Mosaic lowers."""
    f32 = jnp.float32
    heads = len(q)
    c = g[0].shape[0]
    r, col = _grid(c, c)
    qf, kf, vf = ([x.astype(f32) for x in xs] for xs in (q, k, v))
    cum = [_cumsum(x) for x in g]
    beta = [_column(x) for x in beta]
    p_mat, a_mat = [], []
    for h in range(heads):
        p, a = _decayed_products(qf[h], kf[h], cum[h], mmt)
        p_mat.append(jnp.where(col <= r, p, 0.0))
        a_mat.append(jnp.where(col < r, a, 0.0) * beta[h])
    if inverses is None:  # two heads' tiles side by side; no gradient
        t_mat = []
        for h in range(0, heads, 2):
            pair = _tile_inverse(jnp.concatenate(a_mat[h:h + 2], axis=1))
            t_mat += [pair[:, i * c:(i + 1) * c] for i in range(pair.shape[1] // c)]
    else:
        t_mat = [_known_inverse(a, t) for a, t in zip(a_mat, inverses)]

    outs, states = [], []
    for h in range(heads):
        decay = jnp.exp(cum[h])
        last = _row(cum[h], c - 1)
        w = _mm(t_mat[h], beta[h] * kf[h] * decay, (1, 0), mmt)
        uv = _mm(t_mat[h], beta[h] * vf[h], (1, 0), mmt)
        u = uv - _mm(w, state[h], (1, 0), mmt)
        outs.append(
            _mm(qf[h] * decay, state[h], (1, 0), mmt) + _mm(p_mat[h], u, (1, 0), mmt)
        )
        k_out = kf[h] * jnp.exp(last - cum[h])
        states.append(
            _column(jnp.exp(last)) * state[h] + _mm(k_out, u, (0, 0), mmt)
        )
    return tuple(outs), tuple(states), tuple(t_mat)


def _fwd_kernel(*refs, mmt, heads, keep):
    """Grid (batch, heads / ``heads``, chunk): the chunk axis in order,
    the float32 state of the step's heads from chunk to chunk in scratch.
    With ``keep``, each chunk's entering state and ``T`` go out for the
    backward pass."""
    q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, o_ref, end_ref = refs[:8]
    entering_ref, inverse_ref = refs[8:10] if keep else (None, None)
    state_ref = refs[-1]
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        state_ref[...] = start_ref[0]

    each = lambda get: tuple(get(h) for h in range(heads))
    entering = each(lambda h: state_ref[h])
    outs, states, inverses = _chunk(
        *(each(lambda h, ref=ref: ref[0, h]) for ref in (q_ref, k_ref, v_ref, g_ref)),
        each(lambda h: beta_ref[0, h, pl.ds(n, 1), :]), entering, mmt,
    )
    for h in range(heads):
        o_ref[0, h], state_ref[h] = outs[h], states[h]
        if keep:
            entering_ref[0, h, 0], inverse_ref[0, h, 0] = entering[h], inverses[h]

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        end_ref[0] = state_ref[...]


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, entering_ref, inverse_ref, do_ref,
    dend_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstart_ref,
    dstate_ref, *, mmt, heads,
):
    """The same grid with the chunk axis backwards (the index maps turn it
    round): the state's gradient in float32 scratch, each chunk's
    gradients the ``jax.vjp`` of :func:`_chunk` at its entering state and
    the ``T`` the forward pass kept."""
    step = pl.program_id(2)
    n = pl.num_programs(2) - 1 - step

    @pl.when(step == 0)
    def _():
        dstate_ref[...] = dend_ref[0]

    each = lambda get: tuple(get(h) for h in range(heads))
    inverses = each(lambda h: inverse_ref[0, h, 0])
    _, pull = jax.vjp(
        lambda *x: _chunk(*x, mmt, inverses)[:2],
        *(each(lambda h, ref=ref: ref[0, h]) for ref in (q_ref, k_ref, v_ref, g_ref)),
        each(lambda h: beta_ref[0, h, pl.ds(n, 1), :]),
        each(lambda h: entering_ref[0, h, 0]),
    )
    dq, dk, dv, dg, dbeta, dstate = pull(
        (each(lambda h: do_ref[0, h]), each(lambda h: dstate_ref[h]))
    )
    for h in range(heads):
        dq_ref[0, h], dk_ref[0, h], dv_ref[0, h] = dq[h], dk[h], dv[h]
        dg_ref[0, h], dstate_ref[h] = dg[h], dstate[h]
        dbeta_ref[0, h, pl.ds(n, 1), :] = dbeta[h]

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dstart_ref[0] = dstate_ref[...]


def _call_params(interpret):
    if interpret:
        return {"interpret": True}
    return {
        "interpret": False,
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    }


def _specs(b, h, s, dk, dv, backwards):
    """Block specs of a call's operands by kind, its grid, and the heads a
    grid step takes."""
    c = KERNEL_CHUNK
    n = s // c
    heads = next(x for x in range(_HEADS_A_STEP, 0, -1) if h % x == 0)
    at = (lambda i: n - 1 - i) if backwards else (lambda i: i)
    tokens = lambda d: pl.BlockSpec(
        (1, heads, c, d), lambda bi, hi, i: (bi, hi, at(i), 0)
    )
    by_chunk = lambda *tile: pl.BlockSpec(
        (1, heads, 1, *tile), lambda bi, hi, i: (bi, hi, at(i), 0, 0)
    )
    return {
        "heads": heads, "grid": (b, h // heads, n),
        "k": tokens(dk), "v": tokens(dv),
        # every chunk's beta of the step's heads: a row a chunk, read by index
        "beta": pl.BlockSpec((1, heads, n, c), lambda bi, hi, i: (bi, hi, 0, 0)),
        "state": pl.BlockSpec((1, heads, dk, dv), lambda bi, hi, i: (bi, hi, 0, 0)),
        "entering": by_chunk(dk, dv), "inverse": by_chunk(c, c),
        "scratch": [pltpu.VMEM((heads, dk, dv), jnp.float32)],
    }


# jitted: the layers and passes of a model call these with one signature, and
# the kernel is then traced and lowered once a program, not once a call
@functools.partial(jax.jit, static_argnames=("interpret", "keep"))
def _scan_fwd_call(q, k, v, g, beta, state, interpret, keep):
    """(o, state after) and with ``keep`` every chunk's entering state
    (B, H, N, d_k, d_v) and ``T`` (B, H, N, c, c) beside them."""
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    sp = _specs(b, h, s, dk, dv, backwards=False)
    f32 = jnp.float32
    out_shape = [
        jax.ShapeDtypeStruct((b, h, s, dv), f32),
        jax.ShapeDtypeStruct((b, h, dk, dv), f32),
    ]
    out_specs = [sp["v"], sp["state"]]
    if keep:
        n, c = s // KERNEL_CHUNK, KERNEL_CHUNK
        out_shape += [
            jax.ShapeDtypeStruct((b, h, n, dk, dv), f32),
            jax.ShapeDtypeStruct((b, h, n, c, c), f32),
        ]
        out_specs += [sp["entering"], sp["inverse"]]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, mmt=q.dtype, heads=sp["heads"], keep=keep),
        grid=sp["grid"],
        in_specs=[sp["k"], sp["k"], sp["v"], sp["k"], sp["beta"], sp["state"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=sp["scratch"], name="kda_scan_fwd",
        **_call_params(interpret),
    )(q, k, v, g, beta.reshape(b, h, -1, KERNEL_CHUNK), state)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_bwd_call(q, k, v, g, beta, entering, inverses, do, dend, interpret):
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    sp = _specs(b, h, s, dk, dv, backwards=True)
    f32 = jnp.float32
    like = lambda x, dtype: jax.ShapeDtypeStruct(x.shape, dtype)
    by_chunk = beta.reshape(b, h, -1, KERNEL_CHUNK)
    *grads, dbeta, dstart = pl.pallas_call(
        functools.partial(_bwd_kernel, mmt=q.dtype, heads=sp["heads"]),
        grid=sp["grid"],
        in_specs=[
            sp["k"], sp["k"], sp["v"], sp["k"], sp["beta"], sp["entering"],
            sp["inverse"], sp["v"], sp["state"],
        ],
        out_specs=[sp["k"], sp["k"], sp["v"], sp["k"], sp["beta"], sp["state"]],
        out_shape=[
            like(q, q.dtype), like(k, k.dtype), like(v, v.dtype), like(g, f32),
            like(by_chunk, f32), like(dend, f32),
        ],
        scratch_shapes=sp["scratch"], name="kda_scan_bwd",
        **_call_params(interpret),
    )(q, k, v, g, by_chunk, entering, inverses, do, dend)
    return (*grads, dbeta.reshape(beta.shape), dstart)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernels(q, k, v, g, beta, state, interpret):
    return tuple(_scan_fwd_call(q, k, v, g, beta, state, interpret, keep=False))


def _scan_kernels_fwd(q, k, v, g, beta, state, interpret):
    o, end, *kept = _scan_fwd_call(q, k, v, g, beta, state, interpret, keep=True)
    return (o, end), (q, k, v, g, beta, *kept)


def _scan_kernels_bwd(interpret, res, cotangents):
    return _scan_bwd_call(*res, *cotangents, interpret)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd, optimize_remat=True)


def uses_kernels(q_shape, v_shape, chunk: int, force: Optional[str] = None) -> bool:
    """Whether :func:`kda_scan` takes the Pallas kernels for these shapes
    (``force`` as :func:`sparknet_tpu.ops.attention.attention` has it:
    "flash" the kernels where the shapes fit them, "reference" never,
    None the kernels on a TPU): whole chunks of ``KERNEL_CHUNK`` and head
    sizes in whole lane tiles."""
    fits = (
        chunk == KERNEL_CHUNK and q_shape[2] % chunk == 0
        and q_shape[3] % 128 == 0 and v_shape[3] % 128 == 0
    )
    return fits and uses_flash(force)


def kda_chunks(seq_len: int, chunk: int = 64) -> int:
    """Chunks :func:`kda_scan` walks, one after another, for a sequence."""
    return math.ceil(seq_len / chunk)


def kda_scan(
    q, k, v, g, beta, *, chunk: int = 64, initial_state=None,
    return_state: bool = False, force: Optional[str] = None,
    interpret: bool = False,
):
    """The chunked form (module header).  ``q``, ``k``: (B, H, S, d_k) and
    ``v``: (B, H, S, d_v) in the compute type, which the matrix products
    take their inputs in; ``g`` (B, H, S, d_k), the log decay in
    ``[KDA_MIN_LOG_DECAY, 0]``, and ``beta`` (B, H, S) in float32.  Any
    ``S``: the last chunk is filled with tokens that change nothing
    (``k = 0``, ``g = 0``).  The state starts at ``initial_state``
    (B, H, d_k, d_v) float32, zeros where None.  Returns ``o``
    (B, H, S, d_v) in float32, and with ``return_state`` the state after
    the last token beside it.  ``force`` ("flash", "reference" or None)
    and the shapes choose between the Pallas kernels and ``jax.numpy``
    (:func:`uses_kernels`); ``interpret`` runs the kernels in Pallas's
    interpreter, for tests off a TPU."""
    if chunk > SUB and (chunk % SUB or (chunk // SUB) & (chunk // SUB - 1)):
        raise ValueError(
            f"chunk {chunk}: at most {SUB}, or {SUB} times a power of two"
        )
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    mmt, f32 = q.dtype, jnp.float32
    sub = min(chunk, SUB)
    if uses_kernels(q.shape, v.shape, chunk, force):
        if initial_state is None:
            initial_state = jnp.zeros((b, h, dk, dv), f32)
        with scope("kda.scan"):
            out, state = _scan_kernels(
                q, k.astype(mmt), v.astype(mmt), g.astype(f32),
                beta.astype(f32), initial_state.astype(f32), interpret,
            )
        return (out, state) if return_state else out
    dot = lambda spec, x, y: jnp.einsum(
        spec, x.astype(mmt), y.astype(mmt), preferred_element_type=f32
    )

    def cut(x):
        """(B, H, S, ...) -> (N, B, H, chunk, ...), N chunks."""
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
        x = x.reshape(b, h, (s + pad) // chunk, chunk, *x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    def one_chunk(state, x):
        w_n, uv_n, q_n, p_n, k_n, decay_n = x
        u = uv_n - dot("bhtk,bhkv->bhtv", w_n, state)
        out = dot("bhtk,bhkv->bhtv", q_n, state) + dot("bhts,bhsv->bhtv", p_n, u)
        state = decay_n * state + dot("bhtk,bhtv->bhkv", k_n, u)
        return state, out

    with scope("kda.scan"):
        q, k, v = cut(q), cut(k), cut(v)
        cum = jnp.cumsum(cut(g.astype(f32)), axis=3)  # G, inclusive
        beta = cut(beta.astype(f32))[..., None]
        qf, kf = q.astype(f32), k.astype(f32)

        # P and A, SUB rows at a time against the columns up to those rows
        rows = []
        for i in range(chunk // sub):
            at = slice(i * sub, (i + 1) * sub)
            upto = slice(0, (i + 1) * sub)
            ref = cum[..., i * sub:i * sub + 1, :]
            own = jnp.exp(cum[..., at, :] - ref)
            mine = jnp.stack([qf[..., at, :] * own, kf[..., at, :] * own], axis=3)
            theirs = kf[..., upto, :] * jnp.exp(ref - cum[..., upto, :])
            block = dot("nbhxtc,nbhsc->nbhxts", mine, theirs)
            rows.append(jnp.pad(
                block, ((0, 0),) * 5 + ((0, chunk - (i + 1) * sub),)
            ))
        both = jnp.concatenate(rows, axis=4)  # (N, B, H, 2, chunk, chunk)
        t_at = jnp.arange(chunk)[:, None]
        s_at = jnp.arange(chunk)[None, :]
        p_mat = jnp.where(s_at <= t_at, both[:, :, :, 0], 0.0)
        a_mat = jnp.where(s_at < t_at, both[:, :, :, 1], 0.0) * beta
        t_mat = _unit_lower_inverse(a_mat)

        decay = jnp.exp(cum)  # Gamma
        w = dot("nbhts,nbhsc->nbhtc", t_mat, beta * kf * decay)
        uv = dot("nbhts,nbhsc->nbhtc", t_mat, beta * v.astype(f32))
        last = cum[..., -1:, :]
        q_in = (qf * decay).astype(mmt)
        k_out = (kf * jnp.exp(last - cum)).astype(mmt)
        carry_decay = jnp.exp(last[..., 0, :])[..., None]  # (N, B, H, d_k, 1)

        if initial_state is None:
            initial_state = jnp.zeros((b, h, dk, dv), f32)
        state, out = lax.scan(
            one_chunk, initial_state,
            (w.astype(mmt), uv, q_in, p_mat.astype(mmt), k_out, carry_decay),
        )
        out = jnp.moveaxis(out, 0, 2).reshape(b, h, s + pad, dv)
    out = out[:, :, :s] if pad else out
    return (out, state) if return_state else out
