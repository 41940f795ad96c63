"""The state-space scan of Mamba-2 (SSD, arXiv:2405.21060): per head a
scalar decay and a ``d_head x d_state`` state, written by every token and
read by every token, with ``B`` and ``C`` shared by the heads (one group).

Per head ``h``, with ``S`` (P, N) starting at ``S_0`` (zeros unless
given), ``a_t = exp(delta_t A_h)`` (``A_h < 0``, ``delta_t > 0``)::

    S_t = a_t S_{t-1} + delta_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

On packed documents the decay is taken as 0 at a document's first token
(``segment_ids``): nothing written in one document is read in the next.

- :func:`ssd_recurrent` — exactly that, a ``lax.scan`` over tokens, in
  float32: the numerics oracle the tests hold the chunked form to.
- :func:`ssd_scan` — the same result ``chunk`` tokens at a time, so that
  matrix products do the work.  With ``G`` the inclusive cumulative sum of
  ``delta A`` inside a chunk and ``Xd = delta x``::

      Y_diag[t] = sum_(s <= t, same document) (C_t . B_s) exp(G_t - G_s) Xd_s
      Y_off[t]  = exp(G_t) C_t S_in             (no document begun by t)
      S_out     = exp(G_L) S_in                 (no document begun in it)
                  + sum_(s in the chunk's last document) exp(G_L - G_s) Xd_s B_s^T

  The decays are built from the cumulative sums of the true ``delta A`` and
  masked in the exponent (``exp(where(mask, G_t - G_s, -inf))``), never from
  a ``-inf`` decay at a boundary: a segment sum over one would read ``-inf -
  (-inf)``, a NaN in the gradient.  Every exponent kept is at most 0.  The
  products take their inputs in ``x``'s type with float32 accumulation;
  ``G``, the decays and the state stay float32.  It comes in two forms, one
  algorithm, chosen by what the call shows (:func:`uses_kernels`: the
  backend and the shapes, or the caller's ``force``, as
  :func:`sparknet_tpu.ops.attention.attention` chooses):

  * **The Pallas kernels** (a TPU; whole chunks of a multiple of 128,
    ``d_state`` and a block of heads in whole lane tiles): ``ssd_scan_fwd``
    walks a call's chunks in order with the float32 state of a block of
    heads in VMEM scratch, ``S^T`` as ``(d_state, heads x d_head)``, and
    forms each chunk's ``C B^T`` (once for the block), decays, ``Y_diag``,
    ``Y_off`` and next state in VMEM, so HBM sees the inputs, ``y`` and the
    state once a pass.  The heads of a lane tile go together (two of 64):
    their decays and ``Xd`` per lane, ``Y_diag`` one product a head with
    the other head's lanes of ``Xd`` zeroed.  ``G`` and the documents a
    token has begun in its chunk are formed outside, small, and passed by
    token and by head.  Under a ``jax.custom_vjp`` the forward pass also
    keeps each chunk's entering state (nothing chunk-squared), and
    ``ssd_scan_bwd`` walks the chunks backwards with the state's gradient
    in scratch: it forms a chunk's decays again, transposed (rows ``s``,
    columns ``t``), so every product is a plain or a ``A B^T`` one, and
    gives ``x``, ``G`` (by token and by head), ``delta`` and each head
    block's part of ``B`` and ``C``; XLA adds the parts and walks ``G``'s
    gradient back through the cumulative sum.
  * **``jax.numpy``** (anything else; the CPU path, and the oracle the
    kernels are held to by ``tests/test_ssd_kernel.py``): the chunk
    matrices of a call are formed for all its chunks at once; the states
    walk the chunks in a ``lax.scan`` in float32.  A call's chunk matrices
    live to its backward pass, ``chunks x heads x chunk^2`` floats each: a
    caller with a long sequence passes it a segment at a time
    (``initial_state``, ``return_state``, ``state_segment``), as
    ``models/decoder.py`` does.

  Both round at the same points: ``scores x decay``, ``Xd``, ``Xd x
  to_end`` and the entering state are rounded to ``x``'s type for their
  products; the kernels' backward keeps the decays' gradient in float32
  where the ``jax.numpy`` form's rounds it to ``x``'s type, and sums it
  and ``delta``'s over a head's lanes or a chunk's tokens on the MXU from
  a two-part bfloat16 split of each term (16 bits of it kept, where the
  ``jax.numpy`` form's rounded cotangent keeps 8): a lane reduction on
  the vector units would cost more than the decays.  ``D``'s skip is
  float32, outside either.
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

_HIGHEST = lax.Precision.HIGHEST


def document_starts(segment_ids, state_segment=None):
    """(B, S) bool: the tokens at which a document begins that the state
    before them does not belong to — their id differs from the token
    before; at the first token, from ``state_segment`` (B,), the document
    of the state entering the call (None: the state goes on into the first
    token's document)."""
    before = segment_ids[:, :1] if state_segment is None else state_segment[:, None]
    return segment_ids != jnp.concatenate([before, segment_ids[:, :-1]], axis=1)


def ssd_recurrent(
    x, delta, a, b, c, d=None, *, segment_ids=None, state_segment=None,
    initial_state=None, return_state=False,
):
    """The recurrence token by token, in float32.  ``x`` (B, S, H, P),
    ``delta`` (B, S, H), ``a`` (H,), ``b`` and ``c`` (B, S, N), ``d`` (H,)
    or None; ``segment_ids`` (B, S) and ``state_segment`` as
    :func:`ssd_scan` takes them.  Returns ``y`` (B, S, H, P) float32, and
    with ``return_state`` the state (B, H, P, N) after the last token."""
    f32 = jnp.float32
    x, delta, a, b, c = (t.astype(f32) for t in (x, delta, a, b, c))
    bsz, s, h, p = x.shape
    starts = (
        jnp.zeros((bsz, s), bool) if segment_ids is None
        else document_starts(segment_ids, state_segment)
    )

    def step(state, inp):
        x_t, dl_t, b_t, c_t, start_t = inp
        decay = jnp.where(start_t[:, None], 0.0, jnp.exp(dl_t * a))  # (B, H)
        state = decay[..., None, None] * state + (
            (dl_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        )
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t, precision=_HIGHEST)

    if initial_state is None:
        initial_state = jnp.zeros((bsz, h, p, b.shape[-1]), f32)
    over_time = lambda t: jnp.moveaxis(t, 1, 0)
    state, y = lax.scan(
        step, initial_state.astype(f32),
        tuple(map(over_time, (x, delta, b, c, starts))),
    )
    y = jnp.moveaxis(y, 0, 1)
    if d is not None:
        y = y + d.astype(f32)[:, None] * x
    return (y, state) if return_state else y


def ssd_chunks(seq_len: int, chunk: int = 256) -> int:
    """Chunks :func:`ssd_scan` walks, one after another, for a sequence."""
    return math.ceil(seq_len / chunk)


# ---------------------------------------------------------------------------
# the Pallas kernels: one call's scan with the chunk matrices and the state
# in VMEM (module header)
# ---------------------------------------------------------------------------

_HEADS_A_STEP = 8  # heads a grid step takes, at most
_LANES = 128


def heads_per_block(h: int, p: int) -> Optional[int]:
    """Heads a grid step of the kernels takes: the most, up to
    ``_HEADS_A_STEP``, that divide ``h`` and fill whole lane tiles with
    heads of ``p`` (None: no such number, or heads that straddle a tile)."""
    if _LANES % p and p % _LANES:
        return None
    return next(
        (k for k in range(_HEADS_A_STEP, 0, -1) if h % k == 0 and k * p % _LANES == 0),
        None,
    )


def uses_kernels(x_shape, n_state: int, chunk: int, force: Optional[str] = None) -> bool:
    """Whether :func:`ssd_scan` takes the Pallas kernels for ``x`` of
    ``x_shape`` (B, S, H, P) (``force`` as
    :func:`sparknet_tpu.ops.attention.attention` has it: "flash" the kernels
    where the shapes fit them, "reference" never, None the kernels on a
    TPU): whole chunks of a multiple of 128 tokens, ``d_state`` and a block
    of heads (:func:`heads_per_block`) in whole lane tiles."""
    _, s, h, p = x_shape
    fits = (
        chunk % _LANES == 0 and s % chunk == 0 and n_state % _LANES == 0
        and heads_per_block(h, p) is not None
    )
    return fits and uses_flash(force)


def _dot(x, y, contract=(1, 0)):
    """One 2-D product with float32 accumulation: ``x y`` by default,
    ``x y^T`` with ``contract=(1, 1)``."""
    return lax.dot_general(
        x, y, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _lane(tile, j):
    """Column ``j`` of a (rows, k) tile as (rows, 1)."""
    return tile[:, j:j + 1]


def _into(tile, lane_of, width):
    """``tile`` (rows, k) summed into the lanes ``lane_of`` (a (k, width)
    int32 iota map: the lane each column goes to) of a (rows, width)
    float32 tile, on the MXU: the sums a lane reduction would make, from a
    two-part bfloat16 split of each term (16 of its bits kept; a lane
    reduction on the vector units costs a rotation and an add a step)."""
    hot = (lane_of == _iota((tile.shape[1], width), 1)).astype(jnp.bfloat16)
    hi = tile.astype(jnp.bfloat16)
    lo = (tile - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return _dot(hi, hot) + _dot(lo, hot)


def _placed(parts, shape):
    """(1, cols) rows, one a head, stacked into a tile of ``shape``."""
    at, out = _iota(shape, 0), jnp.zeros(shape, jnp.float32)
    for k, part in enumerate(parts):
        out = jnp.where(at == k, part, out)
    return out


class _Group:
    """What a chunk's tokens carry per lane for the heads of one lane tile
    (``p``-wide heads from ``first`` on): their ``G`` columns, ``G`` and
    ``delta`` spread over each head's lanes, ``Xd``, and the three decays
    of the entering and the leaving state (module header), float32."""

    def __init__(self, x, g_col, dl_col, doc, first, p):
        rows, width = x.shape
        self.per = width // p
        self.head = _iota((rows, width), 1) // p
        self.cols = [_lane(g_col, first + j) for j in range(self.per)]
        self.gx = self.spread(self.cols)
        self.dlx = self.spread([_lane(dl_col, first + j) for j in range(self.per)])
        self.xf = x.astype(jnp.float32)
        self.xd = self.xf * self.dlx
        last = doc[rows - 1:, :]  # (1, 1): documents begun in the chunk
        self.g_last = self.gx[rows - 1:, :]  # (1, width)
        self.from_start = jnp.exp(jnp.where(doc == 0, self.gx, -jnp.inf))
        self.to_end = jnp.exp(jnp.where(doc == last, self.g_last - self.gx, -jnp.inf))
        self.through = jnp.exp(jnp.where(last == 0, self.g_last, -jnp.inf))

    def spread(self, cols):
        out = jnp.broadcast_to(cols[0], self.head.shape)
        for j in range(1, self.per):
            out = jnp.where(self.head == j, cols[j], out)
        return out

    def only(self, j, tile):
        """``tile`` with every lane but head ``j``'s zeroed."""
        return tile if self.per == 1 else jnp.where(self.head == j, tile, 0.0)

    def heads_into(self, tile, first):
        """(rows, width) summed over each head's lanes into lane ``first +
        j`` of a (rows, 128) tile (:func:`_into`)."""
        width = tile.shape[1]
        return _into(tile, _iota((width, _LANES), 0) // (width // self.per) + first, _LANES)


def _groups(width, p):
    """(first lane, first head) of each lane tile of heads in a block."""
    lanes = max(p, _LANES)
    return [(at, at // p) for at in range(0, width, lanes)], lanes


def _row_blocks(rows):
    """The 128-token row blocks of a chunk.  Rows ``t`` of a block pair
    only with columns ``s <= t``, so the part of the square to the right of
    the block's diagonal tile is always masked, and is skipped."""
    return [slice(at, at + _LANES) for at in range(0, rows, _LANES)]


def _fwd_kernel(
    x_ref, gc_ref, dlc_ref, gr_ref, docc_ref, docr_ref, b_ref, bt_ref, c_ref,
    start_ref, y_ref, end_ref, *rest, p, keep,
):
    """Grid (batch, head blocks, chunk): the chunk axis in order, the
    float32 ``S^T`` of the block's heads from chunk to chunk in scratch.
    With ``keep``, each chunk's entering state goes out for the backward
    pass.  Rows ``t`` of a 128-token block take the columns ``s`` up to
    its last."""
    state_ref = rest[-1]
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        state_ref[...] = start_ref[0]

    if keep:
        rest[0][0, 0] = state_ref[...]
    mmt = x_ref.dtype
    doc, c = docc_ref[0], c_ref[0]
    blocks = _row_blocks(doc.shape[0])
    scores, pairs = [], []  # C B^T and the pairs a token reads, (t, s <= block's end)
    for r in blocks:
        upto = slice(0, r.stop)
        scores.append(_dot(c[r], b_ref[0, upto, :], (1, 1)))
        t_at = _iota((_LANES, r.stop), 0) + r.start
        pairs.append((_iota((_LANES, r.stop), 1) <= t_at) & (doc[r] == docr_ref[0, :, upto]))
    starts, lanes = _groups(x_ref.shape[-1], p)
    for at, first in starts:
        part = pl.ds(at, lanes)
        grp = _Group(x_ref[0, :, part], gc_ref[0, 0], dlc_ref[0, 0], doc, first, p)
        state = state_ref[:, part]
        y_off = _dot(c, state.astype(mmt)) * grp.from_start
        xds = [grp.only(j, grp.xd).astype(mmt) for j in range(grp.per)]
        for k, r in enumerate(blocks):
            upto = slice(0, r.stop)
            y = y_off[r]
            for j in range(grp.per):
                g_row = gr_ref[0, 0, first + j:first + j + 1, upto]
                decay = jnp.exp(jnp.where(pairs[k], grp.cols[j][r] - g_row, -jnp.inf))
                y = y + _dot((scores[k] * decay).astype(mmt), xds[j][upto])
            y_ref[0, r, part] = y
        own = _dot(bt_ref[0], (grp.xd * grp.to_end).astype(mmt))
        state_ref[:, part] = grp.through * state + own

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        end_ref[0] = state_ref[...]


def _from(tile, at, width):
    """A (rows, width - at) tile placed at lane ``at`` of (rows, width)."""
    if not at:
        return tile
    return jnp.concatenate([jnp.zeros((tile.shape[0], at), tile.dtype), tile], axis=1)


def _bwd_kernel(
    x_ref, gc_ref, dlc_ref, gr_ref, docc_ref, docr_ref, b_ref, bt_ref, c_ref,
    ct_ref, entering_ref, dy_ref, dend_ref,
    dx_ref, dgc_ref, ddlc_ref, dgr_ref, db_ref, dbt_ref, dc_ref, dct_ref,
    dstart_ref, dstate_ref, *, p,
):
    """The same grid with the chunk axis backwards (the index maps turn it
    round): the gradient of ``S^T`` in float32 scratch, a chunk's decays
    formed again transposed, rows ``s`` and columns ``t`` (module header);
    rows ``s`` of a 128-token block take the columns ``t`` from its first."""
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        dstate_ref[...] = dend_ref[0]

    mmt = x_ref.dtype
    doc, b, c = docc_ref[0], b_ref[0], c_ref[0]
    rows, n_state = b.shape
    blocks = _row_blocks(rows)
    scores_t, pairs_t, dscores_t = [], [], []  # B C^T and its pairs, (s, t >= block's start)
    for r in blocks:
        on = slice(r.start, rows)
        scores_t.append(_dot(b[r], c[on], (1, 1)))
        s_at = _iota((_LANES, rows - r.start), 0)
        pairs_t.append((s_at <= _iota(s_at.shape, 1)) & (doc[r] == docr_ref[0, :, on]))
        dscores_t.append(jnp.zeros(s_at.shape, jnp.float32))
    dc = jnp.zeros((rows, n_state), jnp.float32)
    dbt = jnp.zeros((n_state, rows), jnp.float32)
    # G's and delta's gradients by token, a head a lane; G's by head, a row a head
    dg_cols = [jnp.zeros((_LANES, _LANES), jnp.float32) for _ in blocks]
    ddl_cols = jnp.zeros((rows, _LANES), jnp.float32)
    dg_rows = []
    starts, lanes = _groups(x_ref.shape[-1], p)
    last_row = _iota((rows, lanes), 0) == rows - 1
    for at, first in starts:
        part = pl.ds(at, lanes)
        grp = _Group(x_ref[0, :, part], gc_ref[0, 0], dlc_ref[0, 0], doc, first, p)
        dy, dnext = dy_ref[0, :, part], dstate_ref[:, part]
        entering = entering_ref[0, 0, :, part]
        held = entering.astype(mmt)
        # Y_off = (C S) from_start
        dq = dy * grp.from_start
        dq_m = dq.astype(mmt)
        dheld = _dot(ct_ref[0], dq_m)
        dc = dc + _dot(dq_m, held, (1, 1))
        dgx = dq * _dot(c, held)  # through from_start's exponent
        # S' = through S + B^T (Xd to_end)
        dnext_m = dnext.astype(mmt)
        dthrough = jnp.sum(dnext * entering, axis=0, keepdims=True) * grp.through
        own_in = grp.xd * grp.to_end
        dbt = dbt + _dot(dnext_m, own_in.astype(mmt), (1, 1))
        down = _dot(b, dnext_m)
        dto_end = down * own_in  # through to_end's exponent
        dgx = dgx - dto_end
        # G_L's gradient joins the last token's
        dgx = dgx + jnp.where(
            last_row, dthrough + jnp.sum(dto_end, axis=0, keepdims=True), 0.0
        )
        dgx = grp.heads_into(dgx, first)
        # Y_diag, a head and a row block at a time
        xd_m = grp.xd.astype(mmt)
        dys = [grp.only(j, dy).astype(mmt) for j in range(grp.per)]
        dxd = down * grp.to_end
        dxd_rows = []
        dg_row = [0.0] * grp.per
        for k, r in enumerate(blocks):
            on = slice(r.start, rows)
            dxd_r = dxd[r]
            dg_cols[k] = dg_cols[k] + dgx[r]
            for j in range(grp.per):
                g_row = gr_ref[0, 0, first + j:first + j + 1, on]
                decay_t = jnp.exp(jnp.where(pairs_t[k], g_row - grp.cols[j][r], -jnp.inf))
                weights_t = scores_t[k] * decay_t
                dxd_r = dxd_r + _dot(weights_t.astype(mmt), dys[j][on])
                dweights_t = _dot(xd_m[r], dys[j][on], (1, 1))
                dz = dweights_t * weights_t  # through the decay's exponent
                dg_row[j] = dg_row[j] + _from(jnp.sum(dz, axis=0, keepdims=True), r.start, rows)
                dg_cols[k] = dg_cols[k] - _into(
                    dz, jnp.full((rows - r.start, _LANES), first + j, jnp.int32), _LANES
                )
                dscores_t[k] = dscores_t[k] + dweights_t * decay_t
            dxd_rows.append(dxd_r)
        dxd = jnp.concatenate(dxd_rows, axis=0)
        dstate_ref[:, part] = grp.through * dnext + dheld
        dx_ref[0, :, part] = (dxd * grp.dlx).astype(dx_ref.dtype)
        ddl_cols = ddl_cols + grp.heads_into(dxd * grp.xf, first)
        dg_rows += dg_row
    heads = len(dg_rows)
    dgc_ref[0, 0] = jnp.concatenate(dg_cols, axis=0)[:, :heads]
    ddlc_ref[0, 0] = ddl_cols[:, :heads]
    dgr_ref[0, 0] = _placed(dg_rows, (heads, rows))
    dct = 0.0
    for k, r in enumerate(blocks):
        dscores_m = dscores_t[k].astype(mmt)
        db_ref[0, 0, r, :] = _dot(dscores_m, c[r.start:, :])
        dct = dct + _from(_dot(bt_ref[0, :, r], dscores_m), r.start, rows)
    dct_ref[0, 0] = dct
    dc_ref[0, 0], dbt_ref[0, 0] = dc, dbt

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


def _specs(bsz, s, width, p, n_state, chunk, backwards):
    """Block specs of a call's operands by kind, its grid, and the heads
    and head blocks of a grid step."""
    hb = heads_per_block(width // p, p)
    w, n = hb * p, s // chunk
    at = (lambda i: n - 1 - i) if backwards else (lambda i: i)
    block = lambda shape, index: pl.BlockSpec(shape, lambda bi, hi, i: index(bi, hi, at(i)))
    return {
        "grid": (bsz, width // w, n),
        "tokens": block((1, chunk, w), lambda bi, hi, i: (bi, i, hi)),
        "head_cols": block((1, 1, chunk, hb), lambda bi, hi, i: (bi, hi, i, 0)),
        "head_rows": block((1, 1, hb, chunk), lambda bi, hi, i: (bi, hi, 0, i)),
        "doc_col": block((1, chunk, 1), lambda bi, hi, i: (bi, i, 0)),
        "doc_row": block((1, 1, chunk), lambda bi, hi, i: (bi, 0, i)),
        "by_token": block((1, chunk, n_state), lambda bi, hi, i: (bi, i, 0)),
        "by_state": block((1, n_state, chunk), lambda bi, hi, i: (bi, 0, i)),
        "state": block((1, n_state, w), lambda bi, hi, i: (bi, 0, hi)),
        "entering": block((1, 1, n_state, w), lambda bi, hi, i: (bi, i, 0, hi)),
        # each head block's part of B's and C's gradients
        "part": block((1, 1, chunk, n_state), lambda bi, hi, i: (bi, hi, i, 0)),
        "part_t": block((1, 1, n_state, chunk), lambda bi, hi, i: (bi, hi, 0, i)),
        "scratch": [pltpu.VMEM((n_state, w), jnp.float32)],
        "blocks": width // w,
    }


def _inputs(sp):
    """The specs of what both kernels take first: x, ``G``'s and
    ``delta``'s columns, ``G``'s rows, the documents as a column and as a
    row, B, B^T, C."""
    return [
        sp["tokens"], sp["head_cols"], sp["head_cols"], sp["head_rows"],
        sp["doc_col"], sp["doc_row"], sp["by_token"], sp["by_state"], sp["by_token"],
    ]


# jitted: the layers and passes of a model call these with one signature, and
# the kernel is then traced and lowered once a program, not once a call
@functools.partial(jax.jit, static_argnames=("p", "chunk", "interpret", "keep"))
def _scan_fwd_call(x, g_col, dl_col, g_row, doc_col, doc_row, b, c, state, p, chunk,
                   interpret, keep):
    """``x`` (B, S, H P), heads of ``p``; the state ``S^T`` (B, N, H P).
    Returns ``y`` (B, S, H P) float32, the state after, and with ``keep``
    every chunk's entering state (B, n, N, H P)."""
    bsz, s, width = x.shape
    n_state = b.shape[-1]
    sp = _specs(bsz, s, width, p, n_state, chunk, backwards=False)
    f32 = jnp.float32
    out_shape = [
        jax.ShapeDtypeStruct((bsz, s, width), f32),
        jax.ShapeDtypeStruct(state.shape, f32),
    ]
    out_specs = [sp["tokens"], sp["state"]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((bsz, s // chunk, n_state, width), f32))
        out_specs.append(sp["entering"])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, keep=keep),
        grid=sp["grid"], in_specs=_inputs(sp) + [sp["state"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=sp["scratch"], name="ssd_scan_fwd",
        **_call_params(interpret),
    )(x, g_col, dl_col, g_row, doc_col, doc_row, b, jnp.swapaxes(b, 1, 2), c, state)


@functools.partial(jax.jit, static_argnames=("p", "chunk", "interpret"))
def _scan_bwd_call(x, g_col, dl_col, g_row, doc_col, doc_row, b, c, entering, dy,
                   dend, p, chunk, interpret):
    bsz, s, width = x.shape
    n_state = b.shape[-1]
    sp = _specs(bsz, s, width, p, n_state, chunk, backwards=True)
    f32 = jnp.float32
    like = lambda t, dtype=f32: jax.ShapeDtypeStruct(t.shape, dtype)
    part = jax.ShapeDtypeStruct((bsz, sp["blocks"], s, n_state), f32)
    part_t = jax.ShapeDtypeStruct((bsz, sp["blocks"], n_state, s), f32)
    dx, dg_col, ddl_col, dg_row, db, dbt, dc, dct, dstart = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=sp["grid"],
        in_specs=_inputs(sp) + [sp["by_state"], sp["entering"], sp["tokens"], sp["state"]],
        out_specs=[
            sp["tokens"], sp["head_cols"], sp["head_cols"], sp["head_rows"],
            sp["part"], sp["part_t"], sp["part"], sp["part_t"], sp["state"],
        ],
        out_shape=[
            like(x, x.dtype), like(g_col), like(dl_col), like(g_row),
            part, part_t, part, part_t, like(dend),
        ],
        scratch_shapes=sp["scratch"], name="ssd_scan_bwd",
        **_call_params(interpret),
    )(
        x, g_col, dl_col, g_row, doc_col, doc_row, b, jnp.swapaxes(b, 1, 2), c,
        jnp.swapaxes(c, 1, 2), entering, dy, dend,
    )
    # the head blocks' parts, by token and by state
    whole = lambda by_token, by_state, like: (
        by_token.sum(1) + jnp.swapaxes(by_state.sum(1), 1, 2)
    ).astype(like.dtype)
    return dx, dg_col, ddl_col, dg_row, whole(db, dbt, b), whole(dc, dct, c), dstart


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _scan_kernels(x, g_col, dl_col, g_row, doc_col, doc_row, b, c, state, p, chunk,
                  interpret):
    return tuple(_scan_fwd_call(
        x, g_col, dl_col, g_row, doc_col, doc_row, b, c, state, p, chunk,
        interpret, keep=False,
    ))


def _scan_kernels_fwd(x, g_col, dl_col, g_row, doc_col, doc_row, b, c, state, p,
                      chunk, interpret):
    y, end, entering = _scan_fwd_call(
        x, g_col, dl_col, g_row, doc_col, doc_row, b, c, state, p, chunk,
        interpret, keep=True,
    )
    return (y, end), (x, g_col, dl_col, g_row, doc_col, doc_row, b, c, entering)


def _scan_kernels_bwd(p, chunk, interpret, res, cotangents):
    dx, dg_col, ddl_col, dg_row, db, dc, dstart = _scan_bwd_call(
        *res, *cotangents, p, chunk, interpret
    )
    return dx, dg_col, ddl_col, dg_row, None, None, db, dc, dstart


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd, optimize_remat=True)


def _kernel_scan(x, dl, cum, doc, b, c, state, interpret):
    """The kernels' call on the ``jax.numpy`` form's own ``delta``, ``G``
    and documents by chunk, (B, n, L, H) and (B, n, L), laid out for them:
    x lane-dense, ``G`` and ``delta`` by head block as columns and ``G``
    as rows, the state as ``S^T``.  Returns ``y`` (B, S, H, P) float32 and
    the state after (B, H, P, N)."""
    bsz, s, h, p = x.shape
    chunk = dl.shape[2]
    hb = heads_per_block(h, p)
    by_block = lambda t: t.reshape(bsz, s, h // hb, hb).transpose(0, 2, 1, 3)
    g_col = by_block(cum)  # (B, blocks, S, hb)
    doc = doc.reshape(bsz, s)
    y, end = _scan_kernels(
        x.reshape(bsz, s, h * p), g_col, by_block(dl), jnp.swapaxes(g_col, 2, 3),
        doc[:, :, None], doc[:, None, :], b, c,
        state.transpose(0, 3, 1, 2).reshape(bsz, -1, h * p), p, chunk, interpret,
    )
    return y.reshape(bsz, s, h, p), end.reshape(bsz, -1, h, p).transpose(0, 2, 3, 1)


def ssd_scan(
    x, delta, a, b, c, d=None, *, chunk: int = 256, segment_ids=None,
    state_segment=None, initial_state=None, return_state: bool = False,
    force: Optional[str] = None, interpret: bool = False,
):
    """The chunked form (module header).  ``x`` (B, S, H, P), ``b`` and
    ``c`` (B, S, N) in the compute type, which the products take their
    inputs in; ``delta`` (B, S, H) and ``a`` (H,), ``A`` itself (negative),
    and ``d`` (H,) in float32.  ``segment_ids`` (B, S), ids that do not
    decrease along a sequence, reset the state at every document's first
    token, inside a chunk or at its edge; ``state_segment`` (B,) names the
    document the entering state belongs to (None: the first token's).  Any
    ``S``: the last chunk is filled with tokens that change nothing (``delta
    = 0``).  The state starts at ``initial_state`` (B, H, P, N) float32,
    zeros where None.  Returns ``y`` (B, S, H, P) float32, and with
    ``return_state`` the state after the last token beside it.  ``force``
    ("flash", "reference" or None) and the shapes choose between the Pallas
    kernels and ``jax.numpy`` (:func:`uses_kernels`); ``interpret`` runs the
    kernels in Pallas's interpreter, for tests off a TPU."""
    bsz, s, h, p = x.shape
    n_state = b.shape[-1]
    f32 = jnp.float32
    pad = -s % chunk
    n = (s + pad) // chunk

    def cut(t):
        """(B, S, ...) -> (B, n, chunk, ...), the tail filled with zeros."""
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape(bsz, n, chunk, *t.shape[2:])

    with scope("ssd.scan"):
        starts = (
            jnp.zeros((bsz, s), bool) if segment_ids is None
            else document_starts(segment_ids, state_segment)
        )
        dl = cut(delta.astype(f32))  # (B, n, L, H)
        cum = jnp.cumsum(dl * a.astype(f32), axis=2)  # G, inclusive, <= 0
        # documents begun in the chunk up to each token: two tokens of a
        # chunk share a document iff they have begun as many
        doc = jnp.cumsum(cut(starts).astype(jnp.int32), axis=2)  # (B, n, L)
        if initial_state is None:
            initial_state = jnp.zeros((bsz, h, p, n_state), f32)
        if uses_kernels(x.shape, n_state, chunk, force):
            y, state = _kernel_scan(
                x, dl, cum, doc, b.astype(x.dtype), c.astype(x.dtype),
                initial_state.astype(f32), interpret,
            )
        else:
            y, state = _chunked(cut(x), dl, cum, doc, cut(b), cut(c), initial_state)
            y = y.reshape(bsz, n * chunk, h, p)[:, :s]
        if d is not None:
            y = y + d.astype(f32)[:, None] * x.astype(f32)
    return (y, state) if return_state else y


def _chunked(xs, dl, cum, doc, bs, cs, initial_state):
    """The ``jax.numpy`` form on chunks: ``xs`` (B, n, L, H, P), ``bs`` and
    ``cs`` (B, n, L, N), ``dl`` and ``cum`` (B, n, L, H), ``doc`` (B, n,
    L).  Returns ``y`` (B, n, L, H, P) float32 and the state after."""
    mmt, f32 = xs.dtype, jnp.float32
    chunk = xs.shape[2]
    dot = lambda spec, u, v: jnp.einsum(
        spec, u.astype(mmt), v.astype(mmt), preferred_element_type=f32
    )
    at = jnp.arange(chunk)
    pairs = (at[:, None] >= at[None, :]) & (doc[..., :, None] == doc[..., None, :])
    decay = jnp.exp(jnp.where(
        pairs[..., None], cum[:, :, :, None] - cum[:, :, None], -jnp.inf
    ))  # (B, n, t, s, H)
    xd = xs.astype(f32) * dl[..., None]  # delta x, (B, n, L, H, P)
    scores = dot("bntk,bnsk->bnts", cs, bs)[..., None] * decay
    y = dot("bntsh,bnshp->bnthp", scores, xd)

    # each chunk's own part of the state at its end, and the decay the
    # entering state takes over the chunk (0 where a document begins)
    last = doc[:, :, -1:]
    to_end = jnp.exp(
        jnp.where((doc == last)[..., None], cum[:, :, -1:] - cum, -jnp.inf)
    )
    own = dot("bnshp,bnsk->bnhpk", xd * to_end[..., None], bs)
    through = jnp.exp(
        jnp.where(last == 0, cum[:, :, -1], -jnp.inf)
    )  # (B, n, H)

    def one_chunk(state, inp):
        through_n, own_n = inp
        return through_n[..., None, None] * state + own_n, state

    state, entering = lax.scan(
        one_chunk, initial_state.astype(f32),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(own, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # (B, n, H, P, N)
    from_start = jnp.exp(jnp.where((doc == 0)[..., None], cum, -jnp.inf))
    return y + dot("bntk,bnhpk->bnthp", cs, entering) * from_start[..., None], state
