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
  chunk matrices of a call are formed for all its chunks at once; the
  states walk the chunks in a ``lax.scan`` in float32.  The products take
  their inputs in ``x``'s type with float32 accumulation, as ``ops/kda.py``'s
  ``jax.numpy`` form does.  A call's chunk matrices live to its backward
  pass, ``chunks x heads x chunk^2`` floats each: a caller with a long
  sequence passes it a segment at a time (``initial_state``,
  ``return_state``, ``state_segment``), as ``models/decoder.py`` does.

No Pallas kernel implements it yet: every call is ``jax.numpy``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.profiling import scope

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


def ssd_scan(
    x, delta, a, b, c, d=None, *, chunk: int = 256, segment_ids=None,
    state_segment=None, initial_state=None, return_state: bool = False,
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
    ``return_state`` the state after the last token beside it."""
    bsz, s, h, p = x.shape
    n_state = b.shape[-1]
    mmt, f32 = x.dtype, jnp.float32
    pad = -s % chunk
    n = (s + pad) // chunk
    dot = lambda spec, u, v: jnp.einsum(
        spec, u.astype(mmt), v.astype(mmt), preferred_element_type=f32
    )

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
        xs, bs, cs = cut(x), cut(b), cut(c)
        dl = cut(delta.astype(f32))  # (B, n, L, H)
        cum = jnp.cumsum(dl * a.astype(f32), axis=2)  # G, inclusive, <= 0
        # documents begun in the chunk up to each token: two tokens of a
        # chunk share a document iff they have begun as many
        doc = jnp.cumsum(cut(starts).astype(jnp.int32), axis=2)  # (B, n, L)
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

        if initial_state is None:
            initial_state = jnp.zeros((bsz, h, p, n_state), f32)
        state, entering = lax.scan(
            one_chunk, initial_state.astype(f32),
            (jnp.moveaxis(through, 1, 0), jnp.moveaxis(own, 1, 0)),
        )
        entering = jnp.moveaxis(entering, 0, 1)  # (B, n, H, P, N)
        from_start = jnp.exp(jnp.where((doc == 0)[..., None], cum, -jnp.inf))
        y = y + dot("bntk,bnhpk->bnthp", cs, entering) * from_start[..., None]
        y = y.reshape(bsz, n * chunk, h, p)[:, :s]
        if d is not None:
            y = y + d.astype(f32)[:, None] * x.astype(f32)
    return (y, state) if return_state else y
