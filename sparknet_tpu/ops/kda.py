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

  Everything but the last three lines is computed for all the chunks of a
  call at once; those three run in a ``lax.scan`` over the chunks, which
  carries ``S`` in float32.  It is differentiable as written (``jax.grad``
  walks the scan backwards); no custom VJP, and no checkpoint: one call's
  chunk matrices live to its backward pass.  How much that is, is the
  caller's to decide: at 16 384 tokens and 32 heads of 128 a whole
  sequence's are several GB, so ``models/decoder.py``'s KDA layer passes a
  segment of the sequence at a time, each a ``jax.checkpoint``, and the
  state between them (``initial_state``, ``return_state``).

``exp(G_t - G_s)`` is never formed from ``exp(G_t) * exp(-G_s)`` over a
whole chunk: with decays as strong as ``g = -5`` a token the second factor
overflows float32 after 18 tokens.  Rows are taken ``SUB`` = 16 at a time
and both factors are referred to ``G`` at the first row of their block:
``exp(G_t - ref) <= 1`` on the row side, ``exp(ref - G_s)`` at most
``exp(15 * 5)`` on the column side, and a pair so far apart that a factor
underflows has a true weight below float32's smallest number.  The bound
that makes this safe is the caller's: ``g >= -5`` (``KDA_MIN_LOG_DECAY``).
``T`` comes from forward substitution by rows inside 16 x 16 diagonal
blocks and block merges above them, in float32: a Neumann series would
cancel catastrophically where neighbouring keys are alike.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

SUB = 16
KDA_MIN_LOG_DECAY = -5.0  # SUB * 5 = 80 < log(float32 max) = 88.7
_HIGHEST = lax.Precision.HIGHEST


def kda_recurrent(q, k, v, g, beta):
    """The recurrence token by token, in float32.  ``q``, ``k``, ``g``:
    (B, H, S, d_k); ``v``: (B, H, S, d_v); ``beta``: (B, H, S).  Returns
    ``o`` (B, H, S, d_v) float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    b, h, _, dk = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HIGHEST)

    over_time = lambda x: jnp.moveaxis(x, 2, 0)
    _, out = lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), f32),
        tuple(map(over_time, (q, k, v, g, beta))),
    )
    return jnp.moveaxis(out, 0, 2)


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


def kda_chunks(seq_len: int, chunk: int = 64) -> int:
    """Chunks :func:`kda_scan` walks, one after another, for a sequence."""
    return math.ceil(seq_len / chunk)


def kda_scan(
    q, k, v, g, beta, *, chunk: int = 64, initial_state=None,
    return_state: bool = False,
):
    """The chunked form (module header).  ``q``, ``k``: (B, H, S, d_k) and
    ``v``: (B, H, S, d_v) in the compute type, which the matrix products
    take their inputs in; ``g`` (B, H, S, d_k), the log decay in
    ``[KDA_MIN_LOG_DECAY, 0]``, and ``beta`` (B, H, S) in float32.  Any
    ``S``: the last chunk is filled with tokens that change nothing
    (``k = 0``, ``g = 0``).  The state starts at ``initial_state``
    (B, H, d_k, d_v) float32, zeros where None.  Returns ``o``
    (B, H, S, d_v) in float32, and with ``return_state`` the state after
    the last token beside it."""
    if chunk > SUB and (chunk % SUB or (chunk // SUB) & (chunk // SUB - 1)):
        raise ValueError(
            f"chunk {chunk}: at most {SUB}, or {SUB} times a power of two"
        )
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    mmt, f32 = q.dtype, jnp.float32
    sub = min(chunk, SUB)
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

    with jax.named_scope("kda.scan"):
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
