"""Attention ops: reference MHA, Pallas flash attention, dispatcher.

The reference framework has no attention at all (SparkNet predates
transformers — SURVEY.md §2 notes TP/SP/ring-attention obligations come
from the task spec, not the reference). This module is the compute core
for the BERT family and the long-context path:

- :func:`mha_reference` — O(S^2)-memory jnp attention; numerics oracle
  and CPU fallback.
- :func:`flash_attention` — Pallas TPU kernel, online-softmax tiling in
  VMEM (O(S) memory), f32 accumulation, custom VJP with flash backward
  kernels. Supports causal masking, key-padding masks, and global
  position offsets (``q_offset``/``kv_offset``) so ring-attention shards
  can run the same kernel on their local slice of a longer sequence.
  ``window=w`` (with ``causal``) is a sliding window: query *i* sees keys
  ``i - w < j <= i``; key blocks wholly outside it are neither computed
  nor fetched, in the forward, dq and dkv kernels alike (the last grid
  axis walks the band of blocks a q block — or, in dkv, a key block —
  can touch, not the whole axis). Grouped-query attention: ``k``/``v``
  may carry fewer heads than ``q`` (``H_q % H_kv == 0``); query head *h*
  reads KV head ``h // (H_q / H_kv)``, addressed by group in the block
  specs (K and V are never repeated in HBM) and dk/dv summed over the
  group inside the dkv kernel. Head counts may differ from call to call.
  ``v`` may be narrower or wider than ``q`` and ``k`` (latent attention's
  expanded form: q and k of 192 = 128 + 64 rotary, v of 128): every block,
  scratch and output that holds values, the output or their gradients
  takes v's head size, the rest q's, and no product is padded.
  ``segment_ids`` (with ``causal``) keeps attention inside packed
  documents: the band of blocks a q block walks starts at the block
  holding its first row's document start (and, in dkv, a key block's ends
  at the block holding its last row's document end), from two small
  tables a call; inside a tile a key is seen while the query is not past
  the end of the key's document.
  One set of three kernels (forward, dq, dkv) and one custom VJP serve
  every call: the band is the whole key axis where no causal mask,
  window or document bounds it (the kernels then guard no step), the
  group is 1 where every query head has its own KV head, and the
  dropout rate is 0 where none was asked (dropout runs at group 1
  without a window or documents).  Per score tile the forward does its
  two products, the
  masks that apply (the key mask only if one was passed or keys were
  padded; the position mask on every tile of a causal call), one max and
  one sum across lanes, two ``exp``, and keeps its running max and sum
  as whole lane-replicated tiles (see ``_fwd_kernel``): at head size
  128 it then runs its products at the pace of the two backward kernels
  (≈ 140-150 TFLOP/s of executed work on a v5e; PERF.md §5).
- :func:`attention` — dispatcher: flash on TPU (or ``force="flash"``),
  reference elsewhere.

Layout: ``(batch, heads, seq, head_dim)`` throughout — seq in the
sublane dim and head_dim in the lane dim keeps every matmul MXU-shaped.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 512, not 128: with D=64 heads a 128-row block is a sliver of the MXU
# and per-grid-step overhead dominates (128x128 blocks read markedly
# slower at S=512 and S=8192 — measured once in round 5 on a set-up
# that no longer exists; not re-measured). 512x512 keeps VMEM small
# (the f32 score tile is 1 MB) and _resolve_blocks still shrinks to the
# largest conforming divisor for short or non-conforming sequences.
# A 512x512 tile-head costs the forward ≈ 0.97 µs for its two products
# and the softmax step between them, dq ≈ 1.37 for three, dkv ≈ 1.82 for
# four (v5e, head size 128; PERF.md §5, PR 32): per-tile work that is not
# a product is what a smaller block would multiply.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30  # large-but-finite: keeps fully-masked rows NaN-free


# ---------------------------------------------------------------------------
# Reference implementation (oracle + CPU fallback)
# ---------------------------------------------------------------------------

def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    kv_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Plain attention. q,k,v: (B,H,S,D); kv_mask: (B,Sk) True=valid.
    ``window`` (needs ``causal``): query i sees keys i - window < j <= i.
    ``k``/``v`` may have fewer heads than ``q`` (grouped-query).
    ``segment_ids`` (B, S), for q and k alike: query i sees key j only if
    both carry the same id (packed documents).

    A query row with *no* valid key (fully padded) outputs exactly zero
    and propagates zero gradients — same contract as the flash kernel.
    """
    _check_window(window, causal)
    group = _kv_group(q, k)
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    *_, sq, d = q.shape
    sk = k.shape[2]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    valid = jnp.ones((1, 1, sq, sk), bool)
    if causal:
        qi = jnp.arange(sq)[:, None] + q_offset
        ki = jnp.arange(sk)[None, :] + kv_offset
        valid = valid & (ki <= qi)[None, None]
        if window is not None:
            valid = valid & (qi - ki < window)[None, None]
    if kv_mask is not None:
        valid = valid & kv_mask[:, None, None, :].astype(bool)
    if segment_ids is not None:
        valid = valid & (
            segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        )
    logits = jnp.where(valid, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.any(valid, -1, keepdims=True), p, 0.0)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def _check_window(window, causal) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} is a causal sliding window: it needs "
            f"causal=True and window >= 1"
        )


def _kv_group(q, k) -> int:
    """Query heads per KV head (1 = plain multi-head attention)."""
    h, hkv = q.shape[1], k.shape[1]
    if h % hkv:
        raise ValueError(
            f"grouped-query attention needs H_q ({h}) % H_kv ({hkv}) == 0"
        )
    return h // hkv


def mha_reference_lse(q, k, v, **kw):
    """Reference (out, logsumexp) — for testing flash internals.  The
    kernels' contract: a query row with no valid key has ``NEG_INF``."""
    *_, d = q.shape
    scale = kw.get("scale") or 1.0 / math.sqrt(d)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, jnp.repeat(k, _kv_group(q, k), axis=1),
        preferred_element_type=jnp.float32,
    ) * scale
    valid = jnp.ones(logits.shape[-2:], bool)
    if kw.get("causal"):
        sq, sk = q.shape[2], k.shape[2]
        qi = jnp.arange(sq)[:, None] + kw.get("q_offset", 0)
        ki = jnp.arange(sk)[None, :] + kw.get("kv_offset", 0)
        valid &= ki <= qi
        if kw.get("window") is not None:
            valid &= qi - ki < kw["window"]
    kv_mask = kw.get("kv_mask")
    if kv_mask is not None:
        valid = valid & kv_mask[:, None, None, :].astype(bool)
    lse = jnp.where(
        jnp.any(valid, -1),
        jax.scipy.special.logsumexp(jnp.where(valid, logits, -jnp.inf), axis=-1),
        NEG_INF,
    )
    out = mha_reference(q, k, v, **kw)
    return out, lse


# ---------------------------------------------------------------------------
# Flash forward kernel
#
# K/V are STREAMED: the innermost grid dimension walks k-blocks, Pallas
# block-fetches each (blk_k, d) tile from HBM, and the online-softmax
# running state (acc, m, l) lives in VMEM scratch that persists across
# those grid steps.  VMEM residency is O(blk_q*d + blk_k*d) regardless
# of sequence length — S=32k runs in the same footprint as S=512.
# ---------------------------------------------------------------------------


def _band(i, shift, blk_i, blk_j, nj, lo_back, hi_fwd, doc_lo=None, doc_hi=None):
    """(first, last) block along the other axis that block ``i`` of this
    axis can touch.  Block ``i`` covers local positions ``i*blk_i ..
    i*blk_i + blk_i - 1``; a position p touches ``p + shift - lo_back ..
    p + shift + hi_fwd`` of the other axis (a bound of None is open).
    Forward and dq walk key blocks from a q block: ``shift = q_offset -
    kv_offset``, ``lo_back = window - 1`` (None without a window),
    ``hi_fwd = 0`` if causal.  dkv walks q blocks from a key block:
    ``shift = kv_offset - q_offset``, ``lo_back = 0`` if causal,
    ``hi_fwd = window - 1``.  Packed documents tighten one end: ``doc_lo``
    is the first key block a q block's documents reach back to (forward,
    dq), ``doc_hi`` the last q block a key block's documents reach (dkv),
    from ``_document_tables``; the band is the tighter of the two.  Works
    on Python ints and on traced scalars (index maps and kernels call it
    with the same arguments, so the block a kernel computes on is the block
    that was fetched)."""
    static = all(
        isinstance(x, int) for x in (i, shift, doc_lo, doc_hi) if x is not None
    )
    most, least = (max, min) if static else (jnp.maximum, jnp.minimum)
    lo = 0 if lo_back is None else (
        most(i * blk_i + shift - lo_back, 0) // blk_j
    )
    hi = nj - 1 if hi_fwd is None else least(
        most(i * blk_i + blk_i - 1 + shift + hi_fwd, 0) // blk_j, nj - 1
    )
    if doc_lo is not None:
        lo = most(lo, doc_lo)
    if doc_hi is not None:
        hi = least(hi, doc_hi)
    return lo, hi


def _band_steps(ni, shift, blk_i, blk_j, nj, lo_back, hi_fwd) -> int:
    """Static length of the last grid axis: the most blocks any block
    ``i`` touches — exact where ``shift`` is known (the offsets were
    Python ints), else (``shift`` None) the worst alignment of an interval of ``blk_i + lo_back + hi_fwd``."""
    if lo_back is None or hi_fwd is None:
        return nj
    if shift is not None:
        return max(
            1,
            max(
                hi - lo + 1
                for lo, hi in (
                    _band(i, shift, blk_i, blk_j, nj, lo_back, hi_fwd)
                    for i in range(ni)
                )
            ),
        )
    return min(nj, (blk_i + lo_back + hi_fwd - 1) // blk_j + 2)


def flash_tile_kinds(
    sq: int, sk: int, *, causal: bool, window: Optional[int] = None,
    key_mask: bool = False, q_offset: int = 0, kv_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
) -> tuple:
    """(unmasked, masked) score tiles one batch-head of a
    :func:`flash_attention` call of these shapes executes, in each of its
    kernels.  A tile is masked if a ``where`` runs over its scores: every
    executed tile of a causal call (the position mask is not told apart by
    tile: two bodies, one for tiles wholly inside the band, measured
    slower — PERF.md §6, PR 32) and of a call that passed a key mask or
    padded its keys; none of any other call.  A q block that sees no key
    at all counts one tile, which the kernels walk.  For a call
    with ``segment_ids`` this is the most it executes: what the documents
    of a batch leave of it is :func:`flash_tiles_documents`."""
    pad_q, pad_k, blk_q, blk_k = _resolve_blocks(sq, sk, block_q, block_k)
    nq, nk = (sq + pad_q) // blk_q, (sk + pad_k) // blk_k
    executed = 0
    for qi in range(nq):
        lo, hi = _band(
            qi, q_offset - kv_offset, blk_q, blk_k, nk,
            None if window is None else window - 1, 0 if causal else None,
        )
        executed += hi - lo + 1
    masked = causal or key_mask or pad_k > 0
    return (0, executed) if masked else (executed, 0)


def document_spans(segment_ids: jax.Array):
    """(start, end), both (B, S) int32: the positions of the first and the
    last token of each token's document, for ids that do not decrease
    along a sequence."""
    b, n = segment_ids.shape
    at = jnp.arange(n, dtype=jnp.int32)
    edge = segment_ids[:, 1:] != segment_ids[:, :-1]
    one = jnp.ones((b, 1), bool)
    start = jax.lax.cummax(
        jnp.where(jnp.concatenate([one, edge], axis=1), at, 0), axis=1
    )
    end = jax.lax.cummin(
        jnp.where(jnp.concatenate([edge, one], axis=1), at, n - 1),
        axis=1, reverse=True,
    )
    return start, end


def _document_tables(segment_ids, rows_q, rows_k, blk_q, blk_k):
    """What the kernels take for packed documents, from
    ``segment_ids`` (B, S) with S <= the padded lengths ``rows_q``,
    ``rows_k`` (padding is a document of its own): the keys' document
    ends, sublane-broadcast (B, 8, rows_k) as the key mask is; the first
    key block of each q block — the block holding the first token of the
    document of the q block's FIRST row, since ids do not decrease — flat
    (B * nq,); and the last q block of each key block — the block holding
    the last token of the document of the key block's LAST row — flat
    (B * nk,).  ``_band``'s lower (forward, dq) and upper (dkv) block
    become the tighter of the window's and these."""
    b, s = segment_ids.shape
    n = max(rows_q, rows_k)
    ids = jnp.pad(
        segment_ids.astype(jnp.int32), ((0, 0), (0, n - s)),
        constant_values=jnp.iinfo(jnp.int32).max,
    )
    start, end = document_spans(ids)
    first_kb = start[:, 0:rows_q:blk_q] // blk_k
    last_qb = jnp.minimum(
        end[:, blk_k - 1:rows_k:blk_k] // blk_q, rows_q // blk_q - 1
    )
    doc_end = jnp.broadcast_to(end[:, None, :rows_k], (b, 8, rows_k))
    return doc_end, first_kb.reshape(-1), last_qb.reshape(-1)


def flash_tiles_documents(
    segment_ids: jax.Array, *, window: Optional[int] = None,
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    """Score tiles a batch-head of a causal :func:`flash_attention` call
    with these ``segment_ids`` (B, S) executes in its forward and dq
    kernels (the mean over the batch's rows, float32; traceable): the
    tiles of :func:`flash_tile_kinds` that the documents' first key blocks
    leave, from the same table the kernels get.  dkv walks the transposed
    band, cut at each key block's last q block: as many tiles or a few
    more or fewer."""
    b, s = segment_ids.shape
    pad_q, pad_k, blk_q, blk_k = _resolve_blocks(s, s, block_q, block_k)
    nq, nk = (s + pad_q) // blk_q, (s + pad_k) // blk_k
    _, first_kb, _ = _document_tables(
        segment_ids, s + pad_q, s + pad_k, blk_q, blk_k
    )
    band = [
        _band(i, 0, blk_q, blk_k, nk, None if window is None else window - 1, 0)
        for i in range(nq)
    ]
    lo = jnp.maximum(
        first_kb.reshape(b, nq), jnp.asarray([lo for lo, _ in band], jnp.int32)
    )
    hi = jnp.asarray([hi for _, hi in band], jnp.int32)
    return jnp.mean(jnp.sum(jnp.maximum(hi - lo + 1, 1), axis=1).astype(jnp.float32))


def _position_keep(
    qi, kb, q_offset, kv_offset, blk_q, blk_k, window, doc_end=None
):
    """(blk_q, blk_k) bool: the pairs of tile ``(qi, kb)`` inside the
    causal band and, given ``doc_end`` (a (blk_k,) row: the position of the
    last token of each key's document), inside one document: ids do not
    decrease along a sequence, so a key at or before the query shares its
    document iff the query is not past that document's end."""
    q_pos = (
        jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        + qi * blk_q + q_offset
    )
    k_pos = (
        jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        + kb * blk_k + kv_offset
    )
    keep = k_pos <= q_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    if doc_end is not None:
        keep &= q_pos <= doc_end[None, :]
    return keep


def _table_entry(doc_refs, block):
    """This grid step's entry of the documents' flat (batch row, block)
    table — the kernel's batch row and third grid axis — or None."""
    if doc_refs is None:
        return None
    return doc_refs[0][pl.program_id(0) * pl.num_programs(2) + block]


def _mask_scores(s, kmask, keep):
    """Scores with the keys ``kmask`` (a (blk_k,) row, or None) calls
    invalid and the pairs outside ``keep`` (or None) at ``NEG_INF``."""
    if kmask is not None:
        s = jnp.where(kmask[None, :] != 0, s, NEG_INF)
    if keep is not None:
        s = jnp.where(keep, s, NEG_INF)
    return s


def _lanes(x, n: int):
    """A lane-replicated (rows, 128) array as (rows, n): whole lane
    tiles side by side, or the first ``n`` lanes."""
    if n <= 128:
        return x if n == 128 else x[:, :n]
    return jnp.concatenate([x] * (n // 128), axis=1)


def _dropout_keep(seed, rate, head_id, qi, kb, blk_q, blk_k):
    """Deterministic per-(b,h,q-block,k-block) keep mask; forward and
    both backward kernels regenerate the identical mask from the same
    coordinates.  Mosaic seeds from at most two scalars, so the
    coordinates fold into them: (seed ⊕ batch/head, q-block ⊕ k-block).
    ``head_id`` is the ABSOLUTE head index (grid head-group × fold +
    in-kernel offset) so the mask is invariant to the fold factor."""
    s1 = seed ^ (pl.program_id(0) * 65536 + head_id)
    s2 = qi * 65536 + kb
    pltpu.prng_seed(s1, s2)
    # prng_random_bits is declared int32 (uniform over the full 32-bit
    # range), and Mosaic lowers the comparison SIGNED — an unsigned
    # threshold silently gives the wrong keep rate on hardware (measured
    # keep 0.4 at rate 0.1).  Compare in the signed domain with the
    # threshold shifted by -2^31: P(bits >= t) = 1 - rate exactly.
    # (Interpret mode stubs the bits to 0, which is not random at all:
    # 0 >= t keeps everything for rate <= 0.5 and drops everything
    # above; dropout can only be validated on real hardware.)
    bits = pltpu.prng_random_bits((blk_q, blk_k))
    threshold = int(rate * 4294967296.0) - 2147483648
    threshold = min(max(threshold, -2147483648), 2147483647)
    return bits.astype(jnp.int32) >= jnp.int32(threshold)


def _whole(first, last) -> bool:
    """Whether ``_band`` gave the whole axis: Python ints, as it does
    where no causal mask, window or document table bounds a block (the
    band then starts at block 0)."""
    return isinstance(first, int) and isinstance(last, int)


def _band_block(first, step, last):
    """The block grid step ``step`` of a band addresses: ``first + step``,
    held at ``last`` past the band's end (a block already fetched, so not
    fetched again)."""
    return step if _whole(first, last) else jnp.minimum(first + step, last)


def _in_band(block, first, last, compute) -> None:
    """Run ``compute`` on a grid step whose ``block`` lies in the band
    ``first .. last``; a step past the band's end (above the diagonal,
    past a window or a document, or past the last block) computes
    nothing.  The whole axis needs no guard."""
    if _whole(first, last):
        compute()
    else:
        pl.when(block <= last)(compute)


def _fwd_kernel(
    off_ref,  # SMEM (3,), scalar-prefetched: [q_offset, kv_offset, seed]
    q_ref,    # (1, F, blk_q, d) — F query heads folded per grid step
    k_ref,    # (1, F_kv, blk_k, d) — streamed over the last grid dim
    v_ref,    # (1, F_kv, blk_k, dv)
    m_ref,    # (1, 8, blk_k) int8 kv mask block (sublane-broadcast: TPU
              # requires >=8 sublanes per block; head-independent), or
              # None: the caller passed no mask and padded no key
    o_ref,    # (1, F, blk_q, dv)
    lse_ref,  # (1, F, blk_q, 128) f32, lane-replicated
    acc_s,    # VMEM (F, blk_q, dv) f32 — running numerator per head
    m_s,      # VMEM (F, blk_q, 128) f32 — running max (lane-replicated)
    l_s,      # VMEM (F, blk_q, 128) f32 — running denominator (likewise)
    *,
    causal: bool,
    scale: float,
    nkb: int,
    total_kb: int,
    dropout_rate: float,
    fold: int,
    kv_fold: int,
    window: Optional[int],
    doc_refs=None,
):
    """Grid (b, H/F, nq, ``nkb``): the last axis walks the ``nkb`` key
    blocks, of ``total_kb``, from the first one q block ``qi`` can see
    (``_band``: all of them where no causal mask, window or document
    bounds it).  ``kv_fold`` KV heads arrive per step: ``fold`` where
    every query head has its own, else the one the step's ``fold`` query
    heads of one group read.  ``doc_refs`` (packed documents;
    ``_document_tables``): the scalar-prefetched first key block of each
    (batch row, q block), which tightens the band, and this step's
    (1, 8, blk_k) block of the keys' document ends, which masks inside a
    tile.

    What a score tile costs besides its two products sets the kernel's
    pace, and what cost most was the shape of ``m`` and ``l`` (PERF.md
    §6, PR 32: 27.4 -> 12.7 ms a full layer of ``laguna_xs2``, to the
    bit): they are read, updated and stored as whole lane-replicated
    (blk_q, 128) tiles, widened to the score tile by laying lane tiles
    side by side (``_lanes``) — never sliced to a (blk_q, 1) column and
    broadcast back across lanes.  Those slices and broadcasts, not the two
    reductions across lanes, were what the step waited on."""
    qi = pl.program_id(2)
    step = pl.program_id(3)
    blk_q, dv = q_ref.shape[2], v_ref.shape[3]
    blk_k = k_ref.shape[2]
    q_offset = off_ref[0]
    kv_offset = off_ref[1]
    first_kb, last_kb = _band(
        qi, q_offset - kv_offset, blk_q, blk_k, total_kb,
        None if window is None else window - 1, 0 if causal else None,
        doc_lo=_table_entry(doc_refs, qi),
    )
    kb = first_kb + step

    @pl.when(step == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    def compute():
        # m_ref[0, 0]: (blk_k,) int8, shared by all heads
        kmask = None if m_ref is None else m_ref[0, 0]
        causal_keep = _position_keep(
            qi, kb, q_offset, kv_offset, blk_q, blk_k, window,
            None if doc_refs is None else doc_refs[1][0, 0],
        ) if causal else None
        for hh in range(fold):
            kv = hh * kv_fold // fold
            q = q_ref[0, hh].astype(jnp.float32) * scale  # (blk_q, d)
            k_blk = k_ref[0, kv].astype(jnp.float32)
            v_blk = v_ref[0, kv].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (blk_q, blk_k)
            s = _mask_scores(s, kmask, causal_keep)
            m_prev = m_s[hh]  # (blk_q, 128) — lanes identical
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, blk_k))
            alpha = jnp.exp(m_prev - m_new)
            # l accumulates the UNdropped mass (softmax normalises
            # before dropout); only the value accumulation is masked
            l_s[hh] = alpha * l_s[hh] + jnp.sum(p, axis=1, keepdims=True)
            m_s[hh] = m_new
            if dropout_rate > 0.0:
                keep = _dropout_keep(
                    off_ref[2], dropout_rate,
                    pl.program_id(1) * fold + hh, qi, kb, blk_q, blk_k,
                )
                p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            acc_s[hh] = acc_s[hh] * _lanes(alpha, dv) + jax.lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _in_band(kb, first_kb, last_kb, compute)

    @pl.when(step == nkb - 1)
    def _finalize():
        for hh in range(fold):
            m_i = m_s[hh, :, 0:1]
            l_i = l_s[hh, :, 0:1]
            l_safe = jnp.maximum(l_i, 1e-30)
            # a query row with no valid key (m never rose above
            # NEG_INF) outputs zero, and its lse stays at NEG_INF so
            # the backward kernels' masked-p guard zeroes its grads too
            dead = m_i <= NEG_INF * 0.5
            o_ref[0, hh] = jnp.where(
                dead, 0.0, acc_s[hh] / l_safe
            ).astype(o_ref.dtype)
            lse = jnp.where(dead, NEG_INF, m_i + jnp.log(l_safe))
            lse_ref[0, hh] = jnp.broadcast_to(lse, lse_ref.shape[2:])


# ---------------------------------------------------------------------------
# Flash backward kernels (flash-2 style: dkv over k-blocks, dq over q-blocks)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(
    off_ref, q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_s, *, causal: bool, scale: float, nkb: int, total_kb: int,
    dropout_rate: float, fold: int, kv_fold: int, window: Optional[int],
    doc_refs=None,
):
    """Grid (b, H/F, nq, ``nkb``): K/V stream over the forward's band of
    key blocks, dq (per folded head) accumulates in VMEM scratch, written
    on the final step.  The keywords as in the forward."""
    qi = pl.program_id(2)
    step = pl.program_id(3)
    blk_q = q_ref.shape[2]
    blk_k = k_ref.shape[2]
    q_offset, kv_offset = off_ref[0], off_ref[1]
    first_kb, last_kb = _band(
        qi, q_offset - kv_offset, blk_q, blk_k, total_kb,
        None if window is None else window - 1, 0 if causal else None,
        doc_lo=_table_entry(doc_refs, qi),
    )
    kb = first_kb + step

    @pl.when(step == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def compute():
        kmask = None if m_ref is None else m_ref[0, 0]
        causal_keep = _position_keep(
            qi, kb, q_offset, kv_offset, blk_q, blk_k, window,
            None if doc_refs is None else doc_refs[1][0, 0],
        ) if causal else None
        for hh in range(fold):
            kv = hh * kv_fold // fold
            q = q_ref[0, hh].astype(jnp.float32) * scale
            do = do_ref[0, hh].astype(jnp.float32)
            lse = lse_ref[0, hh, :, 0:1]    # (blk_q, 1), lane-replicated
            delta = delta_ref[0, hh, :, 0:1]
            k_blk = k_ref[0, kv].astype(jnp.float32)
            v_blk = v_ref[0, kv].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            s = _mask_scores(s, kmask, causal_keep)
            # masked logits must yield p=0 even when lse is itself
            # NEG_INF (fully-padded row): exp(NEG_INF-NEG_INF) would be 1
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - lse))
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dropout_rate > 0.0:
                keep = _dropout_keep(
                    off_ref[2], dropout_rate,
                    pl.program_id(1) * fold + hh, qi, kb, blk_q, blk_k,
                )
                dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
            ds = p * (dp - delta)
            dq_s[hh] += jax.lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _in_band(kb, first_kb, last_kb, compute)

    @pl.when(step == nkb - 1)
    def _finalize():
        for hh in range(fold):
            dq_ref[0, hh] = (dq_s[hh] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    off_ref, q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_s, dv_s, *, causal: bool, scale: float, nqb: int,
    total_qb: int, band_qb: int, dropout_rate: float, fold: int,
    kv_fold: int, window: Optional[int], doc_refs=None,
):
    """Grid (b, H_kv/kv_fold, nk, ``nqb`` = R * ``band_qb``): the last
    axis walks, for each of the R blocks of ``fold`` query heads that read
    these KV heads, the ``band_qb`` q blocks, of ``total_qb``, from the
    first that can see this key block; Q/dO/lse/delta stream over it and
    dk/dv accumulate in VMEM scratch, summed over the group, written once
    on the final step.  ``doc_refs``: the scalar-prefetched last q block
    of each (batch row, key block) and this key block's document ends."""
    ki = pl.program_id(2)
    step = pl.program_id(3)
    blk_k = k_ref.shape[2]
    blk_q = q_ref.shape[2]
    q_offset, kv_offset = off_ref[0], off_ref[1]
    first_qb, last_qb = _band(
        ki, kv_offset - q_offset, blk_k, blk_q, total_qb,
        0 if causal else None, None if window is None else window - 1,
        doc_hi=_table_entry(doc_refs, ki),
    )
    qb = first_qb + step % band_qb

    @pl.when(step == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def compute():
        kmask = None if m_ref is None else m_ref[0, 0]
        causal_keep = _position_keep(
            qb, ki, q_offset, kv_offset, blk_q, blk_k, window,
            None if doc_refs is None else doc_refs[1][0, 0],
        ) if causal else None
        for hh in range(fold):
            kv = hh * kv_fold // fold
            k_blk = k_ref[0, kv].astype(jnp.float32)
            v_blk = v_ref[0, kv].astype(jnp.float32)
            q = q_ref[0, hh].astype(jnp.float32) * scale
            do = do_ref[0, hh].astype(jnp.float32)
            lse = lse_ref[0, hh, :, 0:1]   # (blk_q, 1), lane-replicated
            delta = delta_ref[0, hh, :, 0:1]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            s = _mask_scores(s, kmask, causal_keep)
            # same masked-p guard as _bwd_dq_kernel (fully-padded rows)
            p = jnp.where(
                s <= NEG_INF * 0.5, 0.0, jnp.exp(s - lse)
            )  # (blk_q, blk_k)
            if dropout_rate > 0.0:
                # mask coordinates are (q-block, k-block) — matches
                # fwd/dq; the head id is absolute and fold-invariant,
                # right at one query head a KV head (R = 1), the only
                # group dropout runs at
                keep = _dropout_keep(
                    off_ref[2], dropout_rate,
                    pl.program_id(1) * fold + hh, qb, ki, blk_q, blk_k,
                )
                p_drop = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            else:
                p_drop = p
            dv_s[kv] += jax.lax.dot_general(
                p_drop, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dropout_rate > 0.0:
                dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
            ds = p * (dp - delta)
            dk_s[kv] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _in_band(qb, first_qb, last_qb, compute)

    @pl.when(step == nqb - 1)
    def _finalize():
        # q entered the matmuls pre-scaled, so ds^T @ q carries `scale`
        for hh in range(kv_fold):
            dk_ref[0, hh] = dk_s[hh].astype(dk_ref.dtype)
            dv_ref[0, hh] = dv_s[hh].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers + custom VJP
# ---------------------------------------------------------------------------

_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _params(interpret):
    if interpret:
        return {"interpret": True}
    return {
        "interpret": False,
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS
        ),
    }


def _without_mask(kernel, in_specs, args):
    """(kernel, in_specs, args) of a call whose ``args[4]``, the key mask,
    is None — nothing was passed and no key was padded: the mask's spec
    and argument go, and the kernel finds None in its ``m_ref``'s place
    and applies no key mask.  ``in_specs`` start after the
    scalar-prefetched offsets, ``args[0]``."""
    if args[4] is not None:
        return kernel, in_specs, args
    return (
        lambda *refs, **kw: kernel(*refs[:4], None, *refs[4:], **kw),
        in_specs[:3] + in_specs[4:],
        args[:4] + args[5:],
    )


def _with_docs(kernel, in_specs, args, table, doc_end, end_spec):
    """(kernel, in_specs, args, scalar prefetches) of a call: as given
    and 1 without documents; with them ``table`` (a flat int32 bound per
    batch row and block) is prefetched beside the offsets, ``doc_end`` is
    the last input, and the kernel finds both in its ``doc_refs``."""
    if table is None:
        return kernel, in_specs, args, 1
    n = len(args) - 1  # the inputs after the offsets
    return (
        lambda off, tab, *refs: kernel(
            off, *refs[:n], *refs[n + 1:], doc_refs=(tab, refs[n])
        ),
        in_specs + [end_spec],
        (args[0], table, *args[1:], doc_end),
        2,
    )


def _fold_heads(h, group, blk_q, blk_k, d):
    """(fold, kv_fold, R): query heads and KV heads per grid step (the F
    and F_kv in the kernels' (1, F, blk, d) blocks), and the blocks of
    ``fold`` query heads per block of KV heads.  Grouped heads fold within
    one group, so that a step reads one KV head; heads with a KV head each
    fold together.  Folding amortises per-grid-step overhead — the
    round-5 fwd prototype measured ~20 % off wall-clock at BERT shapes —
    but every folded head multiplies the VMEM working set, so F is the
    largest divisor of ``h`` (of ``group``) whose estimated footprint
    (double-buffered in AND out blocks + f32 scratch + lse/delta) fits a
    14 MB budget (F=4 at BERT shapes ≈ 13.6 MB, compile- and
    bench-validated on v5e; the margin to the 16 MB VMEM is thin by design
    — Mosaic's own accounting rejects anything the estimate misses at
    compile time, not at runtime)."""
    per = (
        2 * 2 * (2 * blk_q * d + 2 * blk_k * d)   # bf16 q/do + k/v, 2x buf
        + 2 * 2 * 4 * blk_q * 128                 # f32 lse+delta in, 2x buf
        + 4 * (blk_q * d + 2 * blk_q * 128 + 2 * blk_k * d)  # scratch
        # outputs, 2x buffered: worst of fwd (o bf16 + lse f32) and
        # dkv (dk+dv bf16) ≈ their sum, kept simple and conservative
        + 2 * 2 * (blk_q * d + 2 * blk_k * d)
        + 2 * 4 * blk_q * 128
    )
    heads = h if group == 1 else group
    fold = max(1, (14 * 2**20) // per)
    while heads % fold:
        fold -= 1
    if group == 1:
        return fold, fold, 1
    return fold, 1, group // fold


def _band_specs(
    blk_q, blk_k, d, dv, fold, kv_fold, group, nkb, back, fwd, nqb
):
    """(in_specs for (q, k, v, mask), the q-shaped spec, the o-shaped
    spec, the lane-replicated spec) on a (b, h/F, nq, band) grid; what is
    scalar-prefetched (``pre``: the offsets and, with documents, the flat
    (b, ``nqb``) table of first key blocks) arrives last in every index
    map.  Step j of q block i addresses key block ``min(first + j,
    last)``."""

    def kv_block(b_, i, j, pre):
        off = pre[0]
        first, last = _band(
            i, off[0] - off[1], blk_q, blk_k, nkb, back, fwd,
            doc_lo=pre[1][b_ * nqb + i] if len(pre) > 1 else None,
        )
        return _band_block(first, j, last)

    q_rows = lambda width: pl.BlockSpec(
        (1, fold, blk_q, width), lambda b_, g, i, j, *pre: (b_, g, i, 0)
    )
    kv_rows = lambda width: pl.BlockSpec(
        (1, kv_fold, blk_k, width),
        lambda b_, g, i, j, *pre: (
            b_, g * fold // (group * kv_fold), kv_block(b_, i, j, pre), 0
        ),
    )
    mask_spec = pl.BlockSpec(
        (1, 8, blk_k),
        lambda b_, g, i, j, *pre: (b_, 0, kv_block(b_, i, j, pre)),
    )
    q_spec = q_rows(d)
    return (
        [q_spec, kv_rows(d), kv_rows(dv), mask_spec], q_spec, q_rows(dv),
        q_rows(128),
    )


def _flash_fwd(
    q, k, v, kv_mask, offsets, docs, causal, scale, blk_q, blk_k, interpret,
    dropout_rate, band, shift,
):
    """The forward call: (out, lse lane-replicated (b, h, sq, 128)).
    ``band`` = (window, group): the last grid axis walks the band of key
    blocks a q block can touch (the whole axis where no causal mask,
    window or document bounds it), and K/V are addressed by group (1:
    every query head has its own).  The offsets are scalar-prefetched;
    ``shift`` is ``q_offset - kv_offset`` where both are Python ints (an
    exact band), else None.  ``docs`` (``_document_tables``, or None):
    packed documents."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    nqb, nkb = sq // blk_q, sk // blk_k
    window, group = band
    fold, kv_fold, _ = _fold_heads(h, group, blk_q, blk_k, max(d, dv))
    back, fwd = (None if window is None else window - 1), (0 if causal else None)
    steps = _band_steps(nqb, shift, blk_q, blk_k, nkb, back, fwd)
    in_specs, _, o_spec, lane_spec = _band_specs(
        blk_q, blk_k, d, dv, fold, kv_fold, group, nkb, back, fwd, nqb
    )
    end_spec = in_specs[3]  # a row a key block, as the key mask
    kernel, in_specs, args = _without_mask(
        functools.partial(
            _fwd_kernel, causal=causal, scale=scale, nkb=steps,
            total_kb=nkb, dropout_rate=dropout_rate, fold=fold,
            kv_fold=kv_fold, window=window,
        ),
        in_specs,
        (offsets, q, k, v, kv_mask),
    )
    doc_end, first_kb, _ = docs or (None, None, None)
    kernel, in_specs, args, prefetched = _with_docs(
        kernel, in_specs, args, first_kb, doc_end, end_spec
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetched,
            grid=(b, h // fold, nqb, steps),
            in_specs=in_specs,
            out_specs=[o_spec, lane_spec],
            scratch_shapes=[
                pltpu.VMEM((fold, blk_q, dv), jnp.float32),
                pltpu.VMEM((fold, blk_q, 128), jnp.float32),
                pltpu.VMEM((fold, blk_q, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            # lane-replicated: TPU blocks need a 128-lane trailing dim
            jax.ShapeDtypeStruct((b, h, sq, 128), jnp.float32),
        ],
        name="flash_attention_fwd",
        **_params(interpret),
    )(*args)
    return out, lse


def _flash_bwd(
    q, k, v, kv_mask, offsets, do, lse, delta, *, docs, causal, scale,
    blk_q, blk_k, interpret, dropout_rate, band, shift,
):
    """The two backward calls of ``_flash_fwd``'s, reusable per ring
    block: ``lse`` and ``delta`` arrive lane-replicated (b, h, sq, 128)
    and may be the GLOBAL (ring-merged) values — p = exp(s - lse) then
    yields the exact global softmax probabilities for this kv block,
    which is what makes flash-per-block ring backward exact.  dq walks the
    forward's band of key blocks.  dkv's grid is (b, H_kv/kv_fold, nk,
    R * band): for each of the R blocks of query heads that read a block
    of KV heads, the band of q blocks that can see the key block; dk/dv
    leave summed over them.  With ``docs`` the forward's band starts, and
    dkv's ends, where the documents' bounds say."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    nqb, nkb = sq // blk_q, sk // blk_k
    window, group = band
    fold, kv_fold, reps = _fold_heads(h, group, blk_q, blk_k, max(d, dv))
    span = None if window is None else window - 1
    edge = 0 if causal else None
    static = dict(
        causal=causal, scale=scale, dropout_rate=dropout_rate, fold=fold,
        window=window, kv_fold=kv_fold,
    )

    steps = _band_steps(nqb, shift, blk_q, blk_k, nkb, span, edge)
    in_specs, q_spec, o_spec, lane_spec = _band_specs(
        blk_q, blk_k, d, dv, fold, kv_fold, group, nkb, span, edge, nqb
    )
    doc_end, first_kb, last_qb = docs or (None, None, None)
    args = (offsets, q, k, v, kv_mask, do, lse, delta)
    kernel, dq_specs, dq_args = _without_mask(
        functools.partial(_bwd_dq_kernel, nkb=steps, total_kb=nkb, **static),
        in_specs + [o_spec, lane_spec, lane_spec], args,
    )
    kernel, dq_specs, dq_args, prefetched = _with_docs(
        kernel, dq_specs, dq_args, first_kb, doc_end, in_specs[3]
    )
    dq = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetched,
            grid=(b, h // fold, nqb, steps),
            in_specs=dq_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((fold, blk_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="flash_attention_dq",
        **_params(interpret),
    )(*dq_args)

    steps = _band_steps(
        nkb, None if shift is None else -shift, blk_k, blk_q, nqb, edge, span
    )

    def q_block(b_, i, t, pre):
        off = pre[0]
        first, last = _band(
            i, off[1] - off[0], blk_k, blk_q, nqb, edge, span,
            doc_hi=pre[1][b_ * nkb + i] if len(pre) > 1 else None,
        )
        return _band_block(first, t % steps, last)

    q_rows = lambda width: pl.BlockSpec(
        (1, fold, blk_q, width),
        lambda b_, g, i, t, *pre: (
            b_, g * reps + t // steps, q_block(b_, i, t, pre), 0
        ),
    )
    kv_rows = lambda width: pl.BlockSpec(
        (1, kv_fold, blk_k, width), lambda b_, g, i, t, *pre: (b_, g, i, 0)
    )
    key_row = pl.BlockSpec((1, 8, blk_k), lambda b_, g, i, t, *pre: (b_, 0, i))
    kernel, dkv_specs, dkv_args = _without_mask(
        functools.partial(
            _bwd_dkv_kernel, nqb=reps * steps, total_qb=nqb, band_qb=steps,
            **static,
        ),
        [
            q_rows(d), kv_rows(d), kv_rows(dv), key_row,
            q_rows(dv), q_rows(128), q_rows(128),
        ],
        args,
    )
    kernel, dkv_specs, dkv_args, prefetched = _with_docs(
        kernel, dkv_specs, dkv_args, last_qb, doc_end, key_row
    )
    dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetched,
            grid=(b, h // (group * kv_fold), nkb, reps * steps),
            in_specs=dkv_specs,
            out_specs=[kv_rows(d), kv_rows(dv)],
            scratch_shapes=[
                pltpu.VMEM((kv_fold, blk_k, d), jnp.float32),
                pltpu.VMEM((kv_fold, blk_k, dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        name="flash_attention_dkv",
        **_params(interpret),
    )(*dkv_args)
    return dq, dk, dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13)
)
def _flash(
    q, k, v, kv_mask, offsets, docs, causal, scale, blk_q, blk_k, interpret,
    dropout_rate, band, shift,
):
    """``_flash_fwd``'s output, differentiable through ``_flash_bwd``;
    ``docs``: ``_document_tables``' arrays, or None."""
    return _flash_fwd(
        q, k, v, kv_mask, offsets, docs, causal, scale, blk_q, blk_k,
        interpret, dropout_rate, band, shift,
    )[0]


def _flash_vjp_fwd(*args):
    out, lse = _flash_fwd(*args)
    q, k, v, kv_mask, offsets, docs = args[:6]
    # residual keeps one lane of the lane-replicated lse — 1/128th the
    # HBM; the backward broadcasts it back transiently (like delta)
    return out, (q, k, v, kv_mask, offsets, out, lse[..., 0], docs)


def _flash_vjp_bwd(
    causal, scale, blk_q, blk_k, interpret, dropout_rate, band, shift, res,
    do,
):
    q, k, v, kv_mask, offsets, out, lse, docs = res
    b, h, sq, _ = q.shape
    lse = jnp.broadcast_to(lse[..., None], (b, h, sq, 128))
    delta = jnp.broadcast_to(
        jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32),
            axis=-1, keepdims=True,
        ),
        (b, h, sq, 128),
    )  # lane-replicated, same layout as lse
    dq, dk, dv = _flash_bwd(
        q, k, v, kv_mask, offsets, do, lse, delta, docs=docs, causal=causal,
        scale=scale, blk_q=blk_q, blk_k=blk_k, interpret=interpret,
        dropout_rate=dropout_rate, band=band, shift=shift,
    )
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Raw per-block entry points for ring attention (parallel/sequence.py):
# the ring orchestrates one fwd/bwd kernel pair per kv shard and owns
# the cross-shard online-softmax merge + custom VJP itself.
# ---------------------------------------------------------------------------


def seed_from_rng(dropout_rng) -> jax.Array:
    """int32 kernel seed from a PRNG key: last raw word, bitcast.
    (A typed-key migration — jax.random.key — must update this one
    place; flash_attention and the ring engines all route through it.)"""
    return jax.lax.bitcast_convert_type(
        jnp.asarray(dropout_rng).reshape(-1)[-1], jnp.int32
    )


def _ring_conditioning(q, k, kv_mask, block_q, block_k):
    """(kv_mask8, blk_q, blk_k) for one conforming ring block: local
    lengths must already satisfy Mosaic granularity (the ring dispatch
    falls back to the einsum path otherwise)."""
    b, _, sq, _ = q.shape
    sk = k.shape[2]
    if sq % 8 or sk % 128:
        raise ValueError(
            f"ring flash requires local S_q % 8 == 0 and S_kv % 128 == 0 "
            f"(got {sq}, {sk}); use the einsum ring for odd shards"
        )
    blk_q = math.gcd(sq, block_q)
    blk_k = math.gcd(sk, block_k)
    kv_mask8 = None if kv_mask is None else jnp.broadcast_to(
        kv_mask.astype(jnp.int8)[:, None, :], (b, 8, sk)
    )
    return kv_mask8, blk_q, blk_k


def flash_block_fwd(
    q, k, v, kv_mask, *, q_offset, kv_offset, seed, causal, scale,
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False, dropout_rate: float = 0.0,
):
    """One ring block's flash forward: (out, lse) with lse (B,H,Sq) —
    normalized over THIS kv block only; merge across blocks via
    logaddexp weights (see sequence.ring_attention's flash path).  The
    offsets are traced, so the band is the whole key axis."""
    kv_mask8, blk_q, blk_k = _ring_conditioning(
        q, k, kv_mask, block_q, block_k
    )
    offsets = jnp.stack([
        jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(kv_offset, jnp.int32),
        jnp.asarray(seed, jnp.int32),
    ])
    out, lse = _flash_fwd(
        q, k, v, kv_mask8, offsets, None, causal, scale, blk_q, blk_k,
        interpret, float(dropout_rate), (None, 1), None,
    )
    return out, lse[..., 0]


def flash_block_bwd(
    q, k, v, kv_mask, do, lse, delta, *, q_offset, kv_offset, seed,
    causal, scale, block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K, interpret: bool = False,
    dropout_rate: float = 0.0,
):
    """One ring block's flash backward given the GLOBAL merged lse and
    delta = sum(do*out) (both (B,H,Sq)): returns (dq_partial, dk, dv)
    for this kv block."""
    b, h, sq, _ = q.shape
    kv_mask8, blk_q, blk_k = _ring_conditioning(
        q, k, kv_mask, block_q, block_k
    )
    offsets = jnp.stack([
        jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(kv_offset, jnp.int32),
        jnp.asarray(seed, jnp.int32),
    ])
    lse128 = jnp.broadcast_to(lse[..., None], (b, h, sq, 128))
    delta128 = jnp.broadcast_to(delta[..., None], (b, h, sq, 128))
    return _flash_bwd(
        q, k, v, kv_mask8, offsets, do, lse128, delta128, docs=None,
        causal=causal, scale=scale, blk_q=blk_q, blk_k=blk_k,
        interpret=interpret, dropout_rate=float(dropout_rate),
        band=(None, 1), shift=None,
    )


def _resolve_blocks(sq: int, sk: int, block_q: int, block_k: int):
    """(pad_q, pad_k, block_q, block_k) for Mosaic block legality.

    The q block must be a sublane (8) multiple and the k block a lane
    (128) multiple, each dividing its (padded) axis. Rather than
    snapping a non-conforming length to a *full-axis* block — which at
    S=32k+ is exactly the VMEM blowup the streamed kernel exists to
    avoid — the axes are padded up to granularity and the requested
    blocks shrunk to the largest conforming divisor."""
    requested_q = block_q
    pad_q = -sq % 8
    pad_k = -sk % 128
    block_q = math.gcd(sq + pad_q, block_q)
    if block_q % 8:
        block_q = 8  # sq+pad_q is a sublane multiple, so 8 divides it
    if block_q < min(requested_q, 128) and sq + pad_q > 1024:
        # long sequence stuck with a sliver q-block (e.g. S=32k+8 →
        # gcd 8): pad q to a lane multiple instead — ≤127 wasted rows
        # buys taller MXU tiles. The block never exceeds requested_q
        # (the caller's VMEM bound); sub-8 requests round up to the
        # sublane minimum of 8, best-effort.
        pad_q = -sq % 128
        block_q = math.gcd(sq + pad_q, requested_q)
        if block_q % 8:
            block_q = 8  # sq+pad_q is a lane multiple, so 8 divides it
    block_k = math.gcd(sk + pad_k, block_k)
    if block_k % 128:
        block_k = 128  # sk+pad_k is a lane multiple
    return pad_q, pad_k, block_q, block_k


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    kv_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash attention on (B,H,S,D); ``k``/``v`` may be (B,H_kv,S,D) with
    ``H % H_kv == 0`` (grouped-query), ``v`` (B,H_kv,S,D_v) with a head
    size of its own (the output has it), and ``window`` (with ``causal``)
    restricts query i to keys ``i - window < j <= i`` — both described in
    the module header; neither takes dropout. Any sequence length works:
    non-conforming lengths are zero-padded up to Mosaic's block
    granularity (sublane multiple for q, lane multiple for k) with the
    padded keys masked out and the padded query rows sliced off, so the
    kernel always streams in O(block) VMEM — 128-multiples get
    full-size MXU blocks with no padding; prefer those. Offsets may be
    traced scalars — ring attention passes per-step shard offsets.

    ``segment_ids`` (B, S) int32, non-decreasing along a sequence, for q
    and k alike (causal self-attention, no offsets, no dropout): packed
    documents.  Query i sees key j only inside its own document; key
    blocks that hold only other documents are neither computed nor
    fetched, in all three kernels, as a window's are (two small int32
    tables a call, scalar-prefetched beside the offsets:
    ``_document_tables``).

    Attention-probability dropout runs inside the kernels via the TPU
    PRNG, seeded per (batch, head, q-block, k-block) so forward and both
    backward passes regenerate identical keep masks."""
    _check_window(window, causal)
    group = _kv_group(q, k)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    pad_q, pad_k, block_q, block_k = _resolve_blocks(
        sq, sk, block_q, block_k
    )
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.int8)
    elif pad_k:
        kv_mask = jnp.ones((b, sk), jnp.int8)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded keys are masked invalid: they add nothing forward, and
        # the kernels' masked-p guard zeroes their dk/dv (sliced off
        # below anyway); padded query rows only feed sliced-off outputs
        # and receive zero cotangents, so dk/dv stay exact
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad_k)))
    if kv_mask is not None:
        # sublane-broadcast for the (1, 8, blk_k) mask block spec; with
        # no mask passed and no key padded the kernels take none
        kv_mask = jnp.broadcast_to(
            kv_mask[:, None, :], (b, 8, sk + pad_k)
        )
    if dropout_rate > 0.0 and dropout_rng is not None:
        seed = seed_from_rng(dropout_rng)
    else:
        dropout_rate = 0.0
        seed = jnp.asarray(0, jnp.int32)
    offsets = jnp.stack(
        [
            jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(kv_offset, jnp.int32),
            seed,
        ]
    )
    static = isinstance(q_offset, int) and isinstance(kv_offset, int)
    docs = None
    if segment_ids is not None:
        if not (causal and sq == sk and static and q_offset == kv_offset == 0):
            raise ValueError(
                "segment_ids are for causal self-attention without offsets"
            )
        if segment_ids.shape != (b, sq):
            raise ValueError(
                f"segment_ids {segment_ids.shape} for q {q.shape}"
            )
        docs = _document_tables(
            segment_ids, sq + pad_q, sk + pad_k, block_q, block_k
        )
    if dropout_rate > 0.0 and (
        window is not None or group > 1 or docs is not None
    ):
        # the dkv kernel's dropout head id is right at group 1 only
        raise NotImplementedError(
            "attention dropout with a window, grouped KV heads or "
            "segment_ids"
        )
    out = _flash(
        q, k, v, kv_mask, offsets, docs, causal, scale, block_q, block_k,
        interpret, float(dropout_rate), (window, group),
        q_offset - kv_offset if static else None,
    )
    return out[:, :, :sq] if pad_q else out


def uses_flash(force: Optional[str] = None) -> bool:
    """Whether :func:`attention` takes the flash kernels (its ``force``)."""
    return force == "flash" or (
        force is None and jax.default_backend() == "tpu"
    )


def attention(
    q, k, v, *, causal=False, kv_mask=None, scale=None,
    q_offset=0, kv_offset=0, dropout_rate=0.0, dropout_rng=None,
    window: Optional[int] = None, segment_ids=None,
    force: Optional[str] = None, **flash_kw
):
    """Dispatch: Pallas flash on TPU, reference elsewhere.

    ``force`` = "flash" | "reference" overrides (tests, benchmarks).
    Attention-probability dropout exists on both paths; the flash
    kernels implement it via the in-kernel TPU PRNG, burned in on real
    v5e hardware (keep-rate and fwd/bwd mask-consistency measured), so
    dropout rides the flash path on TPU.
    Note the interpret-mode PRNG is stubbed to constant bits=0 (keeps
    all for rate <= 0.5, drops all above): dropout statistics are only
    meaningful on hardware.
    """
    if uses_flash(force):
        return flash_attention(
            q, k, v, causal=causal, kv_mask=kv_mask, scale=scale,
            q_offset=q_offset, kv_offset=kv_offset,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng,
            window=window, segment_ids=segment_ids, **flash_kw
        )
    return mha_reference(
        q, k, v, causal=causal, kv_mask=kv_mask, scale=scale,
        q_offset=q_offset, kv_offset=kv_offset,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng, window=window,
        segment_ids=segment_ids,
    )
