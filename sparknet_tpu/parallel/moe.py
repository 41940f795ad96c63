"""Mixture-of-Experts FFNs: two routings, which differ in what happens
to a token when an expert is full.

- :func:`moe_ffn` — **drops tokens over capacity.** Switch/GShard
  routing (softmax router, GELU experts with biases) with a fixed
  per-expert capacity ``ceil(T * top_k / E * capacity_factor)``; a slot
  past it contributes zero. All experts live here, or shard over an
  "ep" mesh axis with an all-to-all. ``models/bert.py``'s MoE variant.
- :func:`held_experts_ffn` — **drops nothing.** One chip's share of an
  expert-parallel layer: it is told which experts it holds
  (``experts_held = (first, count)`` of the router's ``num_experts``),
  routes over all of them by the ``router`` it is given
  (:func:`route_sigmoid`: sigmoid scores, the ``top_k`` largest, weights
  normalised over every chosen expert, held here or not, times
  ``routed_scale``; :func:`route_softmax`: softmax probabilities
  normalised over the chosen; :func:`route_grouped`, which chooses by
  groups of experts on biased scores), and computes the part of the
  result its own SwiGLU experts give, for every slot routed to them
  whatever the imbalance.  Nothing of it moves one index at a time
  (XLA's ``gather`` and ``scatter`` on a TPU do, at 7-9 ns an element of
  a vector and 40-90 ns a row): the routers read the chosen scores by
  comparison; the slots are put in the order of their experts, held
  ones first, by one sort that carries slot ids and weights, and their
  inverse permutation by a second (:func:`slot_tables`); the held ones'
  tokens are gathered a chunk at a time, grouped matrix products run over
  the experts held — on a TPU the Pallas kernels of
  :mod:`sparknet_tpu.ops.gmm`, which visit a chunk's live rows only,
  forward and backward (:func:`uses_gmm_kernel`; ``jax.lax.ragged_dot``
  off a TPU and where the shapes do not fit them) — and in the forward
  pass a chunk's rows are summed into token order by the Pallas kernel
  :func:`moe_combine`, which copies each tile of tokens the runs of rows
  it sent to each expert — off a TPU, or where the shapes do not fit the
  kernel (:func:`uses_combine_kernel`), and in the backward pass for the
  tokens' gradient (``_held_chunks_bwd`` says why), by a scatter-add.
  Slots of absent experts cost nothing, and nothing stands in for the
  absent chips or their traffic.  The decoder (``models/decoder.py``)
  uses it.

No reference counterpart (SURVEY.md §2: data parallelism only; EP is a
task-spec obligation). :func:`moe_ffn` in detail:

- ``top_k=1`` — Switch semantics: gate is the chosen expert's raw
  router probability.
- ``top_k>=2`` — GShard semantics: gates renormalised over the chosen
  experts; first choices win capacity over second choices.
- ``z_loss_weight`` — router z-loss (mean logsumexp² of the router
  logits) folded into the aux scalar, stabilising router magnitudes.

Two dispatch implementations, numerically identical:

- ``dispatch="dense"`` — one-hot dispatch/combine einsums, O(T·E·C)
  memory. Static shapes, MXU-friendly; best at small T·E.
- ``dispatch="sort"`` — argsort tokens by expert, position-in-expert
  via searchsorted, scatter/gather into the (E, C, h) buffer. O(T·h)
  memory; the only viable layout at realistic T and E.

Under ``shard_map`` over ``ep``, the expert weight stacks shard on
their leading (expert) axis and tokens travel to their expert's owner
via ``lax.all_to_all`` — the TPU analogue of the all-to-all dispatch in
GShard/Switch. Without an axis (``ep_axis=None``) the same code runs
single-device, which doubles as the test oracle.

Capacity-dropped tokens contribute zero from the expert path (the
caller's residual connection carries them through unchanged) — Switch
semantics.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.attention import uses_flash
from ..ops.gmm import gmm, row_tile, tgmm
from ..ops.matmul import mxu_bmm
from ..utils.profiling import scope


def init_moe_params(
    rng: jax.Array,
    hidden: int,
    ffn: int,
    num_experts: int,
    std: float = 0.02,
) -> Dict[str, jax.Array]:
    k1, k2, k3 = jax.random.split(rng, 3)
    trunc = lambda k, shape: (
        jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std
    )
    return {
        "router_w": trunc(k1, (hidden, num_experts)),
        "w_in": trunc(k2, (num_experts, hidden, ffn)),
        "b_in": jnp.zeros((num_experts, ffn), jnp.float32),
        "w_out": trunc(k3, (num_experts, ffn, hidden)),
        "b_out": jnp.zeros((num_experts, hidden), jnp.float32),
    }


def moe_pspecs(ep_axis: str = "ep"):
    from jax.sharding import PartitionSpec as P

    return {
        "router_w": P(),
        "w_in": P(ep_axis),
        "b_in": P(ep_axis),
        "w_out": P(ep_axis),
        "b_out": P(ep_axis),
    }


def _route(logits: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array]:
    """(gates, experts), both (T, K).  Switch gate for k=1, GShard
    renormalised gates for k>=2."""
    probs = jax.nn.softmax(logits, axis=-1)
    top_probs, top_idx = lax.top_k(probs, top_k)
    if top_k == 1:
        gates = top_probs
    else:
        gates = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)
    return gates, top_idx


def _dense_dispatch(xt, expert_s, gate_s, e_total, cap, top_k):
    """One-hot (S, E, C) dispatch/combine tensors; S = T*top_k slots in
    choice-major order (all first choices before all second choices, so
    first choices win capacity)."""
    t = xt.shape[0]
    onehot = jax.nn.one_hot(expert_s, e_total, dtype=jnp.float32)  # (S, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0
    pos_slot = jnp.sum(pos * onehot, axis=-1)  # (S,)
    keep = (pos_slot < cap) & (pos_slot >= 0)
    pos_oh = jax.nn.one_hot(
        jnp.where(keep, pos_slot, cap).astype(jnp.int32), cap,
        dtype=jnp.float32,
    )  # (S, C); dropped slots land outside the one-hot range -> zeros
    dispatch = onehot[:, :, None] * pos_oh[:, None, :]  # (S, E, C)
    combine = dispatch * gate_s[:, None, None]
    xs = jnp.tile(xt, (top_k, 1))  # slot s holds token s % T
    expert_in = jnp.einsum(
        "sec,sh->ech", dispatch, xs.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # (E, C, h)

    def combine_fn(y):  # y: (E, C, h) -> (T, h)
        out_slots = jnp.einsum(
            "sec,ech->sh", combine, y.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return jnp.sum(out_slots.reshape(top_k, t, -1), axis=0)

    return expert_in, combine_fn


def _sort_dispatch(xt, expert_s, gate_s, e_total, cap, top_k):
    """Sort-based dispatch: O(S log S) routing + O(E*C*h) buffer instead
    of the dense O(S*E*C) tensors.  Same slot priority as the dense
    path (stable sort over choice-major slots)."""
    t, h = xt.shape
    s = t * top_k
    order = jnp.argsort(expert_s, stable=True)  # (S,) slot ids by expert
    sorted_e = expert_s[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_total), side="left")
    pos_sorted = jnp.arange(s) - starts[sorted_e]  # position within expert
    keep = pos_sorted < cap
    dest = jnp.where(keep, sorted_e * cap + pos_sorted, e_total * cap)
    tok_sorted = order % t  # slot -> owning token (choice-major layout)
    buf = jnp.zeros((e_total * cap + 1, h), jnp.float32)
    buf = buf.at[dest].add(xt[tok_sorted].astype(jnp.float32))
    expert_in = buf[:-1].reshape(e_total, cap, h)

    def combine_fn(y):  # y: (E, C, h) -> (T, h)
        y_flat = jnp.concatenate(
            [y.reshape(e_total * cap, h), jnp.zeros((1, h), y.dtype)]
        )
        out_slots = y_flat[dest].astype(jnp.float32) * gate_s[order][:, None]
        return (
            jnp.zeros((t, h), jnp.float32).at[tok_sorted].add(out_slots)
        )

    return expert_in, combine_fn


def moe_ffn(
    x: jax.Array,
    params: Dict[str, jax.Array],
    *,
    ep_axis: Optional[str] = None,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    z_loss_weight: float = 0.0,
    dispatch: str = "dense",
    compute_dtype=jnp.float32,
):
    """MoE FFN. x: (..., T, h) flattened to tokens internally.

    Returns (out, aux) where ``out`` has x's shape (zero rows for
    capacity-dropped tokens — add the residual outside) and ``aux`` is
    the Switch load-balancing loss plus ``z_loss_weight`` times the
    router z-loss (scalar; add to the training loss with a small
    coefficient, e.g. 0.01).
    """
    if dispatch not in ("dense", "sort"):
        raise ValueError(f"dispatch {dispatch!r} (want 'dense' or 'sort')")
    orig_shape = x.shape
    h = orig_shape[-1]
    xt = x.reshape(-1, h)  # (T, h)
    t = xt.shape[0]
    e_total = params["router_w"].shape[-1]
    nep = lax.psum(1, ep_axis) if ep_axis is not None else 1
    if e_total % nep:
        raise ValueError(f"experts ({e_total}) not divisible by ep ({nep})")
    if top_k > e_total:
        raise ValueError(f"top_k ({top_k}) > experts ({e_total})")

    logits = jnp.dot(
        xt.astype(jnp.float32), params["router_w"],
        preferred_element_type=jnp.float32,
    )  # (T, E)
    gates, top_idx = _route(logits, top_k)
    # choice-major slots: all first choices, then all second choices
    expert_s = top_idx.T.reshape(-1)  # (S,)
    gate_s = gates.T.reshape(-1)

    cap = max(1, int(math.ceil(t * top_k / e_total * capacity_factor)))
    dispatch_fn = _dense_dispatch if dispatch == "dense" else _sort_dispatch
    expert_in, combine_fn = dispatch_fn(
        xt, expert_s, gate_s, e_total, cap, top_k
    )

    # Switch aux loss over first-choice assignment:
    # E * sum_e (fraction tokens to e) * (mean prob e)
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.mean(
        jax.nn.one_hot(top_idx[:, 0], e_total, dtype=jnp.float32), axis=0
    )
    mean_prob = jnp.mean(probs, axis=0)
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    z_loss = jnp.mean(jnp.square(z))
    if ep_axis is not None:
        frac = lax.pmean(frac, ep_axis)
        mean_prob = lax.pmean(mean_prob, ep_axis)
        z_loss = lax.pmean(z_loss, ep_axis)
    aux = e_total * jnp.sum(frac * mean_prob) + z_loss_weight * z_loss

    if ep_axis is not None:
        # route token groups to the experts' owners: (E, C, h) ->
        # (E/n, n*C, h); the local expert dim now matches w_in's shard
        expert_in = lax.all_to_all(
            expert_in, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )
    cdt = compute_dtype
    # mxu_bmm: per-expert (E, C, h) @ (E, h, f) at bf16 MXU rate in
    # both directions with f32 accumulation (see ops/matmul.py) — these
    # are the largest matmuls in an expert-parallel step
    y = jax.nn.gelu(
        mxu_bmm(expert_in.astype(cdt), params["w_in"].astype(cdt))
        + params["b_in"][:, None, :],
        approximate=True,
    )
    y = (
        mxu_bmm(y.astype(cdt), params["w_out"].astype(cdt))
        + params["b_out"][:, None, :]
    )
    if ep_axis is not None:
        y = lax.all_to_all(
            y, ep_axis, split_axis=1, concat_axis=0, tiled=True
        )  # back to (E, C, h) token-owner layout
    out = combine_fn(y)
    return out.reshape(orig_shape).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# One chip's share of an expert-parallel layer, without dropping a token
# ---------------------------------------------------------------------------


def init_held_experts_params(
    rng: jax.Array, hidden: int, ffn: int, num_experts: int, held: int,
    std: float = 0.02,
) -> Dict[str, jax.Array]:
    """Router over all ``num_experts``; SwiGLU weights of the ``held``
    experts: ``experts_gate_up`` (held, hidden, 2*ffn) holds gate in its
    first ``ffn`` columns and up in the rest, so one grouped product
    makes both."""
    k1, k2, k3 = jax.random.split(rng, 3)
    trunc = lambda k, shape: (
        jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std
    )
    return {
        "router_w": trunc(k1, (hidden, num_experts)),
        "experts_gate_up": trunc(k2, (held, hidden, 2 * ffn)),
        "experts_down": trunc(k3, (held, ffn, hidden)),
    }


def _chosen(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """``scores[t, idx[t, j]]``, (T, K), read by comparison: of each sum
    over the experts one term is not zero, so these are
    ``take_along_axis``'s values to the bit, and their gradient is its
    transpose's, but neither a gather forward nor a scatter backward (on a
    TPU both walk one index at a time): one fused pass over T x K x E."""
    hit = idx[..., None] == jnp.arange(scores.shape[-1])
    return jnp.sum(jnp.where(hit, scores[:, None, :], 0.0), axis=-1)


def _top_k(scores: jax.Array, chosen_on: jax.Array, top_k: int):
    """(scores of the chosen, their indices): the ``top_k`` largest of
    ``chosen_on`` a row, ties to the lower index.  The choice bears no
    gradient; the scores are read where it points (:func:`_chosen`)."""
    _, idx = lax.top_k(lax.stop_gradient(chosen_on), top_k)
    return _chosen(scores, idx), idx


def route_sigmoid(
    xt: jax.Array, router_w: jax.Array, top_k: int, routed_scale: float,
    bias: Optional[jax.Array] = None, eps: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """(weights, experts), both (T, K): sigmoid scores ``s`` in float32, the
    ``top_k`` largest (of ``s + bias`` where a ``bias`` is given),
    ``routed_scale * s / (sum(s) + eps)`` over the chosen.  The bias, as in
    :func:`route_grouped`, steers the selection only: the weights are the
    scores without it and no gradient reaches it."""
    scores = jax.nn.sigmoid(
        jnp.dot(
            xt.astype(jnp.float32), router_w,
            preferred_element_type=jnp.float32,
        )
    )
    chosen_on = scores if bias is None else lax.stop_gradient(scores + bias)
    top, idx = _top_k(scores, chosen_on, top_k)
    scaled = routed_scale * top
    total = jnp.sum(top, axis=-1, keepdims=True)
    return scaled / (total + eps if eps else total), idx


def route_softmax(
    xt: jax.Array, router_w: jax.Array, top_k: int
) -> Tuple[jax.Array, jax.Array]:
    """(weights, experts), both (T, K): ``p = softmax(xt W_r)`` over all
    the experts in float32, the ``top_k`` largest (ties to the lower
    index), ``p / sum(p)`` over the chosen (a published ``norm_topk_prob:
    true``).  No scaling factor."""
    probs = jax.nn.softmax(
        jnp.dot(
            xt.astype(jnp.float32), router_w,
            preferred_element_type=jnp.float32,
        ),
        axis=-1,
    )
    top, idx = _top_k(probs, probs, top_k)
    return top / jnp.sum(top, axis=-1, keepdims=True), idx


def route_grouped(
    xt: jax.Array, router_w: jax.Array, bias: jax.Array, top_k: int,
    routed_scale: float, n_group: int, topk_group: int,
) -> Tuple[jax.Array, jax.Array]:
    """(weights, experts), both (T, K), of a router that selects by groups:
    sigmoid scores ``s`` in float32; on ``s + bias`` the experts in
    ``n_group`` equal groups, the ``topk_group`` groups with the largest
    sum of their two best, and the ``top_k`` best experts inside those
    groups; weights ``routed_scale * s / sum(s)`` over the chosen, from
    the scores without the bias.  ``bias`` is a buffer that steers the
    selection only: no gradient reaches it.  Ties go to the lower index."""
    scores = jax.nn.sigmoid(
        jnp.dot(
            xt.astype(jnp.float32), router_w,
            preferred_element_type=jnp.float32,
        )
    )
    biased = lax.stop_gradient(scores + bias)  # the whole selection
    t, e = biased.shape
    grouped = biased.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
    _, groups = lax.top_k(group_score, topk_group)
    kept = jnp.any(groups[:, :, None] == jnp.arange(n_group), axis=1)
    top, idx = _top_k(
        scores, jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, e),
        top_k,
    )
    return routed_scale * top / jnp.sum(top, axis=-1, keepdims=True), idx


def uses_gmm_kernel(
    hidden: int, ffn: int, rows: int, force: Optional[str] = None
) -> bool:
    """Whether a chunk's grouped products run as the Pallas kernels of
    :mod:`sparknet_tpu.ops.gmm` (``force`` as :func:`uses_combine_kernel`
    has it), where ``lax.ragged_dot`` runs otherwise: hidden and expert
    widths of whole 128-lane tiles, chunks of whole row tiles."""
    fits = hidden % 128 == 0 and ffn % 128 == 0 and row_tile(rows, 1) > 0
    return fits and uses_flash(force)


def _activation(hid):
    ffn = hid.shape[1] // 2
    return jax.nn.silu(hid[:, :ffn]) * hid[:, ffn:]


def _swiglu_rows(xg, gate_up, down, sizes, cdt, gmm_kernel=None):
    """SwiGLU experts on rows grouped by expert: (R, h) -> (R, h) f32, the
    two grouped products in ``cdt`` with float32 results: by the Pallas
    kernels of :mod:`sparknet_tpu.ops.gmm` where ``gmm_kernel`` is given
    (their ``interpret``; a forward pass only: :func:`_chunk_grads_gmm` is
    its backward), else by ``lax.ragged_dot``.  Rows past ``sum(sizes)``
    belong to no expert and hold whatever the grouped product left there:
    nothing may read them unmasked."""
    if gmm_kernel is not None:
        product = functools.partial(gmm, interpret=gmm_kernel)
    else:
        product = functools.partial(
            lax.ragged_dot, preferred_element_type=jnp.float32
        )
    hid = product(xg.astype(cdt), gate_up.astype(cdt), sizes)
    return product(_activation(hid).astype(cdt), down.astype(cdt), sizes)


def _chunk_grads_gmm(
    xg, gate_up, down, wgt, sizes, dy, dgu, ddown, cdt, interpret
):
    """The gradients of :func:`_chunk_rows` on the kernels' path — of
    ``xg`` (in ``cdt``, as ``lax.ragged_dot``'s gradient rounds it), of both
    expert stacks (float32, summed by ``tgmm`` into the sums ``dgu`` and
    ``ddown`` of the chunks before, in place) and of the rows' weights —
    from the rows' cotangent ``dy``, which every product rounds to ``cdt``
    as it reads it (the caller gathers it in ``cdt`` where a chunk has more
    rows than the layer has tokens: a float32 (R, h) gather is 0.75 GB at
    mellum2's chunk, half of it more than a step's memory may grow by).
    Recomputed: the first product (its float32 ``hid``, which the
    activation's gradient reads), not the second: with ``u = dy @
    down[g].T``, the weights' gradient ``dy . y`` is ``act . u`` and the
    activation's cotangent ``wgt * u``, so the float32 (R, h) output rows
    are not formed again, nor their weighted cotangent (each 0.75 GB at
    mellum2's chunk).  Rows past ``sum(sizes)`` are read by no product;
    what they hold here is masked by the caller."""
    gu, dn = gate_up.astype(cdt), down.astype(cdt)
    hid = gmm(xg.astype(cdt), gu, sizes, interpret=interpret)
    act, act_vjp = jax.vjp(_activation, hid)
    u = gmm(dy, dn, sizes, transpose_rhs=True, interpret=interpret)
    with scope("moe.rows"):
        dw = jnp.sum(act * u, axis=1)
        dact, wact = u * wgt[:, None], (act * wgt[:, None]).astype(cdt)
    ddown = tgmm(wact, dy, sizes, ddown, interpret=interpret)
    dhid = act_vjp(dact)[0].astype(cdt)
    dgu = tgmm(xg.astype(cdt), dhid, sizes, dgu, interpret=interpret)
    dxg = gmm(
        dhid, gu, sizes, transpose_rhs=True, out_dtype=cdt, interpret=interpret
    )
    return dxg, dgu, ddown, dw


def _chunk_rows(xg, gate_up, down, wgt, live, sizes, cdt, gmm_kernel=None):
    """One chunk of sorted held slots, from their gathered tokens ``xg``:
    run their experts and weight the rows.  ``live`` marks rows that are
    slots (the last chunk's tail is not), the others are zeroed."""
    y = _swiglu_rows(xg, gate_up, down, sizes, cdt, gmm_kernel)
    with scope("moe.rows"):
        return jnp.where(live[:, None], y * wgt[:, None], 0.0)


# ---------------------------------------------------------------------------
# The slots' permutation: sorts that carry their payloads, no gather or
# scatter over a vector of slots
# ---------------------------------------------------------------------------


def _placed(order, values):
    """``out[order[i]] = values[i]`` for a permutation ``order``: one sort
    keyed by it (a scatter, one index at a time, costs ten of them)."""
    return lax.sort((order, values), num_keys=1)[1]


@jax.custom_vjp
def _sort_slots(key, weights):
    """``(key[order], order, weights[order])`` with ``order =
    argsort(key, stable)``, from one sort that carries the slot ids and
    the weights beside the key.  The weights' gradient goes back by the
    inverse permutation (:func:`_placed`); JAX's own rule for a sort's
    payload is a ``take_along_axis``, whose transpose is a scatter."""
    return _sort_slots_fwd(key, weights)[0]


def _sort_slots_fwd(key, weights):
    slot = lax.iota(jnp.int32, key.shape[0])
    sorted_ = lax.sort((key, slot, weights), num_keys=1, is_stable=True)
    return sorted_, sorted_[1]


def _sort_slots_bwd(order, cts):
    return None, _placed(order, cts[2])


_sort_slots.defvjp(_sort_slots_fwd, _sort_slots_bwd)


def slot_tables(key, weights, held: int, top_k: int):
    """The held slots' permutation, from each token-major slot's ``key``
    (its expert's index among the ``held``, or ``held`` for an expert held
    elsewhere) and weight, both (S,): ``tok`` and ``wgt`` (S,), the token
    and the weight of the r-th slot in the order of the experts, held
    slots first and a token's order kept inside an expert; ``offsets``
    (held + 1,), where each expert's slots start, the last the number
    held (a binary search of the sorted keys, which the sort gives: held +
    1 elements read a step; counted by comparison, ``sum(key[:, None] <
    arange(held + 1))``, the same numbers cost the compiled step a padded
    (S, 128) tensor, 0.15 GB at 131 072 slots); ``pos`` (S,), where in that
    order each token-major slot lies (the inverse permutation).  Two sorts
    and a search."""
    sorted_key, order, wgt = _sort_slots(key, weights)
    offsets = jnp.searchsorted(
        sorted_key, jnp.arange(held + 1), side="left"
    ).astype(jnp.int32)
    pos = _placed(order, lax.iota(jnp.int32, key.shape[0]))
    return order // top_k, wgt, offsets, pos


# ---------------------------------------------------------------------------
# Combining a chunk's rows in token order
# ---------------------------------------------------------------------------

_COMBINE_SLOTS = 1024  # slots of one tile of tokens: a 1-D SMEM block


def uses_combine_kernel(
    tokens: int, hidden: int, top_k: int, rows: int,
    force: Optional[str] = None,
) -> bool:
    """Whether a chunk's rows are combined by the Pallas kernel
    :func:`moe_combine` (``force`` as
    :func:`sparknet_tpu.ops.attention.attention` has it: "flash" the kernel
    where the shapes fit it, "reference" never, None the kernel on a TPU):
    rows of whole 128-lane tiles, chunks of whole 8-row tiles, tokens in
    whole tiles of ``_COMBINE_SLOTS`` slots."""
    fits = (
        hidden % 128 == 0 and rows % 8 == 0 and _COMBINE_SLOTS % top_k == 0
        and (tokens * top_k) % _COMBINE_SLOTS == 0
    )
    return fits and uses_flash(force)


def tile_runs(key, offsets, slots: int):
    """Where each tile of tokens, ``slots`` token-major slots, finds its
    rows among the sorted slots: ``(start, count)``, both (tiles, held).  A
    token's order is kept inside an expert, so the slots that the tokens of
    one tile send to one held expert are consecutive rows: ``count`` of
    them from ``start``.  ``key`` (S,) as :func:`slot_tables` takes it.
    (Experts lead the comparison: with them last the compiled step pads a
    tensor of slots x held to 128 lanes.)"""
    held = offsets.shape[0] - 1
    sent = jnp.arange(held)[:, None, None] == key.reshape(1, -1, slots)
    count = jnp.sum(sent, axis=2, dtype=jnp.int32).T
    return offsets[:-1] + jnp.cumsum(count, axis=0) - count, count


def _combine_plan(pos, runs, n_held, c, rows: int):
    """What :func:`moe_combine` needs of chunk ``c``: the row copies of each
    tile and each slot's row in the chunk.  A tile's run in an expert's
    rows (:func:`tile_runs`), cut to the chunk's live rows, is copied in
    whole 8-row tiles (a single row of a tiled array is no copy's to take),
    from tile ``a`` of the chunk ``n`` of them, to tile ``b`` of the
    kernel's buffer, one run after another: ``plan`` is ``a``, ``n``,
    ``b``, each (tiles * held,), in one vector.  ``rel`` (S,) is the row
    of each token-major slot in the chunk, -1 for a slot that is not held
    or not in it.  (Vectors of slots stay flat: as (T, K) the compiled step
    pads each to 128 lanes, sixteen times its size at K = 8.)"""
    lo = c * rows
    hi = jnp.minimum(lo + rows, n_held)
    first = jnp.clip(runs[0], lo, hi) - lo
    last = jnp.clip(runs[0] + runs[1], lo, hi) - lo
    a = first // 8
    n = jnp.where(last > first, (last + 7) // 8 - a, 0)
    b = jnp.cumsum(n, axis=1) - n
    rel = jnp.where((pos >= lo) & (pos < hi), pos - lo, -1)
    return jnp.concatenate([a, n, b]).reshape(-1), rel


def _combine_kernel(
    plan_ref, rel_ref, key_ref, w_ref, prev_ref, y_ref, out_ref, buf, sem, *,
    top_k, held,
):
    """One tile of tokens.  Its runs' rows from ``y`` in HBM to ``buf`` as
    the plan says, an 8-row tile a copy; then a token at a time ``prev +
    sum_j w[j] * buf[row j]`` in float32 over the slots with ``rel >= 0``,
    a slot's row in ``buf`` being its row in the chunk moved as its run's
    copies were (8 * (b - a) of the slot's expert)."""
    tile = prev_ref.shape[0]
    runs, mine = pl.num_programs(0) * held, pl.program_id(0) * held

    def row_tile(src, dst):
        return pltpu.make_async_copy(
            y_ref.at[pl.ds(pl.multiple_of(8 * src, 8), 8), :],
            buf.at[pl.ds(pl.multiple_of(8 * dst, 8), 8), :], sem,
        )

    def run(e, started):
        a, n, b = (plan_ref[k * runs + mine + e] for k in range(3))
        lax.fori_loop(
            0, n, lambda u, c: (row_tile(a + u, b + u).start(), c)[1], 0
        )
        return started + n

    started = lax.fori_loop(0, held, run, 0)
    lax.fori_loop(0, started, lambda _, c: (row_tile(0, 0).wait(), c)[1], 0)

    def token(i, carry):
        acc = prev_ref[pl.ds(i, 1), :]
        for j in range(top_k):
            slot = i * top_k + j
            rel, w = rel_ref[slot], w_ref[slot]
            e = mine + jnp.minimum(key_ref[slot], held - 1)
            at = rel + 8 * (plan_ref[2 * runs + e] - plan_ref[e])
            # the row is read wherever the branch goes (at -1 the chip
            # hung), so from inside the buffer
            row = buf[pl.ds(jnp.clip(at, 0, buf.shape[0] - 1), 1), :]
            acc = lax.cond(
                rel >= 0, lambda acc: acc + w * row, lambda acc: acc, acc
            )
        out_ref[pl.ds(i, 1), :] = acc
        return carry

    lax.fori_loop(0, tile, token, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_combine(prev, y, plan, rel, key, w, *, interpret: bool = False):
    """``prev[t] + sum_j w[t, j] * y[rel[t, j]]`` over the slots with
    ``rel >= 0``: a chunk's rows ``y`` (R, h) float32, in the order of the
    experts, summed into token order.  ``plan`` and ``rel`` are
    :func:`_combine_plan`'s, ``key`` each slot's expert among the held (as
    :func:`slot_tables` takes it), ``w`` its float32 weight, all three
    (T * K,) token-major, and ``prev`` (T, h) float32 what earlier chunks
    summed.  Reads the rows of slots (and the rest of their 8-row tiles),
    never a row past the held ones, and sums a token's slots in the order
    of its choices.  A Pallas TPU kernel (``interpret`` for tests off a
    TPU); the ``jax.numpy`` form of the same sum is the scatter-add of
    :func:`_held_chunks`."""
    t, hidden = prev.shape
    top_k = rel.shape[0] // t
    tile = _COMBINE_SLOTS // top_k
    held = plan.shape[0] // (3 * (t // tile))
    slots = pl.BlockSpec(
        (_COMBINE_SLOTS,), lambda i, plan: (i,), memory_space=pltpu.SMEM
    )
    tokens = pl.BlockSpec((tile, hidden), lambda i, plan: (i, 0))
    return pl.pallas_call(
        functools.partial(_combine_kernel, top_k=top_k, held=held),
        name="moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // tile,),
            in_specs=[
                slots, slots, slots, tokens, pl.BlockSpec(memory_space=pl.ANY)
            ],
            out_specs=tokens,
            scratch_shapes=[
                pltpu.VMEM((_COMBINE_SLOTS + 16 * held, hidden), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(prev.shape, jnp.float32),
        input_output_aliases={4: 0},
        # buf is 12-13 MB at the cells' widths, beside two tiles of tokens
        # in and out, double-buffered: over the default 16 MB
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024
        ),
        interpret=interpret,
    )(plan, rel, key, w, prev, y)


def _chunk_of(tok, wgt, offsets, n_held, c, rows):
    """Chunk ``c``'s tokens, weights, live rows and group sizes."""
    lo = c * rows
    edges = jnp.clip(offsets, lo, lo + rows)
    return (
        lax.dynamic_slice_in_dim(tok, lo, rows),
        lax.dynamic_slice_in_dim(wgt, lo, rows),
        lo + jnp.arange(rows) < n_held,
        edges[1:] - edges[:-1],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _held_chunks(
    xt, gate_up, down, tok, wgt, offsets, n_held, tokens_side, rows, cdt,
    kernel, gmm_kernel,
):
    """Sum over the held slots, ``rows`` at a time: ``out[tok[r]] +=
    wgt[r] * E(xt[tok[r]])`` for the sorted slots r < n_held, whose
    expert boundaries are ``offsets`` (held + 1,).  The static number of
    chunks covers every slot being held; a chunk past ``n_held`` is
    skipped (``lax.cond``), so time follows the slots there are and
    memory one chunk.  ``kernel`` (None, or moe_combine's ``interpret``)
    says how a chunk's rows reach their tokens in the forward pass: by
    :func:`moe_combine` from the tokens' side (``tokens_side``: the
    weights in token order, ``key`` and ``pos``, all three (T * K,), and
    :func:`tile_runs`), or by a scatter-add from the rows' side (``tok``,
    ``wgt``).  ``gmm_kernel`` (None, or the ``interpret`` of the kernels of
    :mod:`sparknet_tpu.ops.gmm`) says what computes a chunk's grouped
    products, in every pass: those kernels (:func:`_swiglu_rows` forward,
    :func:`_chunk_grads_gmm` backward), or ``lax.ragged_dot``.  The
    weights' gradient comes back in ``wgt``'s order.  Returns (out (T, h)
    f32, rows computed)."""
    return _held_chunks_fwd(
        xt, gate_up, down, tok, wgt, offsets, n_held, tokens_side, rows, cdt,
        kernel, gmm_kernel,
    )[0]


def _combine(prev, y, tokens_side, n_held, c, rows, kernel):
    """:func:`moe_combine` of chunk ``c``'s rows ``y``."""
    w, key, pos, *runs = tokens_side
    with scope("moe.rows"):
        plan, rel = _combine_plan(pos, runs, n_held, c, rows)
        return moe_combine(prev, y, plan, rel, key, w, interpret=kernel)


def _held_chunks_fwd(
    xt, gate_up, down, tok, wgt, offsets, n_held, tokens_side, rows, cdt,
    kernel, gmm_kernel,
):
    def chunk(carry, c):
        def run(carry):
            out, done = carry
            tok_c, wgt_c, live, sizes = _chunk_of(
                tok, wgt, offsets, n_held, c, rows
            )
            with scope("moe.rows"):
                xg = xt[tok_c]
            if kernel is None:
                y = _chunk_rows(
                    xg, gate_up, down, wgt_c, live, sizes, cdt, gmm_kernel
                )
                with scope("moe.rows"):
                    return out.at[tok_c].add(y), done + jnp.sum(sizes)
            y = _swiglu_rows(xg, gate_up, down, sizes, cdt, gmm_kernel)
            out = _combine(out, y, tokens_side, n_held, c, rows, kernel)
            return out, done + jnp.sum(sizes)

        return lax.cond(c * rows < n_held, run, lambda carry: carry, carry), None

    init = (jnp.zeros(xt.shape, jnp.float32), jnp.zeros((), jnp.int32))
    carry, _ = lax.scan(chunk, init, jnp.arange(tok.shape[0] // rows))
    return carry, (xt, gate_up, down, tok, wgt, offsets, n_held)


def _held_chunks_bwd(rows, cdt, kernel, gmm_kernel, res, cts):
    """The same walk backwards: a live chunk recomputes its rows and adds
    its share to dxt, to the weights' gradients and to its slots' weights;
    a skipped one passes the sums on untouched.  dxt takes XLA's
    scatter-add on every path: with :func:`moe_combine` in this pass as
    well (weights of 1) the compiled steps of two of the three
    configurations kept 0.24 and 0.34 GB more, copies of prefetched
    weights that the compiler then writes back (PERF.md section 6, PR 36),
    over the bound of their memory."""
    xt, gate_up, down, tok, wgt, offsets, n_held = res
    dout = cts[0]
    if gmm_kernel is not None and rows >= dout.shape[0]:
        # all the kernels read of it (_chunk_grads_gmm): a chunk's gather of
        # it then takes 2 bytes a value for 4, and the copy costs no more
        dout = dout.astype(cdt)

    def chunk(carry, c):
        def run(carry):
            dxt, dgu, ddown, dwgt = carry
            tok_c, wgt_c, live, sizes = _chunk_of(
                tok, wgt, offsets, n_held, c, rows
            )
            with scope("moe.rows"):
                xg, dy = xt[tok_c], dout[tok_c]
            if gmm_kernel is None:
                _, vjp = jax.vjp(
                    lambda xg, gu, dn, w: _chunk_rows(
                        xg, gu, dn, w, live, sizes, cdt
                    ),
                    xg, gate_up, down, wgt_c,
                )
                dxg, dgu_c, ddown_c, dw_c = vjp(dy)
                dgu, ddown = dgu + dgu_c, ddown + ddown_c
            else:
                dxg, dgu, ddown, dw_c = _chunk_grads_gmm(
                    xg, gate_up, down, wgt_c, sizes, dy, dgu, ddown, cdt,
                    gmm_kernel,
                )
            with scope("moe.rows"):
                dxg = jnp.where(live[:, None], dxg, 0).astype(jnp.float32)
                dxt = dxt.at[tok_c].add(dxg)
            return (
                dxt, dgu, ddown,
                lax.dynamic_update_slice_in_dim(
                    dwgt, jnp.where(live, dw_c, 0.0), c * rows, 0
                ),
            )

        return lax.cond(c * rows < n_held, run, lambda carry: carry, carry), None

    init = (
        jnp.zeros(xt.shape, jnp.float32), jnp.zeros_like(gate_up),
        jnp.zeros_like(down), jnp.zeros_like(wgt),
    )
    (dxt, dgu, ddown, dwgt), _ = lax.scan(
        chunk, init, jnp.arange(tok.shape[0] // rows)
    )
    return dxt.astype(xt.dtype), dgu, ddown, None, dwgt, None, None, None


_held_chunks.defvjp(_held_chunks_fwd, _held_chunks_bwd)


# a chunk of gathered slots: this many times the even share of the slots
CHUNK_SHARE = 1.25


def held_chunk_rows(slots: int, held: int, num_experts: int) -> int:
    """Rows of one chunk of :func:`held_experts_ffn`: ``CHUNK_SHARE`` x
    the slots an even router sends to ``held`` of ``num_experts``, in
    whole 512-row tiles of the products, and no more than all slots."""
    return min(
        512 * math.ceil(CHUNK_SHARE * slots * held / num_experts / 512),
        8 * math.ceil(slots / 8),
    )


def held_experts_ffn(
    x: jax.Array,
    params: Dict[str, jax.Array],
    *,
    experts_held: Tuple[int, int],
    top_k: int,
    router: Callable,
    chunk_rows: Optional[int] = None,
    compute_dtype=jnp.float32,
    force: Optional[str] = None,
    interpret: bool = False,
):
    """The held experts' part of a sparse FFN (module header). ``x``:
    (..., h), flattened to T tokens; ``router(xt, params) -> (weights,
    experts)``, both (T, top_k), over all the experts.  Returns ``(out,
    counters)``: ``out`` has x's shape — add the shared expert and the
    residual outside — and the counters are scalars of this call:
    ``moe_slots_held`` (slots routed to held experts; T * top_k * held /
    num_experts if the router is even), ``moe_slots_in_kernel`` (as many
    where :func:`moe_combine` sums their rows into ``out``, 0 where the
    scatter-add does), ``moe_load_max_over_mean`` (the fullest held expert
    over their mean), ``moe_slots_dropped`` (held slots that were not
    computed: 0, by construction) and ``moe_slots_in_gmm`` (as many as held
    where the Pallas kernels of :mod:`sparknet_tpu.ops.gmm` compute their
    grouped products, 0 where ``lax.ragged_dot`` does).

    The slots are put in the order of their experts by
    :func:`slot_tables`; rows are processed ``chunk_rows`` at a time
    (:func:`held_chunk_rows` where not given; tests pass small ones), in
    as many chunks as hold all T * top_k slots, and each chunk's rows are
    summed into their tokens by :func:`moe_combine` or, where
    :func:`uses_combine_kernel` says no (``force``; ``interpret`` runs the
    kernel in Pallas's interpreter), by a scatter-add.  A chunk's grouped
    products run as the kernels of :mod:`sparknet_tpu.ops.gmm`, or, where
    :func:`uses_gmm_kernel` says no (the same ``force`` and ``interpret``),
    as ``lax.ragged_dot``."""
    first, held = experts_held
    num_experts = params["router_w"].shape[-1]
    if held != params["experts_down"].shape[0]:
        raise ValueError(
            f"experts_held counts {held}, the weights hold "
            f"{params['experts_down'].shape[0]}"
        )
    if not 0 <= first <= first + held <= num_experts:
        raise ValueError(f"experts_held {experts_held} of {num_experts}")
    orig_shape = x.shape
    t = math.prod(orig_shape[:-1])
    slots = t * top_k

    with scope("moe.route"):
        xt = x.reshape(-1, orig_shape[-1])
        weights, experts = router(xt, params)
        local = experts.reshape(-1) - first  # (S,) token-major slots
        key = jnp.where((local >= 0) & (local < held), local, held)
        tok, wgt, offsets, pos = slot_tables(
            key, weights.reshape(-1), held, top_k
        )
        n_held = offsets[-1]
        rows = chunk_rows or held_chunk_rows(slots, held, num_experts)
        pad = -slots % rows
        if pad:
            tok = jnp.pad(tok, (0, pad))
            wgt = jnp.pad(wgt, (0, pad))
        kernel = tokens_side = gmm_kernel = None
        if uses_gmm_kernel(xt.shape[1], params["experts_down"].shape[1], rows, force):
            gmm_kernel = interpret
        if uses_combine_kernel(t, xt.shape[1], top_k, rows, force):
            kernel = interpret
            tokens_side = (
                lax.stop_gradient(weights).reshape(-1), key, pos,
                *tile_runs(key, offsets, _COMBINE_SLOTS),
            )
    with scope("moe.experts"):
        out, done = _held_chunks(
            xt, params["experts_gate_up"], params["experts_down"], tok, wgt,
            offsets, n_held, tokens_side, rows, compute_dtype, kernel,
            gmm_kernel,
        )
        out = out.reshape(orig_shape).astype(x.dtype)
    with scope("counters"):
        loads = (offsets[1:] - offsets[:-1]).astype(jnp.float32)
        held_f = n_held.astype(jnp.float32)
        counters = {
            "moe_slots_held": held_f,
            "moe_slots_in_kernel": (
                jnp.zeros_like(held_f) if kernel is None else held_f
            ),
            "moe_load_max_over_mean": jnp.max(loads) / jnp.maximum(
                jnp.mean(loads), 1.0 / held
            ),
            "moe_slots_dropped": (n_held - done).astype(jnp.float32),
            "moe_slots_in_gmm": (
                jnp.zeros_like(held_f) if gmm_kernel is None else held_f
            ),
        }
    return out, counters
