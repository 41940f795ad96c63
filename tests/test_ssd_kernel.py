"""The Pallas kernels of ``ops/ssd.py`` (interpret mode off a TPU) against
the ``jax.numpy`` chunked form and the token-by-token recurrence at shapes
the kernels accept: documents that begin inside a chunk, at its edge and at
the first token under another document's state, no documents at all,
strong decays, two head blocks, heads of a whole lane tile; the rounding in
bfloat16; the dispatch ``uses_kernels``; and, on the chip, the compiled
kernels at ``granite_train_packed16k``'s segment."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparknet_tpu.ops.ssd import heads_per_block, ssd_recurrent, ssd_scan, uses_kernels

NAMES = "x delta a b c d initial_state".split()
BF16_TOL = 0.02  # of the largest value: what bfloat16 operands leave either form


def _ids(*rows):
    """(B, S) int32 ids from each row's document lengths."""
    return jnp.asarray(np.stack([np.repeat(np.arange(len(r)), r) for r in rows]), jnp.int32)


def _inputs(s=256, b=2, h=4, p=64, n=128, seed=0, strong=False, dtype=jnp.float32):
    """x, delta, a, b, c, d and an entering state.  ``strong``: ``delta A``
    down to -5 a token (A = -5, delta up to 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    if strong:
        delta = jax.random.uniform(ks[1], (b, s, h), minval=0.05, maxval=1.0)
        a = jnp.full((h,), -5.0)
    else:
        delta = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
        a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    cast = lambda t: t.astype(dtype)
    return (
        cast(jax.random.normal(ks[0], (b, s, h, p))), delta, a,
        cast(0.3 * jax.random.normal(ks[3], (b, s, n))),
        cast(0.3 * jax.random.normal(ks[4], (b, s, n))),
        jax.random.normal(ks[5], (h,)),
        0.3 * jax.random.normal(ks[6], (b, h, p, n)),
    )


# (S = 256, chunks of 128) documents by row: boundaries inside chunks only;
# at a chunk's edge (128) and at 64 / 192; one row the state goes on into,
# the other the first token begins a document of its own (``state_segment``)
_CASES = {
    "inside_chunks": dict(docs=((37, 60, 100, 59), (5, 1, 200, 50)), before=(0, 0)),
    "at_chunk_edges": dict(docs=((128, 128), (64, 64, 64, 64)), before=(0, 0)),
    "new_document_at_the_first_token": dict(docs=((37, 219), (128, 128)), before=(-1, 0)),
    "no_ids": dict(docs=None),
    "strong_decay": dict(docs=((37, 91, 128), (200, 56)), before=(0, 0), strong=True),
    "two_head_blocks": dict(docs=((37, 91, 128),), before=(0,), h=16, b=1),
    "heads_of_a_lane_tile": dict(docs=((100, 156), (256,)), before=(0, 0), p=128, h=2),
}


def _case(name, dtype=jnp.float32):
    case = dict(_CASES[name])
    docs, before = case.pop("docs"), case.pop("before", None)
    inputs = _inputs(dtype=dtype, **case)
    kw = {}
    if docs is not None:
        kw = dict(segment_ids=_ids(*docs), state_segment=jnp.asarray(before, jnp.int32))
    return inputs, kw


def _scan(force, **kw):
    """``ssd_scan`` as a function of the seven inputs that returns (y,
    state after)."""
    def run(x, delta, a, b, c, d, state):
        return ssd_scan(
            x, delta, a, b, c, d, chunk=128, initial_state=state, return_state=True,
            force=force, interpret=force == "flash", **kw,
        )
    return run


def _recurrence(**kw):
    return lambda *t: ssd_recurrent(*t[:6], initial_state=t[6], return_state=True, **kw)


_head = lambda f: lambda *t: (
    lambda y, state: jnp.sum(jnp.sin(y)) + jnp.sum(jnp.cos(3 * state))
)(*f(*t))


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernels_match_the_chunked_form_and_the_recurrence(name):
    """y, the state after and the gradient of every input, the entering
    state's among them, in float32: against the ``jax.numpy`` form to
    float32's rounding of sums in another order (2e-6 of the largest
    value, gradients 1e-5), against the recurrence at ten times that.
    ``a``'s gradient is a sum over every token of terms that cancel: 1e-4
    of its largest against either, and under decays of e^-5 a token, where
    the ``jax.numpy`` form itself keeps 5e-4 of it against the recurrence
    and the kernels 2e-3, 3e-3."""
    inputs, kw = _case(name)
    assert uses_kernels(inputs[0].shape, 128, 128, "flash")
    a_tol = 3e-3 if _CASES[name].get("strong") else 1e-4
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_scan("flash", **kw))(*inputs)
        grads = jax.jit(jax.grad(_head(_scan("flash", **kw)), range(7)))(*inputs)
        for oracle, tol in ((_scan("reference", **kw), 2e-6), (_recurrence(**kw), 2e-5)):
            want = jax.jit(oracle)(*inputs)
            for a, w in zip(got, want):
                assert a.shape == w.shape and bool(jnp.all(jnp.isfinite(a)))
                np.testing.assert_allclose(a, w, atol=tol * max(1.0, float(jnp.abs(w).max())))
            wants = jax.jit(jax.grad(_head(oracle), range(7)))(*inputs)
            for label, a, w in zip(NAMES, grads, wants):
                assert bool(jnp.all(jnp.isfinite(a))), label  # no NaN where a decay is cut
                np.testing.assert_allclose(
                    a, w, atol=(a_tol if label == "a" else 5 * tol) * float(jnp.abs(w).max()),
                    err_msg=label)


def test_kernels_round_their_products_as_the_chunked_form_does():
    """In bfloat16 the kernels and the ``jax.numpy`` form round the same
    operands: y and the state agree far inside what bfloat16 costs either
    of them against the float32 recurrence, and every gradient within
    ``BF16_TOL`` (the kernels keep the decays' gradient in float32, the
    ``jax.numpy`` form rounds it)."""
    inputs, kw = _case("inside_chunks", dtype=jnp.bfloat16)
    got = jax.jit(_scan("flash", **kw))(*inputs)
    same = jax.jit(_scan("reference", **kw))(*inputs)
    exact = jax.jit(_recurrence(**kw))(*inputs)
    for a, w, e in zip(got, same, exact):
        cost = float(jnp.abs(w - e).max())
        assert cost > 0 and float(jnp.abs(a - w).max()) < 0.5 * cost
    grads = jax.jit(jax.grad(_head(_scan("flash", **kw)), range(7)))(*inputs)
    wants = jax.jit(jax.grad(_head(_scan("reference", **kw)), range(7)))(*inputs)
    for label, a, w in zip(NAMES, grads, wants):
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(a - w).max()) <= BF16_TOL * float(jnp.abs(w).max()), label


def test_the_rule_takes_the_kernels_only_where_they_fit():
    """Off a TPU nothing forced is the ``jax.numpy`` form, "reference"
    never the kernels; a head block, ``d_state`` or a chunk that is no
    whole lane tile, a ragged last chunk or heads that straddle a tile take
    the ``jax.numpy`` form whatever is forced, and give its numbers."""
    fits = ((1, 2048, 64, 64), 128, 256)
    assert uses_kernels(*fits, "flash") and not uses_kernels(*fits, "reference")
    assert uses_kernels(*fits, None) == (jax.default_backend() == "tpu")
    assert heads_per_block(64, 64) == 8 and heads_per_block(2, 128) == 2
    for x_shape, n_state, chunk in [
        ((1, 2048, 3, 64), 128, 256),  # three heads of 64: no whole tile
        ((1, 2048, 64, 48), 128, 256),  # heads of 48 straddle tiles
        ((1, 2048, 64, 64), 64, 256),  # d_state under a tile
        ((1, 2048, 64, 64), 128, 64),  # a chunk under a tile
        ((1, 2000, 64, 64), 128, 256),  # a ragged last chunk
    ]:
        assert not uses_kernels(x_shape, n_state, chunk, "flash")
    inputs = _inputs(s=64, h=4, p=16, n=8)
    ids = _ids((20, 44), (64,))
    np.testing.assert_array_equal(  # no interpret: a kernel would not run here
        ssd_scan(*inputs[:6], chunk=16, segment_ids=ids, force="flash"),
        ssd_scan(*inputs[:6], chunk=16, segment_ids=ids, force="reference"),
    )


# ------------------------------------------------------------- on the chip

@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="the compiled kernels need a TPU"
)
def test_compiled_kernels_at_the_cell_s_segment_on_hardware():
    """One segment of ``granite_train_packed16k`` (2048 tokens, 64 heads of
    64, state 128, chunks of 256, bfloat16, a packed segment's ids),
    compiled: y, the state and every gradient against the ``jax.numpy``
    form, within ``BF16_TOL`` of the largest value.  The tensors are the
    jitted functions' arguments: closed over, they are compiled in."""
    inputs = _inputs(s=2048, b=1, h=64, p=64, n=128, dtype=jnp.bfloat16)
    ids = _ids((300, 17, 900, 256, 575))
    kw = dict(segment_ids=ids, state_segment=jnp.asarray([0], jnp.int32))
    run = lambda force: functools.partial(
        lambda *t, force: ssd_scan(*t[:6], chunk=256, initial_state=t[6],
                                   return_state=True, force=force, **kw), force=force)
    got, want = jax.jit(run("flash"))(*inputs), jax.jit(run("reference"))(*inputs)
    grads = jax.jit(jax.grad(_head(run("flash")), range(7)))(*inputs)
    wants = jax.jit(jax.grad(_head(run("reference")), range(7)))(*inputs)
    for label, a, w in zip(["y", "state"] + NAMES, got + grads, want + wants):
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(a - w).max()) <= BF16_TOL * float(jnp.abs(w).max()), label
