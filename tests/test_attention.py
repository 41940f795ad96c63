"""Flash attention (interpret mode) vs the jnp reference oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparknet_tpu.ops.attention import (
    attention,
    flash_attention,
    mha_reference,
)


def rand_qkv(rng, b=2, h=2, sq=128, sk=128, d=32):
    q = jnp.asarray(rng.normal(size=(b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, sk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, sk, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    rng = np.random.default_rng(0)
    q, k, v = rand_qkv(rng, sq=256, sk=256)
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_kv_mask():
    rng = np.random.default_rng(1)
    q, k, v = rand_qkv(rng, b=2, sq=128, sk=128)
    mask = np.ones((2, 128), bool)
    mask[0, 100:] = False  # pad tail of batch row 0
    mask[1, 64:] = False
    ref = mha_reference(q, k, v, kv_mask=jnp.asarray(mask))
    out = flash_attention(q, k, v, kv_mask=jnp.asarray(mask),
                          interpret=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_offsets_match_sliced_causal():
    """Ring-attention contract: running the kernel on a KV shard with
    kv_offset must equal the corresponding slice of full causal attention
    when merged — here checked in the single-shard degenerate case: query
    shard [128:256) of a 256-seq causal attention over full KV."""
    rng = np.random.default_rng(2)
    q, k, v = rand_qkv(rng, b=1, h=1, sq=256, sk=256, d=16)
    full = mha_reference(q, k, v, causal=True)
    out = flash_attention(
        q[:, :, 128:], k, v, causal=True, q_offset=128, kv_offset=0,
        interpret=True, block_q=64, block_k=64,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full[:, :, 128:]), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    rng = np.random.default_rng(3)
    q, k, v = rand_qkv(rng, b=1, h=2, sq=128, sk=128, d=16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True,
                            block_q=64, block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
            err_msg=f"d{name} mismatch",
        )


def test_flash_grads_with_mask():
    rng = np.random.default_rng(4)
    q, k, v = rand_qkv(rng, b=2, h=1, sq=64, sk=64, d=16)
    mask = np.ones((2, 64), bool)
    mask[1, 32:] = False
    mask_j = jnp.asarray(mask)

    def lf(q, k, v):
        o = flash_attention(q, k, v, kv_mask=mask_j, interpret=True,
                            block_q=32, block_k=32)
        return jnp.sum(o * o)

    def lr(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, kv_mask=mask_j)))

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    # grads w.r.t. masked-out V rows must be exactly zero
    assert np.abs(np.asarray(gf[2])[1, :, 32:]).max() == 0.0


def test_dispatcher_cpu_uses_reference():
    rng = np.random.default_rng(5)
    q, k, v = rand_qkv(rng, sq=64, sk=64)
    out = attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mha_reference(q, k, v)), rtol=1e-6
    )


def test_flash_ragged_seq_snaps_blocks():
    """Non-128-multiple seq lens work via gcd block snapping."""
    rng = np.random.default_rng(6)
    q, k, v = rand_qkv(rng, sq=96, sk=96)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mha_reference(q, k, v)),
        rtol=2e-5, atol=2e-5,
    )


def test_resolve_blocks_never_full_axis():
    """A non-conforming length must pad-and-mask, never silently snap
    to a full-axis block (the S=32k VMEM blowup the streamed kernel
    exists to avoid)."""
    from sparknet_tpu.ops.attention import _resolve_blocks

    # S = 32k + 8: an 8-multiple whose gcd with 128 is a sliver —
    # both axes pad to lane multiples and keep full-size blocks
    pad_q, pad_k, bq, bk = _resolve_blocks(32776, 32776, 128, 128)
    assert (pad_q, pad_k, bq, bk) == (120, 120, 128, 128)
    assert (32776 + pad_q) % bq == 0 and (32776 + pad_k) % bk == 0

    # odd length: both axes pad, blocks stay at granularity
    pad_q, pad_k, bq, bk = _resolve_blocks(13, 13, 128, 128)
    assert (13 + pad_q) % 8 == 0 and (13 + pad_k) % 128 == 0
    assert bq % 8 == 0 and bk % 128 == 0

    # conforming lengths: no padding, full-size blocks
    assert _resolve_blocks(4096, 4096, 128, 128) == (0, 0, 128, 128)

    # an under-lane block request is raised to one lane tile, not
    # bounced to the full axis
    pad_q, pad_k, bq, bk = _resolve_blocks(4096, 4096, 64, 64)
    assert (bq, bk) == (64, 128)

    # awkward block requests (coprime-ish with the padded axis) must
    # still come back sublane/lane legal
    for req_q in (129, 132):
        pad_q, pad_k, bq, bk = _resolve_blocks(32776, 32776, req_q, 128)
        assert bq % 8 == 0 and (32776 + pad_q) % bq == 0, (req_q, bq)
        assert bk % 128 == 0 and (32776 + pad_k) % bk == 0


@pytest.mark.parametrize("causal", [False, True])
def test_flash_padded_lengths_match_reference(causal):
    """Odd (sub-granularity) lengths run via pad-and-mask: forward and
    grads match the reference exactly on the unpadded region."""
    rng = np.random.default_rng(11)
    q, k, v = rand_qkv(rng, b=1, h=2, sq=100, sk=77, d=32)

    def f_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(jnp.cos(o)), o

    def f_ref(q, k, v):
        o = mha_reference(q, k, v, causal=causal)
        return jnp.sum(jnp.cos(o)), o

    (_, o1), g1 = jax.value_and_grad(f_flash, (0, 1, 2), has_aux=True)(
        q, k, v
    )
    (_, o2), g2 = jax.value_and_grad(f_ref, (0, 1, 2), has_aux=True)(
        q, k, v
    )
    assert o1.shape == (1, 2, 100, 32)
    np.testing.assert_allclose(
        np.asarray(o1), np.asarray(o2), rtol=2e-5, atol=2e-5
    )
    for a, b, name in zip(g1, g2, "qkv"):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
        )


def test_flash_fully_padded_row():
    """A batch row whose kv_mask is all zero: forward exactly 0, grads
    exactly 0 (the reference path shares this contract)."""
    rng = np.random.default_rng(7)
    q, k, v = rand_qkv(rng, b=2, h=1, sq=64, sk=64, d=16)
    mask = np.ones((2, 64), bool)
    mask[1, :] = False
    mask_j = jnp.asarray(mask)

    for impl in ("flash", "reference"):
        def loss(q, k, v):
            if impl == "flash":
                o = flash_attention(q, k, v, kv_mask=mask_j, interpret=True,
                                    block_q=32, block_k=32)
            else:
                o = mha_reference(q, k, v, kv_mask=mask_j)
            return jnp.sum(jnp.sin(o)), o

        (l, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v
        )
        assert np.abs(np.asarray(o)[1]).max() == 0.0, impl
        for gi, name in zip(g, "qkv"):
            assert np.abs(np.asarray(gi)[1]).max() == 0.0, (impl, name)
            assert np.isfinite(np.asarray(gi)).all(), (impl, name)


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="in-kernel dropout PRNG only exists on real TPU hardware "
    "(interpret mode stubs prng_random_bits to 0)",
)
@pytest.mark.parametrize("rate", [0.1, 0.5])
def test_flash_dropout_keep_rate_on_hardware(rate):
    """Regression for the signed-compare keep-rate bug: with v=ones each
    output row is the (rescaled) kept attention mass, whose expectation
    is exactly 1.0 when the keep probability and 1/(1-rate) rescale are
    right.  The buggy unsigned threshold measured 0.44 at rate=0.1 and
    2.0 at rate=0.5 on v5e."""
    rng = np.random.default_rng(11)
    q, k, _ = rand_qkv(rng, b=2, h=4, sq=512, sk=512, d=64)
    v = jnp.ones_like(q)
    key = jax.random.PRNGKey(42)
    o = flash_attention(q, k, v, dropout_rate=rate, dropout_rng=key)
    mass = float(jnp.mean(o))
    assert abs(mass - 1.0) < 0.05, mass
    # determinism: same rng -> identical mask
    o2 = flash_attention(q, k, v, dropout_rate=rate, dropout_rng=key)
    assert bool(jnp.all(o == o2))
    # fwd/bwd mask consistency: dv row mass has the same expectation
    def loss(vv):
        return flash_attention(
            q, k, vv, dropout_rate=rate, dropout_rng=key
        ).astype(jnp.float32).sum()

    dv = jax.grad(loss)(jnp.asarray(rng.normal(size=q.shape), jnp.float32))
    assert abs(float(jnp.mean(dv)) - 1.0) < 0.05


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="in-kernel dropout PRNG only exists on real TPU hardware",
)
def test_flash_dropout_mask_identical_fwd_bwd_on_hardware():
    """fwd/bwd dropout-mask identity (the statistical keep-rate test
    cannot see a derivation mismatch — two different masks with the
    right rate still have the right expectations).  With v = I the
    forward output IS the dropped probability matrix p~, so dv must
    equal p~^T @ dO.  The comparison is statistical, not bitwise: the
    MXU's multi-pass bf16 f32 matmuls leave ~3e-3 noise, so the test
    asserts the dv error against the EXTRACTED mask is far below the
    error against the keep-all hypothesis (a mismatched derivation
    lands at the keep-all error scale).  S == d so the extraction
    works; h=2 exercises the head-folded path."""
    b, h, s = 1, 2, 256
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(b, h, s, s)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, s)), jnp.float32)
    eye = jnp.broadcast_to(jnp.eye(s, dtype=jnp.float32), (b, h, s, s))
    key = jax.random.PRNGKey(7)
    rate = 0.3

    p_dropped = np.asarray(
        flash_attention(q, k, eye, dropout_rate=rate, dropout_rng=key)
    )  # (b, h, s, s): row i = dropped+rescaled softmax probs of query i
    p_all = np.asarray(flash_attention(q, k, eye))  # undropped softmax

    g_out = jnp.asarray(rng.normal(size=(b, h, s, s)), jnp.float32)

    def loss(vv):
        return jnp.sum(
            flash_attention(q, k, vv, dropout_rate=rate, dropout_rng=key)
            * g_out
        )

    dv = np.asarray(jax.grad(loss)(eye))
    g_np = np.asarray(g_out)
    err_mask = np.abs(
        dv - np.einsum("bhqk,bhqd->bhkd", p_dropped, g_np)
    ).mean()
    err_keepall = np.abs(
        dv - np.einsum("bhqk,bhqd->bhkd", p_all, g_np)
    ).mean()
    # identical masks: only MXU noise remains; a derivation mismatch
    # would sit at (or above) the keep-all error scale
    assert err_mask < 1e-3, err_mask
    assert err_keepall > 5 * err_mask, (err_mask, err_keepall)


# ---------------------------------------------------------------------------
# sliding window and grouped KV heads (the banded kernels)
# ---------------------------------------------------------------------------

def _grouped_qkv(seed, b, h, hkv, sq, sk, d=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(ks[0], (b, h, sq, d)),
        jax.random.normal(ks[1], (b, hkv, sk, d)),
        jax.random.normal(ks[2], (b, hkv, sk, d)),
        jax.random.normal(ks[3], (b, h, sq, d)),
    )


_BANDED = {
    # b, h, hkv, sq, sk, window, block_q, block_k, q_offset, kv_offset
    "window_grouped_blocks_skipped": (1, 4, 2, 512, 512, 100, 64, 128, 0, 0),
    "window_plain_heads": (1, 4, 4, 512, 512, 100, 64, 128, 0, 0),
    "grouped_causal_no_window": (2, 8, 2, 512, 512, None, 128, 128, 0, 0),
    "window_grouped_offsets_longer_keys": (1, 6, 2, 512, 1024, 200, 128, 128, 256, 0),
    "window_odd_offsets_and_length": (1, 4, 2, 384, 384, 130, 64, 128, 5, 3),
    "window_of_one_block_exactly": (1, 2, 1, 512, 512, 128, 128, 128, 0, 0),
}


@pytest.mark.parametrize("case", sorted(_BANDED))
def test_window_and_grouped_kernels_match_reference_fwd_and_grads(case):
    """Forward and all three gradients of the banded kernels (interpret
    mode) against ``mha_reference(window=...)``, at block sizes where whole
    key blocks lie outside the window and are never visited."""
    b, h, hkv, sq, sk, window, bq, bk, qo, ko = _BANDED[case]
    q, k, v, ct = _grouped_qkv(0, b, h, hkv, sq, sk)
    kw = dict(causal=True, window=window, q_offset=qo, kv_offset=ko)
    flash = lambda q, k, v: flash_attention(
        q, k, v, block_q=bq, block_k=bk, interpret=True, **kw
    )
    plain = lambda q, k, v: mha_reference(q, k, v, **kw)
    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * ct), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def test_window_grid_walks_the_band_not_the_axis():
    """The last grid axis of a windowed call is as long as the band of key
    blocks a q block can touch (exact for static offsets, the worst
    alignment for traced ones), in dkv as in the forward."""
    from sparknet_tpu.ops.attention import _band_steps

    # S=8192 in blocks of 512, window 512: two key blocks a q block
    assert _band_steps(16, 0, 512, 512, 16, 511, 0) == 2
    assert _band_steps(16, 0, 512, 512, 16, 0, 511) == 2  # dkv's q blocks
    assert _band_steps(16, None, 512, 512, 16, 511, 0) == 3  # traced offsets
    assert _band_steps(16, 0, 512, 512, 16, None, 0) == 16  # causal, no window
    q, k, v, _ = _grouped_qkv(1, 1, 4, 2, 256, 512)
    traced = jax.jit(lambda qo, ko: flash_attention(
        q, k, v, causal=True, window=96, block_q=64, block_k=128,
        interpret=True, q_offset=qo, kv_offset=ko,
    ))
    for qo, ko in ((200, 0), (0, 0), (300, 37)):
        want = mha_reference(
            q, k, v, causal=True, window=96, q_offset=qo, kv_offset=ko
        )
        np.testing.assert_allclose(traced(qo, ko), want, atol=2e-5, rtol=2e-5)


def test_window_none_is_todays_call_bit_for_bit():
    """``window=None`` with equal head counts traces the plain kernels: the
    same jaxpr as a call that does not name the argument, the banded call
    not in it, and the same bits out."""
    rng = np.random.default_rng(3)
    q, k, v = rand_qkv(rng, sq=256, sk=256)
    named = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=None, interpret=True, block_q=64, block_k=128
    )
    unnamed = lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, block_q=64, block_k=128
    )
    text = str(jax.make_jaxpr(jax.grad(lambda *a: named(*a).sum(), (0, 1, 2)))(q, k, v))
    assert text == str(
        jax.make_jaxpr(jax.grad(lambda *a: unnamed(*a).sum(), (0, 1, 2)))(q, k, v)
    )
    assert "_flash_banded" not in text
    banded = str(jax.make_jaxpr(lambda *a: flash_attention(
        *a, causal=True, window=64, interpret=True, block_q=64, block_k=128
    ))(q, k, v))
    assert "_flash_banded" in banded
    np.testing.assert_array_equal(named(q, k, v), unnamed(q, k, v))
    np.testing.assert_array_equal(
        mha_reference(q, k, v, causal=True, window=None),
        mha_reference(q, k, v, causal=True),
    )


def test_window_and_grouping_refuse_what_they_do_not_do():
    q, k, v, _ = _grouped_qkv(2, 1, 4, 2, 128, 128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=16, interpret=True)
    with pytest.raises(ValueError, match="causal"):
        mha_reference(q, k, v, window=16)
    with pytest.raises(ValueError, match="H_kv"):
        mha_reference(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1))
    with pytest.raises(ValueError, match="fold"):
        flash_attention(q, k, v, causal=True, fold=4, interpret=True)
    with pytest.raises(NotImplementedError, match="dropout"):
        flash_attention(
            q, k, v, causal=True, window=16, interpret=True,
            dropout_rate=0.1, dropout_rng=jax.random.PRNGKey(0),
        )
