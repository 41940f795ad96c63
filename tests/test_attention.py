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


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="holds the compiled kernels, not the interpreter, to the reference",
)
@pytest.mark.parametrize("window", [None, 600], ids=["plain", "banded"])
def test_flash_value_head_narrower_than_keys_on_hardware(window):
    """q and k of 192, v of 128 (MLA's expanded form) in bfloat16 through
    the kernels the chip compiles, at their default blocks: forward, dq, dk
    and dv against ``mha_reference`` in float32 on the same rounded inputs.
    bfloat16's spacing is 2^-8 of a value: each is held to 2e-2 of the
    largest; a wrong head size, mask or scale reads 0.1 of it and more."""
    ks = jax.random.split(jax.random.PRNGKey(192), 4)
    q = jax.random.normal(ks[0], (1, 8, 2048, 192)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 8, 2048, 192)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 8, 2048, 128)).astype(jnp.bfloat16)
    ct = jax.random.normal(ks[3], (1, 8, 2048, 128))
    kw = dict(causal=True, window=window, scale=192 ** -0.5)
    total = lambda f: (lambda *a: jnp.sum(f(*a, **kw).astype(jnp.float32) * ct))
    f32 = lambda t: np.asarray(t.astype(jnp.float32))
    got = (flash_attention(q, k, v, **kw),
           *jax.grad(total(flash_attention), (0, 1, 2))(q, k, v))
    with jax.default_matmul_precision("highest"):
        exact = [x.astype(jnp.float32) for x in (q, k, v)]
        want = (mha_reference(*exact, **kw),
                *jax.grad(total(mha_reference), (0, 1, 2))(*exact))
    assert got[0].shape == (1, 8, 2048, 128) and got[0].dtype == jnp.bfloat16
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(
            f32(g), f32(w), atol=2e-2 * float(jnp.abs(w).max()), err_msg=name
        )


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
    """``window=None`` is the call that does not name the argument: the
    same jaxpr, forward and backward, and the same bits out."""
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
    np.testing.assert_array_equal(named(q, k, v), unnamed(q, k, v))
    np.testing.assert_array_equal(
        mha_reference(q, k, v, causal=True, window=None),
        mha_reference(q, k, v, causal=True),
    )


def _kernels_and_vjps(f, *args):
    """(the names of the kernels ``jax.grad`` of ``f`` traces, the
    backward rules of the custom VJPs ``f`` traces)."""
    def walk(jaxpr, eqns):
        for e in jaxpr.eqns:
            eqns.append(e)
            for p in e.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    walk(inner, eqns)
        return eqns

    grad = jax.make_jaxpr(jax.grad(lambda *a: f(*a).sum(), (0, 1, 2)))(*args)
    kernels = [e.params["name"] for e in walk(grad.jaxpr, [])
               if e.primitive.name == "pallas_call"]
    vjps = [e.params["bwd"].f for e in walk(jax.make_jaxpr(f)(*args).jaxpr, [])
            if e.primitive.name == "custom_vjp_call"]
    return kernels, vjps


@pytest.mark.parametrize("call", ["bert_key_mask", "causal_equal_heads",
                                  "grouped_heads", "documents"])
def test_every_call_traces_the_one_custom_vjp_and_the_three_kernels(call):
    """``bert_mlm``'s call (not causal, a key mask), a causal call with a KV
    head a query head, grouped KV heads and packed documents: one custom
    VJP, ``_flash``'s, and its three kernels forward, dq and dkv, no more."""
    from sparknet_tpu.ops import attention as A

    q, k, v, _ = _grouped_qkv(4, 2, 4, 2 if call == "grouped_heads" else 4, 256, 256)
    kw = {
        "bert_key_mask": dict(kv_mask=jnp.arange(256)[None, :] < jnp.asarray([[256], [200]])),
        "causal_equal_heads": dict(causal=True),
        "grouped_heads": dict(causal=True),
        "documents": dict(causal=True, segment_ids=_segments((100, 156), (256,))),
    }[call]
    kernels, vjps = _kernels_and_vjps(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=128, block_k=128, interpret=True, **kw), q, k, v)
    assert sorted(kernels) == [
        "flash_attention_dkv", "flash_attention_dq", "flash_attention_fwd"]
    assert vjps == [A._flash_vjp_bwd]


def test_window_and_grouping_refuse_what_they_do_not_do():
    q, k, v, _ = _grouped_qkv(2, 1, 4, 2, 128, 128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=16, interpret=True)
    with pytest.raises(ValueError, match="causal"):
        mha_reference(q, k, v, window=16)
    with pytest.raises(ValueError, match="H_kv"):
        mha_reference(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1))
    with pytest.raises(NotImplementedError, match="dropout"):
        flash_attention(
            q, k, v, causal=True, window=16, interpret=True,
            dropout_rate=0.1, dropout_rng=jax.random.PRNGKey(0),
        )


# ---------------------------------------------------------------------------
# the kinds of score tile a call can hold: wholly inside the causal band, on
# the diagonal, on the window's edge, dead; under a key mask (applied only if
# one was passed or keys were padded) or under none
# ---------------------------------------------------------------------------

def _case(b=1, h=2, hkv=None, sq=256, sk=256, d=32, causal=True, window=None,
          bq=128, bk=128, qo=0, ko=0, mask=None, traced=False):
    return dict(b=b, h=h, hkv=hkv or h, sq=sq, sk=sk, d=d, causal=causal,
                window=window, bq=bq, bk=bk, qo=qo, ko=ko, mask=mask,
                traced=traced)


_TILE_KINDS = {
    # every q block: interior tiles, then one on the diagonal
    "interior_and_diagonal": _case(sq=512, sk=512),
    "interior_and_diagonal_mask_passed": _case(sq=512, sk=512, mask="tail"),
    "not_causal_no_mask": _case(causal=False),
    "not_causal_mask_passed": _case(causal=False, mask="tail"),
    # window 160 over blocks of 128: diagonal, window edge, blocks skipped
    "window_edge": _case(sq=512, sk=512, window=160),
    "window_edge_mask_passed": _case(sq=512, sk=512, window=160, mask="tail"),
    # window 600 over blocks of 128: interior tiles between the two edges
    "window_interior_between_edges": _case(sq=1024, sk=1024, window=600),
    # keys 256.. : q blocks 0 and 1 lie wholly before the band (dead rows)
    "q_blocks_past_the_band": _case(sq=512, sk=512, ko=256),
    "q_blocks_past_the_band_window": _case(sq=512, sk=512, ko=256, window=160),
    "dead_rows_by_mask": _case(b=2, causal=False, mask="dead"),
    "dead_rows_by_mask_causal": _case(b=2, mask="dead"),
    "padded_keys": _case(sk=200, causal=False),
    "padded_keys_causal": _case(sq=200, sk=200),
    "grouped_heads": _case(h=4, hkv=2, sq=512, sk=512),
    "grouped_heads_not_causal": _case(h=4, hkv=2, causal=False),
    "grouped_heads_mask_passed": _case(h=4, hkv=2, mask="tail"),
    "blk_q_smaller": _case(sq=512, sk=512, bq=64, bk=128),
    "blk_q_larger": _case(sq=512, sk=512, bq=256, bk=128),
    "blk_q_larger_window": _case(sq=512, sk=512, bq=256, bk=128, window=130),
    "longer_keys": _case(sq=256, sk=512, qo=256),
    "longer_queries": _case(sq=512, sk=256, ko=128),
    # traced offsets (the ring's case): the predicate cannot fold at trace time
    "traced_diagonal_inside_tiles": _case(qo=37, ko=0, traced=True),
    "traced_diagonal_on_tile_edges": _case(qo=128, ko=0, traced=True),
    "traced_diagonal_outside": _case(qo=512, ko=0, traced=True),
    "traced_all_dead": _case(qo=0, ko=512, traced=True),
    "traced_window_inside_tiles": _case(sq=512, sk=512, qo=37, ko=5, window=160, traced=True),
    "traced_window_on_tile_edges": _case(sq=512, sk=512, qo=128, ko=0, window=128, traced=True),
    "traced_grouped_diagonal_outside": _case(h=4, hkv=2, qo=512, ko=0, traced=True),
}


def _tile_case_inputs(c, dtype=jnp.float32):
    q, k, v, ct = _grouped_qkv(7, c["b"], c["h"], c["hkv"], c["sq"], c["sk"], c["d"])
    mask = None
    if c["mask"] is not None:
        m = np.ones((c["b"], c["sk"]), bool)
        m[0, c["sk"] - 40:] = False
        if c["mask"] == "dead":
            m[1, :] = False
        mask = jnp.asarray(m)
    return tuple(t.astype(dtype) for t in (q, k, v, ct)) + (mask,)


def _flash_out_and_lse(q, k, v, mask, c, qo, ko):
    """(out, lse) of the forward kernel as ``flash_attention`` calls it."""
    from sparknet_tpu.ops import attention as A

    b, h, sq, d = q.shape
    sk = k.shape[2]
    pad_q, pad_k, bq, bk = A._resolve_blocks(sq, sk, c["bq"], c["bk"])
    if mask is None and pad_k:
        mask = jnp.ones((b, sk), bool)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad_k), (0, 0))) for t in (k, v))
    if mask is not None:
        mask = jnp.broadcast_to(
            jnp.pad(mask.astype(jnp.int8), ((0, 0), (0, pad_k)))[:, None, :],
            (b, 8, sk + pad_k),
        )
    offsets = jnp.stack([jnp.asarray(qo, jnp.int32), jnp.asarray(ko, jnp.int32),
                         jnp.asarray(0, jnp.int32)])
    out, lse = A._flash_fwd(
        q, k, v, mask, offsets, None, c["causal"], 1.0 / np.sqrt(d), bq, bk,
        True, 0.0, (c["window"], c["h"] // c["hkv"]),
        None if c["traced"] else qo - ko,
    )
    return out[:, :, :sq], lse[:, :, :sq]


@pytest.mark.parametrize("case", sorted(_TILE_KINDS))
def test_tile_kinds_match_reference_fwd_lse_and_grads(case):
    """Forward output, ``lse`` (lane-replicated, NEG_INF on dead rows) and
    dq / dk / dv against the reference, over every kind of tile the kernels
    tell apart."""
    from sparknet_tpu.ops.attention import NEG_INF, mha_reference_lse

    c = _TILE_KINDS[case]
    q, k, v, ct, mask = _tile_case_inputs(c)
    static = dict(causal=c["causal"], window=c["window"], kv_mask=mask)

    def flash(q, k, v, qo, ko):
        return flash_attention(q, k, v, block_q=c["bq"], block_k=c["bk"],
                               interpret=True, q_offset=qo, kv_offset=ko, **static)

    def both(q, k, v, qo, ko):
        out, lse = _flash_out_and_lse(q, k, v, mask, c, qo, ko)
        grads = jax.grad(lambda *a: jnp.sum(flash(*a, qo, ko) * ct), (0, 1, 2))(q, k, v)
        return flash(q, k, v, qo, ko), out, lse, grads

    if c["traced"]:
        both = jax.jit(both)
    got, out, lse, grads = both(q, k, v, c["qo"], c["ko"])
    kw = dict(static, q_offset=c["qo"], kv_offset=c["ko"])
    want, want_lse = mha_reference_lse(q, k, v, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse[..., :1]).repeat(128, -1))
    np.testing.assert_allclose(lse[..., 0], want_lse, atol=2e-5, rtol=2e-5)
    dead = np.asarray(want_lse) == NEG_INF
    if "dead" in case or "past_the_band" in case:
        assert dead.any() and (case == "traced_all_dead" or not dead.all())
    assert (np.asarray(lse[..., 0])[dead] == NEG_INF).all()
    assert (np.asarray(got)[dead] == 0).all()
    ref_grads = jax.grad(
        lambda *a: jnp.sum(mha_reference(*a, **kw) * ct), (0, 1, 2)
    )(q, k, v)
    for g, w, name in zip(grads, ref_grads, ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5, err_msg=name)


@pytest.mark.parametrize("case", [
    "interior_and_diagonal", "window_edge", "grouped_heads",
    "not_causal_mask_passed", "padded_keys_causal",
])
def test_tile_kinds_bfloat16_inputs_match_reference(case):
    """bfloat16 in, bfloat16 out, against ``mha_reference`` on the same
    inputs.  The kernels cast their operands to float32 (the chip's MXU
    then rounds them to bfloat16 again, to the bit what a cast would give:
    PERF.md §6, PR 32; the interpreter here does not), the reference hands
    q, k, v and p to its products in bfloat16.  bfloat16's spacing is 2^-8
    of a value, so a rounding moves it by at most 2^-9 of itself.  Outputs
    here are below 2 in size: rounding the output costs up to 2^-8 = 3.9e-3
    on either side, and the reference's rounded p (each probability off by
    up to 0.2 % of itself) under 4e-3 more: 2e-2 holds the forward with
    room.  The gradients (cotangents of size 1, sums over up to 512 keys,
    values up to 8) are held to 6e-2, twice bfloat16's spacing there — a
    wrong mask or a lost scale reads 0.1 and more."""
    c = _TILE_KINDS[case]
    q, k, v, ct, mask = _tile_case_inputs(c, jnp.bfloat16)
    kw = dict(causal=c["causal"], window=c["window"], kv_mask=mask,
              q_offset=c["qo"], kv_offset=c["ko"])
    flash = lambda q, k, v: flash_attention(
        q, k, v, block_q=c["bq"], block_k=c["bk"], interpret=True, **kw)
    plain = lambda q, k, v: mha_reference(q, k, v, **kw)
    f32 = lambda t: np.asarray(t.astype(jnp.float32))
    got = flash(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(f32(got), f32(plain(q, k, v)), atol=2e-2)
    loss = lambda f: (lambda *a: jnp.sum((f(*a) * ct).astype(jnp.float32)))
    for g, w, name in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                          jax.grad(loss(plain), (0, 1, 2))(q, k, v),
                          ("dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(f32(g), f32(w), atol=6e-2, err_msg=name)


def _brute_force_tile_kinds(c):
    """(unmasked, masked) tiles by building every tile's validity: the
    tiles with a visible pair are executed (one dead tile for a q block
    with none), all under a mask if the call is causal, passed a key mask
    or padded its keys."""
    from sparknet_tpu.ops.attention import _resolve_blocks

    pad_q, pad_k, bq, bk = _resolve_blocks(c["sq"], c["sk"], c["bq"], c["bk"])
    sq, sk = c["sq"] + pad_q, c["sk"] + pad_k
    qi = np.arange(sq)[:, None] + c["qo"]
    ki = np.arange(sk)[None, :] + c["ko"]
    valid = np.ones((sq, sk), bool)
    if c["causal"]:
        valid &= ki <= qi
        if c["window"] is not None:
            valid &= qi - ki < c["window"]
    executed = 0
    for i in range(sq // bq):
        seen = sum(valid[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()
                   for j in range(sk // bk))
        executed += max(seen, 1)
    if c["causal"] or c["mask"] is not None or pad_k:
        return 0, executed
    return executed, 0


@pytest.mark.parametrize(
    "case", sorted(n for n, c in _TILE_KINDS.items() if not c["traced"])
)
def test_flash_tile_kinds_counts_what_a_brute_force_count_does(case):
    from sparknet_tpu.ops.attention import flash_tile_kinds

    c = _TILE_KINDS[case]
    got = flash_tile_kinds(
        c["sq"], c["sk"], causal=c["causal"], window=c["window"],
        key_mask=c["mask"] is not None, q_offset=c["qo"], kv_offset=c["ko"],
        block_q=c["bq"], block_k=c["bk"],
    )
    assert got == _brute_force_tile_kinds(c)


def test_flash_tile_kinds_of_the_benchmark_cells():
    from sparknet_tpu.ops.attention import flash_tile_kinds

    # laguna_train_s8k: 16 q blocks of 512; a full layer's q block i walks
    # the i blocks under the diagonal and the diagonal's, a window layer's two
    assert flash_tile_kinds(8192, 8192, causal=True) == (0, 136)
    assert flash_tile_kinds(8192, 8192, causal=True, window=512) == (0, 31)
    # bert_mlm: one tile a batch-head, under the key mask
    assert flash_tile_kinds(512, 512, causal=False, key_mask=True) == (0, 1)
    assert flash_tile_kinds(512, 512, causal=False) == (1, 0)
    assert flash_tile_kinds(512, 500, causal=False) == (0, 1)  # padded keys


# ---------------------------------------------------------------------------
# packed documents: segment ids in the banded kernels
# ---------------------------------------------------------------------------

def _segments(*rows, total=None):
    """(B, S) int32 ids from each row's document lengths."""
    ids = [np.repeat(np.arange(len(r)), r) for r in rows]
    assert len({len(x) for x in ids}) == 1 and (total is None or len(ids[0]) == total)
    return jnp.asarray(np.stack(ids), jnp.int32)


_PACKED = {
    # heads, KV heads, window, blocks, the rows' document lengths (S = 512)
    "full_documents_start_mid_block": (4, 4, None, 128, [(100, 156, 256), (300, 112, 100)]),
    "full_grouped": (4, 2, None, 128, [(100, 156, 256), (300, 112, 100)]),
    "window_grouped": (4, 2, 96, 128, [(100, 156, 256), (300, 112, 100)]),
    "window_plain_heads": (2, 2, 200, 128, [(100, 156, 256), (300, 112, 100)]),
    "a_document_of_one_token": (4, 2, None, 128, [(127, 1, 1, 383), (1, 510, 1)]),
    "window_and_a_document_of_one_token": (4, 2, 64, 128, [(127, 1, 1, 383), (1, 510, 1)]),
    "a_document_a_block_and_longer_ones": (4, 1, None, 128, [(128, 128, 256), (256, 255, 1)]),
    "q_blocks_other_than_key_blocks": (4, 2, None, (64, 256), [(100, 156, 256), (300, 112, 100)]),
    "unequal_blocks_and_a_window": (4, 2, 150, (256, 128), [(100, 156, 256), (300, 112, 100)]),
}


def _packed_call(case, **over):
    h, hkv, window, blocks, rows = _PACKED[case]
    bq, bk = blocks if isinstance(blocks, tuple) else (blocks, blocks)
    seg = _segments(*rows, total=512)
    q, k, v, ct = _grouped_qkv(35, seg.shape[0], h, hkv, 512, 512)
    kw = dict(causal=True, window=window, segment_ids=seg)
    kw.update(over)
    flash = lambda q, k, v: flash_attention(
        q, k, v, block_q=bq, block_k=bk, interpret=True, **kw)
    return (q, k, v, ct), flash, (lambda q, k, v: mha_reference(q, k, v, **kw)), seg


@pytest.mark.parametrize("case", sorted(_PACKED))
def test_segment_ids_through_the_flash_kernels_match_reference_fwd_and_grads(case):
    """Documents that start mid-block, that are one token, under a window,
    over grouped KV heads: output, dq, dk and dv of the three kernels in
    interpret mode against ``mha_reference``'s dense ``seg_q == seg_k``."""
    (q, k, v, ct), flash, plain, seg = _packed_call(case)
    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=2e-5, rtol=2e-5)
    total = lambda f: (lambda *a: jnp.sum(f(*a) * ct))
    for g, w, name in zip(jax.grad(total(flash), (0, 1, 2))(q, k, v),
                          jax.grad(total(plain), (0, 1, 2))(q, k, v),
                          ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5, err_msg=name)
    # and the ids did something: without them the output is another
    assert float(jnp.abs(plain(q, k, v) - mha_reference(
        q, k, v, causal=True, window=_PACKED[case][2])).max()) > 1e-2


@pytest.mark.parametrize("blocks", [128, (64, 256)], ids=["blocks_128", "blocks_64_256"])
def test_segment_ids_at_head_size_64_with_a_scale_of_its_own(blocks):
    """A Mamba hybrid's attention layer: heads of 64, four query heads a KV
    head, scores scaled by 0.015625 (not 64^-1/2), packed documents, so the
    banded kernels at a head size under a lane tile: output, dq, dk and dv
    in interpret mode against ``mha_reference`` with the same scale."""
    bq, bk = blocks if isinstance(blocks, tuple) else (blocks, blocks)
    seg = _segments((100, 156, 256), (1, 300, 211))
    q, k, v, ct = _grouped_qkv(38, 2, 8, 2, 512, 512, d=64)
    kw = dict(causal=True, segment_ids=seg, scale=0.015625)
    flash = lambda q, k, v: flash_attention(
        q, k, v, block_q=bq, block_k=bk, interpret=True, **kw)
    plain = lambda q, k, v: mha_reference(q, k, v, **kw)
    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=2e-5, rtol=2e-5)
    total = lambda f: (lambda *a: jnp.sum(f(*a) * ct))
    for g, w, name in zip(jax.grad(total(flash), (0, 1, 2))(q, k, v),
                          jax.grad(total(plain), (0, 1, 2))(q, k, v),
                          ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5, err_msg=name)
    # the scale is the one passed: 64^-1/2 reads another output
    other = mha_reference(q, k, v, causal=True, segment_ids=seg)
    assert float(jnp.abs(other - plain(q, k, v)).max()) > 1e-2


@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("hkv", [4, 2])
def test_one_document_a_sequence_is_the_call_without_ids(window, hkv):
    q, k, v, ct = _grouped_qkv(36, 2, 4, hkv, 512, 512)
    seg = jnp.zeros((2, 512), jnp.int32) + jnp.asarray([[3], [7]])
    call = lambda **kw: flash_attention(
        q, k, v, causal=True, window=window, block_q=128, block_k=128,
        interpret=True, **kw)
    np.testing.assert_allclose(call(segment_ids=seg), call(), atol=1e-6)
    total = lambda **kw: (lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=window, block_q=128, block_k=128,
        interpret=True, **kw) * ct))
    for g, w in zip(jax.grad(total(segment_ids=seg), (0, 1, 2))(q, k, v),
                    jax.grad(total(), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, w, atol=2e-6)


def test_segment_ids_with_a_length_the_blocks_do_not_divide():
    """200 tokens: keys padded to 256 and masked, the padding a document of
    its own in the tables."""
    seg = _segments((70, 130), (1, 199))
    q, k, v, ct = _grouped_qkv(37, 2, 4, 2, 200, 200)
    kw = dict(causal=True, segment_ids=seg)
    got = flash_attention(q, k, v, interpret=True, **kw)
    np.testing.assert_allclose(got, mha_reference(q, k, v, **kw), atol=2e-5, rtol=2e-5)
    total = lambda f, **e: (lambda *a: jnp.sum(f(*a, **kw, **e) * ct))
    for g, w in zip(jax.grad(total(flash_attention, interpret=True), (0, 1, 2))(q, k, v),
                    jax.grad(total(mha_reference), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def test_segment_ids_refuse_what_they_do_not_do():
    q, k, v, _ = _grouped_qkv(38, 1, 2, 2, 256, 256)
    seg = jnp.zeros((1, 256), jnp.int32)
    with pytest.raises(ValueError, match="causal self-attention without offsets"):
        flash_attention(q, k, v, segment_ids=seg, interpret=True)
    with pytest.raises(ValueError, match="causal self-attention without offsets"):
        flash_attention(q, k, v, causal=True, q_offset=256, segment_ids=seg, interpret=True)
    with pytest.raises(ValueError, match="segment_ids"):
        flash_attention(q, k, v, causal=True, segment_ids=seg[:, :128], interpret=True)
    with pytest.raises(NotImplementedError, match="dropout"):
        flash_attention(q, k, v, causal=True, segment_ids=seg, dropout_rate=0.1,
                        dropout_rng=jax.random.PRNGKey(0), interpret=True)


def _random_rows(rng, s, n):
    cuts = np.sort(rng.choice(np.arange(1, s), size=n - 1, replace=False))
    return tuple(np.diff(np.concatenate([[0], cuts, [s]])))


@pytest.mark.parametrize("window", [None, 100, 300])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 256), (256, 128)])
def test_bands_document_bounds_hold_every_seen_pair_and_no_block_more(window, blocks):
    """``_band`` on Python ints with the documents' bounds from
    ``_document_tables``, against a brute-force mask: every pair the three
    masks leave lies in a tile the forward (and dq) band of its q block
    walks and in one the dkv band of its key block walks; the band's
    tightened end holds a seen pair (no block is walked for nothing there);
    and ``flash_tiles_documents`` counts the forward band's tiles."""
    from sparknet_tpu.ops.attention import (
        _band, _document_tables, document_spans, flash_tiles_documents,
    )

    s, (bq, bk) = 1024, blocks  # blocks the kernels would keep as given
    rng = np.random.default_rng(5)
    seg = _segments(_random_rows(rng, s, 5), _random_rows(rng, s, 2),
                    (1,) * 3 + (s - 3,), (s,))
    start, end = (np.asarray(x) for x in document_spans(seg))
    doc_end, first_kb, last_qb = (
        np.asarray(x) for x in _document_tables(seg, s, s, bq, bk))
    nq, nk = s // bq, s // bk
    first_kb, last_qb = first_kb.reshape(-1, nq), last_qb.reshape(-1, nk)
    back = None if window is None else window - 1
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    tiles = 0
    for b, ids in enumerate(np.asarray(seg)):
        np.testing.assert_array_equal(doc_end[b, 0], end[b])
        assert (ids[start[b]] == ids).all() and (ids[end[b]] == ids).all()
        seen = (j <= i) & (ids[:, None] == ids[None, :])
        if window is not None:
            seen &= i - j < window
        tile = seen.reshape(nq, bq, nk, bk).any(axis=(1, 3))  # (nq, nk)
        for qb in range(nq):
            lo, hi = _band(qb, 0, bq, bk, nk, back, 0, doc_lo=int(first_kb[b, qb]))
            assert isinstance(lo, int) and lo <= hi
            walked = np.zeros(nk, bool)
            walked[lo:hi + 1] = True
            assert not (tile[qb] & ~walked).any(), (b, qb)
            assert tile[qb, lo] and tile[qb, hi], (b, qb, lo, hi)
            tiles += hi - lo + 1
        for kb in range(nk):
            lo, hi = _band(kb, 0, bk, bq, nq, 0, back, doc_hi=int(last_qb[b, kb]))
            walked = np.zeros(nq, bool)
            walked[lo:hi + 1] = True
            assert not (tile[:, kb] & ~walked).any(), (b, kb)
            assert tile[lo, kb] and tile[hi, kb], (b, kb, lo, hi)
    counted = flash_tiles_documents(seg, window=window, block_q=bq, block_k=bk)
    assert float(counted) == tiles / seg.shape[0]
    # the last row is one document: the causal band's own count
    from sparknet_tpu.ops.attention import flash_tile_kinds
    whole = flash_tiles_documents(seg[-1:], window=window, block_q=bq, block_k=bk)
    assert float(whole) == flash_tile_kinds(
        s, s, causal=True, window=window, block_q=bq, block_k=bk)[1]


def test_flash_tiles_documents_of_the_packed_cell():
    """8192 tokens in 512-blocks: sixteen documents of one block each walk
    the diagonal alone; a batch's counter is the mean over its rows."""
    from sparknet_tpu.ops.attention import flash_tiles_documents

    blocks = _segments((512,) * 16, (8192,))
    assert float(flash_tiles_documents(blocks[:1])) == 16
    assert float(flash_tiles_documents(blocks[1:])) == 136
    assert float(flash_tiles_documents(blocks)) == (16 + 136) / 2
    assert float(flash_tiles_documents(blocks, window=1024)) == (16 + 45) / 2


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="holds the compiled kernels, not the interpreter, to the reference",
)
@pytest.mark.parametrize("window", [None, 1024], ids=["full", "window"])
def test_segment_ids_at_the_packed_cells_shapes_on_hardware(window):
    """``mellum_train_packed8k``'s call — bfloat16 ``(4, 32, 8192, 128)``
    over 4 KV heads, the packing feed's own documents — through the kernels
    the chip compiles: output, dq, dk and dv against ``mha_reference`` in
    float32 on the same rounded inputs, a batch row and a query head at a
    time (a head's dense scores are 268 MB), each held to 2e-2 of the
    largest value; a wrong bound or mask reads 0.1 of it and more."""
    from sparknet_tpu.data.text import packed_dataset, packed_feed

    b, h, hkv, s, d = 4, 32, 4, 8192, 128
    ds = packed_dataset(vocab_size=24576, n_tokens=1 << 18, seq_len=s, seed=35)
    seg = jnp.asarray(next(iter(packed_feed(ds, b, seed=35)))["segment_ids"])
    ks = jax.random.split(jax.random.PRNGKey(35), 4)
    q = jax.random.normal(ks[0], (b, h, s, d)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d)).astype(jnp.bfloat16)
    ct = jax.random.normal(ks[3], (b, h, s, d))
    kw = dict(causal=True, window=window)
    total = lambda f: (lambda q, k, v, seg, ct: jnp.sum(
        f(q, k, v, segment_ids=seg, **kw).astype(jnp.float32) * ct))
    f32 = lambda t: np.asarray(t.astype(jnp.float32))
    got = [f32(flash_attention(q, k, v, segment_ids=seg, **kw)),
           *map(f32, jax.grad(total(flash_attention), (0, 1, 2))(q, k, v, seg, ct))]

    @jax.jit
    def one_head(q1, k1, v1, seg1, ct1):
        with jax.default_matmul_precision("highest"):
            exact = [x.astype(jnp.float32) for x in (q1, k1, v1)]
            out = mha_reference(*exact, segment_ids=seg1, **kw)
            return (out, *jax.grad(total(mha_reference), (0, 1, 2))(*exact, seg1, ct1))

    want = [np.zeros(x.shape, np.float32) for x in got]
    for row in range(b):
        for head in range(h):
            kv = head // (h // hkv)
            at = np.s_[row:row + 1, head:head + 1]
            kv_at = np.s_[row:row + 1, kv:kv + 1]
            o, dq, dk, dv = one_head(q[at], k[kv_at], v[kv_at], seg[row:row + 1], ct[at])
            want[0][at], want[1][at] = np.asarray(o), np.asarray(dq)
            want[2][kv_at] += np.asarray(dk)
            want[3][kv_at] += np.asarray(dv)
    for g, w, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(
            g, w, atol=2e-2 * float(np.abs(w).max()), err_msg=name)
