"""Sequence parallelism (ring + Ulysses) on the 8-device CPU mesh:
sharded results must match single-device full attention, forward and
backward, and the SP BERT train step must train."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from sparknet_tpu.ops.attention import mha_reference
from sparknet_tpu.parallel.mesh import make_mesh
from sparknet_tpu.parallel.sequence import (
    make_sp_train_step,
    ring_attention,
    ulysses_attention,
)

SP = 4


def sp_mesh(n=SP):
    return make_mesh({"sp": n}, jax.devices()[:n])


def rand(rng, shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _run_sp(fn, mesh, q, k, v, mask):
    mapped = jax.shard_map(
        lambda q_, k_, v_, m_: fn(q_, k_, v_, kv_mask=m_),
        mesh=mesh,
        in_specs=(P(None, None, "sp"), P(None, None, "sp"),
                  P(None, None, "sp"), P(None, "sp")),
        out_specs=P(None, None, "sp"),
        check_vma=False,
    )
    return mapped(q, k, v, mask)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sp_attention_matches_full(impl, causal):
    rng = np.random.default_rng(0)
    b, h, s, d = 2, 4, 64, 8  # h=4 divides sp=4 for ulysses
    q, k, v = rand(rng, (b, h, s, d)), rand(rng, (b, h, s, d)), rand(rng, (b, h, s, d))
    mask = np.ones((b, s), np.int32)
    mask[0, 50:] = 0
    mask_j = jnp.asarray(mask)
    fn = ring_attention if impl == "ring" else ulysses_attention
    out = _run_sp(
        partial(fn, axis_name="sp", causal=causal), sp_mesh(), q, k, v, mask_j
    )
    ref = mha_reference(q, k, v, causal=causal, kv_mask=mask_j)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sp_attention_grads_match_full(impl):
    rng = np.random.default_rng(1)
    b, h, s, d = 1, 4, 32, 8
    q, k, v = rand(rng, (b, h, s, d)), rand(rng, (b, h, s, d)), rand(rng, (b, h, s, d))
    mask_j = jnp.ones((b, s), jnp.int32)
    fn = ring_attention if impl == "ring" else ulysses_attention
    mesh = sp_mesh()

    def loss_sp(q, k, v):
        out = _run_sp(partial(fn, axis_name="sp", causal=True),
                      mesh, q, k, v, mask_j)
        return jnp.sum(jnp.sin(out))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=True)))

    gs = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gs, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name} ({impl})")


def test_ring_attention_long_seq_many_shards():
    """8-way ring on a longer sequence (the long-context configuration)."""
    rng = np.random.default_rng(2)
    b, h, s, d = 1, 2, 512, 16
    q, k, v = rand(rng, (b, h, s, d)), rand(rng, (b, h, s, d)), rand(rng, (b, h, s, d))
    mask_j = jnp.ones((b, s), jnp.int32)
    out = _run_sp(
        partial(ring_attention, axis_name="sp", causal=True),
        sp_mesh(8), q, k, v, mask_j,
    )
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sp_bert_train_step_runs_and_learns():
    from sparknet_tpu.data.text import mlm_dataset, mlm_feed_tokens
    from sparknet_tpu.models.bert import BertConfig, BertMLM
    from sparknet_tpu.proto.caffe_pb import SolverParameter
    from sparknet_tpu.solver.caffe_solver import init_opt_state

    mesh = make_mesh({"dp": 2, "sp": 4}, jax.devices()[:8])
    cfg = BertConfig.bert_tiny(vocab_size=64)
    b, s = 4, 64
    model = BertMLM(
        cfg, {"input_ids": (b, s), "mlm_positions": (b, 8)},
        attention_impl="ring",
    )
    params, _ = model.init(jax.random.PRNGKey(0))
    sp = SolverParameter(
        base_lr=3e-3, lr_policy="fixed", solver_type="ADAMW",
        momentum=0.9, weight_decay=0.01, max_iter=100,
    )
    opt_state = init_opt_state(sp, params)
    step = make_sp_train_step(model, sp, mesh)

    ds, vsize = mlm_dataset(vocab_size=64, n_tokens=8192, seq_len=s)
    feed = mlm_feed_tokens(ds, b, vsize, seed=0)
    # one FIXED batch: memorisation decreases loss deterministically,
    # where a 12-step run over a random stream is threshold-flaky
    batch = {k_: jnp.asarray(v_) for k_, v_ in next(feed).items()}
    losses = []
    rng = jax.random.PRNGKey(1)
    for it in range(12):
        rng, srng = jax.random.split(rng)
        params, opt_state, m = step(
            params, opt_state, batch, jnp.asarray(it, jnp.int32), srng
        )
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4]), losses


def test_ulysses_rejects_indivisible_heads():
    rng = np.random.default_rng(3)
    q = rand(rng, (1, 3, 32, 8))  # 3 heads, sp=4
    mask_j = jnp.ones((1, 32), jnp.int32)
    with pytest.raises(ValueError):
        _run_sp(partial(ulysses_attention, axis_name="sp"),
                sp_mesh(), q, q, q, mask_j)


@pytest.mark.slow  # interpret-mode flash kernels at lane-aligned shapes
@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_full_and_grads(causal):
    """The flash-per-block ring engine (TPU default for lane-aligned
    shards; Pallas interpret mode here): forward AND gradients must
    match single-device full attention exactly — the custom VJP re-runs
    the ring with the GLOBAL merged lse per block and rotates dk/dv
    accumulators home with their kv shards."""
    from sparknet_tpu.parallel.sequence import _ring_einsum

    rng = np.random.default_rng(7)
    b, h, s, d = 2, 2, 512, 64  # s_loc = 128: lane-aligned
    q = rand(rng, (b, h, s, d))
    k = rand(rng, (b, h, s, d))
    v = rand(rng, (b, h, s, d))
    mask = np.ones((b, s), np.int32)
    mask[0, 400:] = 0
    mask_j = jnp.asarray(mask)
    mesh = sp_mesh()
    fn = partial(ring_attention, axis_name="sp", causal=causal,
                 impl="flash", interpret=True)

    def run(fn_, q_, k_, v_):
        return _run_sp(fn_, mesh, q_, k_, v_, mask_j)

    out = run(fn, q, k, v)
    ref = mha_reference(q, k, v, causal=causal, kv_mask=mask_j)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_sp(q_, k_, v_):
        return jnp.sum(jnp.sin(run(fn, q_, k_, v_)))

    def loss_ref(q_, k_, v_):
        return jnp.sum(jnp.sin(
            mha_reference(q_, k_, v_, causal=causal, kv_mask=mask_j)))

    g_sp = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_sp, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5,
            err_msg=f"d{name}",
        )

    # and the two ring engines agree with each other
    fn_e = partial(_ring_einsum, axis_name="sp", causal=causal)
    out_e = run(fn_e, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_e),
                               rtol=2e-5, atol=2e-5)
