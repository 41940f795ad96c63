"""MoE FFN with expert parallelism: sharded == single-device oracle."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from sparknet_tpu.parallel.mesh import make_mesh
from sparknet_tpu.parallel.moe import init_moe_params, moe_ffn, moe_pspecs


def setup(t=64, h=16, f=32, e=8, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(seed), h, f, e)
    return x, params


def test_moe_routes_and_shapes():
    x, params = setup()
    out, aux = moe_ffn(x, params, capacity_factor=2.0)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    # aux loss near 1.0 for near-uniform routing at init
    assert 0.5 < float(aux) < 4.0


def test_moe_capacity_drop_zero_rows():
    """capacity_factor tiny -> most tokens dropped -> zero expert output."""
    x, params = setup(t=64, e=2)
    out, _ = moe_ffn(x, params, capacity_factor=0.05)  # cap = 2 per expert
    zeros = np.sum(np.abs(np.asarray(out)).max(-1) == 0.0)
    assert zeros >= 64 - 2 * 2 * 2  # at most 2*cap kept per expert


def test_moe_ep_matches_single_device():
    """ep=4 sharded forward + grads == unsharded."""
    x, params = setup(t=64, h=16, f=32, e=8)
    mesh = make_mesh({"ep": 4}, jax.devices()[:4])
    pspecs = moe_pspecs()

    def loss_single(params, x):
        out, aux = moe_ffn(x, params, capacity_factor=2.0)
        return jnp.sum(jnp.sin(out)) + 0.01 * aux

    def loss_ep(params, x):
        def inner(params, x):
            out, aux = moe_ffn(x, params, ep_axis="ep", capacity_factor=2.0)
            return jnp.sum(jnp.sin(out)) + 0.01 * aux

        return jax.shard_map(
            inner, mesh=mesh, in_specs=(pspecs, P()), out_specs=P(),
            check_vma=False,
        )(params, x)

    l0 = float(jax.jit(loss_single)(params, x))
    l1 = float(jax.jit(loss_ep)(params, x))
    np.testing.assert_allclose(l1, l0, rtol=1e-5)

    g0 = jax.grad(loss_single)(params, x)
    g1 = jax.grad(loss_ep)(params, x)
    for name in g0:
        np.testing.assert_allclose(
            np.asarray(g1[name]), np.asarray(g0[name]),
            rtol=1e-4, atol=1e-5, err_msg=name,
        )


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_sort_dispatch_matches_dense(top_k):
    """The O(T·h) sort path must be numerically identical to the dense
    one-hot path — forward and gradients — for both routing modes."""
    x, params = setup(t=64, h=16, f=32, e=8)

    def loss(params, x, impl):
        out, aux = moe_ffn(
            x, params, capacity_factor=1.25, top_k=top_k, dispatch=impl,
            z_loss_weight=1e-3,
        )
        return jnp.sum(jnp.sin(out)) + 0.01 * aux

    ld = float(jax.jit(partial(loss, impl="dense"))(params, x))
    ls = float(jax.jit(partial(loss, impl="sort"))(params, x))
    np.testing.assert_allclose(ls, ld, rtol=1e-5)
    gd = jax.grad(loss)(params, x, "dense")
    gs = jax.grad(loss)(params, x, "sort")
    for name in gd:
        np.testing.assert_allclose(
            np.asarray(gs[name]), np.asarray(gd[name]),
            rtol=1e-4, atol=1e-6, err_msg=name,
        )


@pytest.mark.slow
@pytest.mark.parametrize("dispatch", ["dense", "sort"])
def test_moe_top2_ep_matches_single_device(dispatch):
    """top-2 + z-loss under ep=4 shard_map == unsharded, both dispatches."""
    x, params = setup(t=64, h=16, f=32, e=8)
    mesh = make_mesh({"ep": 4}, jax.devices()[:4])
    kw = dict(
        capacity_factor=2.0, top_k=2, z_loss_weight=1e-3, dispatch=dispatch
    )

    def loss_single(params, x):
        out, aux = moe_ffn(x, params, **kw)
        return jnp.sum(jnp.sin(out)) + 0.01 * aux

    def loss_ep(params, x):
        def inner(params, x):
            out, aux = moe_ffn(x, params, ep_axis="ep", **kw)
            return jnp.sum(jnp.sin(out)) + 0.01 * aux

        return jax.shard_map(
            inner, mesh=mesh, in_specs=(moe_pspecs(), P()), out_specs=P(),
            check_vma=False,
        )(params, x)

    l0 = float(jax.jit(loss_single)(params, x))
    l1 = float(jax.jit(loss_ep)(params, x))
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    g0 = jax.grad(loss_single)(params, x)
    g1 = jax.grad(loss_ep)(params, x)
    for name in g0:
        np.testing.assert_allclose(
            np.asarray(g1[name]), np.asarray(g0[name]),
            rtol=1e-4, atol=1e-5, err_msg=name,
        )


def test_moe_top2_gates_renormalised():
    """top-2 output ~= gate-weighted mix: with capacity ample, every
    token gets contributions from both its experts and the gates sum
    to 1, so scaling x scales out through the experts only."""
    x, params = setup(t=32, h=16, f=32, e=4)
    out1, _ = moe_ffn(x, params, capacity_factor=4.0, top_k=2)
    # Each token's row should be nonzero (no drops at cf=4)
    assert np.all(np.abs(np.asarray(out1)).max(-1) > 0)


def test_moe_rejects_bad_dispatch():
    x, params = setup()
    with pytest.raises(ValueError):
        moe_ffn(x, params, dispatch="hash")


def test_moe_rejects_indivisible_experts():
    x, params = setup(e=6)
    mesh = make_mesh({"ep": 4}, jax.devices()[:4])
    with pytest.raises(ValueError):
        jax.shard_map(
            lambda p, x: moe_ffn(x, p, ep_axis="ep")[0],
            mesh=mesh, in_specs=(moe_pspecs(), P()), out_specs=P(),
            check_vma=False,
        )(params, x)


# ---------------------------------------------------------------------------
# held_experts_ffn: one chip's share of the experts, no token dropped
# ---------------------------------------------------------------------------

def _held_setup(first=4, held=4, experts=16, h=32, f=16, tokens=80, std=0.3):
    from sparknet_tpu.parallel.moe import init_held_experts_params, route_sigmoid

    p = init_held_experts_params(jax.random.PRNGKey(0), h, f, experts, held, std=std)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, tokens // 2, h))
    router = lambda xt, p: route_sigmoid(xt, p["router_w"], 2, 2.5)
    return p, x, dict(experts_held=(first, held), top_k=2, router=router)


def _held_plain(x, p, experts_held, top_k, router=None, routed_scale=2.5):
    """Every held expert on every token, masked by the routing weights
    (``_held_setup``'s router, written out)."""
    first, held = experts_held
    f = p["experts_down"].shape[1]
    xt = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(xt @ p["router_w"])
    top, idx = jax.lax.top_k(scores, top_k)
    w = routed_scale * top / top.sum(-1, keepdims=True)
    out = jnp.zeros_like(xt)
    for e in range(held):
        hid = xt @ p["experts_gate_up"][e]
        y = (jax.nn.silu(hid[:, :f]) * hid[:, f:]) @ p["experts_down"][e]
        out = out + jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)[:, None] * y
    return out.reshape(x.shape)


@pytest.mark.parametrize("chunk_rows", [None, 8, 16, 56])
def test_held_experts_match_the_masked_dense_computation(chunk_rows):
    """Output and the gradient of every input, whatever the chunking: one
    chunk, chunks that split an expert's rows, a last chunk partly empty."""
    from sparknet_tpu.parallel.moe import held_experts_ffn

    p, x, kw = _held_setup()
    with jax.default_matmul_precision("highest"):
        fast = lambda x, p: held_experts_ffn(x, p, chunk_rows=chunk_rows, **kw)[0]
        plain = lambda x, p: _held_plain(x, p, **kw)
        np.testing.assert_allclose(fast(x, p), plain(x, p), atol=2e-5)
        got = jax.grad(lambda x, p: jnp.sum(fast(x, p) ** 2), (0, 1))(x, p)
        want = jax.grad(lambda x, p: jnp.sum(plain(x, p) ** 2), (0, 1))(x, p)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-7)


@pytest.mark.parametrize("chunk_rows", [None, 16])
def test_held_experts_drop_nothing_when_one_expert_takes_every_token(chunk_rows):
    """A router forced to send every token's first choice to ONE held expert
    (a Switch layer at capacity 1.25 would drop most of them): every slot
    is computed, the counters say so, and the result is the plain one."""
    from sparknet_tpu.parallel.moe import held_experts_ffn

    p, x, kw = _held_setup()
    x = jnp.abs(x)  # so that one router column can dominate for every token
    router = p["router_w"] * 0.01
    p = {**p, "router_w": router.at[:, 6].set(1.0)}  # expert 6 = held index 2
    out, counters = held_experts_ffn(x, p, chunk_rows=chunk_rows, **kw)
    tokens = x.shape[0] * x.shape[1]
    assert float(counters["moe_slots_dropped"]) == 0.0
    assert float(counters["moe_slots_held"]) >= tokens  # one slot a token at least
    # the fullest held expert holds every token: at least tokens / (held / 4)
    assert float(counters["moe_load_max_over_mean"]) >= 4 * tokens / float(
        counters["moe_slots_held"]
    ) - 1e-6
    with jax.default_matmul_precision("highest"):
        out, _ = held_experts_ffn(x, p, chunk_rows=chunk_rows, **kw)
        np.testing.assert_allclose(out, _held_plain(x, p, **kw), atol=2e-5)


def test_held_experts_counters_on_an_even_split_and_bad_shares():
    from sparknet_tpu.parallel.moe import held_experts_ffn

    p, x, kw = _held_setup(first=0, held=16)  # every expert is held
    _, counters = held_experts_ffn(x, p, **kw)
    assert float(counters["moe_slots_held"]) == 2 * 80  # all T * top_k slots
    assert float(counters["moe_slots_dropped"]) == 0.0
    assert float(counters["moe_load_max_over_mean"]) >= 1.0
    with pytest.raises(ValueError, match="experts_held"):
        held_experts_ffn(x, p, **dict(kw, experts_held=(8, 16)))
    with pytest.raises(ValueError, match="weights hold"):
        held_experts_ffn(x, p, **dict(kw, experts_held=(0, 4)))
