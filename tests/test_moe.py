"""MoE FFN with expert parallelism: sharded == single-device oracle."""

import re
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


# ---------------------------------------------------------------------------
# the held slots' permutation: sorts with payloads, rows combined in token
# order (PR 36)
# ---------------------------------------------------------------------------

ROUTERS = ("sigmoid", "softmax", "grouped")


def _router(kind, top_k=2, impl="new"):
    """``router(xt, p)`` of one of the three kinds, over 16 experts; ``impl``
    "old" is the same router written with ``top_k``'s own values and
    ``take_along_axis``, as the program had it before."""
    from sparknet_tpu.parallel import moe

    if impl == "new":
        return {
            "sigmoid": lambda xt, p: moe.route_sigmoid(xt, p["router_w"], top_k, 2.5),
            "softmax": lambda xt, p: moe.route_softmax(xt, p["router_w"], top_k),
            "grouped": lambda xt, p: moe.route_grouped(
                xt, p["router_w"], p["router_bias"], top_k, 2.5, 4, 2
            ),
        }[kind]

    def old(xt, p):
        logits = xt.astype(jnp.float32) @ p["router_w"]
        if kind == "softmax":
            top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
            return top / top.sum(-1, keepdims=True), idx
        scores = jax.nn.sigmoid(logits)
        if kind == "sigmoid":
            top, idx = jax.lax.top_k(scores, top_k)
        else:
            t, e = scores.shape
            grouped = (scores + jax.lax.stop_gradient(p["router_bias"])).reshape(t, 4, e // 4)
            _, groups = jax.lax.top_k(jnp.sum(jax.lax.top_k(grouped, 2)[0], -1), 2)
            kept = jnp.any(groups[:, :, None] == jnp.arange(4), axis=1)
            _, idx = jax.lax.top_k(
                jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, e), top_k
            )
            top = jnp.take_along_axis(scores, idx, axis=-1)
        return 2.5 * top / top.sum(-1, keepdims=True), idx

    return old


def _plain(x, p, experts_held, top_k, router):
    """Every held expert on every token, masked by the router's weights."""
    first, held = experts_held
    f = p["experts_down"].shape[1]
    xt = x.reshape(-1, x.shape[-1])
    w, idx = router(xt, p)
    out = jnp.zeros_like(xt)
    for e in range(held):
        hid = xt @ p["experts_gate_up"][e]
        y = (jax.nn.silu(hid[:, :f]) * hid[:, f:]) @ p["experts_down"][e]
        out = out + jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)[:, None] * y
    return out.reshape(x.shape)


# name: (setup overrides, chunk_rows, what it is)
CASES = {
    "one_chunk": (dict(), None),
    "two_chunks": (dict(), 80),
    "five_chunks_and_a_pad": (dict(), 36),  # 160 slots in 5 x 36: a pad of 20
    "ties": (dict(ties=True), 16),
    "an_expert_with_no_slot": (dict(starve=5), 16),
    "every_first_choice_on_one_expert": (dict(crowd=6), 16),
    "all_experts_held": (dict(first=0, held=16), 56),
}


def _case(name, kind):
    """(params, x, kwargs of held_experts_ffn) of a case and a router."""
    overrides, chunk_rows = CASES[name]
    overrides = dict(overrides)
    ties, starve, crowd = (overrides.pop(k, None) for k in ("ties", "starve", "crowd"))
    p, x, kw = _held_setup(**overrides)
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    if ties:  # half the tokens' scores are all equal: ties go to the lower index
        x = x.at[0].set(0.0)
    if starve is not None:  # a held expert nobody chooses: the lowest score of every token
        x = jnp.abs(x)
        p["router_w"] = jnp.abs(p["router_w"]).at[:, starve].set(-1.0)
        p["router_bias"] = p["router_bias"].at[starve].set(-10.0)
    if crowd is not None:  # a held expert everybody chooses first
        x = jnp.abs(x)
        p["router_w"] = (p["router_w"] * 0.01).at[:, crowd].set(1.0)
    kw = dict(kw, router=_router(kind), chunk_rows=chunk_rows)
    return p, x, kw


def _grads(fn, x, p):
    return jax.grad(lambda x, p: jnp.sum(fn(x, p) ** 2), (0, 1))(x, p)


@pytest.mark.parametrize("kind", ROUTERS)
@pytest.mark.parametrize("name", list(CASES))
def test_held_experts_and_their_gradients_match_the_plain_form(name, kind):
    """(a) Output and the gradients of ``x``, the router, both expert
    stacks (the grouped router's bias: none) against every held expert on
    every token, to the tolerances of the tests above."""
    from sparknet_tpu.parallel.moe import held_experts_ffn

    p, x, kw = _case(name, kind)
    with jax.default_matmul_precision("highest"):
        fast = lambda x, p: held_experts_ffn(x, p, **kw)[0]
        plain = lambda x, p: _plain(x, p, kw["experts_held"], kw["top_k"], kw["router"])
        np.testing.assert_allclose(fast(x, p), plain(x, p), atol=2e-5)
        got, want = _grads(fast, x, p), _grads(plain, x, p)
    assert not np.asarray(got[1]["router_bias"]).any()
    for key in ("router_w", "experts_gate_up", "experts_down"):
        w = want[1][key]
        np.testing.assert_allclose(
            got[1][key], w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-7, err_msg=key
        )
    np.testing.assert_allclose(
        got[0], want[0], atol=1e-5 * float(jnp.abs(want[0]).max()) + 1e-7
    )
    counters = held_experts_ffn(x, p, **kw)[1]
    assert float(counters["moe_slots_dropped"]) == 0.0
    assert float(counters["moe_slots_in_kernel"]) == 0.0  # (e) the CPU path
    if name == "an_expert_with_no_slot":
        _, idx = kw["router"](x.reshape(-1, x.shape[-1]), p)
        assert not np.any(np.asarray(idx) == 5)
    if name == "every_first_choice_on_one_expert":
        _, idx = kw["router"](x.reshape(-1, x.shape[-1]), p)
        assert np.all(np.asarray(idx)[:, 0] == 6) or kind == "grouped"


@pytest.mark.parametrize("kind", ROUTERS)
@pytest.mark.parametrize("name", ["one_chunk", "ties", "an_expert_with_no_slot", "all_experts_held"])
def test_slot_tables_are_the_argsort_s_element_for_element(name, kind):
    """(b) ``tok``, the sorted weights, ``offsets`` and ``pos`` against
    ``argsort`` / ``searchsorted`` / an inverse by scatter; and the sorted
    weights' gradient against the gather's own."""
    from sparknet_tpu.parallel.moe import slot_tables

    p, x, kw = _case(name, kind)
    first, held = kw["experts_held"]
    weights, experts = kw["router"](x.reshape(-1, x.shape[-1]), p)
    local = experts.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    tok, wgt, offsets, pos = jax.jit(slot_tables, static_argnums=(2, 3))(
        key, weights.reshape(-1), held, 2
    )
    order = jnp.argsort(key, stable=True)
    np.testing.assert_array_equal(tok, order // 2)
    np.testing.assert_array_equal(wgt, weights.reshape(-1)[order])
    np.testing.assert_array_equal(
        offsets, jnp.searchsorted(key[order], jnp.arange(held + 1), side="left")
    )
    np.testing.assert_array_equal(
        pos, jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
    )
    probe = jnp.cos(jnp.arange(order.size, dtype=jnp.float32))
    got = jax.grad(lambda w: jnp.sum(probe * slot_tables(key, w, held, 2)[1]))(weights.reshape(-1))
    want = jax.grad(lambda w: jnp.sum(probe * w[order]))(weights.reshape(-1))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ROUTERS)
@pytest.mark.parametrize("ties", [False, True], ids=["plain", "ties"])
def test_routers_read_the_chosen_scores_bit_for_bit(kind, ties):
    """(b) ``(weights, experts)`` and the gradients of the router's matrix
    and of the tokens equal the ``top_k`` + ``take_along_axis`` form to the
    bit; the grouped router's bias gets none."""
    p, x, _ = _case("ties" if ties else "one_chunk", kind)
    xt = x.reshape(-1, x.shape[-1])
    new, old = _router(kind, impl="new"), _router(kind, impl="old")
    for got, want in zip(new(xt, p), old(xt, p)):
        np.testing.assert_array_equal(got, want)
    probe = jnp.sin(jnp.arange(xt.shape[0] * 2, dtype=jnp.float32)).reshape(-1, 2)
    loss = lambda router: lambda xt, p: jnp.sum(probe * router(xt, p)[0])
    got = jax.grad(loss(new), (0, 1))(xt, p)
    want = jax.grad(loss(old), (0, 1))(xt, p)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1]["router_w"], want[1]["router_w"])
    assert not np.asarray(got[1]["router_bias"]).any()


@pytest.mark.parametrize("family", ["decoder", "hybrid"])
def test_the_lowered_step_holds_no_permutation_of_slots_one_at_a_time(family):
    """(d) In the lowered gradient step of a small sparse decoder no
    ``gather`` or ``scatter`` moves T * top_k elements (a vector of slots,
    or the (T, top_k) scores of the chosen): neither its indices, its
    updates nor its result have that many, and none scatters into a vector
    of slots.  The slots are moved by sorts and read by comparisons.  What
    stays, by name: the offsets' binary search, which reads held + 1
    elements of the sorted keys a step; the row gathers ``xt[tok_c]`` and
    ``dout[tok_c]`` of ``rows`` rows of (T, h); and off a TPU the rows'
    scatter-add into (T, h)."""
    from sparknet_tpu.models.decoder import DecoderConfig, DecoderLM, HybridConfig, HybridLM
    from sparknet_tpu.parallel.moe import held_chunk_rows

    b, s = 2, 160
    if family == "decoder":
        cfg = DecoderConfig.tiny(remat=True)
        model = DecoderLM(cfg, {"input_ids": (b, s)})
    else:
        cfg = HybridConfig.tiny(remat=True, kda_segment=32)
        model = HybridLM(cfg, {"input_ids": (b, s)})
    tokens, k = b * s, cfg.num_experts_per_tok
    slots = tokens * k
    rows = held_chunk_rows(slots, cfg.experts_held[1], cfg.num_experts)
    assert rows < slots  # so that a chunk's rows are told from the slots
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    ids = jax.ShapeDtypeStruct((b, s), jnp.int32)
    batch = {"input_ids": ids, "labels": ids}

    def step(p, batch):
        return jax.grad(
            lambda p_: model.apply(p_, {}, batch, train=True, rng=jax.random.PRNGKey(0))[0]["loss"]
        )(p)

    text = jax.jit(step).lower(params, batch).as_text()
    moved = re.findall(r'"stablehlo\.(gather)"\([^\n]*? : (\([^\n]*)', text) + re.findall(
        r'"stablehlo\.(scatter)"\(.*?\}\) : (\([^\n]*)', text, flags=re.DOTALL
    )
    assert moved  # the embedding's rows at least
    elements = lambda dims: int(np.prod([int(d) for d in dims.split("x")[:-1]]))
    held = cfg.experts_held[1]
    searches = 0
    for op, types in moved:
        operand, *moving = [elements(t) for t in re.findall(r"tensor<([^>]*)>", types)]
        if op == "gather" and operand == slots and set(moving) == {held + 1}:
            searches += 1  # a step of the offsets' binary search
            continue
        assert slots not in moving, (op, types)  # indices, updates, result
        assert op == "gather" or operand != slots, (op, types)
    assert searches
    h = cfg.hidden_size
    rows_of = lambda op: [
        types for o, types in moved
        if o == op and f"tensor<{tokens}x{h}x" in types and f"tensor<{rows}x1xi32>" in types
    ]
    assert rows_of("gather")  # xt[tok_c], dout[tok_c]
    assert rows_of("scatter")  # the CPU path's scatter-add


def _combine_case(tokens=512, top_k=2, held=4, experts=16, seed=0):
    """The tables of a seeded softmax router at a size ``moe_combine``
    takes, and a key for rows to combine."""
    from sparknet_tpu.parallel import moe

    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    xt = jax.random.normal(k[0], (tokens, 32))
    weights, idx = moe.route_softmax(xt, 0.5 * jax.random.normal(k[1], (32, experts)), top_k)
    key = jnp.where(idx.reshape(-1) < held, idx.reshape(-1), held)
    tok, wgt, offsets, pos = moe.slot_tables(key, weights.reshape(-1), held, top_k)
    weights = weights.reshape(-1)
    side = (weights, key, pos, *moe.tile_runs(key, offsets, moe._COMBINE_SLOTS))
    return tok, wgt, offsets, side, weights, k[2]


@pytest.mark.parametrize("rows", [512, 48], ids=["one_chunk", "seven_chunks"])
def test_moe_combine_equals_the_masked_scatter_add(rows):
    """(c) The kernel in Pallas's interpreter against the scatter-add it
    stands for, every chunk, to 1e-6 of the largest value.  Rows that are
    no slot hold NaN: a kernel that summed one fails.  (The backward pass's
    ``dxt`` keeps the scatter-add: ``_held_chunks_bwd`` says why.)"""
    from sparknet_tpu.parallel import moe

    tok, wgt, offsets, side, weights, rng = _combine_case()
    tokens, h, n_held = 512, 128, int(offsets[-1])
    chunks = -(-n_held // rows)
    assert chunks == (1 if rows == 512 else 7)
    got = want = jax.random.normal(rng, (tokens, h))
    for c in range(chunks):
        live = c * rows + jnp.arange(rows) < n_held
        y = jnp.where(
            live[:, None], jax.random.normal(jax.random.fold_in(rng, c), (rows, h)), jnp.nan
        )
        tok_c = jnp.pad(tok, (0, rows))[c * rows:(c + 1) * rows]
        wgt_c = jnp.pad(wgt, (0, rows))[c * rows:(c + 1) * rows]
        want = want.at[tok_c].add(jnp.where(live[:, None], y * wgt_c[:, None], 0.0))
        got = moe._combine(got, y, side, offsets[-1], c, rows, True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-6 * float(jnp.abs(want).max()))


def test_combine_plan_copies_whole_row_tiles_that_cover_each_run():
    """The plan of a chunk: every tile's run in every expert's rows lies
    inside the 8-row tiles the plan copies, the copies fill the buffer one
    after another without overlap, and ``rel`` names the row of ``y`` that
    the sorted order gives the slot: moved as its run's copies were, it
    lies inside them."""
    from sparknet_tpu.parallel import moe

    tok, wgt, offsets, side, weights, _ = _combine_case(tokens=1024, seed=3)  # two tiles
    _, key, pos, start, count = side
    held, rows, n_held = 4, 64, int(offsets[-1])
    for c in range(-(-n_held // rows)):
        plan, rel = moe._combine_plan(pos, (start, count), offsets[-1], c, rows)
        a, n, b = np.asarray(plan).reshape(3, -1, held)
        assert (n >= 0).all() and ((a + n) * 8 <= rows).all()
        np.testing.assert_array_equal(b, np.cumsum(n, axis=1) - n)
        assert (b[:, -1] + n[:, -1]).max() * 8 <= moe._COMBINE_SLOTS + 16 * held
        rel, pos_, key_ = np.asarray(rel), np.asarray(pos), np.asarray(key)
        lo, hi = c * rows, min((c + 1) * rows, n_held)
        inside = (pos_ >= lo) & (pos_ < hi)
        np.testing.assert_array_equal(rel >= 0, inside)
        np.testing.assert_array_equal(rel[inside], pos_[inside] - lo)
        t, e = (np.arange(rel.shape[0]) // moe._COMBINE_SLOTS)[inside], key_[inside]
        at = rel[inside] + 8 * (b[t, e] - a[t, e])  # the buffer row, as the kernel reckons it
        assert ((at >= 8 * b[t, e]) & (at < 8 * (b[t, e] + n[t, e]))).all()


def test_the_kernel_is_taken_by_what_the_call_shows():
    """Off a TPU never, unless forced; forced, where rows are whole lane
    tiles, chunks whole row tiles and the slots whole SMEM blocks."""
    from sparknet_tpu.parallel.moe import uses_combine_kernel

    assert not uses_combine_kernel(32768, 2304, 8, 81920)  # no TPU here
    assert uses_combine_kernel(32768, 2304, 8, 81920, "flash")
    assert uses_combine_kernel(16384, 2048, 8, 20480, "flash")
    assert uses_combine_kernel(16384, 2560, 8, 2560, "flash")
    assert not uses_combine_kernel(32768, 2304, 8, 81920, "reference")
    assert not uses_combine_kernel(32768, 2300, 8, 81920, "flash")  # lanes
    assert not uses_combine_kernel(32768, 2304, 8, 81916, "flash")  # row tiles
    assert not uses_combine_kernel(1000, 2304, 8, 8000, "flash")  # slots
    assert not uses_combine_kernel(1024, 2304, 3, 3072, "flash")  # 1024 % 3


def _step(model, params, batch):
    def loss(p):
        out, _ = model.apply(p, {}, batch, train=True, rng=jax.random.PRNGKey(0))
        return out["loss"], out
    (value, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    return value, out, grads


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_decoder_step_through_the_kernel_agrees_and_counts_its_slots(monkeypatch, remat):
    """(e) A sparse decoder's loss and every gradient with the kernel
    (interpreted) against the scatter-add; ``moe_slots_in_kernel`` equals
    ``moe_slots_held`` there and is 0 on the CPU's own path."""
    from sparknet_tpu.models.decoder import DecoderConfig, DecoderLM

    cfg = DecoderConfig.tiny(
        hidden_size=128, remat=remat, layer_types=("full_attention", "sliding_attention"),
        mlp_layer_types=("dense", "sparse"), num_attention_heads_per_layer=(4, 6),
        loss_chunk=128,
    )
    model = DecoderLM(cfg, {"input_ids": (2, 256)})
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 257), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params, _ = model.init(jax.random.PRNGKey(2))
    loss_p, out_p, grads_p = _step(model, params, batch)
    assert float(out_p["moe_slots_held"]) > 0 and float(out_p["moe_slots_in_kernel"]) == 0.0
    from sparknet_tpu.models import decoder
    from sparknet_tpu.parallel.moe import held_experts_ffn

    # the kernel forced, in Pallas's interpreter: what a test off the chip can run
    monkeypatch.setattr(
        decoder, "held_experts_ffn",
        lambda *a, **kw: held_experts_ffn(*a, **{**kw, "force": "flash", "interpret": True}),
    )
    loss_k, out_k, grads_k = _step(model, params, batch)
    assert float(out_k["moe_slots_in_kernel"]) == float(out_k["moe_slots_held"]) == float(out_p["moe_slots_held"])
    assert float(out_k["moe_slots_dropped"]) == 0.0
    np.testing.assert_allclose(loss_k, loss_p, rtol=2e-6)
    for layer in grads_p:
        for name, w in grads_p[layer].items():
            np.testing.assert_allclose(
                grads_k[layer][name], w, atol=2e-5 * max(float(jnp.abs(w).max()), 1e-12),
                err_msg=f"{layer}.{name}",
            )


def test_the_counter_reaches_the_progress_line_and_the_registry(capsys):
    """``moe_slots_in_kernel`` and ``moe_slots_in_gmm`` beside
    ``moe_slots_held`` on the app's progress line and in the registry: 0 off
    a TPU."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY

    from sparknet_tpu.utils.profiling import StepTimer

    args = lm_app.parser().parse_args(
        ["--config", "tiny", "--seq-len", "32", "--batch-size", "4", "--max-iter", "2",
         "--display", "2", "--synthetic-tokens", "4096"]
    )
    solver, feed, _ = lm_app.build(args)
    metrics = lm_app._fit(solver, iter(feed), args, StepTimer(items_per_step=128, unit="tokens"))
    out = capsys.readouterr().out
    assert re.search(r"moe_slots_held = [\d.]+, moe_slots_in_kernel = 0, moe_load_max_over_mean = ", out)
    assert metrics["moe_slots_in_kernel"] == 0.0 < metrics["moe_slots_held"]
    assert "moe_slots_in_gmm = 0" in out and metrics["moe_slots_in_gmm"] == 0.0
    read = REGISTRY.sources()["train_step"].snapshot()
    assert read["moe_slots_in_kernel"] == 0.0 and read["moe_slots_held"] == metrics["moe_slots_held"]
    assert read["moe_slots_in_gmm"] == 0.0


# ---------------------------------------------------------------------------
# the grouped products as Pallas kernels (ops/gmm.py), in the interpreter
# ---------------------------------------------------------------------------

# name: (group sizes over 512 rows in tiles of 128, the rows' width)
GMM_CASES = {
    # boundaries inside tiles, two empty experts, 130 rows past the last
    "straddling_and_empty": ([100, 0, 150, 130, 0], 256),
    "seven_lane_tiles": ([40, 300, 0, 60, 90], 896),
    "one_expert_all_rows": ([0, 0, 512, 0, 0], 128),
    "no_live_rows": ([0, 0, 0, 0, 0], 128),
}


@pytest.mark.parametrize("name", list(GMM_CASES))
def test_gmm_and_tgmm_equal_ragged_dot_and_its_gradients(name):
    """``gmm`` forward and against the weights read transposed (dx), and
    ``tgmm`` (dW), against ``lax.ragged_dot`` and its ``jax.vjp``, float32
    to 1e-6 of the largest value.  Rows past ``sum(sizes)`` hold NaN in
    both operands: a kernel that read one into a product fails; an empty
    expert's weight gradient is what was summed before it."""
    from sparknet_tpu.ops import gmm as ops_gmm

    sizes, n = GMM_CASES[name]
    sizes = jnp.asarray(sizes, jnp.int32)
    rows, k, live = 512, 128, int(sizes.sum())
    assert ops_gmm.row_tile(rows, sizes.shape[0]) == 128
    r = jax.random.split(jax.random.PRNGKey(7), 3)
    nan_past = lambda a: a.at[live:].set(jnp.nan)
    x = nan_past(jax.random.normal(r[0], (rows, k)))
    w = jax.random.normal(r[1], (sizes.shape[0], k, n))
    dy = nan_past(jax.random.normal(r[2], (rows, n)))
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x[:live], w, sizes), x, w)
        want_dx, want_dw = vjp(dy[:live])
        got = ops_gmm.gmm(x, w, sizes, interpret=True)
        got_dx = ops_gmm.gmm(dy, w, sizes, transpose_rhs=True, interpret=True)
        prior = jax.random.normal(r[0], w.shape)  # the chunks' sum before this one
        got_dw = ops_gmm.tgmm(x, dy, sizes, prior, interpret=True) - prior
    close = lambda g, w: np.testing.assert_allclose(
        g, w, atol=1e-6 * np.abs(np.asarray(w)).max(initial=1.0)
    )
    close(got[:live], want)
    close(got_dx[:live], want_dx[:live])
    assert np.isfinite(np.asarray(got_dw)).all()
    close(got_dw, want_dw)
    assert not np.asarray(got_dw)[np.asarray(sizes) == 0].any()


def test_group_visits_take_each_tile_of_a_group_once_in_order():
    """The visits of ``group_visits``: every group takes the tiles that hold
    its rows, in order, an empty one none (or, for ``tgmm``, one), and the
    tiles past the live rows none."""
    from sparknet_tpu.ops.gmm import group_visits

    sizes = jnp.asarray([100, 0, 150, 130, 0], jnp.int32)
    group, tile, offsets, n = group_visits(sizes, 512, 128, empty=False)
    assert int(n) == 5 and group.shape == tile.shape == (8,)
    assert list(zip(group[:5].tolist(), tile[:5].tolist())) == [(0, 0), (2, 0), (2, 1), (3, 1), (3, 2)]
    assert offsets.tolist() == [0, 100, 100, 250, 380, 380]
    group, tile, _, n = group_visits(sizes, 512, 128, empty=True)
    assert list(zip(group[:int(n)].tolist(), tile[:int(n)].tolist())) == [
        (0, 0), (1, 0), (2, 0), (2, 1), (3, 1), (3, 2), (4, 2)
    ]


def test_the_gmm_kernels_are_taken_by_what_the_call_shows():
    """Off a TPU never, unless forced; forced, where the hidden and expert
    widths are whole lane tiles and the chunk whole row tiles.  Tiles at the
    four decoder cells' shapes come from the widths and the rows alone."""
    from sparknet_tpu.ops.gmm import _VMEM_BUDGET, lane_tiles, row_tile
    from sparknet_tpu.parallel.moe import uses_gmm_kernel

    assert not uses_gmm_kernel(2304, 896, 81920)  # no TPU here
    assert not uses_gmm_kernel(2304, 896, 81920, "reference")
    for hidden, ffn, rows in ((2304, 896, 81920), (2048, 512, 20480), (2560, 768, 2560), (2048, 1792, 40960)):
        assert uses_gmm_kernel(hidden, ffn, rows, "flash")
    assert not uses_gmm_kernel(2300, 896, 81920, "flash")  # lanes
    assert not uses_gmm_kernel(2304, 900, 81920, "flash")
    assert not uses_gmm_kernel(2304, 896, 81920 - 64, "flash")  # row tiles
    # mellum, lfm2: 512 rows; laguna's 32 and ling's 8 experts in short chunks: 128
    assert [row_tile(r, g) for r, g in ((81920, 16), (40960, 8), (20480, 32), (2560, 8))] == [512, 512, 128, 128]
    tk, tn = lane_tiles(512, 2304, 1792, 2, grouped_k=False)
    assert (tk, tn) == (2304, 1792)  # a group's whole gate and up stay in VMEM
    assert lane_tiles(512, 1792, 2304, 2, grouped_k=False)[1] == 1152
    tk, tn = lane_tiles(512, 2304, 1792, 2, grouped_k=True)
    assert 2304 % tk == 1792 % tn == 0 and tk * tn * 4 <= _VMEM_BUDGET // 2


@pytest.mark.parametrize("chunk_rows", [None, 128], ids=["one_chunk", "four_chunks"])
def test_held_experts_through_the_gmm_kernels_match_ragged_dot(chunk_rows):
    """``held_experts_ffn`` with the products forced through the kernels
    (interpreted) against ``lax.ragged_dot``'s path: the output and the
    gradients of the tokens, the router and both expert stacks to float32
    tolerance; ``moe_slots_in_gmm`` equals ``moe_slots_held`` there and is 0
    off it."""
    from sparknet_tpu.parallel.moe import held_experts_ffn

    p, x, kw = _held_setup(h=128, f=128, tokens=256)
    kw = dict(kw, chunk_rows=chunk_rows)
    forced = dict(force="flash", interpret=True)
    run = lambda extra: lambda x, p: held_experts_ffn(x, p, **kw, **extra)
    with jax.default_matmul_precision("highest"):
        (got, c_got), (want, c_want) = run(forced)(x, p), run({})(x, p)
        np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.abs(want).max()))
        g_got = _grads(lambda x, p: run(forced)(x, p)[0], x, p)
        g_want = _grads(lambda x, p: run({})(x, p)[0], x, p)
    for g, w in zip(jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-7)
    assert float(c_got["moe_slots_in_gmm"]) == float(c_got["moe_slots_held"]) > 0
    assert float(c_want["moe_slots_in_gmm"]) == 0.0 == float(c_got["moe_slots_dropped"])
    assert float(c_got["moe_slots_in_kernel"]) == 0.0  # 512 slots: no whole moe_combine tile


# ------------------------------------------------------------- on the chip

@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="the compiled kernel needs a TPU"
)
@pytest.mark.parametrize(
    "tokens,h,experts,held", [(32768, 2304, 64, 16), (16384, 2048, 256, 32)],
    ids=["mellum2", "laguna_xs2"],
)
def test_compiled_moe_combine_at_the_cells_shapes_on_hardware(tokens, h, experts, held):
    """The compiled kernel at a cell's (T, h, rows, top_k), under a seeded
    softmax router, against the scatter-add, within 1e-5 of the largest
    value; rows that are no slot hold NaN."""
    from sparknet_tpu.parallel import moe

    top_k = 8
    rows = moe.held_chunk_rows(tokens * top_k, held, experts)
    assert (tokens, h, rows) in ((32768, 2304, 81920), (16384, 2048, 20480))
    tok, wgt, offsets, side, weights, rng = _combine_case(tokens, top_k, held, experts)
    live = jnp.arange(rows) < offsets[-1]
    y = jnp.where(live[:, None], jax.random.normal(rng, (rows, h)), jnp.nan)
    want = jax.jit(
        lambda y: jnp.zeros((tokens, h)).at[tok[:rows]].add(
            jnp.where(live[:, None], y * wgt[:rows, None], 0.0)
        )
    )(y)
    got = jax.jit(
        lambda y: moe._combine(
            jnp.zeros((tokens, h)), y, side, offsets[-1], 0, rows, False
        )
    )(y)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="the compiled kernels need a TPU"
)
@pytest.mark.parametrize(
    "tokens,h,f,experts,held", [(32768, 2304, 896, 64, 16), (16384, 2048, 512, 256, 32)],
    ids=["mellum2", "laguna_xs2"],
)
def test_compiled_gmm_kernels_at_the_cells_shapes_on_hardware(tokens, h, f, experts, held):
    """One chunk of a cell's expert layer, forward and backward, through the
    compiled ``ops.gmm`` kernels against ``lax.ragged_dot``, 80 % of its rows
    live over uneven experts: the sum and every gradient within 2 % of the
    largest value (both round their operands to bfloat16, not at the same
    places).  Tensors are the jit's arguments, not constants."""
    from sparknet_tpu.parallel import moe

    rows = moe.held_chunk_rows(tokens * 8, held, experts)
    assert moe.uses_gmm_kernel(h, f, rows)
    k = jax.random.split(jax.random.PRNGKey(42), 7)
    share = jax.random.gamma(k[0], 8.0, (held,))
    sizes = jnp.floor(0.8 * rows * share / share.sum()).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)])
    args = (
        jax.random.normal(k[1], (tokens, h), jnp.bfloat16),
        0.02 * jax.random.normal(k[2], (held, h, 2 * f)),
        0.02 * jax.random.normal(k[3], (held, f, h)),
        jax.random.uniform(k[4], (rows,)),
        jax.random.normal(k[5], (tokens, h)),
    )
    tok = jax.random.randint(k[6], (rows,), 0, tokens)

    def grads(gmm_kernel):
        def total(xt, gu, dn, wgt, probe):
            out, _ = moe._held_chunks(
                xt, gu, dn, tok, wgt, offsets, offsets[-1], None, rows,
                jnp.bfloat16, None, gmm_kernel,
            )
            return jnp.sum(out * probe)
        return jax.jit(jax.value_and_grad(total, range(4)))(*args)

    (got, g_got), (want, g_want) = grads(False), grads(None)
    assert abs(float(got - want)) <= 1e-3 * abs(float(want))
    for name, g, w in zip(("xt", "gate_up", "down", "wgt"), g_got, g_want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), name
        np.testing.assert_allclose(g, w, atol=2e-2 * np.abs(w).max(), err_msg=name)
