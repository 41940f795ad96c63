"""The hybrid decoder (KDA linear attention and MLA latent attention over
experts routed by groups) on the CPU at a tiny size that keeps every kind of
layer: against its plain reference (``benchmark/configs/
ling3_flash_reference.py``, which shares no code with it), the chunked scan
against the token-by-token recurrence, the flash kernels at unequal head
sizes, the grouped router against a sort-based one, the add-up of the expert
shares, planted faults, and the app."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.configs import ling3_flash_reference as plain  # noqa: E402
from benchmark.reference import shaken  # noqa: E402
from sparknet_tpu.models import decoder  # noqa: E402
from sparknet_tpu.models.decoder import (  # noqa: E402
    COUNTERS, KDA, KDA_COUNTERS, MLA, HybridConfig, HybridLM, swiglu,
)
from sparknet_tpu.ops.attention import flash_attention, mha_reference  # noqa: E402
from sparknet_tpu.ops.kda import kda_chunks, kda_recurrent, kda_scan  # noqa: E402
from sparknet_tpu.parallel.moe import held_experts_ffn, route_grouped  # noqa: E402

PERIOD = 3  # the tiny preset's layer_group_size: KDA, KDA, MLA


def published_form(cfg: HybridConfig) -> dict:
    """A HybridConfig written the way ``ling3_flash.json`` writes a cut."""
    assert cfg.layer_types == tuple(
        MLA if (i + 1) % PERIOD == 0 else KDA for i in range(cfg.num_layers)
    )
    return {
        "model_type": "bailing_hybrid",
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_attention_heads,
        "head_dim": cfg.head_dim, "layer_group_size": PERIOD,
        "first_k_dense_replace": cfg.mlp_layer_types.count("dense"),
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "short_conv_kernel_size": cfg.short_conv_kernel_size,
        "kda_lower_bound": cfg.kda_lower_bound,
        "num_experts": cfg.experts_held[1],
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "moe_shared_expert_intermediate_size": cfg.shared_expert_intermediate_size,
        "num_shared_experts": 1,
        "routed_scaling_factor": cfg.moe_routed_scaling_factor,
        "rms_norm_eps": cfg.rms_norm_eps,
        "deployment": {
            "num_experts_routed": cfg.num_experts,
            "experts_first": cfg.experts_held[0],
        },
    }


TINY_OWN = dict(loss_chunk=32, kda_chunk=16, kda_segment=32)  # not published keys


def _batch(cfg, b=2, s=64, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0, cfg.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


@pytest.fixture(scope="module")
def tiny():
    cfg = HybridConfig.tiny()
    model = HybridLM(cfg, {"input_ids": (2, 64)})
    params, _ = model.init(jax.random.PRNGKey(3))
    return cfg, model, shaken(params, 3.0)  # off the flat start, as the benchmark does


def test_published_form_round_trips():
    cfg = HybridConfig.tiny()
    assert HybridConfig.from_published(published_form(cfg), **TINY_OWN) == cfg
    assert cfg.layer_types == (KDA, KDA, MLA)
    assert cfg.mlp_layer_types == ("dense", "sparse", "sparse")
    # a cut names the published layers it keeps: 0, 2, 3, 4, 5, 6 of a period of 6
    cut = dict(
        published_form(cfg), num_hidden_layers=6, layer_group_size=6,
        first_k_dense_replace=2,
    )
    cut["deployment"] = dict(cut["deployment"], layers_kept=[0, 2, 3, 4, 5, 6])
    kept = HybridConfig.from_published(cut)
    assert kept.layer_types == (KDA, KDA, KDA, KDA, MLA, KDA)
    assert kept.mlp_layer_types == ("dense",) + ("sparse",) * 5
    with pytest.raises(ValueError, match="layers_kept"):
        HybridConfig.from_published(dict(cut, num_hidden_layers=5))
    with pytest.raises(ValueError, match="kda_lower_bound -8"):
        HybridConfig.from_published(dict(cut, kda_lower_bound=-8))


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_match_the_plain_reference(tiny, remat):
    cfg, model, params = tiny
    if remat:
        model = HybridLM(dataclasses.replace(cfg, remat=True), {"input_ids": (2, 64)})
    batch = _batch(cfg)  # 64 tokens: two segments of two chunks a KDA layer
    reference = plain.make_loss(published_form(cfg))
    with jax.default_matmul_precision("highest"):
        system = lambda p: model.apply(p, {}, batch, train=True)[0]["loss"]
        loss, grads = jax.value_and_grad(system)(params)
        want_loss, want = jax.value_and_grad(lambda p: reference(p, batch))(params)
    assert abs(float(loss) - float(want_loss)) < 2e-6
    assert 3.0 < float(want_loss) < 8.0
    for layer in want:
        for name, w in want[layer].items():
            scale = float(jnp.abs(w).max())
            if name == "router_bias":  # a buffer: it steers the selection only
                assert scale == 0 and not np.any(grads[layer][name])
                continue
            assert scale > 0, (layer, name)  # every other leaf takes part
            np.testing.assert_allclose(
                grads[layer][name], w, atol=2e-4 * scale, err_msg=f"{layer}.{name}"
            )


# ------------------------------------------------------------------ the scan

def _scan_inputs(s, strong, b=2, h=3, dk=8, dv=6, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (b, h, s, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, h, s, dk)))
    v = jax.random.normal(keys[2], (b, h, s, dv))
    # strong: most channels decay by nearly exp(-5) a token, the bound
    spread, centre = (3.0, 4.0) if strong else (1.0, -2.0)
    g = -5 * jax.nn.sigmoid(spread * jax.random.normal(keys[3], (b, h, s, dk)) + centre)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, h, s)))
    return q, k, v, g, beta


def _in_segments(x, segment, chunk):
    """``kda_scan`` over ``segment`` tokens a call with the state carried
    between calls, as ``HybridLM._kda`` cuts a sequence; one call where
    ``segment`` is None."""
    if segment is None:
        return kda_scan(*x, chunk=chunk)
    outs, state = [], None
    for at in range(0, x[0].shape[2], segment):
        out, state = kda_scan(
            *(a[:, :, at:at + segment] for a in x), chunk=chunk,
            initial_state=state, return_state=True,
        )
        outs.append(out)
    return jnp.concatenate(outs, axis=2)


@pytest.mark.parametrize("strong", [False, True], ids=["mild_decay", "decay_at_the_bound"])
@pytest.mark.parametrize("seq,chunk,segment", [
    (64, 16, None), (128, 64, None), (96, 32, 32),  # the chunk divides the sequence
    (50, 16, None), (100, 64, None), (37, 8, 16), (200, 64, 128),  # and does not
])
def test_chunked_scan_matches_the_recurrence(seq, chunk, segment, strong):
    """Forward and every input's gradient, for chunks that do and do not
    divide the sequence, one call and a sequence cut into segments, and
    decays down to the bound, where a factorised ``exp(G_t) exp(-G_s)``
    would overflow."""
    x = _scan_inputs(seq, strong)
    got = _in_segments(x, segment, chunk)
    want = kda_recurrent(*x)
    assert got.shape == want.shape == (2, 3, seq, 6)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=5e-6)
    head = lambda o: jnp.sum(jnp.sin(o))
    grads = jax.grad(lambda *a: head(_in_segments(a, segment, chunk)), range(5))(*x)
    wants = jax.grad(lambda *a: head(kda_recurrent(*a)), range(5))(*x)
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        np.testing.assert_allclose(
            g, w, atol=3e-5 * float(jnp.abs(w).max()), err_msg=name
        )


def test_scan_carries_a_state_in_and_out():
    """Two halves through ``initial_state`` are the whole sequence."""
    x = _scan_inputs(96, strong=False)
    whole, end = kda_scan(*x, chunk=16, return_state=True)
    first = [a[:, :, :40] for a in x]
    rest = [a[:, :, 40:] for a in x]
    left, state = kda_scan(*first, chunk=16, return_state=True)
    right, end_again = kda_scan(*rest, chunk=16, initial_state=state, return_state=True)
    np.testing.assert_allclose(jnp.concatenate([left, right], 2), whole, atol=5e-6)
    np.testing.assert_allclose(end_again, end, atol=5e-6)
    assert kda_chunks(16384) == 256 and kda_chunks(100, 64) == 2
    with pytest.raises(ValueError, match="chunk 48"):
        kda_scan(*x, chunk=48)


# ------------------------------------------------- flash at 192 / 128

@pytest.mark.parametrize("case", ["mla_192_128", "grouped_192_128", "window_192_128"])
def test_flash_kernels_take_a_value_head_narrower_than_the_keys(case):
    """q and k of 192 (128 + 64 rotary), v of 128, as MLA's expanded form
    has them: forward, dq, dk and dv against ``mha_reference``, through the
    plain kernels and the banded ones (interpret mode)."""
    h, hkv, window = {"mla_192_128": (4, 4, None), "grouped_192_128": (4, 2, None),
                      "window_192_128": (4, 4, 100)}[case]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, h, 256, 192))
    k = jax.random.normal(keys[1], (1, hkv, 256, 192))
    v = jax.random.normal(keys[2], (1, hkv, 256, 128))
    kw = dict(causal=True, window=window, scale=192 ** -0.5)
    flash = lambda *a: flash_attention(*a, interpret=True, block_q=128, block_k=128, **kw)
    plain_attention = lambda *a: mha_reference(*a, **kw)
    got, want = flash(q, k, v), plain_attention(q, k, v)
    assert got.shape == (1, h, 256, 128)
    np.testing.assert_allclose(got, want, atol=3e-6)
    head = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    for name, g, w in zip(
        "dq dk dv".split(),
        jax.grad(head(flash), (0, 1, 2))(q, k, v),
        jax.grad(head(plain_attention), (0, 1, 2))(q, k, v),
    ):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=2e-5, err_msg=name)


# ---------------------------------------------------------------- the router

def _router_inputs(ties: bool, tokens=48, hidden=16, experts=32, seed=7):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (tokens, hidden))
    router_w = jax.random.normal(keys[1], (hidden, experts))
    bias = 0.3 * jax.random.normal(keys[2], (experts,))
    if ties:
        # experts 1, 9, 17, 25 (one a group) score alike, and so do 2 and 3
        # of group 0; tokens 0-3 are zero: every expert scores 0.5
        for twin in (9, 17, 25):
            router_w = router_w.at[:, twin].set(router_w[:, 1])
        router_w = router_w.at[:, 3].set(router_w[:, 2])
        bias = bias.at[jnp.asarray([9, 17, 25])].set(bias[1]).at[3].set(bias[2])
        x = x.at[:4].set(0.0)
    return x, router_w, bias


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_grouped_router_against_a_sort_based_one(ties):
    """The program's router (``lax.top_k`` three times over) against the
    reference's (stable sorts): the same experts in the same order, ties
    included, and the same weights, which come from the unbiased scores."""
    x, router_w, bias = _router_inputs(ties)
    config = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 5,
              "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        weights, experts = route_grouped(x, router_w, bias, 5, 2.5, 4, 2)
        want_w, want_e = plain.route(
            config, {"router_w": router_w, "router_bias": bias}, x
        )
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(weights, want_w, rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    groups = np.asarray(experts) // 8
    assert all(len(set(row)) <= 2 for row in groups)  # 2 of the 4 groups
    unbiased = route_grouped(x, router_w, jnp.zeros_like(bias), 5, 2.5, 4, 2)[1]
    if ties:  # a zero token without the bias: every expert scores 0.5
        assert np.array_equal(np.asarray(unbiased[0]), [0, 1, 2, 3, 4])
    # the bias steers the selection and takes no gradient
    assert not np.array_equal(experts, unbiased)
    grad = jax.grad(lambda b: route_grouped(x, router_w, b, 5, 2.5, 4, 2)[0][:, 0].sum())(bias)
    assert not np.any(grad)


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="holds what the chip compiles lax.top_k to, ties and all",
)
def test_grouped_router_against_a_sort_based_one_on_hardware():
    """``ling_train_s16k``'s router shapes on the chip: 16 384 tokens, 512
    experts in 8 groups, 4 groups and 8 experts a token.  The router's
    matrix is the identity and the product runs at the highest precision,
    so both sides score ``sigmoid(x)`` to the bit and must choose the same
    experts in the same order; columns 5, 69 and 70 are alike (a tie across
    groups and one inside a group) and the first 64 tokens score every
    expert alike."""
    keys = jax.random.split(jax.random.PRNGKey(512), 2)
    x = jax.random.normal(keys[0], (16384, 512))
    x = x.at[:, 69].set(x[:, 5]).at[:, 70].set(x[:, 5]).at[:64].set(0.0)
    bias = 0.05 * jax.random.normal(keys[1], (512,))
    bias = bias.at[jnp.asarray([5, 69, 70])].set(0.05)
    config = {"n_group": 8, "topk_group": 4, "num_experts_per_tok": 8,
              "routed_scaling_factor": 2.5}
    p = {"router_w": jnp.eye(512), "router_bias": bias}
    with jax.default_matmul_precision("highest"):
        weights, experts = jax.jit(
            lambda x: route_grouped(x, p["router_w"], bias, 8, 2.5, 8, 4)
        )(x)
        want_w, want_e = jax.jit(lambda x: plain.route(config, p, x))(x)
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(weights, want_w, rtol=1e-5)
    assert all(len(set(row)) <= 4 for row in np.asarray(experts) // 64)
    chosen_tie = np.asarray(experts)[np.any(np.asarray(experts) == 70, axis=1)]
    assert len(chosen_tie) > 100  # the tie is met, not only planted


def test_the_shares_add_up_to_the_uncut_layer():
    """64 chips share a layer of ``ling3_flash``; here 8 shares of 2 experts:
    the routed parts that all the shares compute, with the shared expert
    counted once, add up to what the plain reference gives for the whole,
    uncut layer."""
    cfg = HybridConfig.tiny(experts_held=(0, 16))
    model = HybridLM(cfg, {"input_ids": (2, 64)})
    params, _ = model.init(jax.random.PRNGKey(5))
    lp = shaken(params, 3.0)["layer_01"]  # the shaking moves the bias off 0
    assert float(jnp.abs(lp["router_bias"]).max()) > 0
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = plain._sparse_ffn(published_form(cfg), lp, u)
        total = swiglu(u, lp["shared_gate_w"], lp["shared_up_w"], lp["shared_down_w"])
        slots = 0.0
        for share in range(8):
            first = 2 * share
            mine = dict(
                lp, experts_gate_up=lp["experts_gate_up"][first: first + 2],
                experts_down=lp["experts_down"][first: first + 2],
            )
            routed, counters = held_experts_ffn(
                u, mine, experts_held=(first, 2), top_k=cfg.num_experts_per_tok,
                router=model._router,
            )
            total = total + routed
            slots += float(counters["moe_slots_held"])
    assert slots == 2 * 64 * cfg.num_experts_per_tok  # every slot, once
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(jnp.abs(whole).max()))
    assert float(jnp.abs(total - routed - whole).max()) > 1e-3  # one share is not the layer


# ---------------------------------------------------------- planted faults

def _no_mla(model_cls):
    class SkipsMla(model_cls):
        def _mix(self, li, lp, u, docs=None):
            if self.cfg.layer_types[li] == MLA:
                return jnp.zeros(u.shape, jnp.float32), {}
            return super()._mix(li, lp, u)

    return SkipsMla


def plant(fault, cfg, monkeypatch, model_cls=HybridLM):
    """(model class, configuration) with ``fault`` planted in the program:
    the five mechanisms the issue names.  The chip's table of PERF.md
    section 2 plants the same ones at the cell's shapes."""
    if fault == "mla_layer_skipped":
        return _no_mla(model_cls), cfg
    if fault == "decay_gate_held_at_1":  # no forgetting: g = 0
        return model_cls, dataclasses.replace(cfg, kda_lower_bound=-0.0)
    if fault == "three_of_eight_groups":  # one group fewer than topk_group
        return model_cls, dataclasses.replace(cfg, topk_group=cfg.topk_group - 1)
    if fault == "beta_held_at_1":
        scan = decoder.kda_scan
        monkeypatch.setattr(
            decoder, "kda_scan",
            lambda q, k, v, g, beta, **kw: scan(q, k, v, g, jnp.ones_like(beta), **kw),
        )
    elif fault == "convolution_left_out":
        monkeypatch.setattr(decoder, "causal_conv", lambda x, w, history=None: x)
    else:
        raise KeyError(fault)
    return model_cls, cfg


FAULTS = [
    "mla_layer_skipped", "decay_gate_held_at_1", "beta_held_at_1",
    "convolution_left_out", "three_of_eight_groups",
]


@pytest.mark.parametrize("fault", FAULTS)
def test_reference_tells_each_mechanism(tiny, fault, monkeypatch):
    """Each mechanism moves the tiny loss by far more than rounding does."""
    cfg, model, _ = tiny
    params = shaken(model.init(jax.random.PRNGKey(3))[0], 8.0)
    batch = _batch(cfg)
    want = float(plain.make_loss(published_form(cfg))(params, batch))
    sound = float(model.apply(params, {}, batch)[0]["loss"])
    broken_cls, broken_cfg = plant(fault, cfg, monkeypatch)
    broken = broken_cls(broken_cfg, {"input_ids": (2, 64)})
    got = float(broken.apply(params, {}, batch)[0]["loss"])
    assert abs(sound - want) < 1e-4
    assert abs(got - want) >= 1e-3, (fault, got, want)


# ------------------------------------------------------ counters and the app

def test_counters_reach_the_blobs_and_the_registry():
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY

    args = lm_app.parser().parse_args(
        ["--config", "tiny_hybrid", "--seq-len", "64", "--batch-size", "2",
         "--synthetic-tokens", "2048", "--max-iter", "2", "--seed", "3"]
    )
    solver, feed, cfg = lm_app.build(args)
    assert isinstance(solver.train_net, HybridLM)
    assert solver.train_net.counters == COUNTERS + KDA_COUNTERS
    metrics = solver.step(iter(feed), 2)
    assert float(metrics["moe_slots_dropped"]) == 0.0
    assert 0 < float(metrics["moe_slots_held"]) <= 2 * 64 * cfg.num_experts_per_tok
    assert float(metrics["kda_chunks"]) == 4.0  # 64 tokens in chunks of 16
    assert np.exp(-5.0) < float(metrics["kda_decay_min"]) < 1.0
    read = REGISTRY.sources()["train_step"].snapshot()
    for name in COUNTERS + KDA_COUNTERS:
        assert read[name] == float(metrics[name])
    # the router's bias is a buffer: no step moves it
    assert not np.any(solver.params["layer_01"]["router_bias"])


def test_lm_app_main_trains_the_hybrid_and_prints_the_counters(capsys):
    from sparknet_tpu.apps import lm_app

    metrics = lm_app.main(
        ["--config", "tiny_hybrid", "--seq-len", "32", "--batch-size", "4",
         "--max-iter", "12", "--display", "6", "--lr", "3e-3",
         "--synthetic-tokens", "4096"]
    )
    out = capsys.readouterr().out
    assert "Iteration 12, loss = " in out and "moe_slots_dropped = 0" in out
    assert "kda_chunks = 2, kda_chunks_in_kernel = 0, kda_decay_min = 0." in out
    assert "experts_held=(4, 4) of 16" in out
    assert np.isfinite(metrics["loss"]) and metrics["kda_chunks"] == 2.0


def test_a_published_file_drives_the_app(tmp_path):
    from sparknet_tpu.apps import lm_app

    path = tmp_path / "cut.json"
    path.write_text(json.dumps(published_form(HybridConfig.tiny())))
    args = lm_app.parser().parse_args(
        ["--config", str(path), "--remat", "--seq-len", "16",
         "--batch-size", "2", "--synthetic-tokens", "512"]
    )
    cfg = lm_app.make_config(args)
    defaults = dict(remat=True, loss_chunk=4096, kda_chunk=64, kda_segment=1024)
    assert cfg == HybridConfig.tiny(**defaults)
    assert lm_app.flash_tiles(cfg, 1024) == {"mla_unmasked": 0, "mla_masked": 3}
    solver, feed, _ = lm_app.build(args)  # 16 tokens: one segment, one chunk
    assert np.isfinite(float(solver.step(iter(feed), 1)["loss"]))


def test_a_sequence_is_whole_segments():
    """Longer than ``kda_segment`` and not a multiple of it: refused, and
    not run as one segment without the memory bound."""
    cfg = HybridConfig.tiny()  # segments of 32 tokens
    model = HybridLM(cfg, {"input_ids": (2, 48)})
    params, _ = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="48 tokens .* segments of 32"):
        model.apply(params, {}, _batch(cfg, s=48))


def test_new_scopes_are_in_the_lowered_step():
    model = HybridLM(HybridConfig.tiny(), {"input_ids": (2, 32)})
    params, _ = model.init(jax.random.PRNGKey(0))
    batch = _batch(model.cfg, s=32)
    text = jax.jit(
        jax.grad(lambda p: model.apply(p, {}, batch, train=True)[0]["loss"])
    ).lower(params).as_text(debug_info=True)
    for scope in ("attn.kda", "kda.scan", "attn.mla", "moe.route", "moe.experts",
                  "moe.shared", "lm_head"):
        assert scope in text, scope
