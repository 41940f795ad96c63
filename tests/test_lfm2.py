"""The convolutional hybrid (``ConvHybridLM``, ``--config tiny_conv``) on the
CPU, in float32 at tiny sizes: the router with a selection bias, the model's
loss and every gradient against
``benchmark/configs/lfm2_8b_a1b_reference.py`` packed and not, the four
shares of an expert layer against the uncut layer, each planted fault
moving the loss, the form, the counters, the scopes, the app."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import lfm2_8b_a1b_reference as plain
from benchmark.reference import shaken
from sparknet_tpu.models import decoder
from sparknet_tpu.models.decoder import (
    CONV, CONV_COUNTERS, COUNTERS, FULL, ROPE_COUNTERS, ConvHybridConfig, ConvHybridLM,
)
from sparknet_tpu.parallel.moe import route_sigmoid
from tests.test_granite import packed_batch

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SHAPES = {k: (2, 64) for k in ("input_ids", "segment_ids", "positions")}


# ------------------------------------------------------------------ the router

def test_route_sigmoid_without_a_bias_traces_as_before():
    """The two routers that call it without a bias (``laguna_xs2``'s and the
    planted ones of the tests) get the program of the body they had."""
    from sparknet_tpu.parallel.moe import _top_k

    xt = jnp.ones((16, 8))
    w = jnp.ones((8, 6))

    def before(xt, w):
        scores = jax.nn.sigmoid(jnp.dot(
            xt.astype(jnp.float32), w, preferred_element_type=jnp.float32))
        top, idx = _top_k(scores, scores, 2)
        return 2.5 * top / jnp.sum(top, axis=-1, keepdims=True), idx

    now = lambda xt, w: route_sigmoid(xt, w, 2, 2.5)
    assert str(jax.make_jaxpr(now)(xt, w)) == str(jax.make_jaxpr(before)(xt, w))


def test_route_sigmoid_chooses_on_the_bias_and_weighs_without_it():
    xt = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    weights, experts = route_sigmoid(xt, w, 3, 2.0, bias=bias, eps=1e-6)
    scores = np.asarray(jax.nn.sigmoid(xt @ w))
    order = np.argsort(-(scores + np.asarray(bias)), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(experts, order)
    top = np.take_along_axis(scores, order, -1)
    np.testing.assert_allclose(weights, 2.0 * top / (top.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    unbiased = route_sigmoid(xt, w, 3, 2.0)[1]
    assert np.any(np.asarray(unbiased) != np.asarray(experts))  # the bias moved some
    # no gradient reaches the bias; the weights' reaches the router
    grad = jax.grad(lambda b, w: route_sigmoid(xt, w, 3, 2.0, bias=b)[0].sum() ** 2, (0, 1))
    g_bias, g_w = grad(bias, w)
    assert float(jnp.abs(g_bias).max()) == 0.0 and float(jnp.abs(g_w).max()) > 0.0


# ------------------------------------------------------------------ the form

def lfm2_form(cfg: ConvHybridConfig) -> dict:
    """A ConvHybridConfig of ``tiny``'s layout (conv dense, attention, conv)
    written the way ``lfm2_8b_a1b.json`` writes a cut: the published keys
    with the tiny sizes, a published list of four layers of which
    ``deployment.layers_kept`` holds 0, 2, 3 (layer 1, the second dense
    conv layer, left out as the real file leaves it out)."""
    assert cfg.layer_types == (CONV, FULL, CONV) and cfg.mlp_layer_types == (
        "dense", "sparse", "sparse")
    with open(os.path.join(_ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")) as fh:
        form = json.load(fh)
    form.update(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        num_hidden_layers=3, layer_types=[CONV, CONV, FULL, CONV], num_dense_layers=2,
        conv_L_cache=cfg.conv_L_cache, rope_theta=cfg.rope_theta,
        num_experts=cfg.experts_held[1], num_experts_per_tok=cfg.num_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_size,
        routed_scaling_factor=cfg.moe_routed_scaling_factor,
        expert_bias_std=cfg.expert_bias_std, norm_eps=cfg.rms_norm_eps,
    )
    form["deployment"] = dict(
        form["deployment"], layers_kept=[0, 2, 3], num_experts_routed=cfg.num_experts,
        experts_first=cfg.experts_held[0],
    )
    return form


def _tiny():
    cfg = ConvHybridConfig.tiny()
    model = ConvHybridLM(cfg, _SHAPES)
    params, _ = model.init(jax.random.PRNGKey(5))  # held experts see slots in both layers
    return cfg, model, shaken(params, 3.0), packed_batch(cfg)


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


def test_lfm2_form_round_trips_and_refuses_what_it_does_not_model(tiny):
    cfg, model, params, _ = tiny
    form = lfm2_form(cfg)
    assert ConvHybridConfig.from_published(form, loss_chunk=cfg.loss_chunk) == cfg
    for key, value in [
        ("conv_bias", True), ("norm_topk_prob", False), ("use_expert_bias", False),
        ("tie_word_embeddings", False),
    ]:
        with pytest.raises(ValueError, match=key):
            ConvHybridConfig.from_published(dict(form, **{key: value}))
    with pytest.raises(ValueError, match="layers_kept"):
        ConvHybridConfig.from_published(dict(form, num_hidden_layers=4))
    assert set(params["layer_00"]) == {
        "attn_norm", "ffn_norm", "in_proj", "conv_w", "out_proj", "gate_w", "up_w", "down_w"}
    assert set(params["layer_01"]) == {
        "attn_norm", "ffn_norm", "q_w", "k_w", "v_w", "o_w", "q_norm", "k_norm",
        "router_w", "router_bias", "experts_gate_up", "experts_down"}
    assert set(params["layer_02"]) == {
        "attn_norm", "ffn_norm", "in_proj", "conv_w", "out_proj",
        "router_w", "router_bias", "experts_gate_up", "experts_down"}
    assert model.input_names == ["input_ids", "labels", "segment_ids", "positions"]
    assert model.counters == COUNTERS + ROPE_COUNTERS + CONV_COUNTERS + (
        "doc_count", "loss_positions", "attn_pairs_full", "flash_tiles_docs_full")
    with pytest.raises(ValueError, match="layer type 'mamba'"):
        ConvHybridLM(dataclasses.replace(cfg, layer_types=("mamba",) * 3), _SHAPES)


def test_the_tied_head_and_the_biases_are_counted_once(tiny):
    cfg, model, params, _ = tiny
    assert set(params["head"]) == {"norm"}  # the matrix is the embedding's
    h, d = cfg.hidden_size, cfg.head_dim
    conv = h * 3 * h + h * h + cfg.conv_L_cache * h
    attention = 2 * h * h + 2 * h * (h // 2) + 2 * d
    dense = 3 * h * cfg.intermediate_size
    held, experts = cfg.experts_held[1], cfg.num_experts
    sparse = held * 3 * h * cfg.moe_intermediate_size + h * experts + experts
    assert model.num_params(params) == (
        (conv + dense + 2 * h) + (attention + sparse + 2 * h) + (conv + sparse + 2 * h)
        + cfg.vocab_size * h + h)


def test_initialisation_and_the_buffer(tiny):
    cfg, model, _, _ = tiny
    params, _ = model.init(jax.random.PRNGKey(0))
    bias = params["layer_01"]["router_bias"]
    assert bias.shape == (cfg.num_experts,)
    assert 0.2 * cfg.expert_bias_std < float(jnp.std(bias)) < 3 * cfg.expert_bias_std
    assert float(jnp.abs(params["layer_00"]["conv_w"]).max()) <= 3 ** -0.5
    specs = model.param_specs()
    assert specs["layer_01"]["router_bias"] == (0.0, 0.0)  # no step moves it
    assert {k for k, (_, decay) in specs["layer_01"].items() if decay == 0.0} == {
        "attn_norm", "ffn_norm", "q_norm", "k_norm", "router_bias"}
    zeros = ConvHybridLM(dataclasses.replace(cfg, expert_bias_std=0.0), _SHAPES)
    assert float(jnp.abs(zeros.init(jax.random.PRNGKey(0))[0]["layer_01"]["router_bias"]).max()) == 0


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "not_packed"])
def test_loss_and_every_gradient_match_the_plain_reference(tiny, remat, packed):
    cfg, _, params, batch = tiny
    shapes = _SHAPES if packed else {"input_ids": _SHAPES["input_ids"]}
    if not packed:  # one document a row, the next token at every position
        ids = batch["input_ids"]
        batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    model = ConvHybridLM(dataclasses.replace(cfg, remat=remat), shapes)
    reference = plain.make_loss(lfm2_form(cfg))
    with jax.default_matmul_precision("highest"):
        system = lambda p: model.apply(p, {}, batch, train=True)[0]["loss"]
        loss, grads = jax.value_and_grad(system)(params)
        want_loss, want = jax.value_and_grad(lambda p: reference(p, batch))(params)
    assert abs(float(loss) - float(want_loss)) < 2e-6
    for layer in want:
        for name, w in want[layer].items():
            if name == "router_bias":  # a buffer: no gradient on either side
                assert float(jnp.abs(grads[layer][name]).max()) == 0.0
                continue
            scale = float(jnp.abs(w).max())
            assert scale > 0, (layer, name)  # every leaf takes part
            assert np.all(np.isfinite(grads[layer][name])), (layer, name)
            np.testing.assert_allclose(
                grads[layer][name], w, atol=2e-4 * scale, err_msg=f"{layer}.{name}")


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares (experts 0-1, 2-3, 4-5, 6-7 of
    8 here, as 0-7 .. 24-31 of 32 in the deployment) compute add up to what
    the plain reference gives for the whole, uncut layer; the bias steers
    every share's selection alike."""
    cfg = ConvHybridConfig.tiny(experts_held=(0, 8))
    model = ConvHybridLM(cfg, {"input_ids": (2, 64)})
    lp = shaken(model.init(jax.random.PRNGKey(5))[0], 3.0)["layer_01"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.hidden_size))
    uncut = dict(lfm2_form(ConvHybridConfig.tiny()), num_experts=8)
    uncut["deployment"] = dict(uncut["deployment"], experts_first=0)
    with jax.default_matmul_precision("highest"):
        whole = plain.sparse_ffn(uncut, lp, u)
        total, slots = 0.0, 0.0
        for share in range(4):
            first = 2 * share
            part = ConvHybridLM(ConvHybridConfig.tiny(experts_held=(first, 2)), {"input_ids": (2, 64)})
            mine = {
                "router_w": lp["router_w"], "router_bias": lp["router_bias"],
                "experts_gate_up": lp["experts_gate_up"][first: first + 2],
                "experts_down": lp["experts_down"][first: first + 2],
            }
            routed, counters = part._ffn(1, mine, u)
            total = total + routed
            slots += float(counters["moe_slots_held"])
    assert slots == 2 * 64 * cfg.num_experts_per_tok  # every slot, once
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(jnp.abs(whole).max()))
    assert float(jnp.abs(total - routed - whole).max()) > 1e-3  # one share is not the layer


# ------------------------------------------------------------ planted faults

class _NoBGate(ConvHybridLM):
    def _gated_conv(self, lp, bcx, ids):
        _, c_gate, x = jnp.split(bcx, 3, axis=-1)
        return c_gate * decoder.causal_conv(x, lp["conv_w"], segment_ids=ids)


class _NoQkNorm(ConvHybridLM):
    def _qk_norm(self, lp, name, t):
        return t


class _BiasIgnored(ConvHybridLM):
    def _router(self, xt, lp):
        cfg = self.cfg
        return route_sigmoid(
            xt, lp["router_w"], cfg.num_experts_per_tok, cfg.moe_routed_scaling_factor,
            eps=decoder.ROUTER_EPS)


class _BiasWeighed(ConvHybridLM):
    def _router(self, xt, lp):
        scores = jax.nn.sigmoid(jnp.dot(xt.astype(jnp.float32), lp["router_w"]))
        top, idx = jax.lax.top_k(scores + lp["router_bias"], self.cfg.num_experts_per_tok)
        return top / (jnp.sum(top, -1, keepdims=True) + decoder.ROUTER_EPS), idx


class _Untied(ConvHybridLM):
    def _head_weight(self, params):
        return params["head"]["lm_w"].astype(self.compute_dtype)


_FAULTS = {
    "conv_mask_off": "conv_ids", "b_gate_dropped": _NoBGate, "qk_norm_dropped": _NoQkNorm,
    "bias_ignored_in_selection": _BiasIgnored, "bias_added_to_the_weights": _BiasWeighed,
    "untied_head": _Untied,
}


def plant(fault, monkeypatch, model_cls=ConvHybridLM):
    """The model class of the program with ``fault`` planted."""
    change = _FAULTS[fault]
    if change == "conv_ids":
        whole = decoder.causal_conv
        monkeypatch.setattr(decoder, "causal_conv", lambda *a, segment_ids, **kw: whole(*a, **kw))
        return model_cls
    return change


@pytest.fixture(scope="module")
def telling():
    """Weights and a batch at which every mechanism shows in the tiny loss:
    gain 8 (at 3 the q/k norms barely change the scores), the held
    experts' matrices twice that (else the routed part of a token's update
    is too small beside the residual for its weighting to show) and
    documents of median 8 tokens (a tap crosses a boundary at many
    positions)."""
    cfg, model, _, _ = _tiny()
    params = shaken(model.init(jax.random.PRNGKey(5))[0], 8.0)
    params = {
        layer: {n: 2 * w if n.startswith("experts") else w for n, w in leaves.items()}
        for layer, leaves in params.items()
    }
    return cfg, model, params, packed_batch(cfg, median=8, shortest=2)


@pytest.mark.parametrize("fault", sorted(_FAULTS))
def test_reference_tells_each_mechanism(telling, fault, monkeypatch):
    """A program that convolves across a boundary, drops the B gate or the
    q/k norms, chooses without the bias, weighs with it, or unties the head
    does not agree with the reference: each moves the tiny loss by >=
    1e-3."""
    cfg, model, params, batch = telling
    want = float(plain.make_loss(lfm2_form(cfg))(params, batch))
    sound = float(model.apply(params, {}, batch)[0]["loss"])
    broken = plant(fault, monkeypatch)(cfg, _SHAPES)
    if fault == "untied_head":  # a head of its own, drawn as the embedding is
        head = 8 * 0.02 * jax.random.normal(jax.random.PRNGKey(7), (cfg.hidden_size, cfg.vocab_size))
        params = dict(params, head=dict(params["head"], lm_w=head))
    got = float(broken.apply(params, {}, batch)[0]["loss"])
    assert abs(sound - want) < 1e-5
    assert abs(got - want) > 1e-3, (fault, got, want)


# ---------------------------------------------------- counters, scopes, app

def test_counters_reach_the_blobs_and_the_registry(tiny):
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY

    cfg, model, params, batch = tiny
    blobs = jax.jit(lambda p: model.apply(p, {}, batch)[0])(params)
    docs = int(blobs["doc_count"])
    assert docs == int((batch["positions"] == 0).sum()) > 2
    # documents begun inside the batch, in each of the two conv layers
    assert int(blobs["short_conv_resets"]) == 2 * (docs - 2)
    assert 0.0 < float(blobs["moe_bias_rerouted"]) < 1.0
    assert float(blobs["rope_rows_in_kernel"]) == 0.0  # off a TPU
    assert float(blobs["moe_slots_dropped"]) == 0.0
    unbiased = ConvHybridLM(dataclasses.replace(cfg, expert_bias_std=0.0), _SHAPES)
    zero_bias = {k: dict(v, router_bias=0 * v["router_bias"]) if "router_bias" in v else v
                 for k, v in params.items()}
    assert float(unbiased.apply(zero_bias, {}, batch)[0]["moe_bias_rerouted"]) == 0.0
    args = lm_app.parser().parse_args([
        "--config", "tiny_conv", "--seq-len", "64", "--batch-size", "2",
        "--pack-documents", "--doc-median", "20", "--doc-min", "4", "--doc-max", "64",
        "--synthetic-tokens", "4096"])
    solver, feed, _ = lm_app.build(args)
    metrics = solver.step(iter(feed), 1)
    read = REGISTRY.sources()["train_step"].snapshot()
    for name in solver.train_net.counters:
        assert read[name] == float(metrics[name]), name
    assert float(metrics["short_conv_resets"]) == 2 * (float(metrics["doc_count"]) - 2)


def test_the_compiled_step_carries_the_new_scopes_in_their_nesting(tiny):
    """The scope chains of the compiled step's instructions: the conv
    mixer's projections and its gated taps under ``attn.conv`` in forward,
    backward and recompute; the q/k norms under ``attn.full``."""
    from sparknet_tpu.utils import profiling

    _, _, params, batch = tiny
    model = ConvHybridLM(dataclasses.replace(ConvHybridConfig.tiny(), remat=True), _SHAPES)
    text = jax.jit(jax.grad(lambda p: model.apply(p, {}, batch)[0]["loss"])).lower(
        params).compile().as_text()
    table = profiling.scope_table(text, profiling.declared_scopes())
    chains = {(e.chain, e.pass_) for e in table.values()}
    for outer, inner in (("attn.conv", "attn.proj"), ("attn.conv", "conv.short"),
                         ("attn.full", "norm"), ("attn.full", "attn.proj")):
        for pass_ in ("forward", "backward", "recompute"):
            assert any(c[:1] == (outer,) and inner in c and p == pass_
                       for c, p in chains), (outer, inner, pass_)
    assert any(c[:1] == ("mlp.dense",) for c, _ in chains)
    assert any(c[:1] == ("moe.route",) for c, _ in chains)


def test_lm_app_trains_the_conv_hybrid_and_prints_the_counters(capsys):
    from sparknet_tpu.apps import lm_app

    lm_app.main([
        "--config", "tiny_conv", "--max-iter", "4", "--display", "2",
        "--seq-len", "64", "--batch-size", "2", "--pack-documents",
        "--doc-median", "20", "--doc-min", "4", "--doc-max", "64",
        "--synthetic-tokens", "4096", "--remat"])
    out = capsys.readouterr().out
    assert "short_conv_resets = " in out and "moe_bias_rerouted = " in out
    assert "moe_slots_dropped = 0," in out and "rope_rows_in_kernel = 0," in out
    assert "flash_tiles_docs_full = " in out and "window" not in out.split("LmApp:")[1]


def test_a_published_file_drives_the_app(tmp_path):
    from sparknet_tpu.apps import lm_app

    path = tmp_path / "lfm2_tiny.json"
    path.write_text(json.dumps(lfm2_form(ConvHybridConfig.tiny())))
    args = lm_app.parser().parse_args(["--config", str(path)])
    cfg = lm_app.make_config(args)
    assert isinstance(cfg, ConvHybridConfig) and lm_app.model_class(cfg) is ConvHybridLM
    assert cfg.layer_types == (CONV, FULL, CONV)
    assert cfg.mlp_layer_types == ("dense", "sparse", "sparse")
    assert lm_app.flash_tiles(cfg, 1024) == {
        "full_attention_unmasked": 0, "full_attention_masked": 3}
