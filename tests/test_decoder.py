"""The decoder (window + full attention over sparse experts) on the CPU at a
tiny size that keeps every kind of layer: against its plain reference
(``benchmark/configs/laguna_xs2_reference.py``, which shares no code with
it), the add-up of the expert shares, the feed and the app."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.configs import laguna_xs2_reference as plain  # noqa: E402
from benchmark.reference import shaken  # noqa: E402
from sparknet_tpu.models import decoder  # noqa: E402
from sparknet_tpu.models.decoder import (  # noqa: E402
    COUNTERS, ROPE_COUNTERS, DecoderConfig, DecoderLM, swiglu,
)
from sparknet_tpu.parallel.moe import held_experts_ffn  # noqa: E402


def published_form(cfg: DecoderConfig) -> dict:
    """A DecoderConfig written the way ``laguna_xs2.json`` writes a cut."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_key_value_heads": cfg.num_key_value_heads,
        "head_dim": cfg.head_dim, "layer_types": list(cfg.layer_types),
        "mlp_layer_types": list(cfg.mlp_layer_types),
        "num_attention_heads_per_layer": list(cfg.num_attention_heads_per_layer),
        "rope_parameters": cfg.rope_parameters,
        "sliding_window": cfg.sliding_window,
        "num_experts": cfg.experts_held[1],
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "shared_expert_intermediate_size": cfg.shared_expert_intermediate_size,
        "moe_routed_scaling_factor": cfg.moe_routed_scaling_factor,
        "rms_norm_eps": cfg.rms_norm_eps,
        "deployment": {
            "num_experts_routed": cfg.num_experts,
            "experts_first": cfg.experts_held[0],
        },
    }


def _batch(cfg, b=2, s=64, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0, cfg.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


@pytest.fixture(scope="module")
def tiny():
    cfg = DecoderConfig.tiny()
    model = DecoderLM(cfg, {"input_ids": (2, 64)})
    params, _ = model.init(jax.random.PRNGKey(3))
    return cfg, model, shaken(params, 3.0)  # off the flat start, as the benchmark does


def test_published_form_round_trips():
    cfg = DecoderConfig.tiny()
    again = DecoderConfig.from_published(published_form(cfg), loss_chunk=cfg.loss_chunk)
    assert again == cfg
    assert {cfg.layer_types[0], cfg.layer_types[1]} == {
        "full_attention", "sliding_attention"
    }
    assert len(set(cfg.num_attention_heads_per_layer)) == 2
    assert cfg.mlp_layer_types[0] == "dense" and "sparse" in cfg.mlp_layer_types


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_match_the_plain_reference(tiny, remat):
    cfg, model, params = tiny
    if remat:
        model = DecoderLM(dataclasses.replace(cfg, remat=True), {"input_ids": (2, 64)})
    batch = _batch(cfg)
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
            assert scale > 0, (layer, name)  # every leaf takes part
            np.testing.assert_allclose(
                grads[layer][name], w, atol=2e-4 * scale, err_msg=f"{layer}.{name}"
            )


_FAULTS = {
    "window_doubled": dict(sliding_window=16),
    "one_expert_more_a_token": dict(num_experts_per_tok=3),
    "no_routed_scaling": dict(moe_routed_scaling_factor=1.0),
    "yarn_left_off_full_layers": "yarn",
    "rotary_left_off_sliding_keys": "keys",
}


@pytest.mark.parametrize("fault", sorted(_FAULTS))
def test_reference_tells_each_mechanism(tiny, fault, monkeypatch):
    """Each mechanism moves the loss by far more than rounding: a program
    with another window, another top-k, no scaling factor, no YaRN or
    unrotated sliding keys does not agree with the reference."""
    cfg, model, _ = tiny
    # a larger gain than the fixture's: at this width the scores are flat
    params = shaken(model.init(jax.random.PRNGKey(3))[0], 8.0)
    change = _FAULTS[fault]
    if change == "yarn":
        full = dict(cfg.rope_parameters["full_attention"], rope_type="default")
        change = dict(rope_parameters={**cfg.rope_parameters, "full_attention": full})
    batch = _batch(cfg)
    want = float(plain.make_loss(published_form(cfg))(params, batch))
    sound = float(model.apply(params, {}, batch)[0]["loss"])
    if change == "keys":
        # planted from outside: keys (the tensors with the KV head count)
        # pass unrotated where the whole head rotates, as on sliding layers
        rotate = decoder.apply_rope

        def unrotated_keys(x, positions, inv_freq, scale):
            sliding_key = (
                x.shape[2] == cfg.num_key_value_heads
                and 2 * inv_freq.shape[0] == cfg.head_dim
            )
            return x if sliding_key else rotate(x, positions, inv_freq, scale)

        monkeypatch.setattr(decoder, "apply_rope", unrotated_keys)
        change = {}
    broken = DecoderLM(dataclasses.replace(cfg, **change), {"input_ids": (2, 64)})
    got = float(broken.apply(params, {}, batch)[0]["loss"])
    assert abs(sound - want) < 1e-4
    assert abs(got - want) > 1e-3, (fault, got, want)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that the eight shares of a sparse layer compute,
    with the shared expert counted once, add up to what the plain reference
    gives for the whole, uncut layer."""
    cfg = DecoderConfig.tiny(experts_held=(0, 16))
    model = DecoderLM(cfg, {"input_ids": (2, 64)})
    params, _ = model.init(jax.random.PRNGKey(5))
    lp = shaken(params, 3.0)["layer_01"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = plain._sparse_ffn(published_form(cfg), lp, u)
        total = swiglu(u, lp["shared_gate_w"], lp["shared_up_w"], lp["shared_down_w"])
        slots = 0.0
        for share in range(8):
            first = 2 * share
            mine = {
                "router_w": lp["router_w"],
                "experts_gate_up": lp["experts_gate_up"][first: first + 2],
                "experts_down": lp["experts_down"][first: first + 2],
            }
            routed, counters = held_experts_ffn(
                u, mine, experts_held=(first, 2), top_k=cfg.num_experts_per_tok,
                router=model._router,
            )
            total = total + routed
            slots += float(counters["moe_slots_held"])
    assert slots == 2 * 64 * cfg.num_experts_per_tok  # every slot, once
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(jnp.abs(whole).max()))
    # and one share alone is not the layer
    assert float(jnp.abs(total - routed - whole).max()) > 1e-3


def test_counters_reach_the_blobs_and_the_registry(tiny):
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY

    args = lm_app.parser().parse_args(
        ["--config", "tiny", "--seq-len", "32", "--batch-size", "2",
         "--synthetic-tokens", "2048", "--max-iter", "2", "--seed", "3"]
    )
    solver, feed, cfg = lm_app.build(args)
    metrics = solver.step(iter(feed), 2)
    tokens = 2 * 32
    assert float(metrics["moe_slots_dropped"]) == 0.0
    assert 0 < float(metrics["moe_slots_held"]) <= tokens * cfg.num_experts_per_tok
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    read = REGISTRY.sources()["train_step"].snapshot()
    assert {k: read[k] for k in COUNTERS} == {k: float(metrics[k]) for k in COUNTERS}
    assert read["rope_rows_in_kernel"] == 0.0  # heads of 16: apply_rope's path
    assert read["loss"] == float(metrics["loss"])


def test_clm_feed_shifts_by_one_and_stays_in_the_slice():
    from sparknet_tpu.data.text import clm_dataset, clm_feed

    ds = clm_dataset(vocab_size=50, n_tokens=1000, seq_len=16, seed=2)
    batch = next(iter(clm_feed(ds, 4, seed=2)))
    assert batch["input_ids"].shape == batch["labels"].shape == (4, 16)
    assert batch["input_ids"].dtype == np.int32
    np.testing.assert_array_equal(batch["input_ids"][:, 1:], batch["labels"][:, :-1])
    assert 0 <= batch["input_ids"].min() and batch["labels"].max() < 50
    with pytest.raises(ValueError, match="no window"):
        clm_dataset(vocab_size=50, n_tokens=10, seq_len=16)


def test_lm_app_main_trains_and_prints_the_counters(capsys):
    from sparknet_tpu.apps import lm_app

    metrics = lm_app.main(
        ["--config", "tiny", "--seq-len", "32", "--batch-size", "4", "--max-iter",
         "12", "--display", "6", "--lr", "3e-3", "--synthetic-tokens", "4096"]
    )
    out = capsys.readouterr().out
    assert "Iteration 12, loss = " in out and "moe_slots_dropped = 0" in out
    assert "experts_held=(4, 4) of 16" in out
    assert "flash_tiles=none (reference attention)" in out  # no TPU here
    assert np.isfinite(metrics["loss"]) and metrics["moe_slots_dropped"] == 0.0


def test_lm_app_counts_the_flash_kernels_tiles_by_kind_of_layer():
    """The start-up line's (and the registry's ``flash_tiles`` gauges')
    numbers: tiles a batch-head executes, none told apart from the masked."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY

    cfg = dataclasses.replace(DecoderConfig.tiny(), sliding_window=512)
    tiles = lm_app.flash_tiles(cfg, 8192)
    assert tiles == {
        "full_attention_unmasked": 0, "full_attention_masked": 136,
        "sliding_attention_unmasked": 0, "sliding_attention_masked": 31,
    }
    assert lm_app.flash_tiles_note(tiles) == f"flash_tiles={tiles}"
    series = REGISTRY.snapshot()["metrics"]["flash_tiles"]
    assert series["kind=full_attention_masked"]["value"] == 136


def test_a_published_file_drives_the_app(tmp_path):
    import json

    from sparknet_tpu.apps import lm_app

    path = tmp_path / "cut.json"
    path.write_text(json.dumps(published_form(DecoderConfig.tiny())))
    args = lm_app.parser().parse_args(
        ["--config", str(path), "--remat", "--seq-len", "16",
         "--batch-size", "2", "--synthetic-tokens", "512"]
    )
    cfg = lm_app.make_config(args)
    assert cfg == DecoderConfig.tiny(remat=True, loss_chunk=4096)  # the default


def test_a_loss_chunk_that_does_not_divide_the_tokens_is_refused(tiny):
    cfg, _model, params = tiny
    model = DecoderLM(dataclasses.replace(cfg, loss_chunk=48), {"input_ids": (2, 64)})
    with pytest.raises(ValueError, match="loss_chunk 48 does not divide 128"):
        model.apply(params, {}, _batch(cfg))
    # fewer tokens than a chunk are one chunk
    few = _batch(cfg, b=1, s=24)
    assert np.isfinite(float(model.apply(params, {}, few)[0]["loss"]))


# ---------------------------------------------------------------------------
# packed documents, a softmax router and no shared expert: the Mellum shape
# (``benchmark/configs/mellum2_reference.py``, which shares no code with the
# program and derives positions, mask and loss positions from the ids alone)
# ---------------------------------------------------------------------------

from benchmark.configs import mellum2_reference as packed_plain  # noqa: E402
from sparknet_tpu.models.decoder import DOC_COUNTERS, HybridConfig, HybridLM  # noqa: E402

_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 8,
        "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 4,
        "attention_factor": 1.2,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}


def tiny_mellum(**overrides) -> DecoderConfig:
    """Mellum2's shape at a size for the CPU: three sliding layers to one
    full, one head count, every layer sparse, no shared expert, softmax
    router normalised over the chosen, rotary on the whole head."""
    fields = dict(
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        mlp_layer_types=("sparse",) * 4, num_attention_heads_per_layer=(4,) * 4,
        shared_expert_intermediate_size=0, scoring_func="softmax",
        moe_routed_scaling_factor=1.0, rope_parameters=_ROPE,
    )
    fields.update(overrides)
    return DecoderConfig.tiny(**fields)


def mellum_form(cfg: DecoderConfig) -> dict:
    """A DecoderConfig written the way ``mellum2.json`` writes a cut."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_attention_heads_per_layer[0],
        "num_key_value_heads": cfg.num_key_value_heads,
        "head_dim": cfg.head_dim, "layer_types": list(cfg.layer_types),
        "mlp_layer_types": list(cfg.mlp_layer_types),
        "rope_parameters": cfg.rope_parameters,
        "sliding_window": cfg.sliding_window,
        "num_experts": cfg.experts_held[1],
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "norm_topk_prob": True, "scoring_func": cfg.scoring_func,
        "rms_norm_eps": cfg.rms_norm_eps,
        "deployment": {
            "num_experts_routed": cfg.num_experts,
            "experts_first": cfg.experts_held[0],
        },
    }


_PACKED_SHAPES = {k: (2, 64) for k in ("input_ids", "segment_ids", "positions")}


def packed_batch(cfg, b=2, s=64, seed=1):
    from sparknet_tpu.data.text import packed_dataset, packed_feed

    ds = packed_dataset(
        vocab_size=cfg.vocab_size, n_tokens=64 * s, seq_len=s, median_len=20,
        min_len=4, max_len=s, seed=seed,
    )
    return {k: jnp.asarray(v) for k, v in next(iter(packed_feed(ds, b, seed=seed))).items()}


@pytest.fixture(scope="module")
def packed():
    cfg = tiny_mellum()
    model = DecoderLM(cfg, _PACKED_SHAPES)
    params, _ = model.init(jax.random.PRNGKey(3))
    return cfg, model, shaken(params, 3.0), packed_batch(cfg)


def test_mellum_form_round_trips_and_has_no_shared_expert(packed):
    cfg, model, params, _batch = packed
    assert DecoderConfig.from_published(mellum_form(cfg), loss_chunk=cfg.loss_chunk) == cfg
    assert cfg.scoring_func == "softmax"
    with pytest.raises(ValueError, match="norm_topk_prob"):
        DecoderConfig.from_published(dict(mellum_form(cfg), norm_topk_prob=False))
    for layer, leaves in params.items():
        assert not any(name.startswith("shared_") for name in leaves), layer
    assert set(params["layer_00"]) == {
        "attn_norm", "q_w", "k_w", "v_w", "o_w", "ffn_norm", "router_w",
        "experts_gate_up", "experts_down"}
    assert model.input_names == ["input_ids", "labels", "segment_ids", "positions"]
    assert DecoderLM.counters == COUNTERS + ROPE_COUNTERS
    assert model.counters == DecoderLM.counters + DOC_COUNTERS
    assert set(model.dummy_batch()) == set(model.input_names)
    with pytest.raises(ValueError, match="scoring_func 'tanh'"):
        DecoderLM(tiny_mellum(scoring_func="tanh"), _PACKED_SHAPES)


def test_the_sparse_layers_step_has_no_shared_scope(packed):
    """No ``shared_*`` leaf and no ``moe.shared`` scope where the file gives
    the shared expert no width; the scopes that stay are there."""
    cfg, model, params, batch = packed
    text = jax.jit(lambda p: model.apply(p, {}, batch)[0]["loss"]).lower(
        params).as_text(debug_info=True)
    assert "moe.route" in text and "moe.experts" in text
    assert "moe.shared" not in text


@pytest.mark.parametrize("remat", [False, True])
def test_packed_loss_and_every_gradient_match_the_plain_reference(packed, remat):
    cfg, model, params, batch = packed
    if remat:
        model = DecoderLM(dataclasses.replace(cfg, remat=True), _PACKED_SHAPES)
    reference = packed_plain.make_loss(mellum_form(cfg))
    with jax.default_matmul_precision("highest"):
        system = lambda p: model.apply(p, {}, batch, train=True)[0]["loss"]
        loss, grads = jax.value_and_grad(system)(params)
        want_loss, want = jax.value_and_grad(lambda p: reference(p, batch))(params)
    assert abs(float(loss) - float(want_loss)) < 2e-6
    assert 3.0 < float(want_loss) < 8.0
    for layer in want:
        for name, w in want[layer].items():
            scale = float(jnp.abs(w).max())
            assert scale > 0, (layer, name)  # every leaf takes part
            np.testing.assert_allclose(
                grads[layer][name], w, atol=2e-4 * scale, err_msg=f"{layer}.{name}"
            )


def test_the_reference_reads_positions_and_loss_positions_from_the_ids(packed):
    """It is handed the feed's ``positions`` and ``labels`` and trusts
    neither: its positions are the ids' own, and so is where a loss is."""
    cfg, _model, _params, batch = packed
    np.testing.assert_array_equal(
        packed_plain.document_positions(batch["segment_ids"]), batch["positions"])
    borne = np.asarray(batch["segment_ids"])[:, 1:] == np.asarray(batch["segment_ids"])[:, :-1]
    np.testing.assert_array_equal(np.asarray(batch["labels"])[:, :-1] >= 0, borne)
    seen = np.asarray(packed_plain.seen_mask(batch["segment_ids"], cfg.sliding_window))
    assert seen.sum() == float(DecoderLM(cfg, _PACKED_SHAPES)._doc_counters(batch)[
        "attn_pairs_window"])


def _without_segment_ids(monkeypatch):
    whole = decoder.attention
    monkeypatch.setattr(
        decoder, "attention",
        lambda *a, segment_ids=None, **kw: whole(*a, **kw))


_PACKED_FAULTS = {
    "document_mask_off": "mask",
    "loss_across_boundaries": "labels",
    "window_doubled": dict(sliding_window=16),
    "sigmoid_for_softmax": dict(scoring_func="sigmoid"),
    "chosen_weights_not_normalised": "router",
}


@pytest.mark.parametrize("fault", sorted(_PACKED_FAULTS))
def test_packed_reference_tells_each_mechanism(packed, fault, monkeypatch):
    """A program that lets attention cross documents, takes the loss across
    boundaries, doubles the window, scores by sigmoid or leaves the chosen
    weights unnormalised does not agree with the reference: each moves the
    tiny loss by >= 1e-3."""
    cfg, model, _, batch = packed
    # gain 12: at 8 the router's two faults move this tiny loss by 4e-4 and
    # 8e-4 only (the held experts are a quarter of the 2 a token chooses)
    params = shaken(model.init(jax.random.PRNGKey(3))[0], 12.0)
    want = float(packed_plain.make_loss(mellum_form(cfg))(params, batch))
    sound = float(model.apply(params, {}, batch)[0]["loss"])
    change = _PACKED_FAULTS[fault]
    if change == "mask":
        _without_segment_ids(monkeypatch)
    elif change == "labels":
        nxt = jnp.concatenate([batch["input_ids"][:, 1:], batch["input_ids"][:, :1]], 1)
        batch = dict(batch, labels=nxt)
    elif change == "router":  # p itself, not p / sum(p) over the chosen
        monkeypatch.setattr(decoder, "route_softmax", lambda xt, w, k: jax.lax.top_k(
            jax.nn.softmax(xt.astype(jnp.float32) @ w, axis=-1), k))
    broken = DecoderLM(
        dataclasses.replace(cfg, **(change if isinstance(change, dict) else {})),
        _PACKED_SHAPES)
    got = float(broken.apply(params, {}, batch)[0]["loss"])
    assert abs(sound - want) < 1e-4
    assert abs(got - want) > 1e-3, (fault, got, want)


def test_positions_that_do_not_restart_cannot_be_told_by_any_loss(packed):
    """Rotary scores depend on ``p_i - p_j`` alone, and inside a document
    that is ``i - j`` whether positions restart at its first token or run on
    from the sequence's: with the document mask on, a program that rotates
    by ``arange(S)`` computes the same function (to float32's rounding of
    the larger angles).  The restart is kept for that rounding and for what
    a cache at serving time would hold; no comparison of losses, logits or
    gradients can hold it, here or on the chip (PERF.md section 7 row 6)."""
    cfg, model, params, batch = packed
    running = dict(batch, positions=jnp.broadcast_to(
        jnp.arange(64, dtype=jnp.int32), (2, 64)))
    assert not np.array_equal(running["positions"], batch["positions"])
    with jax.default_matmul_precision("highest"):
        got = float(model.apply(params, {}, running)[0]["loss"])
        want = float(model.apply(params, {}, batch)[0]["loss"])
    assert abs(got - want) < 1e-5


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares (experts 0-3, 4-7, 8-11, 12-15
    of 16 here, as 0-15 .. 48-63 of 64 in the deployment) compute add up to
    what the plain reference gives for the whole, uncut layer: there is no
    shared expert to count once."""
    cfg = tiny_mellum(experts_held=(0, 16))
    model = DecoderLM(cfg, {"input_ids": (2, 64)})
    lp = shaken(model.init(jax.random.PRNGKey(5))[0], 3.0)["layer_01"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = packed_plain._sparse_ffn(mellum_form(cfg), lp, u)
        total, slots = 0.0, 0.0
        for share in range(4):
            first = 4 * share
            part = DecoderLM(tiny_mellum(experts_held=(first, 4)), {"input_ids": (2, 64)})
            mine = {
                "router_w": lp["router_w"],
                "experts_gate_up": lp["experts_gate_up"][first: first + 4],
                "experts_down": lp["experts_down"][first: first + 4],
            }
            routed, counters = part._ffn(1, mine, u)
            total = total + routed
            slots += float(counters["moe_slots_held"])
    assert slots == 2 * 64 * cfg.num_experts_per_tok  # every slot, once
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(jnp.abs(whole).max()))
    assert float(jnp.abs(total - routed - whole).max()) > 1e-3  # one share is not the layer


def test_route_softmax_weights_and_ties():
    from sparknet_tpu.parallel.moe import route_softmax

    xt = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    w = w.at[:, 5].set(w[:, 2])  # experts 2 and 5 always tie
    weights, experts = route_softmax(xt, w, 3)
    probs = jax.nn.softmax(xt @ w, axis=-1)
    order = np.argsort(-np.asarray(probs), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(experts, order)  # ties to the lower index
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        weights, np.take_along_axis(np.asarray(probs), order, -1)
        / np.take_along_axis(np.asarray(probs), order, -1).sum(-1, keepdims=True),
        atol=1e-6)
    assert weights.dtype == jnp.float32 and experts.dtype == jnp.int32


def test_a_batch_without_the_blobs_is_the_unpacked_program(tiny):
    """The unpacked model has no document counter, takes positions from
    ``arange`` and the loss over every position, as before this feed."""
    cfg, model, params = tiny
    assert not model.packed and model.counters == COUNTERS + ROPE_COUNTERS
    assert model.input_names == ["input_ids", "labels"]
    blobs, _ = model.apply(params, {}, _batch(cfg))
    assert set(blobs) == {"loss", "token_acc", *COUNTERS, *ROPE_COUNTERS}


def test_one_document_a_sequence_reads_what_the_unpacked_program_reads(tiny):
    cfg, model, params = tiny
    batch = _batch(cfg)
    whole = dict(
        batch, segment_ids=jnp.zeros((2, 64), jnp.int32),
        positions=jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64)))
    packed_model = DecoderLM(cfg, _PACKED_SHAPES)
    got, _ = packed_model.apply(params, {}, whole)
    want, _ = model.apply(params, {}, batch)
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-6
    assert float(got["doc_count"]) == 2 and float(got["loss_positions"]) == 128
    assert float(got["attn_pairs_full"]) == 2 * 64 * 65 / 2
    assert float(got["attn_pairs_window"]) == 2 * (8 * 9 / 2 + 56 * 8)


def test_the_hybrid_refuses_packed_documents():
    with pytest.raises(NotImplementedError, match="reset at a boundary"):
        HybridLM(HybridConfig.tiny(), _PACKED_SHAPES)


# -- the packing feed ---------------------------------------------------------

def _pool(seed=2, **kw):
    from sparknet_tpu.data.text import packed_dataset

    args = dict(vocab_size=50, n_tokens=1 << 14, seq_len=256, median_len=60,
                min_len=8, max_len=256, seed=seed)
    args.update(kw)
    return packed_dataset(**args)


def test_packed_feed_same_seed_same_batches_and_the_blobs_shapes():
    from sparknet_tpu.data.rdd import BatchIterator
    from sparknet_tpu.data.text import packed_feed

    feed = packed_feed(_pool(), 4, seed=2)
    assert isinstance(feed, BatchIterator)
    first, second = next(feed), next(feed)
    again = next(iter(packed_feed(_pool(), 4, seed=2)))
    other = next(iter(packed_feed(_pool(seed=3), 4, seed=3)))
    assert set(first) == {"input_ids", "labels", "segment_ids", "positions"}
    for name, x in first.items():
        assert x.shape == (4, 256) and x.dtype == np.int32, name
        np.testing.assert_array_equal(x, again[name])
    assert not np.array_equal(first["input_ids"], second["input_ids"])
    assert not np.array_equal(first["input_ids"], other["input_ids"])


def test_packed_feed_has_no_padding_and_keeps_to_the_slice():
    from sparknet_tpu.data.text import NUM_SPECIAL, packed_feed

    for batch in [*zip(range(3), packed_feed(_pool(), 8, seed=2))]:
        ids = batch[1]["input_ids"]
        assert NUM_SPECIAL <= ids.min() and ids.max() < 50  # every position a token
        labels = batch[1]["labels"]
        assert ((labels == -100) | ((labels >= NUM_SPECIAL) & (labels < 50))).all()


def test_packed_labels_are_minus_100_exactly_at_document_ends_and_positions_restart():
    from sparknet_tpu.data.text import packed_feed

    batch = next(iter(packed_feed(_pool(), 8, seed=2)))
    seg, pos, ids, labels = (batch[k] for k in (
        "segment_ids", "positions", "input_ids", "labels"))
    assert (seg[:, 0] == 0).all() and (np.diff(seg, axis=1) >= 0).all()
    assert (np.diff(seg, axis=1) <= 1).all()  # 0, 1, 2, ... along a sequence
    opens = np.concatenate([np.ones((8, 1), bool), np.diff(seg, axis=1) == 1], 1)
    np.testing.assert_array_equal(pos == 0, opens)  # positions restart
    inside = ~opens[:, 1:]
    np.testing.assert_array_equal(pos[:, 1:][inside], pos[:, :-1][inside] + 1)
    ends = np.concatenate([opens[:, 1:], np.ones((8, 1), bool)], 1)
    np.testing.assert_array_equal(labels == -100, ends)
    np.testing.assert_array_equal(labels[:, :-1][inside], ids[:, 1:][inside])
    assert opens.sum() >= 3 * 8  # several documents a sequence at this median


def test_a_document_cut_by_a_sequences_end_continues_as_a_document_of_its_own():
    """Rows in the pool's own order are the stream cut every ``seq_len``:
    the documents' lengths come back from the rows, each in [min, max] but
    where a sequence's end cut one: its two parts add up to a legal length,
    the second starts the next sequence at position 0."""
    ds = _pool(n_tokens=1 << 13, num_partitions=1)
    rows = ds._fns[0]()
    seg, pos, labels = rows["segment_ids"], rows["positions"], rows["labels"]
    assert (pos[:, 0] == 0).all() and (labels[:, -1] == -100).all()
    lengths, cut = [], 0
    for r in range(len(seg)):
        counts = np.bincount(seg[r])
        assert counts.sum() == 256
        for n, c in enumerate(counts):
            last_of_row = n == len(counts) - 1
            if n == 0 and lengths and lengths[-1][1]:
                lengths[-1] = (lengths[-1][0] + c, last_of_row and c == 256)
                cut += 1
            else:
                lengths.append((c, last_of_row))
    whole = [n for n, open_ended in lengths[:-1]]
    assert cut >= 5  # sequences' ends did cut documents
    assert min(whole) >= 8 and max(whole) <= 256
    assert 30 < np.median(whole) < 120  # the lognormal's median is 60


def test_packing_refuses_sequences_shorter_than_the_shortest_document(capsys):
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.data.text import packed_dataset

    with pytest.raises(ValueError, match="shorter than the shortest document"):
        packed_dataset(vocab_size=50, n_tokens=4096, seq_len=16, min_len=32)
    args = lm_app.parser().parse_args(
        ["--config", "tiny", "--seq-len", "16", "--pack-documents"])
    with pytest.raises(SystemExit, match="--seq-len 16 is shorter than the shortest"):
        lm_app.build(args)


def test_lm_app_trains_on_packed_documents_and_says_what_traffic_it_had(capsys):
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY

    argv = ["--config", "tiny", "--seq-len", "64", "--batch-size", "4", "--max-iter",
            "6", "--display", "3", "--pack-documents", "--doc-median", "20",
            "--doc-min", "4", "--doc-max", "64", "--synthetic-tokens", "4096"]
    metrics = lm_app.main(argv)
    out = capsys.readouterr().out
    assert ("train feed: packed documents, lengths clip(lognormal(median 20, "
            "sigma 1), 4, 64) tokens, cut every 64; first batch doc_count=") in out
    assert "Iteration 6, loss = " in out and "doc_count = " in out
    assert "flash_tiles_docs_full = " in out and "attn_pairs_window = " in out
    assert np.isfinite(metrics["loss"]) and metrics["moe_slots_dropped"] == 0.0
    assert 4 <= metrics["doc_count"] <= 4 * 16
    assert metrics["loss_positions"] == 4 * 64 - metrics["doc_count"]
    assert metrics["attn_pairs_window"] <= metrics["attn_pairs_full"] <= 4 * 64 * 65 / 2
    solver, feed, _cfg = lm_app.build(lm_app.parser().parse_args(argv))
    stepped = solver.step(iter(feed), 1)
    read = REGISTRY.sources()["train_step"].snapshot()
    assert {k: read[k] for k in DOC_COUNTERS} == {k: float(stepped[k]) for k in DOC_COUNTERS}
