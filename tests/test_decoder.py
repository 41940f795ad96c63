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
    COUNTERS, DecoderConfig, DecoderLM, swiglu,
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
                routed_scale=cfg.moe_routed_scaling_factor,
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
