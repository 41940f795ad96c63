"""The laguna_xs2 configuration's part of the benchmark, on the CPU: its cell
rehearses tiny through the functions ``main`` calls, its FLOPs and roofline
functions give hand-worked numbers, and every reader it brings reads a
synthetic trace whose operations are named as the chip names them (and
nothing from a run that lacks them)."""

import copy
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run  # noqa: E402  (no jax at import)
from benchmark.configs import laguna_xs2_flops as work  # noqa: E402

CELL = "laguna_train_s8k"
SHAPES = {"input_ids": (2, 8192), "labels": (2, 8192)}
NEW_METRICS = [
    "window_attention_ms", "full_attention_ms", "window_attention_roofline",
    "moe_experts_ms", "moe_experts_roofline", "moe_route_ms",
    "moe_load_max_over_mean",
]


def _config():
    with open(os.path.join(_ROOT, "benchmark", "configs", "laguna_xs2.json")) as fh:
        return json.load(fh)


# -------------------------------------------------------------- the config

def test_config_keeps_every_published_number_but_the_three_reduced():
    """Against the catalog row where the guides are installed, else against
    the numbers of the issue: no width differs."""
    config = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as fh:
            row = next(r for r in map(json.loads, fh) if r["name"] == "Laguna-XS.2")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config[key] != value and config["published"][key] == value
            else:
                assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (config["hidden_size"], config["head_dim"], config["sliding_window"]) == (
        2048, 128, 512)
    assert (config["moe_intermediate_size"], config["num_experts_per_tok"]) == (512, 8)
    assert config["deployment"]["num_experts_routed"] == 256
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # one leading dense layer and one whole period of the layer pattern
    n = config["num_hidden_layers"]
    assert config["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4
    assert config["layer_types"][1:n] == config["layer_types"][5:9]
    for key in ("router", "norms", "gating", "weights", "compute_dtype"):
        assert key in config["assumed"]


def test_parameter_count_is_the_models():
    import jax

    from sparknet_tpu.models.decoder import DecoderConfig, DecoderLM

    config = _config()
    model = DecoderLM(DecoderConfig.from_published(config), {"input_ids": (2, 8192)})
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    counted = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    assert counted == config["parameters"] == 691034112
    # the issue's arithmetic, by part
    attention = lambda heads: 2 * 2048 * heads * 128 + 2 * 2048 * 8 * 128
    sparse = 32 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048 * 256
    by_hand = (
        attention(48) + 3 * 2048 * 8192 + 3 * (attention(64) + sparse)
        + attention(48) + sparse + 2 * 12544 * 2048 + (2 * 5 + 1) * 2048
    )
    assert counted == by_hand


# ---------------------------------------------------- FLOPs, bytes, rooflines

def test_flops_against_the_hand_worked_count():
    config = _config()
    per_token = work.matmul_macs_per_token(config)
    attention = lambda heads: 2 * 2048 * heads * 128 + 2 * 2048 * 8 * 128
    assert per_token["attention_projections"] == 2 * attention(48) + 3 * attention(64)
    assert per_token["dense_ffn"] == 3 * 2048 * 8192
    assert per_token["experts"] == 4 * 1 * 3 * 2048 * 512  # one held slot a token
    assert per_token["shared_expert"] == 4 * 3 * 2048 * 512
    assert per_token["router"] == 4 * 2048 * 256
    assert per_token["head"] == 2048 * 12544
    assert work.held_slots_per_token(config) == 1.0
    # pairs under the mask: causal, and causal within 512
    assert work.seen_pairs(8192) == 8192 * 8193 // 2
    assert work.seen_pairs(8192, 512) == 512 * 513 // 2 + (8192 - 512) * 512
    assert work.seen_pairs(4, 2) == 7 and work.seen_pairs(3, 8) == 6
    full = 2 * 2 * 48 * work.seen_pairs(8192) * 128
    sliding = 3 * 2 * 64 * work.seen_pairs(8192, 512) * 128
    assert work.attention_macs(config, 2, 8192, "full_attention") == full
    assert work.attention_macs(config, 2, 8192, "sliding_attention") == sliding
    total = work.train_step(config, SHAPES)
    assert total == 6 * 16384 * sum(per_token.values()) + 12 * (full + sliding)
    # the issue's figures: 1.65 GFLOP a token in matmuls, 0.60 and 0.15 in scores
    assert 6 * sum(per_token.values()) / 1e9 == pytest.approx(1.65, abs=0.005)
    assert 12 * full / 16384 / 1e9 == pytest.approx(0.60, abs=0.005)
    assert 12 * sliding / 16384 / 1e9 == pytest.approx(0.15, abs=0.005)
    # a window kernel that masked without skipping would do a full layer's pairs
    masked = 3 * 2 * 64 * work.seen_pairs(8192) * 128
    extra = 12 * (masked - sliding)
    assert extra / 16384 / 1e9 == pytest.approx(1.06, abs=0.01)
    assert extra / (total + extra) == pytest.approx(0.30, abs=0.01)  # of such a step


def test_kernel_work_counts_every_tensor_once():
    config = _config()
    flops, nbytes = work.attention_kernels_work(config, SHAPES, "sliding_attention")
    assert flops == 12 * work.attention_macs(config, 2, 8192, "sliding_attention")
    q_like, kv_like = 2 * 64 * 8192 * 128 * 2, 2 * 8 * 8192 * 128 * 2
    assert nbytes == 3 * (6 * q_like + 6 * kv_like)  # K and V per KV head, not per query head
    flops, nbytes = work.expert_products_work(config, SHAPES)
    assert flops == 4 * 3 * 2 * 16384 * 3 * 2048 * 512
    assert nbytes == 4 * 2 * 3 * (32 * 3 * 2048 * 512 + 16384 * (2 * 2048 + 3 * 512))
    # given the slots a step really held (all sparse layers), it counts those
    flops, nbytes = work.expert_products_work(config, SHAPES, slots=50000)
    assert flops == 3 * 2 * 50000 * 3 * 2048 * 512
    assert nbytes == 2 * 3 * (4 * 32 * 3 * 2048 * 512 + 50000 * (2 * 2048 + 3 * 512))


# ------------------------------------------------------------- the readers

def _flash(kind, n, heads, extra=""):
    return (
        f"%{kind}.{n} = (bf16[2,{heads},8192,128]{{3,2,1,0:T(8,128)(2,1)}}, "
        f"f32[2,{heads},8192,128]{{3,2,1,0:T(8,128)}}) custom-call(s32[3]{{0:T(128)S(1)}} "
        f"%copy-done.{n}, bf16[2,{heads},8192,128]{{3,2,1,0:T(8,128)(2,1)}} %fusion.{n}, "
        f"bf16[2,8,8192,128]{{3,2,1,0:T(8,128)(2,1)}} %fusion.{n + 1}{extra}), "
        f'custom_call_target="tpu_custom_call"'
    )


def _synthetic_run(seconds_scale=1.0):
    """A traced run's record with operations named as the chip names them:
    per step, three window layers' and two full layers' kernels, grouped
    products, routing operations on the routing's shapes, and others."""
    ops = {}
    for n in range(3):
        ops[_flash("flash_attention_fwd", n, 64)] = 0.012
        ops[_flash("checkpoint_flash_attention_fwd", 10 + n, 64)] = 0.012
        ops[_flash("flash_attention_dq", 20 + n, 64)] = 0.018
        ops[_flash("flash_attention_dkv", 30 + n, 64)] = 0.024
    for n in range(2):
        ops[_flash("flash_attention_fwd", 40 + n, 48)] = 0.060
        ops[_flash("flash_attention_dq", 50 + n, 48)] = 0.080
        ops[_flash("flash_attention_dkv", 60 + n, 48)] = 0.100
    ops["%ragged-dot-none.3 = f32[20480,1024]{1,0:T(8,128)} custom-call(s32[1]{0:T(128)} "
        "%get-tuple-element.9, bf16[20480,2048]{1,0:T(8,128)(2,1)} %fusion.77)"] = 0.030
    ops["%ragged-dot-metadata.1 = (s32[33]{0}, s32[95]{0}) custom-call(s32[32]{0} %x)"] = 0.001
    ops["%fusion.77 = bf16[20480,2048]{1,0:T(8,128)(2,1)} fusion(bf16[16384,2048]{1,0} "
        "%p, s32[20480]{0} %slice.4), kind=kCustom, calls=%fused_computation.77"] = 0.020
    ops["%sort.2 = (s32[131072]{0}, s32[131072]{0}) sort(s32[131072]{0} %a, s32[131072]{0} %b)"] = 0.006
    ops["%fusion.80 = f32[16384,256]{1,0} fusion(bf16[16384,2048]{1,0} %u), kind=kOutput"] = 0.004
    ops["%while.7 = (s32[], f32[16384,2048]{1,0}, s32[143360]{0}) while((s32[], "
        "f32[16384,2048]{1,0}, s32[143360]{0}) %tuple.3), body=%b"] = 0.500  # a container
    ops["%fusion.90 = f32[2048,8192]{1,0} fusion(f32[2048,8192]{1,0} %m), kind=kLoop"] = 0.050
    steps = 4
    return {
        "trace": {
            "steps": steps, "device_step_s": [1.0] * steps, "window_s": 4.0,
            "busy_s": 3.99,
            "op_seconds": {k: v * steps * seconds_scale for k, v in ops.items()},
        },
        "shapes": SHAPES, "config": _config(), "chips": 1,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "flops_per_step": 39.35e12,
    }


def _read(metric, recorded):
    return run.metric_reader(run.load_cell(CELL)["layers_dir"], metric)(recorded)


def _step_counters(**metrics):
    """The newest step's metrics, where ``Solver.step`` leaves them.  The
    registry holds its sources weakly: keep what this returns."""
    from sparknet_tpu.telemetry.registry import REGISTRY, LastStep

    last = LastStep()
    last.metrics = metrics
    REGISTRY.register_source("train_step", last)
    return last


def test_readers_read_the_decoders_operations_by_what_they_are():
    recorded = _synthetic_run()
    last = _step_counters(moe_slots_held=16384.0)  # a layer's mean: an even router's
    assert _read("window_attention_ms", recorded) == pytest.approx(
        1e3 * 3 * (0.012 + 0.012 + 0.018 + 0.024))
    assert _read("full_attention_ms", recorded) == pytest.approx(
        1e3 * 2 * (0.060 + 0.080 + 0.100))
    assert _read("moe_experts_ms", recorded) == pytest.approx(31.0)
    # gather + sort + router scores; not the container, the products or the rest
    assert _read("moe_route_ms", recorded) == pytest.approx(30.0)
    flops, _bytes = work.attention_kernels_work(
        recorded["config"], SHAPES, "sliding_attention")
    assert _read("window_attention_roofline", recorded) == pytest.approx(
        100 * (flops / 197e12) / (3 * 0.066))
    flops, _bytes = work.expert_products_work(recorded["config"], SHAPES)
    assert _read("moe_experts_roofline", recorded) == pytest.approx(
        100 * (flops / 197e12) / 0.031)
    # the work is the step's own count of held slots, not the even share
    last.metrics = {"moe_slots_held": 20000.0}
    flops, _bytes = work.expert_products_work(recorded["config"], SHAPES, 4 * 20000.0)
    assert _read("moe_experts_roofline", recorded) == pytest.approx(
        100 * (flops / 197e12) / 0.031)
    last.metrics = {"loss": 9.5}  # a program without the counter
    assert _read("moe_experts_roofline", recorded) is None


def test_route_reader_refuses_a_run_chunked_otherwise_than_the_program_says(capsys):
    """Grouped products but no tensor of ``held_chunk_rows`` rows: the
    gathers and scatter-adds would drop out of the sum, so nothing is read
    and a ``bench:`` line says why."""
    recorded = _synthetic_run()
    ops = recorded["trace"]["op_seconds"]
    recorded["trace"]["op_seconds"] = {
        (k if "ragged-dot" in k.split(" = ")[0] else k.replace("[20480", "[24576")): v
        for k, v in ops.items()
    }
    assert _read("moe_experts_ms", recorded) == pytest.approx(31.0)
    assert _read("moe_route_ms", recorded) is None
    assert "bench: moe_route_ms: grouped products but no operation" in capsys.readouterr().out


def test_a_roofline_share_cannot_pass_100_on_work_counted_once():
    """Kernels that took exactly the least time the chip could take for the
    counted work read 100; real kernels do at least that work (more where
    they recompute or mask what they could skip), so they read lower."""
    recorded = _synthetic_run()
    last = _step_counters(moe_slots_held=16384.0)
    least = {}
    for kind, fn in (
        ("window", lambda: work.attention_kernels_work(
            recorded["config"], SHAPES, "sliding_attention")),
        ("experts", lambda: work.expert_products_work(recorded["config"], SHAPES)),
    ):
        flops, nbytes = fn()
        least[kind] = max(flops / 197e12, nbytes / 819e9)
    ops = recorded["trace"]["op_seconds"]
    steps = recorded["trace"]["steps"]
    for name in list(ops):
        head = name.split(" = ")[0]
        if "ragged-dot" in head:
            ops[name] = steps * least["experts"] / 2  # two such operations
        elif "flash_attention" in head and "[2,64," in name:
            ops[name] = steps * least["window"] / 12  # twelve such operations
    assert _read("window_attention_roofline", recorded) == pytest.approx(100.0)
    assert _read("moe_experts_roofline", recorded) == pytest.approx(100.0)
    slower = _synthetic_run(seconds_scale=3.0)
    assert 0 < _read("window_attention_roofline", slower) < 100
    assert 0 < _read("moe_experts_roofline", slower) < 100
    del last


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_return_nothing_where_there_is_nothing_to_read(metric):
    """Another configuration's run, or an older program's: None, no raise."""
    from sparknet_tpu.telemetry.registry import REGISTRY

    REGISTRY.reset()
    bert = {
        "trace": {"steps": 2, "op_seconds": {
            "%custom-call.5 = bf16[64,12,512,64]{3,2,1,0} custom-call(s32[3]{0} %x)": 0.1}},
        "shapes": {"input_ids": (64, 512)}, "chips": 1,
        "config": {"num_attention_heads": 12, "hidden_size": 768},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    assert _read(metric, bert) is None
    untraced = dict(_synthetic_run(), trace=None)
    if metric != "moe_load_max_over_mean":
        assert _read(metric, untraced) is None


def test_load_counter_is_read_from_the_registrys_train_step_source():
    last = _step_counters(loss=9.5, moe_load_max_over_mean=1.25)
    assert _read("moe_load_max_over_mean", _synthetic_run()) == 1.25


# ------------------------------------------------------------ the rehearsal

def tiny_reference_loss(params, batch):
    """The plain reference on the tiny configuration the rehearsal runs."""
    from benchmark.configs import laguna_xs2_reference

    from tests.test_decoder import published_form
    from sparknet_tpu.models.decoder import DecoderConfig

    return laguna_xs2_reference.make_loss(published_form(DecoderConfig.tiny()))(
        params, batch
    )


def _tiny_cell(config="tiny"):
    cell = copy.deepcopy(run.load_cell(CELL))
    cell["config"]["argv"] = ["--config", config, "--remat"]  # float32 on the CPU
    cell["traffic"]["argv"] = [
        "--seq-len", "64", "--batch-size", "2", "--synthetic-tokens", "4096"]
    cell["config"]["min_tpu_custom_calls"] = 0  # the CPU picks reference attention
    cell["config"].pop("parameters")  # the tiny preset's count is its own
    cell["config"]["reference"]["forward"] = (
        "tests.benchmark.test_laguna:tiny_reference_loss")
    cell["traffic"]["warm_steps"] = 1
    cell["traffic"]["trace"].update(
        dispatch_steps=2, skip_steps=1, steps=2, fenced_steps=2)
    return cell


@pytest.fixture(scope="module")
def clock():
    return run.CompileClock()


def test_manifest_entries_are_the_issues():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna_xs2", "clm_s8192_bs2", 1)
    loaded = run.load_cell(CELL)
    assert loaded["traffic"]["argv"][:4] == ["--seq-len", "8192", "--batch-size", "2"]
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == NEW_METRICS
    assert all(m["moves"] == "samples_per_s" for m in mine)
    reported = {m["name"] for m in loaded["per_layer"]}
    assert reported >= set(NEW_METRICS) | {
        "input_wait_share", "dispatch_ms", "device_step_ms", "mfu_device",
        "device_idle_share", "feed_source_ms", "feed_h2d_ms", "feed_backpressure_ms"}
    assert "flash_attention_ms" not in reported  # bert_mlm's, by its own list


def test_cell_rehearses_tiny_through_the_functions_main_calls(clock, tmp_path):
    out = run.run_cell(
        _tiny_cell(), seed=4000000007, seconds=0.5, trace=False, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    )
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {
        "samples_per_s", "pace_ms_p90", "step_hbm_gb", "setup_s"}
    assert out["compared"]["reference_abs_diff"]["value"] < 1e-4  # f32 against f32
    json.dumps(out)


def _eight_bit(x):
    """float32 rounded to 3 bits of mantissa: the nearest precision below
    bfloat16's 7."""
    import jax
    import jax.numpy as jnp

    i = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    i = (i + jnp.uint32(1 << 19)) & jnp.uint32(0xFFFFFFFF ^ ((1 << 20) - 1))
    return jax.lax.bitcast_convert_type(i, jnp.float32)


@pytest.mark.parametrize("fault", ["eight_bit_weights", "window_doubled"])
def test_a_planted_fault_reads_not_correct_through_the_cell(
    fault, clock, tmp_path, monkeypatch
):
    """The control and one mechanism, planted in the program and taken
    through ``run.run_cell`` and ``reference.compare`` as a real run is:
    everything else holds, the reference check does not, ``correct`` is
    false.  (Gain 8: at the tiny width a smaller one leaves the scores
    flat, as ``tests/test_decoder.py`` finds.)"""
    import jax

    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.models.decoder import DecoderConfig

    from tests.test_decoder import published_form

    config = "tiny"
    if fault == "window_doubled":  # the program's window, not the reference's
        path = tmp_path / "window16.json"
        path.write_text(json.dumps(
            published_form(DecoderConfig.tiny(sliding_window=16))))
        config = str(path)
    else:
        class RoundedWeights(lm_app.DecoderLM):
            def apply(self, params, *args, **kwargs):
                params = jax.tree_util.tree_map(_eight_bit, params)
                return super().apply(params, *args, **kwargs)

        monkeypatch.setattr(lm_app, "DecoderLM", RoundedWeights)
    cell = _tiny_cell(config)
    cell["config"]["reference"]["weight_gain"] = 8.0
    out = run.run_cell(
        cell, seed=4000000011, seconds=0.2, trace=False, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    )
    compared = out["compared"]["reference_abs_diff"]
    assert compared["value"] > compared["at_most"] == 4e-4, out
    assert out["correct"] is False
    assert out["failed"] == 0  # the steps themselves ran


def test_traced_rehearsal_reports_the_shared_metrics_and_the_counter(
    clock, tmp_path, monkeypatch
):
    """The CPU has no device plane, so a synthetic record stands in for the
    profiler's; the timeline parts, the shared readers and the program's
    counter run for real."""
    def recorded_steps(solver, feed, loss_key, skip, count, trace_dir):
        log = run.run_steps(solver, feed, loss_key, count=skip + count)
        trace = dict(_synthetic_run()["trace"], program="jit_fused(1)")
        return {**log, "trace": trace}

    monkeypatch.setattr(run, "traced_steps", recorded_steps)
    out = run.run_cell(
        _tiny_cell(), seed=7, seconds=0.5, trace=True, clock=clock,
        trace_dir=str(tmp_path),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )
    assert out["correct"] is True, out
    metrics = out["metrics"]
    # the tiny batch has other shapes than the synthetic operations: the
    # shape-matched readers find nothing; the rest are there
    assert {"dispatch_ms", "device_step_ms", "mfu_device", "device_idle_share",
            "input_wait_share", "feed_source_ms", "feed_h2d_ms",
            "feed_backpressure_ms", "moe_experts_ms",
            "moe_load_max_over_mean"} <= set(metrics)
    assert metrics["moe_load_max_over_mean"]["value"] >= 1.0
    assert {name for name, _s in out["breakdown"]["idle_gaps"]} >= {"input_wait"}
