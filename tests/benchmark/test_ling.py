"""The ling3_flash configuration's part of the benchmark, on the CPU: its
file against the catalog row, its parameter count, its FLOPs and roofline
functions against hand-worked numbers, the readers it brings on a synthetic
trace whose operations are named as the chip names them (and nothing from a
run that lacks them), and its cell rehearsed tiny through the functions
``main`` calls."""

import copy
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run  # noqa: E402  (no jax at import)
from benchmark.configs import ling3_flash_flops as work  # noqa: E402

CELL = "ling_train_s16k"
SHAPES = {"input_ids": (1, 16384), "labels": (1, 16384)}
NEW_METRICS = [
    "kda_scan_ms", "kda_scan_roofline", "mla_attention_ms", "mla_attention_roofline",
    "grouped_route_ms",
]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(_ROOT, "benchmark", "configs", "ling3_flash.json")) as fh:
        return json.load(fh)


# -------------------------------------------------------------- the config

def test_config_keeps_every_published_number_but_the_four_reduced():
    """Against the catalog row where the guides are installed, else against
    the numbers of the issue: no width differs."""
    config = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as fh:
            row = next(r for r in map(json.loads, fh) if r["name"] == "Ling-3.0-flash")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config[key] != value and config["published"][key] == value
            else:
                assert config[key] == value, key
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "num_nextn_predict_layers"]
    assert (config["hidden_size"], config["head_dim"], config["num_attention_heads"]) == (
        2560, 128, 32)
    assert (config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"]) == (512, 128, 64, 128)
    assert (config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["n_group"], config["topk_group"]) == (768, 8, 8, 4)
    assert config["deployment"]["chips_sharing_a_layer"] == 64
    assert config["deployment"]["num_experts_routed"] == 512
    assert config["num_experts"] * 64 == config["published"]["num_experts"]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # one whole period, five KDA to one MLA, the leading dense layer inside it
    assert config["deployment"]["layers_kept"] == [0, 2, 3, 4, 5, 6]
    assert work.layer_kinds(config) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"), ("kda", "sparse"),
        ("mla", "sparse"), ("kda", "sparse")]
    # no clamp on any kept layer
    assert not any(config["expert_swiglu_limit_list"][i] for i in range(7))
    assert not any(config["share_expert_swiglu_limit_list"][i] for i in range(7))
    for key in ("layer_pattern", "norms", "kda", "mla", "router", "not_modelled",
                "weights", "compute_dtype", "loss", "parameters"):
        assert key in config["assumed"]
    for word in ("head_wise", "num_nextn_predict_layers"):
        assert word in config["assumed"]["not_modelled"]


def test_parameter_count_is_the_models():
    import jax

    from sparknet_tpu.models.decoder import HybridConfig, HybridLM

    config = _config()
    model = HybridLM(HybridConfig.from_published(config), {"input_ids": (1, 16384)})
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    counted = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    assert counted == config["parameters"] == 766927136
    # the issue's arithmetic, by part
    kda = 6 * 2560 * 4096 + 3 * 4096 * 4 + 2560 * 32 + 32 + 4096 + 128
    mla = 2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256 + 4096 * 2560
    dense = 3 * 2560 * 6144
    sparse = 9 * 3 * 2560 * 768 + 2560 * 512 + 512
    assert (kda, mla, dense, sparse) == (63049888, 31883776, 47185920, 54395392)
    by_hand = (
        kda + dense + 4 * (kda + sparse) + mla + sparse + 6 * 2 * 2560
        + 2 * 19648 * 2560 + 2560
    )
    assert counted == by_hand
    # the leaves an optimizer step does not move or decay
    specs = model.param_specs()
    assert specs["layer_01"]["router_bias"] == (0.0, 0.0)
    assert specs["layer_00"]["A_log"] == specs["layer_00"]["o_norm"] == (1.0, 0.0)
    assert specs["layer_00"]["q_conv"] == specs["layer_04"]["kv_b_w"] == (1.0, 1.0)


# ---------------------------------------------------- FLOPs, bytes, rooflines

def test_flops_against_the_hand_worked_count():
    config = _config()
    per_token = work.matmul_macs_per_token(config)
    assert per_token["kda_projections"] == 5 * (6 * 2560 * 4096 + 2560 * 32)
    assert per_token["kda_convolutions"] == 5 * 3 * 4 * 4096
    assert per_token["kda_recurrence"] == 5 * 32 * 3 * 128 * 128
    assert per_token["mla_projections"] == (
        2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560)
    assert per_token["dense_ffn"] == 3 * 2560 * 6144
    assert per_token["router"] == 5 * 2560 * 512
    assert per_token["shared_expert"] == 5 * 3 * 2560 * 768
    assert work.held_slots_per_token(config) == 0.125  # 8 of 512, 8 a token
    assert per_token["experts"] == 5 * 0.125 * 3 * 2560 * 768
    assert per_token["head"] == 2560 * 19648
    pairs = 16384 * 16385 // 2
    assert work.mla_macs(config, 1, 16384) == 32 * pairs * (192 + 128)
    total = work.train_step(config, SHAPES)
    assert total == 6 * 16384 * sum(per_token.values()) + 6 * 32 * pairs * 320
    # the issue's figures: 2.75 TFLOP of causal scores forward in the MLA layer
    # against 2.06 in a KDA layer's projections; 56.6 TFLOP a step in all
    assert 2 * work.mla_macs(config, 1, 16384) / 1e12 == pytest.approx(2.75, abs=0.005)
    assert 2 * 16384 * 6 * 2560 * 4096 / 1e12 == pytest.approx(2.06, abs=0.005)
    assert total / 1e12 == pytest.approx(56.63, abs=0.01)
    # the held experts see 2048 slots a step and layer
    assert 16384 * work.held_slots_per_token(config) == 2048


def test_kernel_work_counts_every_tensor_once():
    config = _config()
    flops, nbytes = work.kda_scan_work(config, SHAPES)
    assert flops == 5 * 16384 * 32 * 3 * (3 * 2 * 128 * 128)  # forward + 2 x backward
    # q, k, v, o in bfloat16, g and beta in float32, and a gradient of each
    assert nbytes == 5 * 16384 * 32 * 2 * (4 * 128 * 2 + 128 * 4 + 4)
    assert nbytes / 819e9 > flops / 197e12  # the memory side bounds it
    flops, nbytes = work.mla_attention_work(config, SHAPES)
    assert flops == 6 * work.mla_macs(config, 1, 16384)
    per_head = (192 + 192 + 128 + 128) + (192 + 192 + 128 + 128 + 128 + 192 + 192 + 128)
    assert nbytes == 16384 * 32 * per_head * 2
    assert flops / 197e12 > nbytes / 819e9  # the products bound it


# ------------------------------------------------------------- the readers

def _flash(kind, n):
    return (
        f"%{kind}.{n} = (bf16[1,32,16384,128]{{3,2,1,0:T(8,128)(2,1)}}, "
        f"f32[1,32,16384,128]{{3,2,1,0:T(8,128)}}) custom-call(s32[3]{{0:T(128)S(1)}} "
        f"%copy-done.{n}, bf16[1,32,16384,192]{{3,2,1,0:T(8,128)(2,1)}} %fusion.{n}, "
        f"bf16[1,32,16384,192]{{3,2,1,0:T(8,128)(2,1)}} %fusion.{n + 1}, "
        f"bf16[1,32,16384,128]{{3,2,1,0:T(8,128)(2,1)}} %fusion.{n + 2}), "
        f'custom_call_target="tpu_custom_call"'
    )


# names as a traced run of the cell gave them (my chip run, PR 33), cut short
SCAN_OPS = {
    # a segment's chunk matrices: (chunks a segment, B, H, chunk, ...)
    "%fusion.11199 = f32[16,1,32,64,128]{4,2,3,1,0:T(8,128)S(1)} fusion(f32[16,1,32,64,128]"
    "{4,2,3,1,0:T(8,128)S(1)} %copy_bitcast_fusion.211), kind=kOutput, calls=%fused_computation.10553": 0.004,
    # the triangular inverse's rows; XLA drops the batch of 1
    "%multiply_reduce_fusion.1195 = f32[16,32,4,16]{1,2,0,3:T(4,128)S(1)} fusion(f32[16,1,32,4,16,16]"
    "{2,5,3,0,4,1:T(8,128)S(1)} %copy-done.415, f32[16,32,4,16]{1,3,2,0:T(8,128)S(1)} %fusion.11253), kind=kLoop": 0.002,
    "%copy-done.363 = f32[16,1,32,4,16,16]{2,5,3,0,4,1:T(8,128)} copy-done((f32[16,1,32,4,16,16]"
    "{2,5,3,0,4,1:T(8,128)}, f32[16,1,32,4,16,16]{2,5,3,0,4,1:T(8,128)S(1)}, u32[]{:S(2)}) %copy-start.363)": 0.001,
    # the recurrence's body: one chunk against the state
    "%bitcast_add_fusion.318 = f32[1,32,128,128]{3,2,1,0:T(8,128)S(1)} fusion(f32[1,32,128,128]"
    "{3,2,1,0:T(8,128)S(1)} %copy.16818, f32[32,128]{1,0:T(8,128)S(1)} %dynamic-slice_bitcast_fusion.168, "
    "f32[32,64,128]{2,1,0:T(8,128)S(1)} %get-tuple-element.37400), kind=kLoop": 0.003,
    "%fusion.9001 = f32[32,64,128]{2,1,0:T(8,128)S(1)} fusion(bf16[32,64,64]{2,1,0} %p, "
    "f32[32,64,128]{2,1,0} %u), kind=kOutput": 0.001,
}
OTHER_OPS = {
    # the loop that walks a segment's chunks, and the layer's loop over segments:
    # containers, whose bodies are counted
    "%while.1634 = (s32[]{:T(128)}, f32[1,32,128,128]{2,3,1,0:T(8,128)S(1)}, bf16[16,1,32,64,128]"
    "{4,3,2,1,0:T(8,128)(2,1)S(1)}) while((s32[], f32[1,32,128,128]{2,3,1,0}, bf16[16,1,32,64,128]"
    "{4,3,2,1,0}) %tuple.9), body=%b": 0.004,
    "%while.1584 = (s32[]{:T(128)}, f32[2560,4096]{1,0:T(8,128)}, f32[4,4096]{1,0:T(4,128)}, "
    "f32[1,32,128,128]{3,2,1,0}, bf16[16,1,1024,2560]{3,2,1,0}) while((s32[]) %t), body=%b": 0.120,
    # the KDA layer's projections, what it hands the scan, and the rest of the step
    "%convolution_bitcast_fusion.323 = f32[1,1024,4096]{2,1,0:T(8,128)S(1)} fusion(bf16[2560,4096]"
    "{1,0:T(8,128)(2,1)} %remat2.3147, bf16[1,1024,2560]{2,1,0:T(8,128)(2,1)S(1)} %d), kind=kOutput": 0.020,
    "%fusion.10197 = bf16[1,32,1024,128]{3,2,1,0:T(8,128)(2,1)S(1)} fusion(f32[32,1024]{1,0:T(8,128)S(1)} "
    "%add_rsqrt_fusion.103, f32[1,1024,4096]{2,1,0:T(8,128)S(1)} %convolution_bitcast_fusion.262, "
    "f32[1,3,4096]{2,1,0:T(4,128)S(1)} %copy.17449), kind=kLoop": 0.005,
    "%copy-done.867 = f32[1,1024,4096]{2,1,0:T(8,128)} copy-done((f32[1,1024,4096]{2,1,0:T(8,128)}, "
    "f32[1,1024,4096]{2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) %copy-start.867)": 0.003,
    "%select_add_fusion.7 = f32[2560,4096]{1,0} fusion(f32[2560,4096]{1,0} %acc, bf16[1024,2560]{1,0} %u, "
    "bf16[1024,4096]{1,0} %dy), kind=kOutput": 0.030,
}
ROUTER_OPS = {
    # the grouped router: the two best a group, the scores' product, the
    # gradient scattered back into the scores
    "%sort.3 = (f32[16384,8,64]{2,1,0}, s32[16384,8,64]{2,1,0}) sort(f32[16384,8,64]{2,1,0} %s, "
    "s32[16384,8,64]{2,1,0} %i), dimensions={2}": 0.006,
    "%fusion.61 = f32[16384,512]{1,0:T(8,128)} fusion(f32[16384,2560]{1,0} %x, f32[2560,512]{1,0} %w), "
    "kind=kOutput": 0.002,
    "%fusion.172 = f32[16384,512]{1,0} fusion(f32[131072]{0} %dw, s32[131072]{0} %idx), kind=kInput": 0.001,
    "%slice_reduce_fusion.4 = f32[16384,8]{1,0} fusion(f32[16384,8,2]{2,1,0} %best), kind=kLoop": 0.0005,
}


def _synthetic_run(seconds_scale=1.0):
    """A traced run's record with operations named as the chip names them:
    the MLA layer's three flash kernels and its recomputed forward, the
    scan's fusions and loops, and others."""
    ops = dict(SCAN_OPS, **OTHER_OPS, **ROUTER_OPS)
    ops[_flash("flash_attention_fwd", 1)] = 0.040
    ops[_flash("checkpoint_flash_attention_fwd", 5)] = 0.040
    ops[_flash("flash_attention_dq", 9)] = 0.060
    ops[_flash("flash_attention_dkv", 13)] = 0.080
    steps = 4
    return {
        "trace": {
            "steps": steps, "device_step_s": [1.0] * steps, "window_s": 4.0,
            "busy_s": 3.99,
            "op_seconds": {k: v * steps * seconds_scale for k, v in ops.items()},
        },
        "shapes": SHAPES, "config": _config(), "chips": 1, "peaks": PEAKS,
        "flops_per_step": 56.63e12,
    }


def _read(metric, recorded):
    return run.metric_reader(run.load_cell(CELL)["layers_dir"], metric)(recorded)


def test_readers_read_the_hybrids_operations_by_what_they_are():
    recorded = _synthetic_run()
    assert _read("mla_attention_ms", recorded) == pytest.approx(1e3 * 0.220)
    # the scan's fusions, in a segment and in the recurrence; no container,
    # no projection
    assert _read("kda_scan_ms", recorded) == pytest.approx(1e3 * sum(SCAN_OPS.values()))
    # the router's own tensors; not a weight matrix as wide as a chunk has rows
    assert _read("grouped_route_ms", recorded) == pytest.approx(1e3 * sum(ROUTER_OPS.values()))
    flops, nbytes = work.mla_attention_work(recorded["config"], SHAPES)
    assert _read("mla_attention_roofline", recorded) == pytest.approx(
        100 * (flops / 197e12) / 0.220)
    flops, nbytes = work.kda_scan_work(recorded["config"], SHAPES)
    assert _read("kda_scan_roofline", recorded) == pytest.approx(
        100 * (nbytes / 819e9) / sum(SCAN_OPS.values()))


def test_a_roofline_share_cannot_pass_100_on_work_counted_once():
    recorded = _synthetic_run()
    ops = recorded["trace"]["op_seconds"]
    steps = recorded["trace"]["steps"]
    flops, nbytes = work.mla_attention_work(recorded["config"], SHAPES)
    least_mla = max(flops / 197e12, nbytes / 819e9)
    flops, nbytes = work.kda_scan_work(recorded["config"], SHAPES)
    least_kda = max(flops / 197e12, nbytes / 819e9)
    for name in list(ops):
        if "flash_attention" in name.split(" = ")[0]:
            ops[name] = steps * least_mla / 4  # four such operations
        elif name in SCAN_OPS:
            ops[name] = steps * least_kda / len(SCAN_OPS)
    assert _read("mla_attention_roofline", recorded) == pytest.approx(100.0)
    assert _read("kda_scan_roofline", recorded) == pytest.approx(100.0)
    slower = _synthetic_run(seconds_scale=3.0)
    assert 0 < _read("mla_attention_roofline", slower) < 100
    assert 0 < _read("kda_scan_roofline", slower) < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_return_nothing_where_there_is_nothing_to_read(metric):
    """Another configuration's run, an untraced one, or one of a program
    whose scan chunks otherwise: None, no raise."""
    laguna = dict(_synthetic_run(), shapes={"input_ids": (2, 8192)})
    with open(os.path.join(_ROOT, "benchmark", "configs", "laguna_xs2.json")) as fh:
        laguna["config"] = json.load(fh)
    assert _read(metric, laguna) is None
    bert = {
        "trace": {"steps": 2, "op_seconds": {
            "%custom-call.5 = bf16[64,12,512,64]{3,2,1,0} custom-call(s32[3]{0} %x)": 0.1}},
        "shapes": {"input_ids": (64, 512)}, "chips": 1,
        "config": {"num_attention_heads": 12, "hidden_size": 768}, "peaks": PEAKS,
    }
    assert _read(metric, bert) is None
    assert _read(metric, dict(_synthetic_run(), trace=None)) is None
    if not metric.startswith("kda"):  # told by the batch's shape
        other_shapes = dict(_synthetic_run(), shapes={"input_ids": (2, 4096)})
        assert _read(metric, other_shapes) is None
    else:  # by the program's chunking, of a sequence that is whole segments
        short = dict(_synthetic_run(), shapes={"input_ids": (1, 64)})
        assert _read(metric, short) is None


# ------------------------------------------------------------ the rehearsal

def tiny_reference_loss(params, batch):
    """The plain reference on the tiny configuration the rehearsal runs."""
    from benchmark.configs import ling3_flash_reference

    from tests.test_hybrid import published_form
    from sparknet_tpu.models.decoder import HybridConfig

    return ling3_flash_reference.make_loss(published_form(HybridConfig.tiny()))(
        params, batch
    )


def _tiny_cell():
    cell = copy.deepcopy(run.load_cell(CELL))
    cell["config"]["argv"] = ["--config", "tiny_hybrid", "--remat"]  # float32 on the CPU
    cell["traffic"]["argv"] = [
        "--seq-len", "64", "--batch-size", "2", "--synthetic-tokens", "4096"]
    cell["config"]["min_tpu_custom_calls"] = 0  # the CPU picks reference attention
    cell["config"].pop("parameters")  # the tiny preset's count is its own
    cell["config"]["reference"]["forward"] = (
        "tests.benchmark.test_ling:tiny_reference_loss")
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
        "ling3_flash", "clm_s16384_bs1", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == "ling3_flash")
    assert entry["reduced"] == _config()["reduced"]
    assert entry["source"] == _config()["source"]
    loaded = run.load_cell(CELL)
    assert loaded["traffic"]["argv"] == [
        "--seq-len", "16384", "--batch-size", "1", "--synthetic-tokens", "4194304",
        "--max-iter", "100000"]
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == NEW_METRICS
    assert all(m["moves"] == "samples_per_s" for m in mine)
    assert [m["layer"] for m in mine] == ["Kernels"] * 4 + ["Expert layer"]
    assert manifest["per_layer"][-5:] == mine and manifest["workloads"][-1] == cell
    reported = {m["name"] for m in loaded["per_layer"]}
    assert reported == set(NEW_METRICS) | {
        "input_wait_share", "dispatch_ms", "device_step_ms", "mfu_device",
        "device_idle_share", "feed_source_ms", "feed_h2d_ms", "feed_backpressure_ms"}


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


@pytest.mark.parametrize("fault", ["eight_bit_weights", "convolution_left_out"])
def test_a_planted_fault_reads_not_correct_through_the_cell(
    fault, clock, tmp_path, monkeypatch
):
    """The control and one mechanism, planted in the program and taken
    through ``run.run_cell`` and ``reference.compare`` as a real run is:
    everything else holds, the reference check does not, ``correct`` is
    false."""
    import jax

    from sparknet_tpu.apps import lm_app

    from tests.benchmark.test_laguna import _eight_bit
    from tests.test_hybrid import plant

    if fault == "eight_bit_weights":
        class RoundedWeights(lm_app.HybridLM):
            def apply(self, params, *args, **kwargs):
                params = jax.tree_util.tree_map(_eight_bit, params)
                return super().apply(params, *args, **kwargs)

        monkeypatch.setattr(lm_app, "HybridLM", RoundedWeights)
    else:
        plant(fault, None, monkeypatch)
    cell = _tiny_cell()
    cell["config"]["reference"]["weight_gain"] = 8.0  # flat scores at the tiny width
    cell["config"]["reference"]["abs_tolerance"] = 1e-3
    out = run.run_cell(
        cell, seed=4000000011, seconds=0.2, trace=False, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    )
    compared = out["compared"]["reference_abs_diff"]
    assert compared["value"] > compared["at_most"] == 1e-3, out
    assert out["correct"] is False
    assert out["failed"] == 0  # the steps themselves ran


def test_traced_rehearsal_reports_the_shared_metrics(
    clock, tmp_path, monkeypatch
):
    """The CPU has no device plane, so a synthetic record stands in for the
    profiler's; the timeline parts and the shared readers run for real."""
    def recorded_steps(solver, feed, loss_key, skip, count, trace_dir):
        log = run.run_steps(solver, feed, loss_key, count=skip + count)
        trace = dict(_synthetic_run()["trace"], program="jit_fused(1)")
        return {**log, "trace": trace}

    monkeypatch.setattr(run, "traced_steps", recorded_steps)
    out = run.run_cell(
        _tiny_cell(), seed=7, seconds=0.5, trace=True, clock=clock,
        trace_dir=str(tmp_path), peaks=PEAKS,
    )
    assert out["correct"] is True, out
    # the tiny batch has other shapes than the synthetic operations: the
    # readers matched by the batch's shape find nothing; the rest are there
    assert {"dispatch_ms", "device_step_ms", "mfu_device", "device_idle_share",
            "input_wait_share", "feed_source_ms", "feed_h2d_ms",
            "feed_backpressure_ms"} <= set(out["metrics"])
    assert not set(NEW_METRICS) & set(out["metrics"])
