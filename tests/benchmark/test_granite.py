"""The granite4_h_micro configuration's part of the benchmark, on the CPU: the
file against the published row's numbers it was built from, the parameter count and the FLOPs against hand-worked
counts, the three readers it brings (and nothing from a run that lacks what
they read), its manifest entries, and its cell rehearsed tiny through the
functions ``main`` calls, traced and untraced, with a planted fault to
``correct: false``; the same at the cell's own size on the chip, ``-k
on_hardware``."""

import copy
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run  # noqa: E402  (no jax at import)
from benchmark.configs import granite4_h_micro_flops as work  # noqa: E402
from tests.benchmark import test_mellum, test_scope_metrics  # noqa: E402
from tests.benchmark.test_laguna import _eight_bit  # noqa: E402

CELL = "granite_train_packed16k"
SHAPES = {
    name: (1, 16384) for name in ("input_ids", "labels", "positions", "segment_ids")
}
NEW_METRICS = ["ssm_mixer_ms", "ssd_scan_ms", "ssd_scan_roofline"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
_SHARED = {
    "input_wait_share", "dispatch_ms", "device_step_ms", "mfu_device",
    "device_idle_share", "feed_source_ms", "feed_h2d_ms", "feed_backpressure_ms",
}
# the published config.json's numbers as the configuration was built from
# them (a copy, so that no later edit of a catalog can fail this test)
_PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
    "layer_types": ((["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4),
}
_SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"


def _config():
    with open(os.path.join(_ROOT, "benchmark", "configs", "granite4_h_micro.json")) as fh:
        return json.load(fh)


# -------------------------------------------------------------- the config

def test_config_keeps_every_published_number_but_the_two_reduced():
    config = _config()
    assert config["source"] == _SOURCE
    assert set(_PUBLISHED) <= set(config)
    for key, value in _PUBLISHED.items():
        if key in config["reduced"]:
            assert config[key] != value and config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["num_hidden_layers"] == 10 and config["vocab_size"] * 8 == 100352
    n = config["num_hidden_layers"]
    # one whole period: nine Mamba-2 layers to one attention layer, as published
    assert config["layer_types"][:n] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert config["layer_types"][:n] == config["layer_types"][n:2 * n]
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    for key in ("mamba2", "attention", "multipliers", "mlp", "head", "packing",
                "weights", "optimizer", "compute_dtype", "loss", "parameters"):
        assert key in config["assumed"], key
    assert config["flops"] == "benchmark.configs.granite4_h_micro_flops:train_step"
    assert config["reference"]["forward"] == (
        "benchmark.configs.granite4_h_micro_reference:loss")
    assert config["min_tpu_custom_calls"] >= 4  # the attention layer's flash kernels


def test_parameter_count_is_the_models_and_the_hand_counts():
    import jax

    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.models.decoder import MambaHybridConfig, MambaHybridLM

    config = _config()
    args = lm_app.parser().parse_args(["--config", "benchmark/configs/granite4_h_micro.json"])
    cfg = lm_app.make_config(args)
    assert isinstance(cfg, MambaHybridConfig) and lm_app.model_class(cfg) is MambaHybridLM
    model = MambaHybridLM(cfg, {k: (1, 16384) for k in ("input_ids", "segment_ids", "positions")})
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    counted = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    assert counted == config["parameters"] == 772160448
    # the count by part
    mamba = 2048 * 8512 + 4352 * 5 + 3 * 64 + 4096 + 4096 * 2048 + (
        2048 * 16384 + 8192 * 2048) + 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2048 * 16384 + 8192 * 2048 + 2 * 2048
    assert (mamba, attention) == (76182976, 60821504)
    assert counted == 9 * mamba + attention + 12544 * 2048 + 2048
    assert "lm_w" not in params["head"]  # tied: the embedding counted once


# ---------------------------------------------------- FLOPs, bytes, rooflines

def test_flops_against_the_hand_worked_count():
    test_mellum._pool_gauges()  # no packed feed built: one document a sequence
    config = _config()
    per_token = work.matmul_macs_per_token(config)
    assert per_token == {
        "mamba_projections": 9 * (2048 * 8512 + 4096 * 2048),
        "mamba_convolutions": 9 * 4 * 4352,
        "mamba_recurrence": 9 * 2 * 64 * 64 * 128,
        "attention_projections": 2 * 2048 * 2048 + 2 * 2048 * 512,
        "mlp": 10 * 3 * 2048 * 8192,
        "head": 2048 * 12544,
    }
    unbroken = 16384 * 16385 // 2
    assert work.attention_macs(config, SHAPES) == unbroken * 2048
    total = 6 * 16384 * sum(per_token.values()) + 12 * unbroken * 2048
    assert work.train_step(config, SHAPES) == pytest.approx(total)
    test_mellum._pool_gauges(full=1.1e7, window=1.1e7)  # the pool's mean sequence
    assert work.attention_macs(config, SHAPES) == 1.1e7 * 2048
    # 16 384 x 6 x 772 M = 75.9 TFLOP of the layers' and the head's products
    # (76.8 with the scan and the convolutions); attention inside documents
    # adds under 2
    assert 76.8e12 < work.train_step(config, SHAPES) < 75.9e12 + 2e12 + 1e12
    flops, nbytes = work.ssd_scan_work(config, SHAPES)
    assert flops == 3 * 2 * 16384 * 9 * 64 * 2 * 64 * 128
    assert flops / 1e12 == pytest.approx(0.93, abs=0.01)
    per_token_bytes = 2 * 4096 + 2 * 2 * 128 + 4 * 64 + 4 * 4096  # x, B, C, delta, y
    assert nbytes == 2 * 16384 * 9 * per_token_bytes  # and each one's gradient
    assert nbytes / 819e9 > flops / 197e12  # bytes bound it


# ------------------------------------------------------------- the readers

def _scoped_record():
    """A record as ``run_cell`` hands the readers one: the tiny Mamba
    hybrid's own compiled step lowered (its scope table published), and a
    trace whose operations are named by that step's instructions, one ms
    each, two steps."""
    from sparknet_tpu.apps import lm_app

    solver, batches, _ = lm_app.build(lm_app.parser().parse_args([
        "--config", "tiny_mamba", "--seq-len", "64", "--batch-size", "1",
        "--pack-documents", "--doc-median", "20", "--doc-min", "4",
        "--doc-max", "64", "--synthetic-tokens", "4096", "--remat",
    ]))
    solver.lower_step(next(iter(batches)))  # as run.step_program does
    table = solver.step_scopes()
    seconds = {f"%{name} = f32[8]{{0}} fusion(%x)": 2e-3 for name in table}
    recorded = {
        "trace": {"op_seconds": seconds, "steps": 2, "program": "jit_fused(1)",
                  "device_step_s": [1e-3 * len(table)] * 2},
        "config": _config(), "shapes": SHAPES, "chips": 1, "peaks": PEAKS,
    }
    return solver, table, recorded


@pytest.fixture(scope="module")
def scoped():
    """(table, record); the solver, which publishes the table (a weak
    reference), lives as long as the fixture."""
    solver, table, recorded = _scoped_record()
    yield table, recorded
    del solver


def _read(metric, recorded):
    return run.metric_reader(run.load_cell(CELL)["layers_dir"], metric)(recorded)


def test_readers_sum_the_steps_own_instructions_by_scope(scoped):
    table, recorded = scoped
    count = lambda keep: float(sum(1 for e in table.values() if e.chain and keep(e)))
    mixer = count(lambda e: e.chain[0] == "attn.ssm")
    scan = count(lambda e: "ssd.scan" in e.chain)
    assert 0 < scan < mixer
    assert _read("ssm_mixer_ms", recorded) == pytest.approx(mixer)
    assert _read("ssd_scan_ms", recorded) == pytest.approx(scan)
    flops, nbytes = work.ssd_scan_work(recorded["config"], SHAPES)
    least_ms = 1e3 * max(flops / 197e12, nbytes / 819e9)
    assert _read("ssd_scan_roofline", recorded) == pytest.approx(100 * least_ms / scan)


def test_a_roofline_share_cannot_pass_100_on_work_counted_once(scoped):
    table, recorded = scoped
    flops, nbytes = work.ssd_scan_work(recorded["config"], SHAPES)
    least_s = max(flops / 197e12, nbytes / 819e9)
    names = [n for n, e in table.items() if "ssd.scan" in e.chain]
    ops = {f"%{n} = f32[8]{{0}} fusion(%x)": 2 * least_s / len(names) for n in names}
    fresh = {k: v for k, v in recorded.items() if k != "scope_time"}  # not the sums read
    exact = {**fresh, "trace": {**recorded["trace"], "op_seconds": ops}}
    assert _read("ssd_scan_roofline", exact) == pytest.approx(100.0)
    slower = {**fresh, "trace": {**recorded["trace"], "op_seconds": {
        k: 3 * v for k, v in ops.items()}}}
    assert 0 < _read("ssd_scan_roofline", slower) < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_return_nothing_where_there_is_nothing_to_read(scoped, metric, monkeypatch):
    """An untraced run; a program that publishes no table (an older
    program); another configuration's run: None, no raise."""
    from sparknet_tpu.utils import profiling

    _, recorded = scoped
    assert _read(metric, {}) is None
    assert _read(metric, dict(recorded, trace=None)) is None
    fresh = {k: v for k, v in recorded.items() if k != "scope_time"}
    monkeypatch.setattr(profiling, "step_scopes", lambda: None)
    assert _read(metric, dict(fresh)) is None
    monkeypatch.delattr(profiling, "step_scopes")
    assert _read(metric, dict(fresh)) is None


def test_a_program_without_the_scan_reads_no_scan(scoped, monkeypatch):
    """The parent's program has the scope table but no ``attn.ssm`` or
    ``ssd.scan``: the two times read 0.0, the share None."""
    _, recorded = scoped
    others = {"%fusion.1 = f32[8]{0} fusion(%x)": 1e-3}
    run_ = {k: v for k, v in recorded.items() if k != "scope_time"}
    run_["trace"] = {**recorded["trace"], "op_seconds": others}
    assert _read("ssm_mixer_ms", dict(run_)) == 0.0
    assert _read("ssd_scan_ms", dict(run_)) == 0.0
    assert _read("ssd_scan_roofline", dict(run_)) is None
    bert = dict(run_, config={"num_attention_heads": 12}, shapes={"input_ids": (64, 512)})
    assert _read("ssd_scan_roofline", bert) is None


# --------------------------------------------------------------- the manifest

def test_manifest_entries_are_appended_after_the_others():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "granite4_h_micro", "clm_packed_s16384_bs1", 1)
    entry = manifest["configs"][-1]
    assert entry["name"] == "granite4_h_micro"
    assert entry["reduced"] == _config()["reduced"] and entry["source"] == _config()["source"]
    assert 0 < len(cell["why"]) <= 200 and 0 < len(entry["why"]) <= 200
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index(test_mellum.CELL) + 1
    mine = manifest["per_layer"][-3:]
    assert [m["name"] for m in mine] == NEW_METRICS
    assert [m["layer"] for m in mine] == ["Compiled step", "Kernels", "Kernels"]
    assert [m["unit"] for m in mine] == ["ms", "ms", "%"]
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "samples_per_s"
        assert m["source"] == "device_trace"
    loaded = run.load_cell(CELL)
    argv = loaded["traffic"]["argv"]
    assert argv[:5] == ["--seq-len", "16384", "--batch-size", "1", "--pack-documents"]
    assert argv[argv.index("--doc-max") + 1] == "16384"
    assert argv[argv.index("--prefetch") + 1] == "2"
    assert loaded["config"]["argv"][-2:] == ["--bf16", "--remat"]
    assert {m["name"] for m in loaded["per_layer"]} == set(NEW_METRICS) | _SHARED
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "samples_per_s", "pace_ms_p90", "step_hbm_gb", "setup_s"}
    # one more cell fits the check's time at the accepted run_seconds
    runs = 2 + 14 * len(cells)
    assert runs * (manifest["run_seconds"] + 60) + 2 * 90 * len(cells) + 1200 <= 43200


def test_the_scope_metrics_keep_all_but_their_place_at_the_end():
    """``test_scope_metrics``' manifest test also asserts that the scope
    metrics are the LAST of ``per_layer``; this PR appended three after
    them and may not edit that file, so ``tests/conftest.py`` marks it
    ``xfail``.  Run here, that assertion is the first and only one to
    fail, and what follows it there is asserted here."""
    with pytest.raises(AssertionError) as caught:
        test_scope_metrics.test_manifest_entries_are_the_issues_appended_last()
    failing = str(caught.traceback[-1].statement)
    assert "per_layer[-len(ENTRIES):]" in failing, failing
    per_layer = test_scope_metrics._manifest()["per_layer"]
    names = [m["name"] for m in per_layer]
    at = names.index("scope_coverage")
    block = per_layer[at:at + len(test_scope_metrics.ENTRIES)]
    assert [m["name"] for m in block] == list(test_scope_metrics.ENTRIES)
    for m in block:
        unit, better, layer, cells = test_scope_metrics.ENTRIES[m["name"]]
        assert (m["unit"], m["better"], m["layer"], m["workloads"]) == (
            unit, better, layer, cells), m
        assert m["source"] == "device_trace" and m["moves"] == "samples_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert names[at + len(block):] == NEW_METRICS


# ------------------------------------------------------------ the rehearsal

def _tiny_form():
    from sparknet_tpu.models.decoder import MambaHybridConfig
    from tests.test_granite import granite_form

    return granite_form(MambaHybridConfig.tiny())


def tiny_reference_loss(params, batch):
    """The plain reference on the tiny configuration the rehearsal runs."""
    from benchmark.configs import granite4_h_micro_reference

    return granite4_h_micro_reference.make_loss(_tiny_form())(params, batch)


def _tiny_cell(tmp_path):
    path = tmp_path / "tiny_granite.json"
    path.write_text(json.dumps(_tiny_form()))
    cell = copy.deepcopy(run.load_cell(CELL))
    cell["config"]["argv"] = ["--config", str(path), "--remat"]  # float32 on the CPU
    cell["traffic"]["argv"] = [
        "--seq-len", "64", "--batch-size", "1", "--pack-documents", "--doc-median",
        "20", "--doc-min", "4", "--doc-max", "64", "--synthetic-tokens", "4096"]
    cell["config"]["min_tpu_custom_calls"] = 0  # the CPU picks reference attention
    cell["config"].pop("parameters")  # the tiny preset's count is its own
    cell["config"]["reference"]["forward"] = (
        "tests.benchmark.test_granite:tiny_reference_loss")
    cell["traffic"]["warm_steps"] = 1
    cell["traffic"]["trace"].update(
        dispatch_steps=2, skip_steps=1, steps=2, fenced_steps=2)
    return cell


@pytest.fixture(scope="module")
def clock():
    return run.CompileClock()


def test_cell_rehearses_tiny_through_the_functions_main_calls(clock, tmp_path, capsys):
    out = run.run_cell(
        _tiny_cell(tmp_path), seed=3900000007, seconds=0.5, trace=False,
        clock=clock, trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    )
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {"samples_per_s", "pace_ms_p90", "step_hbm_gb", "setup_s"}
    assert out["compared"]["reference_abs_diff"]["value"] < 1e-4  # f32 against f32
    printed = capsys.readouterr().out
    assert "train feed: packed documents, lengths clip(lognormal(median 20" in printed
    assert "'segment_ids': (1, 64)" in printed
    json.dumps(out)


def test_traced_rehearsal_reports_the_shared_metrics_and_the_new_ones(
    clock, tmp_path, monkeypatch
):
    """The CPU has no device plane: the profiler's part is replaced by a
    trace whose operations are named by the instructions of the cell's own
    compiled step (lowered for it as ``run.step_program`` lowers it), so the
    three scope readers read the program's own table."""
    def recorded_steps(solver, feed, loss_key, skip, count, trace_dir):
        log = run.run_steps(solver, feed, loss_key, count=skip + count)
        solver.lower_step(next(feed))
        seconds = {f"%{name} = f32[8]{{0}} fusion(%x)": 2e-3 for name in solver.step_scopes()}
        trace = {"op_seconds": seconds, "steps": 2, "program": "jit_fused(1)",
                 "device_step_s": [1e-3 * len(seconds)] * 2, "busy_s": 1.0,
                 "window_s": 1.0}
        return {**log, "trace": trace}

    monkeypatch.setattr(run, "traced_steps", recorded_steps)
    out = run.run_cell(
        _tiny_cell(tmp_path), seed=3900000011, seconds=0.5, trace=True, clock=clock,
        trace_dir=str(tmp_path), peaks=PEAKS,
    )
    assert out["correct"] is True, out
    metrics = out["metrics"]
    assert _SHARED - {"device_idle_share"} <= set(metrics)
    for name in NEW_METRICS:
        assert metrics[name]["value"] > 0, name
    assert metrics["ssd_scan_ms"]["value"] < metrics["ssm_mixer_ms"]["value"]


FAULTS = ["eight_bit_weights", "z_gate_dropped"]


def _plant(fault, monkeypatch):
    """The control (the nearest precision below bfloat16's) or the
    mechanism's fault, in the program that ``lm_app.build`` builds."""
    import jax

    from sparknet_tpu.apps import lm_app
    from tests import test_granite

    if fault == "eight_bit_weights":
        class RoundedWeights(lm_app.MambaHybridLM):
            def apply(self, params, *args, **kwargs):
                params = jax.tree_util.tree_map(_eight_bit, params)
                return super().apply(params, *args, **kwargs)

        monkeypatch.setattr(lm_app, "MambaHybridLM", RoundedWeights)
    else:  # a mechanism the comparison holds at the cell's gain
        _, planted = test_granite.plant(fault, None, monkeypatch)
        monkeypatch.setattr(lm_app, "MambaHybridLM", planted)


def _reads_not_correct(out):
    compared = out["compared"]["reference_abs_diff"]
    assert compared["value"] > compared["at_most"], out
    assert compared["at_most"] == _config()["reference"]["abs_tolerance"]
    assert out["correct"] is False
    assert out["failed"] == 0  # the steps themselves ran


@pytest.mark.parametrize("fault", ["state_not_reset", *FAULTS])
def test_a_planted_fault_reads_not_correct_through_the_cell(
    fault, clock, tmp_path, monkeypatch
):
    """The control and two mechanisms, planted in the program and taken
    through ``run.run_cell`` and ``reference.compare`` as a real run is:
    everything else holds, the reference check does not.  (Gain 20 and
    short documents: ``tests/test_granite.py`` says why.)"""
    _plant(fault, monkeypatch)
    cell = _tiny_cell(tmp_path)
    cell["config"]["reference"]["weight_gain"] = 20.0
    cell["traffic"]["argv"][cell["traffic"]["argv"].index("--doc-median") + 1] = "6"
    cell["traffic"]["argv"][cell["traffic"]["argv"].index("--doc-min") + 1] = "2"
    _reads_not_correct(run.run_cell(
        cell, seed=3900000013, seconds=0.2, trace=False, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    ))


@pytest.mark.skipif(
    os.environ.get("SPARKNET_TEST_TPU", "") in ("", "0"),
    reason="the cell at its own size, on the chip: SPARKNET_TEST_TPU=1",
)
@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_reads_not_correct_on_hardware(fault, tmp_path, monkeypatch):
    """The control and a mechanism the comparison holds on every seed,
    through ``run.run_cell`` at the cell's own shapes, gain and tolerance:
    8-bit weights read 5.57e-3 .. 2.55e-2 and the z gate dropped 3.66e-3 ..
    5.45e-2 against 2.0e-3 (``granite4_h_micro.json``'s ``reference.why``).
    ``reference.compare`` hands the weights to its programs as arguments:
    nothing is compiled in as a constant."""
    import gc

    import jax

    from benchmark import flops

    gc.collect()
    _plant(fault, monkeypatch)
    out = run.run_cell(
        run.load_cell(CELL), seed=3900000061 + FAULTS.index(fault), seconds=3.0,
        trace=False, clock=run.CompileClock(), trace_dir=str(tmp_path),
        peaks=flops.peaks(jax.devices()[0].device_kind),
    )
    compared = out["compared"]
    print(f"{fault}: {compared['reference_abs_diff']} {compared['tpu_custom_calls']}")
    _reads_not_correct(out)
    # rounding by bit operations passes no gradient, so the 8-bit program's
    # step has no backward pass and fewer kernels
    dropped = {"tpu_custom_calls"} if fault == "eight_bit_weights" else set()
    for name in set(compared) - {"reference_abs_diff"} - dropped:
        assert run.holds(compared[name]), (name, compared[name])
