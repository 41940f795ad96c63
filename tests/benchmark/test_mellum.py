"""The mellum2 configuration's part of the benchmark, on the CPU: the file
against the catalog's row, its cell rehearsed tiny through the functions
``main`` calls (sound to ``correct: true``; 8-bit weights and the document
mask off to ``correct: false``, and the same two at the cell's own size on
the chip, ``-k on_hardware``), its FLOPs and roofline functions against
hand-worked numbers, and the two readers it brings on a synthetic trace
whose operations are named as the chip names them (and nothing from a run
that lacks them)."""

import copy
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run  # noqa: E402  (no jax at import)
from benchmark.configs import mellum2_flops as work  # noqa: E402
from tests.benchmark import test_ling  # noqa: E402
from tests.benchmark.test_laguna import _eight_bit  # noqa: E402

CELL = "mellum_train_packed8k"
SHAPES = {
    name: (4, 8192) for name in ("input_ids", "labels", "positions", "segment_ids")
}
NEW_METRICS = ["doc_attention_ms", "doc_attention_roofline"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(_ROOT, "benchmark", "configs", "mellum2.json")) as fh:
        return json.load(fh)


# -------------------------------------------------------------- the config

def test_config_keeps_every_published_number_but_the_three_reduced():
    config = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config[key] != value and config["published"][key] == value
            else:
                assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (config["hidden_size"], config["head_dim"], config["sliding_window"]) == (
        2304, 128, 1024)
    assert (config["num_attention_heads"], config["num_key_value_heads"]) == (32, 4)
    assert (config["moe_intermediate_size"], config["num_experts_per_tok"]) == (896, 8)
    assert config["norm_topk_prob"] is True and config["scoring_func"] == "softmax"
    assert config["deployment"]["num_experts_routed"] == 64
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    assert config["num_experts"] * 4 == config["published"]["num_experts"]
    # one whole period of the layer pattern, every layer sparse
    n = config["num_hidden_layers"]
    assert config["layer_types"][:n] == ["sliding_attention"] * 3 + ["full_attention"]
    assert config["layer_types"][:n] == config["layer_types"][n:2 * n]
    assert set(config["mlp_layer_types"]) == {"sparse"}
    assert set(config["rope_parameters"]) == {"full_attention", "sliding_attention"}
    for key in ("router", "norms", "rotary", "packing", "mtp", "weights", "compute_dtype"):
        assert key in config["assumed"]
    assert "NOT a published key" in config["assumed"]["router"]
    assert config["min_tpu_custom_calls"] == 16  # 4 layers x fwd, fwd again, dq, dkv


def test_parameter_count_is_the_models():
    import jax

    from sparknet_tpu.models.decoder import DecoderConfig, DecoderLM

    config = _config()
    cfg = DecoderConfig.from_published(config)
    assert cfg.scoring_func == "softmax"
    assert cfg.shared_expert_intermediate_size == 0
    assert cfg.experts_held == (0, 16) and cfg.num_experts == 64
    assert set(cfg.num_attention_heads_per_layer) == {32}
    model = DecoderLM(cfg, {"input_ids": (4, 8192)})
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    counted = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    assert counted == config["parameters"] == 595153152
    # the issue's arithmetic, by part
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    layer = attention + 2 * 2304 + 2304 * 64 + 16 * 3 * 2304 * 896
    assert (attention, layer) == (21233664, 120476160)
    assert counted == 4 * layer + 2 * 24576 * 2304 + 2304
    assert not any(n.startswith("shared_") for n in params["layer_00"])


# ---------------------------------------------------- FLOPs, bytes, rooflines

def _pool_gauges(full=None, window=None):
    """The registry as ``lm_app.packing_note`` leaves it, or without the
    gauges: a program that built no packed feed."""
    from sparknet_tpu.telemetry.registry import REGISTRY

    REGISTRY.reset()
    if full is not None:
        REGISTRY.gauge("attn_pairs_pool", kind="full").set(full)
        REGISTRY.gauge("attn_pairs_pool", kind="window").set(window)


def test_flops_against_the_hand_worked_count():
    _pool_gauges()
    config = _config()
    per_token = work.matmul_macs_per_token(config)
    assert per_token == {
        "attention_projections": 4 * 21233664, "router": 4 * 2304 * 64,
        "head": 2304 * 24576,
    }
    # no packed feed was built: one document a sequence
    assert work.unbroken_pairs(8192) == 8192 * 8193 // 2
    assert work.unbroken_pairs(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024
    assert work.unbroken_pairs(4, 2) == 7 and work.unbroken_pairs(3, 8) == 6
    assert work.pool_pairs("full") is None
    assert work.seen_pairs(config, SHAPES) == {
        "full_attention": 4 * work.unbroken_pairs(8192),
        "sliding_attention": 4 * work.unbroken_pairs(8192, 1024),
    }
    # an even router's slots: a quarter of the experts are held
    assert work.held_slots(config, SHAPES) == 4 * 32768 * 8 * 16 / 64
    # the pool's mean sequence, where the program counted it
    _pool_gauges(full=10.9e6, window=5.75e6)
    pairs = work.seen_pairs(config, SHAPES)
    assert pairs == {"full_attention": 43.6e6, "sliding_attention": 23.0e6}
    one_product = (43.6e6 + 3 * 23.0e6) * 32 * 128
    assert work.attention_macs(config, pairs) == one_product
    dense = 32768 * sum(per_token.values())
    experts = 4 * 65536 * 3 * 2304 * 896
    assert work.train_step(config, SHAPES) == pytest.approx(
        2 * (3 * (dense + experts) + 6 * one_product))
    # the issue's reckoning: 32768 tokens and 65536 slots a layer are some 43 TFLOP
    assert work.train_step(config, SHAPES) / 1e12 == pytest.approx(43.2, abs=0.3)
    flops, nbytes = work.doc_attention_work(config, SHAPES)
    assert flops == 12 * one_product
    q_like, kv_like = 4 * 32 * 8192 * 128 * 2, 4 * 4 * 8192 * 128 * 2
    assert nbytes == 4 * (6 * q_like + 6 * kv_like)  # K and V once a KV head


def test_the_pools_mean_pairs_are_the_documents_own():
    """``data.text.pool_pairs`` against a count by hand from the lengths of
    the pool's documents, and steady over seeds where a batch is not."""
    import numpy as np

    from sparknet_tpu.data.text import packed_dataset, pool_pairs

    kw = dict(vocab_size=512, n_tokens=1 << 16, seq_len=256, median_len=40.0,
              min_len=4, max_len=256)
    ds = packed_dataset(seed=3, **kw)
    rows = np.concatenate(
        [ds.collect_partition(i)["segment_ids"] for i in range(ds.num_partitions)])
    full = window = 0
    for row in rows:
        for n in np.bincount(row):  # a document of n tokens
            full += n * (n + 1) // 2
            window += work.unbroken_pairs(int(n), 16)
    assert pool_pairs(ds) == pytest.approx(full / len(rows))
    assert pool_pairs(ds, 16) == pytest.approx(window / len(rows))
    others = [pool_pairs(packed_dataset(seed=seed, **kw)) for seed in (4, 5)]
    assert max(others) / min(others) < 1.15  # a batch of 4: 0.45-1.75 x the mean


# ------------------------------------------------------------- the readers

def _flash(kind, n):
    big = "bf16[4,32,8192,128]{3,2,1,0:T(8,128)(2,1)}"
    return (
        f"%{kind}.{n} = ({big}, f32[4,32,8192,128]{{3,2,1,0:T(8,128)}}) "
        f"custom-call(s32[3]{{0:T(128)S(1)}} %copy-done.{n}, s32[64]{{0:T(128)S(1)}} "
        f"%fusion.{n}, {big} %fusion.{n + 1}, bf16[4,4,8192,128]{{3,2,1,0:T(8,128)(2,1)}} "
        f"%fusion.{n + 2}, s32[4,8,8192]{{2,1,0:T(8,128)}} %broadcast.{n}), "
        f'custom_call_target="tpu_custom_call"'
    )


def _synthetic_run(seconds_scale=1.0):
    """A traced run's record with operations named as the chip names them:
    a step's sixteen flash kernels on ``bf16[4,32,8192,128]``, grouped
    products, routing operations on the routing's shapes, and others."""
    ops = {}
    for n in range(4):
        ops[_flash("flash_attention_fwd", n)] = 0.006
        ops[_flash("checkpoint_flash_attention_fwd", 10 + n)] = 0.006
        ops[_flash("flash_attention_dq", 20 + n)] = 0.010
        ops[_flash("flash_attention_dkv", 30 + n)] = 0.014
    ops["%ragged-dot-none.3 = f32[81920,1792]{1,0:T(8,128)} custom-call(s32[1]{0:T(128)} "
        "%get-tuple-element.9, bf16[81920,2304]{1,0:T(8,128)(2,1)} %fusion.77)"] = 0.240
    ops["%ragged-dot-metadata.1 = (s32[17]{0}, s32[95]{0}) custom-call(s32[16]{0} %x)"] = 0.002
    ops["%fusion.77 = bf16[81920,2304]{1,0:T(8,128)(2,1)} fusion(bf16[32768,2304]{1,0} "
        "%p, s32[81920]{0} %slice.4), kind=kCustom, calls=%fused_computation.77"] = 0.100
    ops["%sort.2 = (s32[262144]{0}, s32[262144]{0}) sort(s32[262144]{0} %a, s32[262144]{0} %b)"] = 0.040
    ops["%fusion.80 = f32[32768,64]{1,0} fusion(bf16[32768,2304]{1,0} %u), kind=kOutput"] = 0.030
    ops["%fusion.81 = f32[32768,8]{1,0} fusion(f32[32768,64]{1,0} %u), kind=kLoop"] = 0.010
    ops["%while.7 = (s32[], f32[32768,2304]{1,0}, s32[327680]{0}) while((s32[], "
        "f32[32768,2304]{1,0}, s32[327680]{0}) %tuple.3), body=%b"] = 0.900  # a container
    ops["%fusion.90 = f32[2304,4096]{1,0} fusion(f32[2304,4096]{1,0} %m), kind=kLoop"] = 0.050
    steps = 4
    return {
        "trace": {
            "steps": steps, "device_step_s": [1.0] * steps, "window_s": 4.0,
            "busy_s": 3.99,
            "op_seconds": {k: v * steps * seconds_scale for k, v in ops.items()},
        },
        "shapes": SHAPES, "config": _config(), "chips": 1, "peaks": PEAKS,
        "flops_per_step": 43.2e12,
    }


def _read(metric, recorded):
    return run.metric_reader(run.load_cell(CELL)["layers_dir"], metric)(recorded)


def test_readers_read_the_packed_cells_operations_by_what_they_are():
    recorded = _synthetic_run()
    _pool_gauges(full=10.9e6, window=5.75e6)
    assert _read("doc_attention_ms", recorded) == pytest.approx(
        1e3 * 4 * (0.006 + 0.006 + 0.010 + 0.014))
    flops, nbytes = work.doc_attention_work(recorded["config"], SHAPES)
    assert flops / 197e12 > nbytes / 819e9  # compute-bound
    assert _read("doc_attention_roofline", recorded) == pytest.approx(
        100 * (flops / 197e12) / 0.144)
    # the work is the pool's mean sequence, whatever the newest step had
    _pool_gauges(full=7.5e6, window=5e6)
    assert _read("doc_attention_roofline", recorded) == pytest.approx(
        100 * (12 * 4 * (7.5e6 + 3 * 5e6) * 32 * 128 / 197e12) / 0.144)
    _pool_gauges()  # a program without the gauges
    assert _read("doc_attention_roofline", recorded) is None
    assert _read("doc_attention_ms", recorded) is not None  # the trace's own


def test_a_roofline_share_cannot_pass_100_on_work_counted_once():
    recorded = _synthetic_run()
    _pool_gauges(full=10.9e6, window=5.75e6)
    flops, nbytes = work.doc_attention_work(recorded["config"], SHAPES)
    least = max(flops / 197e12, nbytes / 819e9)
    ops, steps = recorded["trace"]["op_seconds"], recorded["trace"]["steps"]
    for name in list(ops):
        if "flash_attention" in name.split(" = ")[0]:
            ops[name] = steps * least / 16  # sixteen such operations
    assert _read("doc_attention_roofline", recorded) == pytest.approx(100.0)
    slower = _synthetic_run(seconds_scale=3.0)
    assert 0 < _read("doc_attention_roofline", slower) < 100


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
        "peaks": PEAKS,
    }
    assert _read(metric, bert) is None
    assert _read(metric, dict(_synthetic_run(), trace=None)) is None
    alexnet = dict(bert, shapes={"data": (1024, 227, 227, 3)}, config={})
    assert _read(metric, alexnet) is None


# ------------------------------------------------------------ the rehearsal

def _tiny_form():
    from tests.test_decoder import mellum_form, tiny_mellum

    return mellum_form(tiny_mellum())


def tiny_reference_loss(params, batch):
    """The plain reference on the tiny configuration the rehearsal runs."""
    from benchmark.configs import mellum2_reference

    return mellum2_reference.make_loss(_tiny_form())(params, batch)


def _tiny_cell(tmp_path, form=None):
    path = tmp_path / "tiny_mellum.json"
    path.write_text(json.dumps(form or _tiny_form()))
    cell = copy.deepcopy(run.load_cell(CELL))
    cell["config"]["argv"] = ["--config", str(path), "--remat"]  # float32 on the CPU
    cell["traffic"]["argv"] = [
        "--seq-len", "64", "--batch-size", "4", "--pack-documents", "--doc-median",
        "20", "--doc-min", "4", "--doc-max", "64", "--synthetic-tokens", "4096"]
    cell["config"]["min_tpu_custom_calls"] = 0  # the CPU picks reference attention
    cell["config"].pop("parameters")  # the tiny preset's count is its own
    cell["config"]["reference"]["forward"] = (
        "tests.benchmark.test_mellum:tiny_reference_loss")
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
        "mellum2", "clm_packed_s8192_bs4", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == "mellum2")
    assert entry["reduced"] == _config()["reduced"] and entry["source"] == _config()["source"]
    assert 0 < len(cell["why"]) <= 200 and 0 < len(entry["why"]) <= 200  # the contract's
    loaded = run.load_cell(CELL)
    argv = loaded["traffic"]["argv"]
    assert argv[:5] == ["--seq-len", "8192", "--batch-size", "4", "--pack-documents"]
    assert argv[argv.index("--prefetch") + 1] == "2"
    assert loaded["traffic"]["trace"] == {
        "dispatch_steps": 12, "fenced_steps": 8, "skip_steps": 2, "steps": 12}
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == NEW_METRICS
    assert all(m["moves"] == "samples_per_s" for m in mine)
    reported = {m["name"] for m in loaded["per_layer"]}
    assert reported == set(NEW_METRICS) | {
        "input_wait_share", "dispatch_ms", "device_step_ms", "mfu_device",
        "device_idle_share", "feed_source_ms", "feed_h2d_ms", "feed_backpressure_ms"}
    # the accepted decoder cells keep their lists
    for name in ("window_attention_ms", "moe_route_ms", "mla_attention_ms"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]


def test_the_cell_before_this_one_keeps_all_but_its_place_at_the_end():
    """``test_ling``'s manifest test also asserts that its cell's entries are
    the LAST of ``workloads`` and ``per_layer``; this PR appended a cell, as
    the contract says new entries are, and may not edit that file, so
    ``tests/conftest.py`` marks it ``xfail``.  Nothing else of it is muted:
    run here, that assertion is the first and only one to fail, and what
    follows it there is asserted here."""
    with pytest.raises(AssertionError) as caught:
        test_ling.test_manifest_entries_are_the_issues()
    failing = str(caught.traceback[-1].statement)
    assert 'manifest["per_layer"][-5:] == mine' in failing, failing
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    # its place now: a block, just before this cell's entries (which need
    # not stay the last either)
    names = [m["name"] for m in manifest["per_layer"]]
    at, block = names.index(test_ling.NEW_METRICS[0]), test_ling.NEW_METRICS + NEW_METRICS
    assert names[at:at + len(block)] == block
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == cells.index(test_ling.CELL) + 1
    reported = {m["name"] for m in run.load_cell(test_ling.CELL)["per_layer"]}
    assert reported == set(test_ling.NEW_METRICS) | {
        "input_wait_share", "dispatch_ms", "device_step_ms", "mfu_device",
        "device_idle_share", "feed_source_ms", "feed_h2d_ms", "feed_backpressure_ms"}


def test_cell_rehearses_tiny_through_the_functions_main_calls(clock, tmp_path, capsys):
    out = run.run_cell(
        _tiny_cell(tmp_path), seed=4000000007, seconds=0.5, trace=False,
        clock=clock, trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    )
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {
        "samples_per_s", "pace_ms_p90", "step_hbm_gb", "setup_s"}
    assert out["compared"]["reference_abs_diff"]["value"] < 1e-4  # f32 against f32
    printed = capsys.readouterr().out
    assert "train feed: packed documents, lengths clip(lognormal(median 20" in printed
    assert "'segment_ids': (4, 64)" in printed and "'positions': (4, 64)" in printed
    json.dumps(out)


FAULTS = ["eight_bit_weights", "document_mask_off"]


def _plant(fault, monkeypatch):
    """The control (the nearest precision below bfloat16's) or the
    mechanism's fault, in the program that ``lm_app.build`` builds."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.models import decoder

    if fault == "eight_bit_weights":
        class RoundedWeights(lm_app.DecoderLM):
            def apply(self, params, *args, **kwargs):
                params = jax.tree_util.tree_map(_eight_bit, params)
                return super().apply(params, *args, **kwargs)

        monkeypatch.setattr(lm_app, "DecoderLM", RoundedWeights)
    else:  # the same kernels and tables, told that a sequence is one document
        whole = decoder.attention
        monkeypatch.setattr(
            decoder, "attention",
            lambda *a, segment_ids, **kw: whole(
                *a, segment_ids=jnp.zeros_like(segment_ids), **kw))


def _reads_not_correct(out):
    compared = out["compared"]["reference_abs_diff"]
    assert compared["value"] > compared["at_most"], out
    assert compared["at_most"] == _config()["reference"]["abs_tolerance"]
    assert out["correct"] is False
    assert out["failed"] == 0  # the steps themselves ran


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_reads_not_correct_through_the_cell(
    fault, clock, tmp_path, monkeypatch
):
    """The control and the mechanism, planted in the program and taken
    through ``run.run_cell`` and ``reference.compare`` as a real run is:
    everything else holds, the reference check does not, ``correct`` is
    false.  (Gain 12: ``tests/test_decoder.py`` says why.)"""
    _plant(fault, monkeypatch)
    cell = _tiny_cell(tmp_path)
    cell["config"]["reference"]["weight_gain"] = 12.0
    _reads_not_correct(run.run_cell(
        cell, seed=4000000011, seconds=0.2, trace=False, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    ))


@pytest.mark.skipif(
    os.environ.get("SPARKNET_TEST_TPU", "") in ("", "0"),
    reason="the cell at its own size, on the chip: SPARKNET_TEST_TPU=1",
)
@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_reads_not_correct_on_hardware(fault, tmp_path, monkeypatch):
    """The two readings the cell's tolerance lies between, through
    ``run.run_cell`` at the cell's own shapes, gain and tolerance: 8-bit
    weights read 7.6e-4 .. 1.96e-3 and the document mask off 5.7e-3 .. 1.4e-2
    against 3.2e-4 (``mellum2.json``'s ``reference.why``).  A step program
    takes 16.5 GB: what the test before left on the chip goes first."""
    import gc

    import jax

    from benchmark import flops

    gc.collect()
    _plant(fault, monkeypatch)
    out = run.run_cell(
        run.load_cell(CELL), seed=3500000061 + FAULTS.index(fault), seconds=3.0,
        trace=False, clock=run.CompileClock(), trace_dir=str(tmp_path),
        peaks=flops.peaks(jax.devices()[0].device_kind),
    )
    compared = out["compared"]
    print(f"{fault}: {compared['reference_abs_diff']} {compared['tpu_custom_calls']}")
    _reads_not_correct(out)
    # all else is the cell's.  But rounding by bit operations passes no
    # gradient, so the 8-bit program's step has no backward pass: its lowered
    # text holds the 4 forward kernels for 16 (and 9.6 GB for 16.5)
    dropped = {"tpu_custom_calls"} if fault == "eight_bit_weights" else set()
    for name in set(compared) - {"reference_abs_diff"} - dropped:
        assert run.holds(compared[name]), (name, compared[name])


def test_traced_rehearsal_reports_the_shared_metrics_and_the_new_ones(
    clock, tmp_path, monkeypatch
):
    """The CPU has no device plane, so a synthetic record stands in for the
    profiler's; the timeline parts, the shared readers and the program's
    counters run for real.  The tiny batch has other shapes than the
    synthetic operations, so the shape-matched readers find nothing."""
    def recorded_steps(solver, feed, loss_key, skip, count, trace_dir):
        log = run.run_steps(solver, feed, loss_key, count=skip + count)
        trace = dict(_synthetic_run()["trace"], program="jit_fused(1)")
        return {**log, "trace": trace}

    monkeypatch.setattr(run, "traced_steps", recorded_steps)
    out = run.run_cell(
        _tiny_cell(tmp_path), seed=7, seconds=0.5, trace=True, clock=clock,
        trace_dir=str(tmp_path), peaks=PEAKS,
    )
    assert out["correct"] is True, out
    metrics = out["metrics"]
    assert {"dispatch_ms", "device_step_ms", "mfu_device", "device_idle_share",
            "input_wait_share", "feed_source_ms", "feed_h2d_ms",
            "feed_backpressure_ms"} <= set(metrics)
    assert not set(NEW_METRICS) & set(metrics)  # no operation on (4, 32, 64, 128)
    # the model work is a mean step's: the batch times the pool's mean sequence
    assert 0 < work.pool_pairs("window") <= work.pool_pairs("full") < 64 * 65 / 2
