"""The benchmark's harness, on the CPU: the manifest resolves to its files,
new cells and metrics are found by name, both cells rehearse tiny through
the functions ``main`` calls, ``main`` refuses anything but a TPU, and the
yardstick (trace reduction, FLOPs, peaks) gives hand-checked numbers."""

import copy
import hashlib
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
from types import SimpleNamespace

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import flops, run, trace_reduce  # noqa: E402  (no jax at import)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
_RECORDED = os.path.join(os.path.dirname(__file__), "recorded_trace.json")


def _manifest():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _recorded():
    with open(_RECORDED) as fh:
        return json.load(fh)


# ------------------------------------------------------------- the manifest

def test_manifest_names_units_and_files_resolve():
    m = _manifest()
    metrics = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in metrics + m["configs"] + m["workloads"]]
    names += [w["traffic"] for w in m["workloads"]]
    assert all(_NAME.match(n) for n in names), names
    assert all(_UNIT.match(x["unit"]) for x in metrics)
    assert all(x["better"] in ("lower", "higher") for x in metrics)
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    end_to_end = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in end_to_end
    assert all(0 < x["bound"] <= 0.1 for x in m["end_to_end"])
    for entry in m["configs"]:
        assert any(entry["file"].startswith(p + "/") for p in m["paths"])
        assert os.path.isfile(os.path.join(_ROOT, entry["file"]))
    for w in m["workloads"]:
        cell = run.load_cell(w["name"])  # reads the config and traffic files
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert cell["config"]["reduced"] == next(
            c["reduced"] for c in m["configs"] if c["name"] == w["config"]
        )
        for metric in cell["per_layer"]:
            assert metric["moves"] in end_to_end
            assert callable(run.metric_reader(cell["layers_dir"], metric["name"]))
        assert {x["name"] for x in cell["end_to_end"]} <= {
            "samples_per_s", "pace_ms_p90", "step_hbm_gb", "setup_s",
        }  # what run.end_to_end takes


# ------------------------------------------------------ the pace's slow tenth

def _pairs(first, n=260, late=()):
    """Steps that complete in pairs, as ``alexnet_live``'s do since the
    loader's two workers finish together: 68.5 ms (the second batch is
    ready, the device paces) and 150 ms (the wait for the next pair) in
    turn, starting on ``first``; at each index of ``late`` a batch comes
    100 ms late and the device catches up on the next."""
    short, long = 0.0685, 0.150
    steps = [(short, long)[(i + (first == "long")) % 2] for i in range(n)]
    for i in late:
        steps[i] += 0.100
        steps[i + 1] = max(0.025, steps[i + 1] - 0.100)
    return steps


_mean, _median = statistics.fmean, statistics.median

_STRETCH = [0.1] * 105 + [0.2] * 30 + [0.1] * 105  # an eighth at twice the pace
_RECURS = ([0.1] * 21 + [0.2] * 9) * 8             # the same, every 3 s
_PACE_CASES = {
    # steps, what the statistic has to read (lo, hi) as shares of `of`
    "pairs_short_first": (_pairs("short"), (0.97, 1.03), _mean),
    "pairs_long_first": (_pairs("long"), (0.97, 1.03), _mean),
    "pairs_with_late_batches_short_first": (
        _pairs("short", late=(31, 90, 171, 222)), (0.97, 1.03), _mean),
    "pairs_with_late_batches_long_first": (
        _pairs("long", late=(31, 90, 171, 222)), (0.97, 1.03), _mean),
    "pairs_with_stalls_of_200_to_300_ms": (
        [x + y for x, y in zip(
            _pairs("long"), [{40: 0.05, 200: 0.15}.get(i, 0.0)
                             for i in range(260)])],
        (0.97, 1.03), _mean),
    "steady_275_ms": ([0.275] * 109, (0.99999, 1.00001), _mean),
    "one_stretch_in_eight_at_twice_the_pace": (_STRETCH, (1.4, 2.0), _median),
    "a_slow_stretch_that_recurs": (_RECURS, (1.4, 2.0), _median),
    "a_window_shorter_than_two_runs": (
        [0.1] * 16 + [0.9] * 15, (0.99999, 1.00001), _mean),
    "one_step": ([0.3], (0.99999, 1.00001), _mean),
}


@pytest.mark.parametrize("case", sorted(_PACE_CASES))
def test_pace_p90_keeps_a_slow_stretch_and_loses_the_phase_of_a_pairing(case):
    steps, (lo, hi), of = _PACE_CASES[case]
    got = run.pace_p90(steps)
    assert lo * of(steps) <= got <= hi * of(steps), (got, of(steps))
    if case.startswith("pairs"):
        # what the retired statistic did on the same steps: the 90th
        # percentile of the single intervals sits on the long half
        assert run.pace_p90(steps, 1) >= 1.3 * _mean(steps)


def test_end_to_end_is_the_pace_and_the_manifest_names_no_single_step_tail():
    m = _manifest()
    names = [x["name"] for x in m["end_to_end"]]
    assert names == ["samples_per_s", "pace_ms_p90", "step_hbm_gb", "setup_s"]
    assert "step_ms_p90" not in json.dumps(m)
    assert all(x["moves"] in names for x in m["per_layer"])
    pace = next(x for x in m["end_to_end"] if x["name"] == "pace_ms_p90")
    assert (pace["unit"], pace["better"], pace["source"]) == (
        "ms", "lower", "host_clock")
    steps = _pairs("long", late=(31, 90))
    got = run.end_to_end({
        "window": {"step_s": steps, "completed": len(steps),
                   "window_s": sum(steps), "opened": run._PROCESS_T0 + 40.0},
        "samples": 1024, "program": {"bytes": {"total": 5.5e9}},
        "reference": {"seconds": 4.0},
    })
    assert got["pace_ms_p90"] == pytest.approx(1e3 * run.pace_p90(steps))
    assert got["samples_per_s"] == pytest.approx(1024 / _mean(steps))
    assert got["setup_s"] == pytest.approx(36.0) and got["step_hbm_gb"] == 5.5


def _digests(top):
    out = {}
    for folder, _dirs, files in os.walk(top):
        for f in files:
            if f.endswith(".pyc"):
                continue
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_config_traffic_and_layer_metric_are_found_by_name(tmp_path):
    """A later PR adds files and manifest entries, and edits no file."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(_ROOT, "benchmark"), os.path.join(root, "benchmark"))
    before = _digests(os.path.join(root, "benchmark"))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "toy.json"), "w") as fh:
        json.dump({"name": "toy", "builder": "x", "argv": [], "reduced": []}, fh)
    with open(os.path.join(bench, "traffic", "toy_mix.json"), "w") as fh:
        json.dump({"name": "toy_mix", "argv": ["--batch-size", "3"]}, fh)
    with open(os.path.join(bench, "layers", "toy_metric.py"), "w") as fh:
        fh.write("def read(run):\n    return run.get('toy')\n")
    m = _manifest()
    m["configs"].append({
        "name": "toy", "source": "none", "file": "benchmark/configs/toy.json",
        "reduced": [], "why": "a test",
    })
    m["workloads"].append({
        "name": "toy_cell", "config": "toy", "traffic": "toy_mix", "chips": 1,
        "why": "a test",
    })
    m["per_layer"].append({
        "name": "toy_metric", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "Step loop",
        "moves": "samples_per_s", "workloads": ["toy_cell"],
    })
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)

    cell = run.load_cell("toy_cell", root=root)
    assert cell["config"]["name"] == "toy"
    assert cell["traffic"]["argv"] == ["--batch-size", "3"]
    per_layer = [x["name"] for x in cell["per_layer"]]
    assert "toy_metric" in per_layer and "flash_attention_ms" not in per_layer
    read = run.metric_reader(cell["layers_dir"], "toy_metric")
    assert read({"toy": 1.5}) == 1.5
    assert read({}) is None  # nothing to read: the harness leaves it out
    # the other cells do not see the new metric, and no file was edited
    other = run.load_cell("bert_mlm", root=root)
    assert "toy_metric" not in [x["name"] for x in other["per_layer"]]
    after = _digests(bench)
    assert {k: after[k] for k in before} == before


# ---------------------------------------------------------------- rehearsals

_TINY = {
    "alexnet_live": {
        "config_argv": ["--arch", "alexnet"],  # float32: the CPU has no bf16 units
        "traffic_argv": ["--synthetic", "--synthetic-n", "64", "--batch-size", "2"],
    },
    "bert_mlm": {
        "config_argv": ["--config", "tiny"],
        "traffic_argv": ["--seq-len", "64", "--batch-size", "2",
                         "--synthetic-tokens", "4096"],
    },
}


def _tiny_cell(name):
    cell = copy.deepcopy(run.load_cell(name))
    cell["config"]["argv"] = _TINY[name]["config_argv"]
    cell["traffic"]["argv"] = _TINY[name]["traffic_argv"]
    cell["config"]["min_tpu_custom_calls"] = 0  # the CPU picks reference attention
    if name == "bert_mlm":
        cell["config"].pop("parameters")  # the tiny preset's count is its own
    cell["traffic"]["warm_steps"] = 1
    cell["traffic"]["trace"].update(
        dispatch_steps=2, skip_steps=1, steps=2, fenced_steps=2,
    )
    if "lead_steps" in cell["traffic"]["trace"]:  # bert_mlm names none
        cell["traffic"]["trace"]["lead_steps"] = 3
    return cell


@pytest.fixture(scope="module")
def clock():
    return run.CompileClock()  # one for the process: jax cannot unregister it


@pytest.mark.parametrize("name", sorted(_TINY))
def test_cell_rehearses_tiny_through_the_functions_main_calls(name, clock, tmp_path):
    cell = _tiny_cell(name)
    out = run.run_cell(
        cell, seed=4000000007, seconds=0.5, trace=False, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    )
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"  # named for what it ran on
    assert out["device"]["memory_peak_bytes"] > 0
    json.dumps(out)


def _round_mantissa(x, bits):
    """float32 ``x`` rounded to ``bits`` bits of mantissa (bfloat16 has 7,
    an 8-bit float 2 or 3)."""
    import jax
    import jax.numpy as jnp

    drop = 23 - bits
    i = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    i = (i + jnp.uint32(1 << (drop - 1))) & jnp.uint32(0xFFFFFFFF ^ ((1 << drop) - 1))
    return jax.lax.bitcast_convert_type(i, jnp.float32)


def _eight_bit_weights(params):
    import jax

    return jax.tree_util.tree_map(lambda x: _round_mantissa(x, 3), params)


def _constant_logits(params):
    """The last matrix before the loss multiplies by nothing: AlexNet's fc8,
    BERT's MLM-head dense layer."""
    params = dict(params)
    name = "fc8" if "fc8" in params else "mlm_head"
    params[name] = {
        k: v * 0.0 if k in ("weight", "dense_w") else v
        for k, v in params[name].items()
    }
    return params


def _skipped_layer(params):
    """One layer passes its input on: AlexNet's conv4 (384 to 384 channels
    in two groups, after a ReLU) as the identity filter, BERT's last
    encoder layer with nothing but its residual paths."""
    import jax.numpy as jnp

    params = dict(params)
    if "conv4" in params:
        w = params["conv4"]["weight"]  # HWIO, I = the inputs of one group
        per_group = w.shape[2]
        eye = jnp.tile(jnp.eye(per_group, dtype=w.dtype), (1, w.shape[3] // per_group))
        identity = jnp.zeros_like(w).at[w.shape[0] // 2, w.shape[1] // 2].set(eye)
        params["conv4"] = {"weight": identity, "bias": 0.0 * params["conv4"]["bias"]}
    else:
        last = sorted(k for k in params if k.startswith("layer_"))[-1]
        params[last] = {
            k: v * 0.0 if k in ("out_w", "out_b", "ffn_out_w", "ffn_out_b") else v
            for k, v in params[last].items()
        }
    return params


_FAULTS = {
    "intact": None,
    "eight_bit_weights": _eight_bit_weights,
    "constant_logits": _constant_logits,
    "skipped_layer": _skipped_layer,
}


@pytest.fixture(scope="module")
def built_tiny():
    """Each tiny cell built once for the reference tests."""
    made = {}

    def get(name):
        if name not in made:
            cell = _tiny_cell(name)
            built = run.build_cell(cell["config"], cell["traffic"], seed=5)
            made[name] = (cell, built, next(built["feed"]))
        return made[name]

    yield get
    for _cell, built, _batch in made.values():
        run.close_feed(built)


@pytest.mark.parametrize("fault", sorted(_FAULTS))
@pytest.mark.parametrize("name", sorted(_TINY))
def test_reference_check_passes_the_system_and_fails_a_broken_one(
    name, fault, built_tiny
):
    """The check has to have teeth at the configuration's own gain and
    tolerance: the float32 system agrees with the plain reference to
    rounding on the shaken weights, and a system with
    8-bit weights, with logits that do not depend on the input, or with a
    layer that adds nothing is refused.  (At the seed's unshaken weights
    all four read ln(classes) within any tolerance bfloat16 passes.)"""
    from benchmark import reference

    cell, built, batch = built_tiny(name)
    solver = built["solver"]
    net, break_it = solver.train_net, _FAULTS[fault]
    if break_it:
        system = SimpleNamespace(
            apply=lambda params, *a, **kw: net.apply(break_it(params), *a, **kw),
            loss_and_metrics=net.loss_and_metrics,
        )
    else:
        system = net
    check = cell["config"]["reference"]
    out = reference.compare(
        SimpleNamespace(train_net=system, params=solver.params, state=solver.state),
        batch, run.resolve(check["forward"]), check["weight_gain"],
        check["abs_tolerance"],
    )
    assert len(out["system_losses"]) == 2  # one per example of the batch of 2
    assert all(5.0 < x < 30.0 for x in out["reference_losses"]), out
    if break_it:
        assert not out["ok"] and out["abs_diff"] > 2 * check["abs_tolerance"], out
    else:
        assert out["abs_diff"] < 1e-4, out  # float32 against float32
        shaken = max(out["reference_losses"]) - min(out["reference_losses"])
        assert shaken > 10 * check["abs_tolerance"], out  # the loss carries signal


def test_a_fallen_back_feed_or_a_missing_kernel_is_not_correct(clock, tmp_path):
    cell = _tiny_cell("bert_mlm")
    cell["traffic"]["feed_type"] = "NativeLoader"
    cell["config"]["min_tpu_custom_calls"] = 36
    out = run.run_cell(
        cell, seed=1, seconds=0.2, trace=False, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 1e12},
    )
    assert out["correct"] is False


def _recorded_steps(solver, feed, loss_key, skip, count, trace_dir):
    """``run.traced_steps`` without a device plane to trace: the same loop,
    and the recorded list reduced in the trace's place."""
    log = run.run_steps(solver, feed, loss_key, count=skip + count)
    reduced = trace_reduce.reduce_trace(_recorded()["devices"], skip, count)
    return {**log, "trace": reduced}


def test_traced_run_reports_the_cells_layer_metrics(clock, tmp_path, monkeypatch):
    """The CPU has no device plane to trace, so the recorded list stands in
    for the profiler; the timeline parts and the readers run for real."""

    monkeypatch.setattr(run, "traced_steps", _recorded_steps)
    cell = _tiny_cell("bert_mlm")
    out = run.run_cell(
        cell, seed=7, seconds=0.5, trace=True, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 197e12},
    )
    assert out["correct"] is True, out
    # flash_attention_ms finds no kernel in the recorded (AlexNet) list and
    # is left out; every other per-layer metric of the cell is there
    wanted = {m["name"] for m in cell["per_layer"]} - {"flash_attention_ms"}
    assert set(out["metrics"]) == wanted
    # busy and window are the trace's own: two recorded steps of 68 ms,
    # back to back over their resident batch
    busy, window = out["device"]["busy_s"], out["device"]["window_s"]
    assert busy == pytest.approx(0.1367, rel=0.01) and busy <= window < 0.14
    idle = out["metrics"]["device_idle_share"]["value"]
    assert idle == pytest.approx(100 * (1 - busy / window))
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert {name for name, _s in out["breakdown"]["idle_gaps"]} >= {"input_wait"}
    assert 0 <= out["metrics"]["input_wait_share"]["value"] <= 100
    assert out["metrics"]["dispatch_ms"]["value"] > 0


def test_main_without_a_tpu_exits_non_zero_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmark", "run.py"),
         "--workload", "bert_mlm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert "platform=cpu" in out.stderr
    assert out.stdout.strip() == ""


# ------------------------------------------------------------ trace reduction

def test_busy_is_a_union_and_a_step_is_its_own_operations():
    modules = [("jit_step(1)", 0, 100), ("jit_step(1)", 200, 100), ("jit_step(1)", 400, 100)]
    ops = [
        ("a", 0, 60), ("b", 40, 40),       # overlap: 80 busy, not 100
        ("a", 200, 50), ("b", 260, 40),    # a gap of 10 inside the step
        ("a", 400, 100),
    ]
    out = trace_reduce.reduce_device(modules, ops, skip=0, count=3)
    assert out["device_step_s"] == [80e-9, 90e-9, 100e-9]
    assert out["busy_s"] == pytest.approx(270e-9)
    assert out["window_s"] == pytest.approx(500e-9)
    assert out["op_seconds"] == {"a": pytest.approx(210e-9), "b": pytest.approx(80e-9)}


def test_steps_before_the_first_counted_one_are_ignored():
    modules = [("jit_other(9)", 0, 5)] + [("jit_step(1)", 100 * i, 50) for i in range(1, 6)]
    ops = [("slow_first", 100, 50)] + [("a", 100 * i, 20) for i in range(2, 6)]
    out = trace_reduce.reduce_device(modules, ops, skip=1, count=3)
    assert out["program"] == "jit_step(1)"
    assert out["device_step_s"] == [20e-9] * 3
    assert "slow_first" not in out["op_seconds"]
    assert out["window_s"] == pytest.approx(250e-9)  # 200 .. 450
    with pytest.raises(ValueError, match="fewer than"):
        trace_reduce.reduce_device(modules, ops, skip=3, count=3)


def test_the_one_plane_that_ran_is_reduced_and_several_are_an_error():
    step = lambda d: {"modules": [("jit_step(1)", 0, 100)], "ops": [("a", 0, d)]}
    idle = {"modules": [], "ops": []}
    out = trace_reduce.reduce_trace(
        {"/device:TPU:0": idle, "/device:TPU:1": step(60)}, skip=0, count=1
    )
    assert out["device_step_s"] == [pytest.approx(60e-9)]
    for devices in (
        {"/device:TPU:0": step(40), "/device:TPU:1": step(60)},
        {"/device:TPU:0": idle},
    ):
        with pytest.raises(ValueError, match="exactly one"):
            trace_reduce.reduce_trace(devices, skip=0, count=1)


def test_async_ops_and_other_lines_are_not_read_as_busy():
    ev = lambda name, s, d: SimpleNamespace(name=name, start_ns=s, duration_ns=d)
    line = lambda name, events: SimpleNamespace(name=name, events=events)
    planes = [
        SimpleNamespace(name="/device:TPU:0", lines=[
            line("Steps", [ev("0", 0, 100)]),
            line("XLA Modules", [ev("jit_step(1)", 0, 100)]),
            line("XLA Ops", [ev("a", 0, 30)]),
            line("Async XLA Ops", [ev("copy-start", 0, 100)]),
        ]),
        SimpleNamespace(name="/host:CPU", lines=[
            line("python3", [ev("PjitFunction(fused)", 0, 9)]),
        ]),
    ]
    loaded = trace_reduce.events_of(planes)
    assert loaded == {
        "/device:TPU:0": {"modules": [("jit_step(1)", 0, 100)], "ops": [("a", 0, 30)]},
    }
    out = trace_reduce.reduce_trace(loaded, skip=0, count=1)
    assert out["busy_s"] == pytest.approx(30e-9)


def test_recorded_chip_trace_reduces_to_what_was_read_by_hand():
    """A few steps recorded on the chip (TPU v5 lite): see the file's
    ``what`` key for where it comes from and the numbers to expect."""
    recorded = _recorded()
    expect = recorded["expect"]
    out = trace_reduce.reduce_trace(recorded["devices"], expect["skip"], expect["count"])
    assert out["steps"] == expect["count"]
    assert out["busy_s"] <= out["window_s"]
    ops = next(iter(recorded["devices"].values()))["ops"]
    assert out["busy_s"] < sum(d for _n, _s, d in ops) / 1e9  # union, not sum
    assert out["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert out["device_step_s"] == pytest.approx(expect["device_step_s"], rel=1e-9)
    assert sum(out["op_seconds"].values()) >= out["busy_s"]


# ---------------------------------------------------------- FLOPs and peaks

def test_alexnet_flops_against_the_hand_worked_count():
    config = run.load_cell("alexnet_live")["config"]
    macs = flops.convnet_layer_macs(config["layers"], 227, 3)
    assert macs == {
        "conv1": 55 * 55 * 96 * 11 * 11 * 3,        # 105 415 200
        "conv2": 27 * 27 * 256 * 5 * 5 * 48,        # two groups of 48 in
        "conv3": 13 * 13 * 384 * 3 * 3 * 256,
        "conv4": 13 * 13 * 384 * 3 * 3 * 192,
        "conv5": 13 * 13 * 256 * 3 * 3 * 192,
        "fc6": 6 * 6 * 256 * 4096,
        "fc7": 4096 * 4096,
        "fc8": 4096 * 1000,
    }
    assert sum(macs.values()) == 724_406_816
    # forward + two backward passes, but conv1 needs no input gradient
    per_image = 2 * (3 * 724_406_816 - 105_415_200)
    assert per_image == 4_135_610_496
    assert flops.convnet(config, {"data": (1024, 227, 227, 3)}) == 1024 * per_image


def test_bert_flops_against_the_hand_worked_count():
    config = run.load_cell("bert_mlm")["config"]
    tokens, predicted = 64 * 512, 64 * 77
    layer = tokens * (4 * 768 * 768 + 2 * 768 * 3072) + 64 * 2 * 512 * 512 * 768
    assert layer == 257_698_037_760
    head = predicted * (768 * 768 + 768 * 30522)
    expected = 6 * (12 * layer + head)
    assert expected == 19_264_799_047_680
    got = flops.bert_mlm(config, {"input_ids": (64, 512), "mlm_positions": (64, 77)})
    assert got == expected


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
