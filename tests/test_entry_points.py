"""Driver-facing entry points must never regress.

These tests import and execute the exact artifacts the driver runs —
``__graft_entry__.entry``, ``__graft_entry__.dryrun_multichip`` and
``bench.py`` — so any regression fails CI before it can cost a round.
``bench.main`` measures a TPU and refuses anything else; its arms are
rehearsed here as functions, on the CPU, at tiny sizes.
"""

import os
import sys

import numpy as np
import pytest
import jax

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def test_console_entry_points_resolve():
    """Every [project.scripts] target in pyproject.toml must import and
    expose its callable — ``serve`` and friends ship as console
    commands, and a typo'd target only fails at install time otherwise."""
    import importlib
    import re

    with open(os.path.join(_ROOT, "pyproject.toml")) as fh:
        text = fh.read()
    section = re.search(
        r"\[project\.scripts\]\n(.*?)(\n\[|\Z)", text, re.S
    ).group(1)
    targets = dict(re.findall(r'([\w-]+)\s*=\s*"([\w.:]+)"', section))
    assert "serve" in targets and "cifar-app" in targets
    for name, target in targets.items():
        mod_name, _, attr = target.partition(":")
        mod = importlib.import_module(mod_name)
        assert callable(getattr(mod, attr)), f"{name} -> {target}"


def test_entry_compiles_and_runs():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    loss = jax.jit(fn)(*args)
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_dryrun_multichip_all_axes():
    # conftest already forced the 8-device CPU mesh; _ensure_devices
    # must find it and say so
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_dryrun_ensure_devices_is_idempotent(capsys):
    import __graft_entry__ as ge

    ge._ensure_devices(8)
    assert len(jax.devices()) >= 8
    # the dry run names the platform it runs on
    assert "platform=cpu" in capsys.readouterr().out


def test_dryrun_native_with_too_few_devices_is_an_error(monkeypatch):
    """GRAFT_DRYRUN_NATIVE=1 means the accelerator as it is: asking for
    more devices than it has fails instead of becoming a CPU run."""
    import __graft_entry__ as ge

    monkeypatch.setenv("GRAFT_DRYRUN_NATIVE", "1")
    with pytest.raises(RuntimeError, match="needs 64 devices"):
        ge._ensure_devices(64)


def test_bench_main_refuses_without_tpu(monkeypatch, capsys):
    """No chip, no record: ``bench.main`` exits non-zero naming the
    platform it found, and prints no JSON line."""
    import bench

    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "platform=cpu" in str(exc.value.code)
    assert capsys.readouterr().out.strip() == ""


def test_bench_py_exits_nonzero_without_tpu():
    """The command itself (what the driver runs): non-zero exit, no
    record on stdout — the CPU numbers of BENCH_r03-r05 cannot recur."""
    import subprocess

    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        capture_output=True, text=True, timeout=300, cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "platform=cpu" in out.stderr


def test_bench_failure_is_not_swallowed(monkeypatch, capsys):
    """A failing arm propagates (non-zero exit, traceback) — there is no
    ``{"value": 0.0, "error": ...}`` record any more."""
    import bench

    monkeypatch.setattr(bench, "_require_tpu", lambda: jax.devices()[0])
    monkeypatch.setenv("BENCH_MODEL", "no-such-arm")
    with pytest.raises(ValueError, match="no-such-arm"):
        bench.main()
    assert capsys.readouterr().out.strip() == ""


def test_bench_alexnet_record(monkeypatch):
    import bench

    monkeypatch.setenv("BENCH_BATCH", "4")
    monkeypatch.setenv("BENCH_ITERS", "2")
    rec = bench.bench_imagenet("cpu")
    assert rec["metric"] == "alexnet_train_images_per_sec_per_chip"
    assert rec["value"] > 0 and "error" not in rec
    assert rec["platform"] == "cpu"
    assert rec["tflops"] > 0


@pytest.mark.slow
def test_bench_alexnet_input_pipeline_mode(monkeypatch):
    import bench

    monkeypatch.setenv("BENCH_BATCH", "4")
    monkeypatch.setenv("BENCH_ITERS", "1")
    monkeypatch.setenv("BENCH_INPUT_PIPELINE", "1")
    rec = bench.bench_imagenet("cpu")
    assert rec["value"] > 0 and rec["input_pipeline"] == "1"


@pytest.mark.slow
def test_bench_alexnet_native_pipeline_mode(monkeypatch):
    import bench
    from sparknet_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    monkeypatch.setenv("BENCH_BATCH", "4")
    monkeypatch.setenv("BENCH_ITERS", "1")
    monkeypatch.setenv("BENCH_INPUT_PIPELINE", "native")
    rec = bench.bench_imagenet("cpu")
    assert rec["value"] > 0 and rec["input_pipeline"] == "native"


@pytest.mark.slow
def test_bench_e2e_subrecord_on_accelerator_path(monkeypatch):
    """Accelerator runs append an input_pipeline sub-record (host-fed
    loop vs compute-only). That branch is platform-gated off on CPU, so
    cover its record assembly by faking the platform; a failure inside
    it propagates (it used to be downgraded to an error field)."""
    import bench

    monkeypatch.setenv("BENCH_BATCH", "2")
    monkeypatch.setenv("BENCH_ITERS", "1")
    monkeypatch.delenv("BENCH_PROFILE", raising=False)
    monkeypatch.delenv("BENCH_INPUT_PIPELINE", raising=False)
    rec = bench.bench_imagenet("fake-accel", "alexnet")
    ip = rec["input_pipeline"]
    assert ip["mode"] == "python+prefetch", ip
    assert ip["img_per_sec"] > 0 and ip["iters"] >= 4
    assert ip["vs_compute_only"] > 0


@pytest.mark.slow
def test_bench_bert_record(monkeypatch):
    import bench

    monkeypatch.setenv("BENCH_BATCH", "2")
    monkeypatch.setenv("BENCH_SEQ", "64")
    monkeypatch.setenv("BENCH_ITERS", "1")
    rec = bench.bench_bert("cpu")
    assert rec["metric"] == "bert_base_mlm_tokens_per_sec_per_chip"
    assert rec["value"] > 0 and "error" not in rec


@pytest.mark.slow
def test_bench_resnet50_record(monkeypatch):
    import bench

    monkeypatch.setenv("BENCH_BATCH", "2")
    monkeypatch.setenv("BENCH_ITERS", "1")
    rec = bench.bench_imagenet("cpu", "resnet50")
    assert rec["metric"] == "resnet50_train_images_per_sec_per_chip"
    assert rec["value"] > 0 and "error" not in rec
    assert rec["vs_baseline"] is None  # the K40 anchor is AlexNet-only


def test_bench_out_of_memory_is_an_error(monkeypatch):
    """RESOURCE_EXHAUSTED fails the arm: the batch is part of the
    metric's meaning, so it is never halved and retried under the same
    metric name."""
    import bench
    from sparknet_tpu.solver import trainer

    def fake_step(self, batches, n=1, log_fn=None):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory (fake)")

    monkeypatch.setattr(trainer.Solver, "step", fake_step)
    monkeypatch.setenv("BENCH_BATCH", "4")
    monkeypatch.setenv("BENCH_ITERS", "1")
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        bench.bench_imagenet("cpu")


def test_bench_remat_mode_record(monkeypatch):
    """BENCH_REMAT=1: the remat solver build + remat-tagged record must
    be CI-exercised before it first runs on hardware."""
    import bench

    monkeypatch.setenv("BENCH_BATCH", "2")
    monkeypatch.setenv("BENCH_ITERS", "1")
    monkeypatch.setenv("BENCH_REMAT", "1")
    rec = bench.bench_imagenet("cpu")
    assert rec["value"] > 0 and "error" not in rec
    assert rec["remat"] is True
