"""chip_smoke.py: refuses anything but a TPU, and its phases — functions
of their sizes — rehearse tiny on the CPU through the same entry points
the chip run drives."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402


def _run(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_refuses_without_tpu_and_names_the_platform():
    out = _run()
    assert out.returncode != 0
    assert "platform=cpu" in out.stderr
    assert out.stdout.strip() == ""  # no result line to mistake for a pass


def test_takes_no_arguments():
    out = _run("--batch-size", "2")
    assert out.returncode != 0 and "no arguments" in out.stderr


def test_train_phases_rehearse_tiny():
    clock = chip_smoke.CompileClock()
    before = clock.read()
    out = chip_smoke.train_alexnet(
        batch_size=2, iters=2, bf16=False, synthetic_n=64
    )
    assert out["feed"].startswith(("native loader", "python feed"))
    assert set(out["test"]) >= {"loss"}
    out = chip_smoke.train_bert(
        config="tiny", seq_len=64, batch_size=2, iters=2, bf16=False,
        require_pallas=False,
    )
    # the CPU picks reference attention, and the check can tell
    assert out["pallas_kernel_calls"] == 0
    with pytest.raises(chip_smoke.SmokeFailure, match="Pallas"):
        chip_smoke.train_bert(
            config="tiny", seq_len=64, batch_size=2, iters=1, bf16=False,
            require_pallas=True,
        )
    spent = clock.read()
    assert spent["compile_s"] > before["compile_s"]


def test_serve_phase_rehearses_tiny():
    out = chip_smoke.serve(classify_requests=3, generate_steps=3)
    assert out["errors"] == 0
    assert out["generate"]["hit_steps"] < out["generate"]["cold_steps"]


def test_multichip_phase_rehearses_one_layout():
    out = chip_smoke.multichip(
        batch_size=4, bf16=False, synthetic_n=64,
        runs=(("layout dp=2,tp=2", ("--layout", "dp=2,tp=2"), 2),),
    )
    # the CPU reports no memory statistics: the balance check is the
    # chip's; the sampler still ran over every device
    assert len(out["layout dp=2,tp=2"]["max_bytes_in_use"]) >= 4


@pytest.mark.slow
def test_multichip_phase_rehearses_every_run():
    chip_smoke.multichip(batch_size=8, bf16=False, synthetic_n=64)


def test_training_output_checks():
    good = (
        "Iteration 1, loss = 6.9\nIteration 2, loss = 6.8, mlm_acc = 0.1\n"
        "Optimization Done. 2 iters in 1.0s (2.0 it/s)\n"
    )
    assert chip_smoke._check_training_output(good, 2) == {
        "first_loss": 6.9, "last_loss": 6.8,
    }
    for bad, why in (
        (good.replace("6.8", "nan"), "non-finite"),
        (good.replace("Done. 2", "Done. 1"), "did not reach"),
        ("Optimization Done. 2 iters", "no 'Iteration"),
    ):
        with pytest.raises(chip_smoke.SmokeFailure, match=why):
            chip_smoke._check_training_output(bad, 2)


def test_a_failing_phase_is_recorded_not_raised(capsys):
    def boom():
        raise chip_smoke.SmokeFailure("what came out is wrong")

    rec = chip_smoke.run_phase("boom", boom, chip_smoke.CompileClock())
    assert rec["ok"] is False and "what came out is wrong" in rec["error"]
    assert "boom: FAIL" in capsys.readouterr().out
