"""Checkpoint/resume: Caffe ``.solverstate`` parity (SURVEY.md §5).

The contract: save at iteration k, restore into a FRESH solver, feed the
same batches — every parameter, optimizer slot, and metric must be
bit-identical to the uninterrupted run.
"""

from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sparknet_tpu.proto import caffe_pb
from sparknet_tpu.solver import snapshot
from sparknet_tpu.solver.trainer import Solver
from sparknet_tpu.parallel import ParallelSolver, make_mesh

REPO = Path(__file__).resolve().parents[1]
ZOO = REPO / "sparknet_tpu" / "models" / "prototxt"


def test_save_state_round_trip(tmp_path):
    tree = {
        "a": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
        "b": [np.ones(2, np.int32), (np.zeros(1), None)],
        "c": {"nested": {"deep": np.float64(3.5)}},
    }
    path = str(tmp_path / "st.npz")
    snapshot.save_state(path, tree=tree, it=42, scalar=1.5, name="x")
    out = snapshot.load_state(path)
    assert out["it"] == 42 and out["scalar"] == 1.5 and out["name"] == "x"
    np.testing.assert_array_equal(out["tree"]["a"]["w"], tree["a"]["w"])
    np.testing.assert_array_equal(out["tree"]["b"][0], tree["b"][0])
    assert isinstance(out["tree"]["b"][1], tuple)
    assert out["tree"]["b"][1][1] is None
    np.testing.assert_array_equal(
        out["tree"]["c"]["nested"]["deep"], tree["c"]["nested"]["deep"]
    )


def _batches(n, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "data": jnp.asarray(rng.normal(size=(bs, 32, 32, 3)), jnp.float32),
            "label": jnp.asarray(rng.integers(0, 10, bs), jnp.int32),
        }
        for _ in range(n)
    ]


def _make_cifar_solver(parallel=None, tau=1, bs=8):
    sp = caffe_pb.load_solver(str(ZOO / "cifar10_quick_solver.prototxt"))
    sp.base_lr = 0.01
    shapes = {"data": (bs, 32, 32, 3), "label": (bs,)}
    if parallel is None:
        return Solver(sp, shapes, solver_dir=str(REPO))
    return ParallelSolver(
        sp, shapes, solver_dir=str(REPO),
        mesh=make_mesh({"dp": 2}, jax.devices()[:2]), mode=parallel, tau=tau,
    )


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    assert len(la) == len(lb)
    for (pa, xa), (pb, xb) in zip(la, lb):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=str(pa))


@pytest.mark.parametrize("mode,tau", [(None, 1), ("sync", 1), ("local", 2)])
def test_resume_is_bit_identical(tmp_path, mode, tau):
    batches = _batches(8, seed=5)
    path = str(tmp_path / "ck.solverstate.npz")

    # uninterrupted run: 4 + 4
    s1 = _make_cifar_solver(mode, tau)
    s1.step(iter(batches[:4]), 4)
    s1.save(path)
    s1.step(iter(batches[4:]), 4)

    # fresh solver, restored mid-run, fed the same tail
    s2 = _make_cifar_solver(mode, tau)
    s2.restore(path)
    assert s2.iter == 4
    s2.step(iter(batches[4:]), 4)

    assert s2.iter == s1.iter
    _assert_trees_equal(s1.params, s2.params)
    _assert_trees_equal(s1.opt_state, s2.opt_state)
    _assert_trees_equal(s1.state, s2.state)
    np.testing.assert_array_equal(np.asarray(s1.rng), np.asarray(s2.rng))


def test_latest_solverstate(tmp_path):
    prefix = str(tmp_path / "run")
    assert snapshot.latest_solverstate(prefix) is None
    for it in (2, 10, 6):
        open(f"{prefix}_iter_{it}.solverstate.npz", "wb").close()
    open(f"{prefix}_iter_99.npz", "wb").close()  # weights-only: ignored
    assert snapshot.latest_solverstate(prefix) == (
        f"{prefix}_iter_10.solverstate.npz"
    )


@pytest.mark.slow
def test_cifar_app_restore_cli(tmp_path):
    """The CifarApp --restore flag end-to-end: snapshot at iter 2, resume
    to 4, matching the uninterrupted params exactly."""
    from sparknet_tpu.apps import cifar_app

    prefix = str(tmp_path / "snap")
    common = [
        "--synthetic", "--synthetic-n", "1000", "--batch-size", "8",
        "--seed", "7",
    ]

    def run(extra):
        import sys

        solver_txt = tmp_path / "solver.prototxt"
        base = (ZOO / "cifar10_quick_solver.prototxt").read_text()
        base += f"\nsnapshot: 2\nsnapshot_prefix: \"{prefix}\"\n"
        solver_txt.write_text(base)
        return cifar_app.main(
            ["--solver", str(solver_txt), "--max-iter", "4"] + common + extra
        )

    run([])  # writes snap_iter_2.solverstate.npz and snap_iter_4...
    import sparknet_tpu.nets.weights as W

    p_full = W.load_npz(f"{prefix}_iter_4.npz")
    # wipe the iter-4 artifacts, resume from iter 2
    run(["--restore", f"{prefix}_iter_2.solverstate.npz"])
    p_resumed = W.load_npz(f"{prefix}_iter_4.npz")
    _assert_trees_equal(p_full, p_resumed)

    # --auto-resume picks the newest remaining solverstate (iter 2 after
    # the iter-4 one "is lost in the preemption") and re-reaches iter 4
    import os

    os.remove(f"{prefix}_iter_4.npz")
    os.remove(f"{prefix}_iter_4.solverstate.npz")
    run(["--auto-resume"])
    p_auto = W.load_npz(f"{prefix}_iter_4.npz")
    _assert_trees_equal(p_full, p_auto)


def test_orbax_solverstate_round_trip(tmp_path):
    """--snapshot-format orbax: save/restore through the Orbax backend
    is bit-identical to continuing the uninterrupted run, exactly like
    the npz path."""
    import os

    pytest.importorskip("orbax.checkpoint")
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.proto import caffe_pb
    from sparknet_tpu.solver import snapshot
    from sparknet_tpu.solver.trainer import Solver

    net_txt = """
name: "ob"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 3
          weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
"""

    def make():
        sp = caffe_pb.load_solver(
            "base_lr: 0.1\nlr_policy: \"fixed\"\nmomentum: 0.9\n"
            "max_iter: 10\nsolver_type: ADAM\n",
            is_path=False,
        )
        sp.net_param = caffe_pb.load_net(net_txt, is_path=False)
        return Solver(sp, {"data": (4, 6), "label": (4,)})

    def feed():
        rng = np.random.default_rng(3)
        while True:
            yield {
                "data": rng.normal(size=(4, 6)).astype(np.float32),
                "label": rng.integers(0, 3, 4).astype(np.int32),
            }

    a = make()
    fa = feed()
    a.step(fa, 4)
    path = str(tmp_path / f"ob_iter_4{snapshot.ORBAX_SUFFIX}")
    a.save(path)
    assert os.path.isdir(path)  # orbax checkpoints are directories
    a.step(fa, 4)  # uninterrupted continuation

    b = make()
    fb = feed()
    b.restore(path, fb)
    assert b.iter == 4
    b.step(fb, 4)
    for layer in a.params:
        for name in a.params[layer]:
            np.testing.assert_array_equal(
                np.asarray(a.params[layer][name]),
                np.asarray(b.params[layer][name]),
            )
    # auto-resume finds the orbax checkpoint too
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        assert snapshot.latest_solverstate("ob") == f"ob_iter_4{snapshot.ORBAX_SUFFIX}"
    finally:
        os.chdir(cwd)


@pytest.mark.slow  # two subprocess training runs (~25s warm)
def test_sigterm_preemption_snapshot_and_resume(tmp_path):
    """Preemption grace end-to-end (SURVEY.md §5 failure handling): a
    real SIGTERM against the CifarApp process must finish the in-flight
    iteration, write a solverstate, and exit 0; a relaunch with
    --auto-resume must pick that snapshot up and run to completion."""
    import glob
    import os
    import re
    import signal
    import subprocess
    import sys
    import time as _time

    prefix = str(tmp_path / "pre")
    solver_txt = tmp_path / "solver.prototxt"
    base = (ZOO / "cifar10_quick_solver.prototxt").read_text()
    base += f'\nsnapshot_prefix: "{prefix}"\n'
    solver_txt.write_text(base)
    base_cmd = [
        sys.executable, "-m", "sparknet_tpu.apps.cifar_app",
        "--solver", str(solver_txt), "--synthetic", "--synthetic-n", "1000",
        "--batch-size", "8", "--seed", "3",
    ]
    cmd = base_cmd + ["--max-iter", "5000"]
    env = dict(os.environ)
    # the subprocess runs on the CPU like the suite, from the repo
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONUNBUFFERED"] = "1"  # readline() must see lines promptly
    import threading

    proc = subprocess.Popen(
        cmd, env=env, cwd=str(REPO),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    # a reader thread drains stdout so the main thread can enforce the
    # deadline even if the subprocess wedges before printing anything
    lines = []
    started = threading.Event()

    def _drain():
        for line in proc.stdout:
            lines.append(line)
            if "Test net output" in line:
                started.set()

    reader = threading.Thread(target=_drain, daemon=True)
    reader.start()
    try:
        assert started.wait(timeout=300), "".join(lines)
        _time.sleep(5)  # let a few training iterations run
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise AssertionError(
                "SIGTERM did not stop the app:\n" + "".join(lines)
            )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        reader.join(timeout=30)
    full = "".join(lines)
    assert proc.returncode == 0, full
    assert "SIGTERM: preempted at iteration" in full, full
    states = glob.glob(f"{prefix}_iter_*.solverstate.npz")
    assert states, full
    it = max(
        int(re.search(r"_iter_(\d+)\.solverstate", s).group(1))
        for s in states
    )

    # relaunch with --auto-resume: must restore and finish cleanly
    out2 = subprocess.run(
        base_cmd + ["--max-iter", str(it + 2), "--auto-resume"],
        env=env, cwd=str(REPO),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=300,
    )
    assert out2.returncode == 0, out2.stdout
    assert "Restoring previous solver status" in out2.stdout, out2.stdout
    assert "Optimization Done" in out2.stdout, out2.stdout


def test_preemption_grace_noop_off_main_thread():
    """Embedded use: installing a signal handler off the main thread is
    illegal; the context manager must no-op cleanly, not raise."""
    import threading

    from sparknet_tpu.solver.preempt import preemption_grace

    class Dummy:
        stop_requested = False

    results = {}

    def run():
        try:
            with preemption_grace(Dummy()):
                results["entered"] = True
        except Exception as e:  # pragma: no cover
            results["error"] = e

    # daemon: if the context ever wedges, the join timeout must report
    # the failure instead of blocking interpreter exit forever
    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=30)
    assert results.get("entered") is True
    assert "error" not in results, results
