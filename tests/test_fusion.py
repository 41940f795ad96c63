"""The Solver's one step program: an iteration is one compiled
dispatch (train step, rng split and iteration counter in one XLA
program, ``jit_fused``), ``step(feed, n)`` is n of them, ``lower_step``
lowers that same program, a restore re-seeds the device's counter, and
ParallelSolver's sync mode overrides the dispatch with its mesh program
(host split, scalar counter) — which stays the oracle for the rng
stream.  And the trace-driven audit (scripts/fusion_audit.py) finds the
gaps that grounded the fusion."""

import collections
import glob
import itertools
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu.proto import caffe_pb
from sparknet_tpu.solver.trainer import Solver

REPO = os.path.join(os.path.dirname(__file__), "..")
AUDIT = os.path.join(REPO, "scripts", "fusion_audit.py")

TINY_NET = """
name: "tiny"
layer { name: "d" type: "Input" top: "data" top: "label" }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
        inner_product_param { num_output: 16
          weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2" bottom: "label" top: "loss" }
"""
SOLVER_TXT = "base_lr: 0.1 momentum: 0.9 lr_policy: 'fixed' weight_decay: 0.001"
SHAPES = {"data": (8, 8), "label": (8,)}


def make_solver(seed=7, cls=Solver, **kw):
    return cls(
        caffe_pb.load_solver(SOLVER_TXT, is_path=False), SHAPES,
        net_param=caffe_pb.load_net(TINY_NET, is_path=False), seed=seed, **kw,
    )


def feed():
    rng = np.random.default_rng(11)
    while True:
        yield {
            "data": rng.normal(size=(8, 8)).astype(np.float32),
            "label": rng.integers(0, 4, size=(8,)).astype(np.int32),
        }


def leaves(params):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        jax.device_get(params)
    )]


def assert_same_training(a, b):
    """Two solvers hold bitwise the same weights, net state, optimizer
    slots and rng key, at the same iteration."""
    assert a.iter == b.iter
    for tree in ("params", "state", "opt_state", "rng"):
        for x, y in zip(leaves(getattr(a, tree)), leaves(getattr(b, tree)),
                        strict=True):
            np.testing.assert_array_equal(x, y, err_msg=tree)


# one tiny solver per family the cells train, built by the entry points'
# own ``build``: (solver, feed), the same from every call
def _prototxt():
    return make_solver(), feed()


def _bert():
    from sparknet_tpu.apps import bert_app

    solver, batches, _ = bert_app.build(bert_app.make_args(
        config="tiny", vocab_size=64, seq_len=32, batch_size=4,
        synthetic_tokens=4096,
    ))
    return solver, batches


def _decoder():
    from sparknet_tpu.apps import lm_app

    solver, batches, _ = lm_app.build(lm_app.parser().parse_args([
        "--config", "tiny", "--seq-len", "32", "--batch-size", "2",
        "--synthetic-tokens", "4096",
    ]))
    return solver, batches


FAMILIES = pytest.mark.parametrize(
    "build", [_prototxt, _bert, _decoder], ids=["prototxt", "bert", "decoder"]
)


def lowered_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def executions(log_dir):
    """What the profiler saw the host start: (executions of a compiled
    program, names of the jitted functions called)."""
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(log_dir, "plugins/profile/*/*.xplane.pb"))
    names = collections.Counter(
        ev.name
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
    )
    return names["PjRtCpuExecutable::Execute"], {
        n for n in names if n.startswith("PjitFunction(")
    }


def profiled_steps(solver, batch, n, log_dir):
    """``n`` iterations on a device-resident batch under the profiler,
    with every implicit host-to-device transfer refused."""
    resident = itertools.repeat(jax.device_put(batch))
    jax.block_until_ready(solver.step(resident, 2))  # compile, seed counter
    jax.profiler.start_trace(log_dir)
    try:
        with jax.transfer_guard_host_to_device("disallow"):
            jax.block_until_ready(solver.step(resident, n))
    finally:
        jax.profiler.stop_trace()
    return executions(log_dir)


@FAMILIES
def test_step_runs_one_program_an_iteration(build, tmp_path):
    """Warm, with the batch on the device: an iteration starts ONE
    compiled program, the one named ``fused``, and hands the device
    nothing from the host — no key, no counter."""
    solver, batches = build()
    count, programs = profiled_steps(solver, next(batches), 3, str(tmp_path))
    assert count == 3
    assert programs == {"PjitFunction(fused)"}


@FAMILIES
def test_step_n_is_n_steps(build):
    """``step(feed, 6)`` and six ``step(feed, 1)``: bitwise the same
    weights, state, slots and key — the loop carries nothing between
    iterations but what the program returns."""
    at_once, feed_a = build()
    one_by_one, feed_b = build()
    at_once.step(feed_a, 6)
    for _ in range(6):
        one_by_one.step(feed_b, 1)
    assert at_once.iter == 6
    assert_same_training(at_once, one_by_one)


@FAMILIES
def test_lower_step_is_the_program_step_runs(build):
    """What a caller reads kernels and memory off is what ``step``
    dispatches: the module is ``jit_fused``, the key's split and the
    counter's increment are inside it, and compiled and run on a copy
    of the solver's state it leaves bitwise what one ``step`` leaves."""
    solver, batches = build()
    solver.step(batches, 2)  # off the initial state; counter now at 2
    batch = next(batches)
    lowered = solver.lower_step(batch)
    text = lowered.as_text()
    assert lowered_name(lowered) == "jit_fused"
    assert "threefry" in text
    # lowering twice gives the same text: nothing in it hangs on when
    # it was asked for
    assert solver.lower_step(batch).as_text() == text
    copy = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
    params, state, opt_state, it, rng, metrics = lowered.compile()(
        copy(solver.params), copy(solver.state), copy(solver.opt_state),
        batch, jnp.asarray(solver.iter, jnp.int32), copy(solver.rng),
    )
    stepped = solver.step(iter([batch]), 1)
    assert int(it) == solver.iter == 3
    for mine, theirs in (
        (params, solver.params), (state, solver.state),
        (opt_state, solver.opt_state), (rng, solver.rng), (metrics, stepped),
    ):
        for x, y in zip(leaves(mine), leaves(theirs), strict=True):
            np.testing.assert_array_equal(x, y)


def test_step_options_are_read_at_construction(monkeypatch):
    """The compiler options of the step program are those of the
    environment the Solver was BUILT in: a variable set later reaches
    no compile, and stepping builds no further ``jax.jit``."""
    from sparknet_tpu.solver import trainer as T

    seen = []
    real_jit = jax.jit

    def spy_jit(fn, **kw):
        seen.append((fn.__name__, kw.pop("compiler_options", None)))
        return real_jit(fn, **kw)  # CPU jit would reject the TPU option

    monkeypatch.setattr(T.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(T.jax, "jit", spy_jit)
    monkeypatch.setenv("SPARKNET_SCOPED_VMEM_KIB", "0")
    solver = make_solver()
    built = list(seen)
    assert built == [("fused", None), ("eval_step", None)]
    monkeypatch.setenv("SPARKNET_SCOPED_VMEM_KIB", "49152")
    solver.step(feed(), 2)
    solver.lower_step(next(feed()))
    assert seen == built
    # and the default, where nothing is set
    monkeypatch.delenv("SPARKNET_SCOPED_VMEM_KIB")
    seen.clear()
    make_solver()
    assert seen[0] == ("fused", {"xla_tpu_scoped_vmem_limit_kib": "32768"})


CONV_NET = """
name: "tiny_conv"
layer { name: "d" type: "Input" top: "data" top: "label" }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
        convolution_param { num_output: 4 kernel_size: 3
          weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
        lrn_param { local_size: 3 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool1" type: "Pooling" bottom: "norm1" top: "pool1"
        pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "drop1" type: "Dropout" bottom: "pool1" top: "pool1"
        dropout_param { dropout_ratio: 0.5 } }
layer { name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip1" bottom: "label" top: "loss" }
"""


def test_prototxt_remat_trains_like_plain():
    """``Solver(remat=True)`` on a prototxt net recomputes each layer in
    the backward pass and trains to the same weights: the dropout mask
    of the recomputed forward is the one the first forward drew."""
    shapes = {"data": (4, 8, 8, 3), "label": (4,)}

    def train(remat):
        solver = Solver(
            caffe_pb.load_solver(SOLVER_TXT, is_path=False), shapes,
            net_param=caffe_pb.load_net(CONV_NET, is_path=False), seed=5,
            remat=remat,
        )
        rng = np.random.default_rng(2)
        batches = iter([
            {"data": rng.normal(size=shapes["data"]).astype(np.float32),
             "label": rng.integers(0, 4, size=(4,)).astype(np.int32)}
            for _ in range(4)
        ])
        loss = float(solver.step(batches, 4)["loss"])
        return solver, loss

    plain, plain_loss = train(False)
    remat, remat_loss = train(True)
    assert remat.train_net.remat and not plain.train_net.remat
    # the TEST net keeps no backward, so nothing to recompute
    assert not remat.test_net.remat
    # the recomputation is in the program, not only in a flag
    zeros = {"data": np.zeros(shapes["data"], np.float32),
             "label": np.zeros((4,), np.int32)}
    assert "optimization_barrier" in remat.lower_step(zeros).as_text()
    assert "optimization_barrier" not in plain.lower_step(zeros).as_text()
    assert np.isfinite(remat_loss)
    np.testing.assert_allclose(remat_loss, plain_loss, rtol=1e-5)
    for a, b in zip(leaves(plain.params), leaves(remat.params), strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(leaves(plain.rng)[0], leaves(remat.rng)[0])


def sync_on_one_device():
    from sparknet_tpu.parallel import ParallelSolver, make_mesh

    return make_solver(
        cls=ParallelSolver, mesh=make_mesh(devices=jax.devices()[:1]),
        mode="sync",
    )


def test_fused_step_bitwise_equals_host_split():
    """jax.random.split is the same deterministic function inside and
    outside jit.  ParallelSolver's sync mode still splits on the host
    and places the counter every iteration; on a one-device mesh it is
    the base Solver's oracle: the key advances identically, bitwise,
    and so do the weights."""
    base, par = make_solver(), sync_on_one_device()
    base.step(feed(), 6)
    par.step(feed(), 6)
    assert base.iter == 6
    assert_same_training(base, par)


def test_parallel_solver_overrides_the_dispatch(tmp_path):
    """Sync mode OVERRIDES the method that advances the solver, and
    ``lower_step`` with it, instead of steering the base class: its
    program is its own (no key or counter comes back from it), and its
    iteration takes the key and the counter from the host."""
    from sparknet_tpu.parallel import ParallelSolver

    assert ParallelSolver._dispatch is not Solver._dispatch
    assert ParallelSolver.lower_step is not Solver.lower_step
    base, par = make_solver(), sync_on_one_device()
    batch = next(feed())
    assert lowered_name(base.lower_step(batch)) == "jit_fused"
    assert lowered_name(par.lower_step(batch)) != "jit_fused"
    outputs = lambda s: len(
        jax.tree_util.tree_leaves(s.lower_step(batch).out_info)
    )
    assert outputs(par) == outputs(base) - 2
    with pytest.raises(Exception, match="[Dd]isallowed host-to-device"):
        profiled_steps(par, batch, 1, str(tmp_path))


def test_fused_resume_reseeds_device_counter(tmp_path):
    """restore() must invalidate the on-device iteration counter, so
    an interrupted run resumes bit-identically to the uninterrupted
    one (LR schedules read the counter)."""
    base = make_solver()
    base.step(feed(), 8)

    first = make_solver()
    f = feed()
    first.step(f, 4)
    path = str(tmp_path / "mid_iter_4.solverstate.npz")
    first.save(path)

    resumed = make_solver()
    resumed.step(feed(), 2)  # park the counter somewhere wrong
    resumed.restore(path)
    assert resumed._it_dev is None
    resumed.align_feed(g := feed())
    resumed.step(g, 4)
    assert resumed.iter == 8
    for a, b in zip(leaves(base.params), leaves(resumed.params)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ fusion audit
def synth_trace(gap_us=0.0, iters=5, put_us=50.0):
    """A timeline-shaped Chrome trace: input_wait -> device_put ->
    compiled_step per iteration, with ``gap_us`` of unattributed host
    time inserted before each compiled_step."""
    evs = []
    ts = 1000.0
    for _ in range(iters):
        for name, dur in (
            ("input_wait", 100.0),
            ("device_put", put_us),
            ("compiled_step", 800.0),
        ):
            if name == "compiled_step":
                ts += gap_us
            evs.append({"name": name, "ph": "X", "ts": ts, "dur": dur,
                        "pid": 1, "tid": 1, "cat": "timeline"})
            ts += dur
    return {"traceEvents": evs}


def run_audit(tmp_path, doc, *args):
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(doc))
    return subprocess.run(
        [sys.executable, AUDIT, str(p), *args],
        capture_output=True, text=True, timeout=120,
    )


def test_audit_finds_dispatch_gap(tmp_path):
    r = run_audit(tmp_path, synth_trace(gap_us=300.0), "--json",
                  "--informational")
    assert r.returncode == 0, r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    kinds = [f["kind"] for f in rec["findings"]]
    assert "dispatch_gap" in kinds
    assert rec["iterations"] == 5
    # the gap aggregates on the transition where it was inserted
    top = next(iter(rec["transitions"]))
    assert top == "device_put -> compiled_step"
    # gating mode: findings exit 1 without --informational
    assert run_audit(tmp_path, synth_trace(gap_us=300.0)).returncode == 1


def test_audit_clean_trace_has_no_findings(tmp_path):
    r = run_audit(tmp_path, synth_trace(gap_us=0.0), "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    gating = [f for f in rec["findings"]
              if not f.get("informational")]
    assert gating == []


def test_audit_flags_device_put_stalls(tmp_path):
    doc = synth_trace(gap_us=0.0, put_us=900.0)
    r = run_audit(tmp_path, doc, "--json", "--informational")
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert "device_put_stall" in [f["kind"] for f in rec["findings"]]


def test_audit_reads_a_real_solver_trace(tmp_path):
    """End to end: a traced run's capture parses, attributes the
    timeline phases, and counts the iterations."""
    from sparknet_tpu.telemetry import timeline as ttl
    from sparknet_tpu.telemetry import trace as tr

    path = str(tmp_path / "real.json")
    s = make_solver()
    tr.enable(path)
    try:
        tl = ttl.Timeline(fence=True)
        s.timeline = tl
        tl.start()
        s.step(feed(), 5)
        tl.stop()
        tr.write(path)
    finally:
        tr.disable()
    r = subprocess.run(
        [sys.executable, AUDIT, path, "--json", "--informational"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["iterations"] == 5
    assert "compiled_step" in rec["phases"]
    assert "perf_counter" not in open(AUDIT).read()
