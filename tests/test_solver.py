"""Solver math (lr policies, update rules) and end-to-end training."""

import numpy as np
import jax
import jax.numpy as jnp

from sparknet_tpu.proto import caffe_pb
from sparknet_tpu.solver.caffe_solver import (
    init_opt_state,
    learning_rate,
    make_update_fn,
)
from sparknet_tpu.solver.trainer import Solver

from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
ZOO = REPO / "sparknet_tpu" / "models" / "prototxt"


def sp_from(text: str) -> caffe_pb.SolverParameter:
    return caffe_pb.load_solver(text, is_path=False)


def test_lr_policies():
    it = jnp.asarray(1000, jnp.int32)
    np.testing.assert_allclose(
        float(learning_rate(sp_from("base_lr: 0.1 lr_policy: 'fixed'"), it)), 0.1, rtol=1e-6
    )
    lr = learning_rate(
        sp_from("base_lr: 0.1 lr_policy: 'step' gamma: 0.5 stepsize: 400"), it
    )
    np.testing.assert_allclose(float(lr), 0.1 * 0.5**2, rtol=1e-6)
    lr = learning_rate(
        sp_from("base_lr: 0.1 lr_policy: 'inv' gamma: 0.0001 power: 0.75"), it
    )
    np.testing.assert_allclose(float(lr), 0.1 * (1 + 0.0001 * 1000) ** -0.75, rtol=1e-6)
    lr = learning_rate(
        sp_from(
            "base_lr: 0.1 lr_policy: 'multistep' gamma: 0.1 stepvalue: 500 stepvalue: 2000"
        ),
        it,
    )
    np.testing.assert_allclose(float(lr), 0.01, rtol=1e-6)
    lr = learning_rate(
        sp_from("base_lr: 0.1 lr_policy: 'poly' power: 2 max_iter: 2000"), it
    )
    np.testing.assert_allclose(float(lr), 0.1 * 0.25, rtol=1e-6)


def test_sgd_momentum_update_matches_caffe_formula():
    sp = sp_from("base_lr: 0.1 momentum: 0.9 weight_decay: 0.01 lr_policy: 'fixed'")
    params = {"l": {"weight": jnp.asarray([1.0, -2.0])}}
    grads = {"l": {"weight": jnp.asarray([0.5, 0.25])}}
    opt = init_opt_state(sp, params)
    update = make_update_fn(sp)
    it = jnp.asarray(0, jnp.int32)

    # v1 = 0.9*0 + 0.1*(g + 0.01*w); w1 = w - v1
    g_reg = np.array([0.5 + 0.01 * 1.0, 0.25 + 0.01 * -2.0])
    v1 = 0.1 * g_reg
    p1, opt = update(params, grads, opt, it)
    np.testing.assert_allclose(np.asarray(p1["l"]["weight"]), [1.0, -2.0] - v1, rtol=1e-6)
    # second step accumulates momentum
    p2, opt = update(p1, grads, opt, it)
    g_reg2 = np.array(
        [0.5 + 0.01 * float(p1["l"]["weight"][0]), 0.25 + 0.01 * float(p1["l"]["weight"][1])]
    )
    v2 = 0.9 * v1 + 0.1 * g_reg2
    np.testing.assert_allclose(
        np.asarray(p2["l"]["weight"]), np.asarray(p1["l"]["weight"]) - v2, rtol=1e-6
    )


def test_lr_mult_and_clip():
    sp = sp_from("base_lr: 1.0 momentum: 0.0 lr_policy: 'fixed' clip_gradients: 1.0")
    params = {"l": {"weight": jnp.asarray([0.0]), "bias": jnp.asarray([0.0])}}
    grads = {"l": {"weight": jnp.asarray([3.0]), "bias": jnp.asarray([4.0])}}
    lr_m = {"l": {"weight": 1.0, "bias": 2.0}}
    dec_m = {"l": {"weight": 1.0, "bias": 0.0}}
    update = make_update_fn(sp, lr_m, dec_m)
    opt = init_opt_state(sp, params)
    p, _ = update(params, grads, opt, jnp.asarray(0, jnp.int32))
    # ||g|| = 5 -> scale 0.2; bias lr_mult 2 -> step 2*0.8
    np.testing.assert_allclose(float(p["l"]["weight"][0]), -0.6, rtol=1e-6)
    np.testing.assert_allclose(float(p["l"]["bias"][0]), -1.6, rtol=1e-6)


def test_adam_first_step_magnitude():
    sp = sp_from("base_lr: 0.001 type: 'Adam' momentum: 0.9 momentum2: 0.999 lr_policy: 'fixed'")
    params = {"l": {"w": jnp.asarray([1.0])}}
    grads = {"l": {"w": jnp.asarray([10.0])}}
    opt = init_opt_state(sp, params)
    update = make_update_fn(sp)
    p, _ = update(params, grads, opt, jnp.asarray(0, jnp.int32))
    # Adam's first step is ~lr regardless of grad magnitude
    np.testing.assert_allclose(float(p["l"]["w"][0]), 1.0 - 0.001, rtol=1e-3)


def test_end_to_end_memorize():
    """cifar10_quick with a higher LR memorizes a fixed 8-sample batch:
    loss must drop below 0.1 — exercises forward, backward, and update."""
    sp = caffe_pb.load_solver(str(ZOO / "cifar10_quick_solver.prototxt"))
    sp.base_lr = 0.01
    shapes = {"data": (8, 32, 32, 3), "label": (8,)}
    s = Solver(sp, shapes, solver_dir=str(REPO))
    rng = np.random.default_rng(0)
    batch = {
        "data": jnp.asarray(rng.normal(size=(8, 32, 32, 3)), jnp.float32),
        "label": jnp.asarray(np.arange(8) % 10, jnp.int32),
    }

    def batches():
        while True:
            yield batch

    m = s.step(batches(), 150)
    assert float(m["loss"]) < 0.1, f"did not memorize: loss={float(m['loss'])}"
    acc = s.test(batches(), 1)
    assert acc["accuracy"] == 1.0


def test_iter_size_accumulation_matches_full_batch():
    """iter_size=2 over two half-batches == one full batch (mean losses)."""
    net_text = """
    name: "tiny"
    layer { name: "d" type: "Input" top: "data" top: "label" }
    layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
            inner_product_param { num_output: 3
              weight_filler { type: "gaussian" std: 0.1 } } }
    layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
    """
    net_param = caffe_pb.load_net(net_text, is_path=False)
    rng = np.random.default_rng(0)
    data = rng.normal(size=(8, 5)).astype(np.float32)
    labels = (np.arange(8) % 3).astype(np.int32)

    def run(iter_size, shapes, feed):
        sp = sp_from(f"base_lr: 0.5 momentum: 0.9 lr_policy: 'fixed' iter_size: {iter_size}")
        s = Solver(sp, shapes, net_param=net_param, seed=3)
        s.step(iter(feed), 1)
        return np.asarray(s.params["ip"]["weight"])

    full = run(
        1,
        {"data": (8, 5), "label": (8,)},
        [{"data": jnp.asarray(data), "label": jnp.asarray(labels)}],
    )
    halves = run(
        2,
        {"data": (4, 5), "label": (4,)},
        [
            {"data": jnp.asarray(data[:4]), "label": jnp.asarray(labels[:4])},
            {"data": jnp.asarray(data[4:]), "label": jnp.asarray(labels[4:])},
        ],
    )
    np.testing.assert_allclose(full, halves, rtol=1e-5, atol=1e-6)


def test_solver_without_net_raises():
    import pytest

    with pytest.raises(ValueError, match="no net"):
        Solver(sp_from("base_lr: 0.1 lr_policy: 'fixed'"), {})


def test_average_loss_and_test_initialization():
    """average_loss smooths displayed losses over the window; the
    parsed test_initialization/average_loss fields carry defaults."""
    from sparknet_tpu.proto import caffe_pb

    sp = caffe_pb.load_solver(
        "net: \"x\"\nbase_lr: 0.1\nlr_policy: \"fixed\"\n"
        "average_loss: 3\ntest_initialization: false\nmax_iter: 6\n"
        "display: 1\n",
        is_path=False,
    )
    assert sp.average_loss == 3 and sp.test_initialization is False
    # defaults (Caffe: test_initialization true, average_loss 1)
    sp2 = caffe_pb.load_solver(
        "net: \"x\"\nbase_lr: 0.1\nlr_policy: \"fixed\"\n", is_path=False
    )
    assert sp2.test_initialization is True and sp2.average_loss == 1

    import numpy as np

    from sparknet_tpu.solver.trainer import Solver

    net_txt = """
name: "t"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 2
          weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
"""
    sp.net_param = caffe_pb.load_net(net_txt, is_path=False)
    sp.net = sp.train_net = None
    solver = Solver(sp, {"data": (4, 8), "label": (4,)})
    rng = np.random.default_rng(0)

    def feed():
        while True:
            yield {
                "data": rng.normal(size=(4, 8)).astype(np.float32),
                "label": rng.integers(0, 2, 4).astype(np.int32),
            }

    logged = []
    solver.step(feed(), 6, log_fn=lambda it, m: logged.append((it, m["loss"])))
    assert len(logged) == 6
    # the 3rd displayed loss must equal the mean of the first 3 raw
    # losses — recompute from a replay with average_loss=1
    sp_raw = caffe_pb.load_solver(
        "base_lr: 0.1\nlr_policy: \"fixed\"\nmax_iter: 6\ndisplay: 1\n",
        is_path=False,
    )
    sp_raw.net_param = sp.net_param
    solver2 = Solver(sp_raw, {"data": (4, 8), "label": (4,)})
    rng = np.random.default_rng(0)
    raw = []
    solver2.step(feed(), 6, log_fn=lambda it, m: raw.append(m["loss"]))
    np.testing.assert_allclose(
        logged[2][1], np.mean(raw[:3]), rtol=1e-6
    )
    np.testing.assert_allclose(
        logged[5][1], np.mean(raw[3:6]), rtol=1e-6
    )


def test_stop_requested_cooperative_stop():
    """stop_requested (the preemption-grace hook) must stop BOTH solver
    types at an iteration boundary and leave the solver reusable once
    the flag is cleared."""
    from sparknet_tpu.parallel import ParallelSolver, make_mesh

    sp = sp_from(
        "base_lr: 0.01 lr_policy: 'fixed' max_iter: 100\n"
        "net_param { name: 'n'\n"
        "  layer { name: 'data' type: 'Input' top: 'data'\n"
        "          input_param { shape { dim: 8 dim: 4 } } }\n"
        "  layer { name: 'label' type: 'Input' top: 'label'\n"
        "          input_param { shape { dim: 8 } } }\n"
        "  layer { name: 'ip' type: 'InnerProduct' bottom: 'data' top: 'ip'\n"
        "          inner_product_param { num_output: 3\n"
        "            weight_filler { type: 'xavier' } } }\n"
        "  layer { name: 'loss' type: 'SoftmaxWithLoss'\n"
        "          bottom: 'ip' bottom: 'label' top: 'loss' } }"
    )
    import itertools

    def feed():
        batch = {
            "data": jnp.ones((8, 4), jnp.float32),
            "label": jnp.zeros((8,), jnp.int32),
        }
        return itertools.repeat(batch)

    shapes = {"data": (8, 4), "label": (8,)}
    for make in (
        lambda: Solver(sp, shapes),
        lambda: ParallelSolver(
            sp, shapes, mesh=make_mesh({"dp": 2}, jax.devices()[:2]),
            mode="local", tau=2,
        ),
    ):
        solver = make()
        solver.step(feed(), 4)
        assert solver.iter == 4
        solver.stop_requested = True
        solver.step(feed(), 10)
        assert solver.iter == 4  # stopped at the boundary, no progress
        solver.stop_requested = False  # consumed -> reusable
        solver.step(feed(), 2)
        assert solver.iter == 6


def test_remat_matches_no_remat():
    """Per-layer rematerialization must be numerically transparent: the
    same seed and batches give (near-)identical params after training,
    including through BatchNorm state and PRNG-keyed dropout (masks
    recompute from the same fold_in key, not from saved buffers)."""
    import itertools

    net_txt = """
    net_param { name: 'remat'
      layer { name: 'data' type: 'Input' top: 'data'
              input_param { shape { dim: 4 dim: 8 dim: 8 dim: 3 } } }
      layer { name: 'label' type: 'Input' top: 'label'
              input_param { shape { dim: 4 } } }
      layer { name: 'conv' type: 'Convolution' bottom: 'data' top: 'conv'
              convolution_param { num_output: 6 kernel_size: 3 pad: 1
                weight_filler { type: 'xavier' } } }
      layer { name: 'bn' type: 'BatchNorm' bottom: 'conv' top: 'bn' }
      layer { name: 'relu' type: 'ReLU' bottom: 'bn' top: 'bn' }
      layer { name: 'drop' type: 'Dropout' bottom: 'bn' top: 'bn'
              dropout_param { dropout_ratio: 0.3 } }
      layer { name: 'ip' type: 'InnerProduct' bottom: 'bn' top: 'ip'
              inner_product_param { num_output: 5
                weight_filler { type: 'xavier' } } }
      layer { name: 'loss' type: 'SoftmaxWithLoss'
              bottom: 'ip' bottom: 'label' top: 'loss' } }
    base_lr: 0.05 lr_policy: 'fixed' momentum: 0.9 max_iter: 20
    """
    sp = sp_from(net_txt)
    shapes = {"data": (4, 8, 8, 3), "label": (4,)}
    rng = np.random.default_rng(5)
    batch = {
        "data": jnp.asarray(rng.normal(size=shapes["data"]), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 5, 4), jnp.int32),
    }

    def train(remat):
        s = Solver(sp, shapes, seed=11, remat=remat)
        s.step(itertools.repeat(batch), 5)
        return jax.device_get(s.params), jax.device_get(s.state)

    p0, st0 = train(False)
    p1, st1 = train(True)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6),
        (p0, st0), (p1, st1),
    )


def test_step_compiler_options_env_contract(monkeypatch):
    """The SPARKNET_SCOPED_VMEM_KIB knob: default on TPU, 0/blank (and
    padded spellings) disable, garbage fails fast, CPU always off."""
    from sparknet_tpu.solver import trainer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("SPARKNET_SCOPED_VMEM_KIB", raising=False)
    assert T._step_compiler_options() == {
        "xla_tpu_scoped_vmem_limit_kib": "32768"
    }
    for off in ("0", " 0 ", ""):
        monkeypatch.setenv("SPARKNET_SCOPED_VMEM_KIB", off)
        assert T._step_compiler_options() is None
    monkeypatch.setenv("SPARKNET_SCOPED_VMEM_KIB", "49152")
    assert T._step_compiler_options() == {
        "xla_tpu_scoped_vmem_limit_kib": "49152"
    }
    monkeypatch.setenv("SPARKNET_SCOPED_VMEM_KIB", "32M")
    try:
        T._step_compiler_options()
    except ValueError as e:
        assert "SPARKNET_SCOPED_VMEM_KIB" in str(e)
    else:
        raise AssertionError("garbage value must fail fast")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.delenv("SPARKNET_SCOPED_VMEM_KIB", raising=False)
    assert T._step_compiler_options() is None


def test_step_compile_kw_forwards_to_jit(monkeypatch):
    """The option dict must actually reach jax.jit as
    ``compiler_options`` (the kwarg name is load-bearing: a typo would
    silently compile without the option on TPU while every CPU test
    stays green). On CPU the kw is empty; forwarding is asserted by
    building a Solver under a faked TPU backend with jit intercepted."""
    from sparknet_tpu.solver import trainer as T

    seen = []
    real_jit = jax.jit

    def spy_jit(fn, **kw):
        seen.append(kw.get("compiler_options"))
        kw.pop("compiler_options", None)  # CPU jit would reject it
        return real_jit(fn, **kw)

    monkeypatch.setattr(T.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(T.jax, "jit", spy_jit)
    monkeypatch.delenv("SPARKNET_SCOPED_VMEM_KIB", raising=False)
    sp = sp_from("base_lr: 0.1 lr_policy: 'fixed'")
    net = caffe_pb.load_net(
        """layer { name: "d" type: "Input" top: "data" top: "label" }
           layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
             inner_product_param { num_output: 3 } }
           layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
             bottom: "label" top: "loss" }""",
        is_path=False,
    )
    Solver(sp, {"data": (4, 5), "label": (4,)}, net_param=net)
    assert {"xla_tpu_scoped_vmem_limit_kib": "32768"} in seen
