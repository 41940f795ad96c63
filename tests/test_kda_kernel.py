"""The Pallas kernels of ``ops/kda.py`` (interpret mode off a TPU) against
the token-by-token recurrence and the ``jax.numpy`` chunked form at shapes
the kernels accept; the dispatch between the two forms, the kernels' names
and the state's shape in the lowered step (what the benchmark's reader of
``kda_scan_ms`` matches), and the ``kda_chunks_in_kernel`` counter."""

import functools
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from sparknet_tpu.models import decoder  # noqa: E402
from sparknet_tpu.models.decoder import KDA, HybridConfig, HybridLM  # noqa: E402
from sparknet_tpu.ops import kda  # noqa: E402
from sparknet_tpu.ops.kda import kda_recurrent, kda_scan, uses_kernels  # noqa: E402

D = 128  # the head size the kernels take: whole lane tiles


def _inputs(case, s, b=1, h=2, seed=0, dtype=jnp.float32):
    """q, k, v, g, beta and an entering state.  ``at_the_bound``: every
    channel decays by exp(-5) a token; ``alike_keys``: each key is its
    neighbour's but for a thousandth and beta is nearly 1, where
    ``(I + A)^-1`` has large entries that cancel (a Neumann series of it
    does not converge)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (b, h, s, D))) * D ** -0.5
    k = jax.random.normal(keys[1], (b, h, s, D))
    v = jax.random.normal(keys[2], (b, h, s, D))
    g = -5 * jax.nn.sigmoid(jax.random.normal(keys[3], (b, h, s, D)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, h, s)))
    if case == "at_the_bound":
        g = jnp.full_like(g, kda.KDA_MIN_LOG_DECAY)
    if case == "alike_keys":
        k = k[:, :, :1] + 1e-3 * jax.random.normal(keys[5], k.shape)
        g = 0.01 * g  # little is forgotten inside a chunk
        beta = 1.0 - 1e-3 * beta
    state = 0.1 * jax.random.normal(keys[6], (b, h, D, D))
    return tuple(x.astype(dtype) for x in (q, unit(k), v)) + (g, beta, state)


def _scan(force, cut=None):
    """``kda_scan`` as a function of (q, k, v, g, beta, state) that returns
    (o, state after): one call, or two with the state carried over
    ``cut`` tokens in."""
    call = functools.partial(
        kda_scan, chunk=64, return_state=True, force=force, interpret=True
    )

    def run(*x):
        if cut is None:
            return call(*x[:5], initial_state=x[5])
        left, state = call(*(a[:, :, :cut] for a in x[:5]), initial_state=x[5])
        right, state = call(*(a[:, :, cut:] for a in x[:5]), initial_state=state)
        return jnp.concatenate([left, right], axis=2), state

    return run


_recurrence = lambda *x: kda_recurrent(*x[:5], initial_state=x[5], return_state=True)
_head = lambda f: lambda *x: (
    lambda o, state: jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(3 * state))
)(*f(*x))
NAMES = "q k v g beta initial_state".split()


@pytest.mark.parametrize("case,seq,cut", [
    ("plain", 128, None), ("carried", 192, 64), ("carried", 192, 128),
    ("at_the_bound", 128, None), ("alike_keys", 128, None),
], ids=["plain_128", "carried_64_128", "carried_128_64", "decay_at_the_bound", "alike_keys_beta_1"])
def test_kernels_match_the_recurrence_and_the_chunked_form(case, seq, cut):
    """Output, the state after and every gradient, the entering state's
    among them, at ``test_chunked_scan_matches_the_recurrence``'s
    tolerances; a sequence in two calls is one call over both.  (With
    every channel at the bound the gradient of ``g`` is a thousandth of
    the others and what is left of sums that cancel: there either chunked
    form keeps 3e-3 of it against the recurrence, and the kernels keep
    3e-5 against the ``jax.numpy`` form.)"""
    x = _inputs(case, seq)
    assert uses_kernels(x[0].shape, x[2].shape, 64, "flash")
    got = jax.jit(_scan("flash", cut))(*x)
    grads = jax.jit(jax.grad(_head(_scan("flash", cut)), range(6)))(*x)
    for oracle in (_recurrence, _scan("reference")):
        want = jax.jit(oracle)(*x)
        for a, w in zip(got, want):
            assert a.shape == w.shape and bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(a, w, atol=5e-6 * max(1.0, float(jnp.abs(w).max())))
        wants = jax.jit(jax.grad(_head(oracle), range(6)))(*x)
        for name, a, w in zip(NAMES, grads, wants):
            cancels = case == "at_the_bound" and name == "g" and oracle is _recurrence
            np.testing.assert_allclose(
                a, w, atol=(3e-3 if cancels else 3e-5) * float(jnp.abs(w).max()),
                err_msg=name,
            )


def test_kernels_round_their_products_as_the_chunked_form_does():
    """In bfloat16 the kernels and the ``jax.numpy`` form round the same
    operands: they agree far inside what bfloat16 costs either of them
    against the float32 recurrence."""
    x = _inputs("plain", 128, dtype=jnp.bfloat16)
    got = jax.jit(_scan("flash"))(*x)
    same = jax.jit(_scan("reference"))(*x)
    exact = jax.jit(_recurrence)(*x)
    for a, w, e in zip(got, same, exact):
        cost = float(jnp.abs(w - e).max())
        assert float(jnp.abs(a - w).max()) < 0.5 * cost


def test_shapes_the_kernels_refuse_take_the_chunked_form():
    """Head sizes under a lane tile, another chunk, a ragged last chunk:
    the ``jax.numpy`` form, by shape, whatever is forced; off a TPU
    nothing forced is the ``jax.numpy`` form too."""
    fits = ((1, 2, 128, D), (1, 2, 128, D), 64)
    assert uses_kernels(*fits, "flash") and not uses_kernels(*fits, "reference")
    assert uses_kernels(*fits, None) == (jax.default_backend() == "tpu")
    for q_shape, v_shape, chunk in [
        ((1, 2, 128, 64), (1, 2, 128, 64), 64), ((1, 2, 128, D), (1, 2, 128, 64), 64),
        ((1, 2, 128, D), (1, 2, 128, D), 16), ((1, 2, 100, D), (1, 2, 100, D), 64),
    ]:
        assert not uses_kernels(q_shape, v_shape, chunk, "flash")
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    small = [jax.random.normal(k_, (1, 2, 32, 64)) for k_ in keys[:3]]
    small += [-jax.nn.sigmoid(jax.random.normal(keys[3], (1, 2, 32, 64)))]
    small += [jax.nn.sigmoid(jax.random.normal(keys[4], (1, 2, 32)))]
    np.testing.assert_array_equal(  # no interpret: a kernel would not run here
        kda_scan(*small, chunk=16, force="flash"), kda_scan(*small, chunk=16, force="reference")
    )


# ------------------------------------------------------------ in the model

def _kernel_sized(**overrides):
    """Two KDA layers whose scan the kernels accept: 2 heads of 128, one
    chunk of 64 a segment, two segments a sequence of 128."""
    fields = dict(
        num_attention_heads=2, head_dim=D, layer_types=(KDA, KDA),
        mlp_layer_types=("dense", "sparse"), kda_chunk=64, kda_segment=64,
    )
    return HybridConfig.tiny(**{**fields, **overrides})


@pytest.fixture
def interpreted(monkeypatch):
    """The model's ``kda_scan`` in interpret mode, as a test off the chip
    has to run a forced kernel."""
    monkeypatch.setattr(
        decoder, "kda_scan", functools.partial(kda_scan, interpret=True)
    )


def _loss_and_grads(model, params, batch):
    def loss(p):
        out, _ = model.apply(p, {}, batch, train=True, rng=jax.random.PRNGKey(0))
        return out["loss"], out
    (value, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    return value, out, grads


def _batch(cfg, b, s, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0, cfg.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_model_takes_the_kernels_where_forced_and_agrees(interpreted, remat):
    """``attention_impl`` governs the KDA kernels as it governs the flash
    kernels: "flash" walks every chunk inside them, "reference" none, and
    loss and every leaf's gradient agree."""
    cfg = _kernel_sized(remat=remat)
    shapes = {"input_ids": (1, 128)}
    batch = _batch(cfg, 1, 128)
    forced = HybridLM(cfg, shapes, attention_impl="flash")
    plain = HybridLM(cfg, shapes, attention_impl="reference")
    params, _ = forced.init(jax.random.PRNGKey(2))
    loss_k, out_k, grads_k = _loss_and_grads(forced, params, batch)
    loss_p, out_p, grads_p = _loss_and_grads(plain, params, batch)
    assert float(out_k["kda_chunks"]) == float(out_k["kda_chunks_in_kernel"]) == 2.0
    assert float(out_p["kda_chunks"]) == 2.0 and float(out_p["kda_chunks_in_kernel"]) == 0.0
    np.testing.assert_allclose(loss_k, loss_p, rtol=2e-6)
    for layer in grads_p:
        for name, w in grads_p[layer].items():
            np.testing.assert_allclose(
                grads_k[layer][name], w, atol=2e-4 * max(float(jnp.abs(w).max()), 1e-12),
                err_msg=f"{layer}.{name}",
            )


def test_a_head_size_the_kernels_refuse_gives_the_same_numbers():
    """The tiny preset (heads of 8, chunks of 16) under "flash": no KDA
    kernel to take, the ``jax.numpy`` form's numbers to the bit."""
    cfg = HybridConfig.tiny(layer_types=(KDA, KDA), mlp_layer_types=("dense", "sparse"))
    shapes, batch = {"input_ids": (2, 64)}, _batch(HybridConfig.tiny(), 2, 64)
    forced = HybridLM(cfg, shapes, attention_impl="flash")
    plain = HybridLM(cfg, shapes, attention_impl="reference")
    params, _ = forced.init(jax.random.PRNGKey(2))
    loss_k, out_k, _ = _loss_and_grads(forced, params, batch)
    loss_p, _, _ = _loss_and_grads(plain, params, batch)
    assert float(loss_k) == float(loss_p)
    assert float(out_k["kda_chunks"]) == 4.0 and float(out_k["kda_chunks_in_kernel"]) == 0.0


def test_the_lowered_step_names_the_kernels_and_carries_the_state():
    """Lowered for a TPU (nothing is compiled or run), the forced step
    holds ``kda_scan_fwd`` and ``kda_scan_bwd`` custom calls, and each of
    them has the state ``[B, H, d_k, d_v]`` among its operands or results:
    ``benchmark/layers/hybrid_ops.scan_pattern`` finds the scan by it."""
    cfg = _kernel_sized(remat=True)
    model = HybridLM(cfg, {"input_ids": (1, 128)}, attention_impl="flash")
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    batch = jax.eval_shape(lambda: _batch(cfg, 1, 128))

    def step(p, x):
        return jax.grad(
            lambda p_: model.apply(p_, {}, x, train=True, rng=jax.random.PRNGKey(0))[0]["loss"]
        )(p)

    text = jax.jit(step).trace(params, batch).lower(lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line and "kda_scan" in line]
    state = "tensor<1x2x128x128xf32>"
    for name in ("kda_scan_fwd", "kda_scan_bwd"):
        mine = [line for line in calls if name in line]
        assert mine, name
        assert all(state in line for line in mine), name
    # the calls are jitted, so the layers and passes share a kernel's text;
    # two layers under remat call a forward kernel three times each, and
    # only the call a backward pass follows writes the chunks' entering
    # states and T
    sites = lambda name: len(re.findall(rf"call @{name}\(", text))
    functions = re.findall(r"func\.func private @(_scan_\w+)\((.*)", text)
    forward = {name: sig for name, sig in functions if name.startswith("_scan_fwd_call")}
    keeping = [name for name, sig in forward.items() if "tensor<1x2x1x128x128xf32>" in sig]
    assert sum(map(sites, forward)) == 6 and sum(map(sites, keeping)) == 2
    assert sites("_scan_bwd_call") == 2


def test_the_counter_reaches_the_progress_line_and_the_registry(interpreted, tmp_path, capsys):
    """``kda_chunks_in_kernel`` beside ``kda_chunks``: all of them under
    ``--attention flash``, none under ``reference``."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY
    from sparknet_tpu.utils.profiling import StepTimer
    from tests.test_hybrid import published_form

    path = tmp_path / "kernel_sized.json"
    path.write_text(json.dumps(published_form(_kernel_sized(kda_segment=1024))))
    for attention, in_kernel in (("flash", 2), ("reference", 0)):
        args = lm_app.parser().parse_args(
            ["--config", str(path), "--seq-len", "128", "--batch-size", "1",
             "--max-iter", "1", "--display", "1", "--synthetic-tokens", "2048",
             "--attention", attention]
        )
        solver, feed, _ = lm_app.build(args)
        metrics = lm_app._fit(solver, iter(feed), args, StepTimer(items_per_step=128, unit="tokens"))
        line = f"kda_chunks = 2, kda_chunks_in_kernel = {in_kernel}, kda_decay_min = 0."
        assert line in capsys.readouterr().out
        assert metrics["kda_chunks_in_kernel"] == in_kernel
        read = REGISTRY.sources()["train_step"].snapshot()
        assert read["kda_chunks_in_kernel"] == in_kernel and read["kda_chunks"] == 2.0


# ------------------------------------------------------------- on the chip

@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="the compiled kernels need a TPU"
)
def test_compiled_kernels_at_the_cell_s_shapes_on_hardware():
    """(1, 32, 1024, 128) in bfloat16, compiled: output, state and every
    gradient against the ``jax.numpy`` form, each within 2 % of the
    largest value (both round their products' operands to bfloat16)."""
    x = _inputs("plain", 1024, b=1, h=32, dtype=jnp.bfloat16)
    run = lambda force: lambda *a: kda_scan(
        *a[:5], initial_state=a[5], return_state=True, force=force
    )
    got, want = jax.jit(run("flash"))(*x), jax.jit(run("reference"))(*x)
    grads = jax.jit(jax.grad(_head(run("flash")), range(6)))(*x)
    wants = jax.jit(jax.grad(_head(run("reference")), range(6)))(*x)
    for name, a, w in zip(["o", "state"] + NAMES, got + grads, want + wants):
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(a - w).max()) <= 0.02 * float(jnp.abs(w).max()), name
