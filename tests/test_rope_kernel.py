"""``ops/rope.py``: the Pallas kernel that rotates q and k and writes them
head-major, in Pallas's interpreter against ``models.decoder.apply_rope``
(the plain form), its ``custom_vjp`` against ``jax.vjp`` of the plain form,
the rule that chooses between the two, planted faults through a small
``DecoderLM``, the counter ``rope_rows_in_kernel`` and, on a TPU, the
compiled kernel at the cells' shapes."""

import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.configs import laguna_xs2_reference as plain  # noqa: E402
from benchmark.reference import shaken  # noqa: E402
from sparknet_tpu.models import decoder  # noqa: E402
from sparknet_tpu.models.decoder import (  # noqa: E402
    DecoderConfig, DecoderLM, apply_rope, rope_inv_freq,
)
from sparknet_tpu.ops.attention import attention  # noqa: E402
from sparknet_tpu.ops.rope import (  # noqa: E402
    rope_tables, rope_to_heads, uses_rope_kernel,
)
from tests.test_decoder import _batch, published_form  # noqa: E402
from tests.test_kda_kernel import _loss_and_grads  # noqa: E402

D = 128
_ROPE = {
    "default": {"rope_type": "default", "rope_theta": 10000.0},
    "yarn": {
        "rope_type": "yarn", "rope_theta": 500000.0, "factor": 32.0,
        "original_max_position_embeddings": 16, "beta_slow": 1.0,
        "beta_fast": 64.0,
    },
}


def _positions(kind, b, s):
    if kind == "arange":
        return jnp.arange(s)
    # packed documents: each sequence restarts at places of its own
    starts = np.zeros((b, s), np.int64)
    for row, cuts in enumerate([(0, 5, 17), (0, 11), (0,), (0, 1, 2, 30)][:b]):
        for cut in cuts:
            starts[row, cut:] = cut
    return jnp.asarray(np.arange(s) - starts, jnp.int32)


def _case(table, fraction, positions, heads, b=2, s=32, seed=0):
    """(x (B, S, n * D) float32, positions, inv_freq, scale, rot)."""
    inv_freq, scale = rope_inv_freq(
        {**_ROPE[table], "partial_rotary_factor": fraction}, D
    )
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(seed), (b, s, heads * D))
    return x, _positions(positions, b, s), inv_freq, scale, 2 * inv_freq.shape[0]


def _plain(x, positions, inv_freq, scale, dtype):
    b, s, lanes = x.shape
    x = x.reshape(b, s, lanes // D, D)
    return apply_rope(x, positions, inv_freq, scale).astype(dtype).transpose(0, 2, 1, 3)


def _kernel(x, positions, inv_freq, scale, dtype):
    cos, sin = rope_tables(positions, inv_freq, scale, D)
    return rope_to_heads(x, cos, sin, 2 * inv_freq.shape[0], dtype, True)


def _products(x, positions, inv_freq, scale):
    """|x cos| + |swap(x) sin|, head-major: the size of what is added; a
    float32 sum is right to an ulp of it."""
    b, s, lanes = x.shape
    rot = 2 * inv_freq.shape[0]
    x = np.abs(np.asarray(x).reshape(b, s, lanes // D, D))
    cos, sin = (np.abs(np.asarray(t))[:, :, None] for t in rope_tables(positions, inv_freq, scale, D))
    swapped = np.concatenate(
        [x[..., rot // 2: rot], x[..., : rot // 2], x[..., rot:]], axis=-1
    )
    return (x * cos + swapped * sin).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [64, 48, 8, 4])
@pytest.mark.parametrize("positions", ["arange", "restarts"])
@pytest.mark.parametrize("table", ["default", "yarn"])
@pytest.mark.parametrize("fraction", [1.0, 0.5], ids=["whole", "half"])
def test_forward_is_apply_rope_rounded_and_head_major(fraction, table, positions, heads, dtype):
    """Float32: equal to an ulp of the products summed (a fused
    multiply-add rounds once where the plain form rounds twice).  bfloat16:
    the same values rounded once more, so all but a thousandth of the
    elements are equal and those are neighbours."""
    x, pos, inv_freq, scale, rot = _case(table, fraction, positions, heads)
    assert rot == int(D * fraction)
    dtype = jnp.dtype(dtype)
    got = _kernel(x, pos, inv_freq, scale, dtype)
    want = _plain(x, pos, inv_freq, scale, dtype)
    assert got.shape == want.shape == (2, heads, 32, D) and got.dtype == dtype
    ulp = np.spacing(_products(x, pos, inv_freq, scale).astype(np.float32))
    got, want = (np.asarray(t.astype(jnp.float32)) for t in (got, want))
    if dtype == jnp.float32:
        assert (np.abs(got - want) <= ulp).all()
        return
    differ = got != want
    assert differ.mean() <= 1e-3
    neighbours = np.abs(want) * 2.0 ** -7 + ulp  # a bfloat16 ulp is at most this
    assert (np.abs(got - want)[differ] <= neighbours[differ]).all()


@pytest.mark.parametrize("heads", [64, 48, 8, 4])
@pytest.mark.parametrize("positions", ["arange", "restarts"])
@pytest.mark.parametrize(
    "table,fraction", [("default", 1.0), ("yarn", 0.5), ("default", 0.5), ("yarn", 1.0)],
    ids=["default_whole", "yarn_half", "default_half", "yarn_whole"],
)
def test_the_custom_vjp_is_the_plain_forms_gradient(table, fraction, positions, heads):
    x, pos, inv_freq, scale, _rot = _case(table, fraction, positions, heads)
    g = jax.random.normal(jax.random.PRNGKey(7), (2, heads, 32, D))
    got, = jax.vjp(lambda x: _kernel(x, pos, inv_freq, scale, jnp.float32), x)[1](g)
    want, = jax.vjp(lambda x: _plain(x, pos, inv_freq, scale, jnp.float32), x)[1](g)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    np.testing.assert_allclose(got, want, atol=1e-6 * float(jnp.abs(want).max()))
    # and it is no identity: the rotation moved the cotangent
    assert float(jnp.abs(want - g.transpose(0, 2, 1, 3).reshape(x.shape)).max()) > 0.1


def test_a_bfloat16_cotangent_is_rounded_once_at_the_store():
    """The cotangent the flash kernels hand back is in the compute type;
    what reaches the projection's backward is the float32 rotation of it
    rounded to that type, as ``mxu_dot``'s backward would round it."""
    x, pos, inv_freq, scale, _rot = _case("default", 0.5, "restarts", 8)
    g = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 32, D)).astype(jnp.bfloat16)
    got, = jax.vjp(lambda x: _kernel(x, pos, inv_freq, scale, jnp.bfloat16), x)[1](g)
    want, = jax.vjp(lambda x: _plain(x, pos, inv_freq, scale, jnp.bfloat16), x)[1](g)
    assert got.dtype == jnp.float32
    rounded = want.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.mean(got != rounded)) <= 1e-3
    np.testing.assert_allclose(got, rounded, atol=2.0 ** -7 * float(jnp.abs(want).max()))


@pytest.mark.parametrize(
    "seq_len,head_dim,rot,force,takes", [
        (8192, 128, 128, "flash", True),
        (8192, 128, 64, "flash", True),
        (48, 256, 2, "flash", True),
        (8192, 64, 64, "flash", False),  # half a lane tile
        (8192, 128, 63, "flash", False),  # no two halves
        (8192, 128, 0, "flash", False),
        (8192, 128, 130, "flash", False),
        (8200, 128, 128, "flash", False),  # a ragged sequence
        (8192, 128, 128, "reference", False),
        (8192, 128, 128, None, False),  # off a TPU
    ],
)
def test_the_rule_takes_the_kernel_where_it_tiles(seq_len, head_dim, rot, force, takes):
    if force is None:  # the backend decides: these tests run off a TPU, or on one
        takes = jax.default_backend() == "tpu"
    assert uses_rope_kernel(seq_len, head_dim, rot, force) is takes


# ------------------------------------------------------- through the model

def _kernel_sized(**overrides) -> DecoderConfig:
    """DecoderConfig.tiny with heads of 128: a full layer that rotates half
    a head by YaRN, sliding layers that rotate all of it."""
    return DecoderConfig.tiny(head_dim=D, **overrides)


@pytest.fixture
def interpreted(monkeypatch):
    """The model's kernels in interpret mode, as a test off the chip has to
    run a forced kernel."""
    monkeypatch.setattr(
        decoder, "attention", functools.partial(attention, interpret=True)
    )
    monkeypatch.setattr(
        decoder, "rope_to_heads",
        lambda x, cos, sin, rot, dtype: rope_to_heads(x, cos, sin, rot, dtype, True),
    )


def _rows(cfg, b, s):
    return b * s * sum(
        heads + cfg.num_key_value_heads for heads in cfg.num_attention_heads_per_layer
    )


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_model_takes_the_kernel_where_forced_and_agrees(interpreted, remat):
    """``attention_impl`` governs the rotary kernel as it governs the flash
    kernels: "flash" rotates every row of q and k inside it, "reference"
    none, and loss and every leaf's gradient agree."""
    cfg = _kernel_sized(remat=remat)
    shapes, batch = {"input_ids": (2, 64)}, _batch(cfg)
    forced = DecoderLM(cfg, shapes, attention_impl="flash")
    plain_path = DecoderLM(cfg, shapes, attention_impl="reference")
    params = shaken(forced.init(jax.random.PRNGKey(3))[0], 3.0)
    loss_k, out_k, grads_k = _loss_and_grads(forced, params, batch)
    loss_p, out_p, grads_p = _loss_and_grads(plain_path, params, batch)
    assert float(out_k["rope_rows_in_kernel"]) == _rows(cfg, 2, 64) == 2 * 64 * 28
    assert float(out_p["rope_rows_in_kernel"]) == 0.0
    np.testing.assert_allclose(loss_k, loss_p, rtol=2e-6)
    for layer in grads_p:
        for name, w in grads_p[layer].items():
            np.testing.assert_allclose(
                grads_k[layer][name], w, atol=2e-4 * max(float(jnp.abs(w).max()), 1e-12),
                err_msg=f"{layer}.{name}",
            )


def test_heads_the_kernel_refuses_take_the_plain_path_under_flash(interpreted):
    """The tiny preset (heads of 16) under "flash": the flash kernels run,
    the rotary kernel has no lane tile to work on and ``apply_rope`` does."""
    cfg = DecoderConfig.tiny()
    shapes, batch = {"input_ids": (2, 64)}, _batch(cfg)
    params = shaken(DecoderLM(cfg, shapes).init(jax.random.PRNGKey(3))[0], 3.0)
    assert not uses_rope_kernel(64, cfg.head_dim, cfg.head_dim, "flash")
    forced = DecoderLM(cfg, shapes, attention_impl="flash").apply(params, {}, batch)[0]
    plain_path = DecoderLM(cfg, shapes, attention_impl="reference").apply(params, {}, batch)[0]
    assert float(forced["rope_rows_in_kernel"]) == 0.0
    np.testing.assert_allclose(forced["loss"], plain_path["loss"], rtol=1e-5)


def _sin_not_negated(monkeypatch):
    def unsigned(positions, inv_freq, scale, head_dim):
        cos, sin = rope_tables(positions, inv_freq, scale, head_dim)
        return cos, sin.at[..., : inv_freq.shape[0]].multiply(-1.0)

    monkeypatch.setattr(decoder, "rope_tables", unsigned)


def _keys_unrotated(monkeypatch, cfg):
    rotate = decoder.rope_to_heads

    def unrotated_keys(x, cos, sin, rot, dtype):
        # keys (the tensors with the KV head count) pass unrotated where
        # the whole head rotates, as on sliding layers
        if x.shape[2] == cfg.num_key_value_heads * D and rot == D:
            b, s, _ = x.shape
            return x.reshape(b, s, -1, D).astype(dtype).transpose(0, 2, 1, 3)
        return rotate(x, cos, sin, rot, dtype)

    monkeypatch.setattr(decoder, "rope_to_heads", unrotated_keys)


@pytest.mark.parametrize("fault", ["sin_not_negated_on_the_first_half", "keys_unrotated"])
def test_a_planted_fault_on_the_kernel_path_reads_wrong(interpreted, monkeypatch, fault):
    """Beside ``test_decoder.test_reference_tells_each_mechanism``, which
    plants unrotated keys on ``apply_rope``'s path: the same on the
    kernel's, and a table without rotate-half's sign.  The sound program
    agrees with the plain reference; each fault does not."""
    cfg = _kernel_sized()
    shapes, batch = {"input_ids": (2, 64)}, _batch(cfg)
    params = shaken(DecoderLM(cfg, shapes).init(jax.random.PRNGKey(3))[0], 8.0)
    want = float(plain.make_loss(published_form(cfg))(params, batch))
    sound = DecoderLM(cfg, shapes, attention_impl="flash").apply(params, {}, batch)[0]
    assert float(sound["rope_rows_in_kernel"]) == _rows(cfg, 2, 64)
    assert abs(float(sound["loss"]) - want) < 1e-4
    if fault == "keys_unrotated":
        _keys_unrotated(monkeypatch, cfg)
    else:
        _sin_not_negated(monkeypatch)
    broken = DecoderLM(cfg, shapes, attention_impl="flash").apply(params, {}, batch)[0]
    assert abs(float(broken["loss"]) - want) > 1e-3, (fault, float(broken["loss"]), want)


def test_the_counter_reaches_the_progress_line_and_the_registry(interpreted, tmp_path, capsys):
    """``rope_rows_in_kernel``: every row of q and k under ``--attention
    flash``, none under ``reference``."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY
    from sparknet_tpu.utils.profiling import StepTimer

    cfg = _kernel_sized()
    path = tmp_path / "kernel_sized.json"
    path.write_text(json.dumps(published_form(cfg)))
    for impl, rows in (("flash", _rows(cfg, 1, 64)), ("reference", 0)):
        args = lm_app.parser().parse_args(
            ["--config", str(path), "--seq-len", "64", "--batch-size", "1",
             "--max-iter", "1", "--display", "1", "--synthetic-tokens", "2048",
             "--attention", impl]
        )
        solver, feed, _ = lm_app.build(args)
        metrics = lm_app._fit(solver, iter(feed), args, StepTimer(items_per_step=64, unit="tokens"))
        assert f"rope_rows_in_kernel = {rows}" in capsys.readouterr().out
        assert metrics["rope_rows_in_kernel"] == rows
        assert REGISTRY.sources()["train_step"].snapshot()["rope_rows_in_kernel"] == rows


# ------------------------------------------------------------- on the chip

@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="the compiled kernel needs a TPU"
)
@pytest.mark.parametrize(
    "b,heads,fraction,positions", [
        (2, 64, 1.0, "arange"), (2, 48, 0.5, "arange"), (4, 32, 1.0, "restarts"),
    ],
    ids=["laguna_sliding", "laguna_full", "mellum_packed"],
)
def test_compiled_rope_kernel_at_the_cells_shapes_on_hardware(b, heads, fraction, positions):
    """The compiled kernel at a cell's q, forward and gradient, against the
    plain form compiled beside it: bfloat16 out, equal on all but a
    thousandth of the elements and neighbours there."""
    table = "yarn" if fraction < 1 else "default"
    x, pos, inv_freq, scale, rot = _case(table, fraction, positions, heads, b=b, s=8192)
    cos, sin = rope_tables(pos, inv_freq, scale, D)
    g = jax.random.normal(jax.random.PRNGKey(7), (b, heads, 8192, D)).astype(jnp.bfloat16)
    assert uses_rope_kernel(8192, D, rot)

    @jax.jit  # arguments, not closures: a closed-over tensor is compiled in as a constant
    def compared(x, g, pos, cos, sin):
        """(share that differs, largest difference, largest value) of the
        forward and of the gradient, on the device."""
        def both(fn):
            out, back = jax.vjp(fn, x)
            return out, back(g)[0].astype(jnp.bfloat16)

        got = both(lambda x: rope_to_heads(x, cos, sin, rot, jnp.bfloat16))
        want = both(lambda x: _plain(x, pos, inv_freq, scale, jnp.bfloat16))
        return [
            (jnp.mean(a != w), jnp.max(jnp.abs(a.astype(jnp.float32) - w.astype(jnp.float32))),
             jnp.max(jnp.abs(w.astype(jnp.float32))))
            for a, w in zip(got, want)
        ]

    for name, (differ, worst, size) in zip(("forward", "gradient"), compared(x, g, pos, cos, sin)):
        assert float(size) > 1.0, name
        assert float(differ) <= 1e-3, (name, float(differ))
        assert float(worst) <= 2.0 ** -6 * float(size), (name, float(worst), float(size))
