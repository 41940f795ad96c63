"""Realistic-shape parallelism steps: the toy
dryrun shapes (bert_tiny, S=64) can hide pspec/memory logic that only
trips at size — e.g. a block size that divides 64 but not 512, a
capacity computation that overflows a shard, a reshape that silently
assumes seq == hidden. One 8-device CPU step per axis at
bert_small/S=512 catches that class.

Slow-marked (each step is a real fwd+bwd compile at size on CPU);
deselect with ``-m 'not slow'`` for quick iteration.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparknet_tpu.data.text import mlm_dataset, mlm_feed_tokens
from sparknet_tpu.models.bert import BertConfig, BertMLM
from sparknet_tpu.parallel.mesh import make_mesh
from sparknet_tpu.proto.caffe_pb import SolverParameter
from sparknet_tpu.solver.caffe_solver import init_opt_state

B, S, VOCAB = 8, 512, 4096


def _sp():
    return SolverParameter(
        base_lr=1e-4, lr_policy="fixed", solver_type="ADAMW",
        momentum=0.9, weight_decay=0.01, max_iter=10,
    )


def _cfg(**overrides):
    c = BertConfig.bert_small()
    return dataclasses.replace(c, vocab_size=VOCAB, max_position=S,
                               **overrides)


def _batch(seq=S):
    ds, vs = mlm_dataset(vocab_size=VOCAB, n_tokens=B * seq * 2, seq_len=seq)
    feed = mlm_feed_tokens(ds, B, vs, seed=0)
    return {k: jnp.asarray(v) for k, v in next(feed).items()}


def _assert_step(step, params, batch):
    p, _, m = step(params, init_opt_state(_sp(), params), batch,
                   jnp.asarray(0, jnp.int32), jax.random.PRNGKey(1))
    assert np.isfinite(float(m["loss"])), m
    return p


@pytest.mark.slow
def test_tp_sp_bert_small_s512():
    """dp2 x tp2 x sp2 at bert_small/S=512 (ring attention shards)."""
    cfg = _cfg()
    shapes = {"input_ids": (B, S), "mlm_positions": (B, 8)}
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2}, jax.devices()[:8])
    model = BertMLM(cfg, shapes, attention_impl="ring", tp_axis="tp",
                    sp_axis="sp")
    params, _ = model.init(jax.random.PRNGKey(0))
    from sparknet_tpu.parallel.tensor import make_tp_train_step

    step = make_tp_train_step(model, _sp(), mesh, dp_axis="dp",
                              tp_axis="tp", sp_axis="sp")
    _assert_step(step, params, _batch())


@pytest.mark.slow
def test_pp_bert_small_s512():
    """dp2 x pp4 at bert_small/S=512, 2 microbatches."""
    cfg = _cfg()
    shapes = {"input_ids": (B, S), "mlm_positions": (B, 8)}
    mesh = make_mesh({"dp": 2, "pp": 4}, jax.devices()[:8])
    model = BertMLM(cfg, shapes)
    params, _ = model.init(jax.random.PRNGKey(0))
    from sparknet_tpu.parallel.pipeline import (
        make_pp_train_step,
        stack_layer_params,
    )

    stacked, rest = stack_layer_params(params, cfg.num_layers)
    step = make_pp_train_step(model, _sp(), mesh, n_micro=2, dp_axis="dp")
    _assert_step(step, {"layers": stacked, "rest": rest}, _batch())


@pytest.mark.slow
def test_ep_bert_small_s512():
    """dp2 x ep4 at bert_small/S=512 with 8 experts, sort dispatch."""
    cfg = _cfg(moe_num_experts=8, moe_dispatch="sort",
               moe_capacity_factor=1.25, moe_top_k=2)
    shapes = {"input_ids": (B, S), "mlm_positions": (B, 8)}
    mesh = make_mesh({"dp": 2, "ep": 4}, jax.devices()[:8])
    model = BertMLM(cfg, shapes, ep_axis="ep")
    params, _ = model.init(jax.random.PRNGKey(0))
    from sparknet_tpu.parallel.expert import make_ep_train_step

    step = make_ep_train_step(model, _sp(), mesh, dp_axis="dp",
                              ep_axis="ep")
    _assert_step(step, params, _batch())
