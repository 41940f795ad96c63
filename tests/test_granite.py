"""The Mamba hybrid (``MambaHybridLM``, ``--config tiny_mamba``) and its
state-space scan (``ops/ssd.py``) on the CPU, in float32 at tiny sizes: the
chunked scan against the token-by-token recurrence, documents packed
against documents alone, the model's loss and every gradient against
``benchmark/configs/granite4_h_micro_reference.py``, each planted fault
moving the loss, the counters, the scopes, the app."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import granite4_h_micro_reference as plain
from benchmark.reference import shaken
from sparknet_tpu.models import decoder
from sparknet_tpu.models.decoder import (
    ATTENTION, MAMBA, SSD_COUNTERS, MambaHybridConfig, MambaHybridLM, causal_conv,
)
from sparknet_tpu.ops.ssd import document_starts, ssd_chunks, ssd_recurrent, ssd_scan

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ the scan

def _scan_inputs(s, b=2, h=3, p=4, n=5, seed=0, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) + (3.0 if strong else 0.0))
    return dict(
        x=jax.random.normal(ks[0], (b, s, h, p)), delta=delta,
        a=-jnp.exp(jax.random.normal(ks[2], (h,))),
        b=jax.random.normal(ks[3], (b, s, n)), c=jax.random.normal(ks[4], (b, s, n)),
        d=jax.random.normal(ks[5], (h,)),
    ), jax.random.normal(ks[6], (b, h, p, n))


def _ids(*rows):
    """(B, S) int32 ids from each row's document lengths."""
    return jnp.asarray(np.stack([np.repeat(np.arange(len(r)), r) for r in rows]), jnp.int32)


# the rows' document lengths (S = 48): boundaries inside a chunk of 8 or 16,
# at a chunk's edge (8, 16, 32), at position 0 (every row), one-token documents
_DOCS = {
    "one_document": ((48,), (48,)),
    "inside_chunks": ((5, 20, 3, 20), (13, 1, 1, 33)),
    "at_chunk_edges": ((8, 8, 16, 16), (32, 16)),
}


@pytest.mark.parametrize("strong", [False, True], ids=["mild_decay", "strong_decay"])
@pytest.mark.parametrize("chunk", [8, 16, 5, 64], ids=lambda c: f"chunk{c}")
@pytest.mark.parametrize("docs", sorted(_DOCS))
def test_chunked_scan_matches_the_recurrence(docs, chunk, strong):
    """Chunks that divide the sequence and that do not (5; 64 > S), with
    an entering state and the state after the last token, every gradient."""
    inputs, state0 = _scan_inputs(48, strong=strong)
    seg = _ids(*_DOCS[docs])
    kw = dict(segment_ids=seg, initial_state=state0, return_state=True)
    with jax.default_matmul_precision("highest"):
        y, state = ssd_scan(**inputs, chunk=chunk, **kw)
        want_y, want_state = ssd_recurrent(**inputs, **kw)
        np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)
        ct = jax.random.normal(jax.random.PRNGKey(9), y.shape)
        loss = lambda f: (lambda *t: jnp.sum(f(*t, **kw)[0] * ct) + jnp.sum(f(*t, **kw)[1]))
        names = ("x", "delta", "a", "b", "c", "d")
        args = [inputs[k] for k in names]
        got = jax.grad(loss(lambda *t, **k: ssd_scan(*t, chunk=chunk, **k)), range(6))(*args)
        want = jax.grad(loss(ssd_recurrent), range(6))(*args)
    for g, w, name in zip(got, want, names):
        assert np.all(np.isfinite(g)), name  # no NaN where the decay is cut
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.abs(w).max()), err_msg=name)


def test_the_state_a_call_enters_with_belongs_to_a_document():
    """``state_segment``: the entering state goes on into the first token's
    document when it is that document's, and is dropped when it is not."""
    inputs, state0 = _scan_inputs(24)
    seg = _ids((10, 14), (24,)) + 5
    run = lambda before: ssd_scan(
        **inputs, chunk=8, segment_ids=seg, state_segment=before, initial_state=state0)
    goes_on = run(jnp.asarray([5, 5], jnp.int32))
    np.testing.assert_allclose(goes_on, run(None), atol=1e-6)
    dropped = run(jnp.asarray([4, 5], jnp.int32))
    fresh = ssd_scan(**inputs, chunk=8, segment_ids=seg)
    np.testing.assert_allclose(dropped[0], fresh[0], atol=1e-5)  # row 0: a new document
    np.testing.assert_allclose(dropped[1], goes_on[1], atol=1e-6)
    assert float(jnp.abs(goes_on[0] - fresh[0]).max()) > 1e-2
    starts = document_starts(seg, jnp.asarray([4, 5], jnp.int32))
    assert starts[0].tolist() == [True] + [False] * 9 + [True] + [False] * 13
    assert not bool(starts[1].any())


def test_a_packed_sequence_reads_what_each_document_reads_alone():
    """The scan and the convolution on a packed row against each document
    run by itself, from nothing."""
    inputs, _ = _scan_inputs(40, b=1)
    lengths = (3, 17, 1, 19)
    seg = _ids(lengths)
    with jax.default_matmul_precision("highest"):
        packed = ssd_scan(**inputs, chunk=8, segment_ids=seg)
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 6))
        w, bias = jax.random.normal(jax.random.PRNGKey(5), (4, 6)), jnp.arange(6.0)
        conv = causal_conv(x, w, bias=bias, segment_ids=seg)
        at = 0
        for n in lengths:
            part = {k: (v if k in ("a", "d") else v[:, at:at + n]) for k, v in inputs.items()}
            np.testing.assert_allclose(
                packed[:, at:at + n], ssd_scan(**part, chunk=8), atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(
                conv[:, at:at + n], causal_conv(x[:, at:at + n], w, bias=bias), atol=1e-6)
            at += n
    # and the ids did something
    assert float(jnp.abs(packed - ssd_scan(**inputs, chunk=8)).max()) > 1e-2


def test_the_convolution_without_ids_or_bias_traces_as_before():
    """``HybridLM``'s KDA layers call ``causal_conv`` as they did: the
    program of the call without bias and ids is the body it had.  (That
    ``HybridLM`` still refuses a packed batch is
    ``test_decoder.test_the_hybrid_refuses_packed_documents``.)"""
    x = jnp.ones((2, 16, 8))
    w, history = jnp.ones((4, 8)), jnp.zeros((2, 3, 8))

    def before(x, w, history):
        s = x.shape[1]
        padded = jnp.concatenate([history, x], axis=1)
        return sum(padded[:, j:j + s] * w[j] for j in range(4))

    assert str(jax.make_jaxpr(causal_conv)(x, w, history)) == str(
        jax.make_jaxpr(before)(x, w, history))
    # a history belongs to the call's documents only where its ids say so
    seg = jnp.zeros((2, 16), jnp.int32)
    mine = causal_conv(x, w, jnp.ones((2, 3, 8)), segment_ids=seg,
                       history_ids=jnp.zeros((2, 3), jnp.int32))
    theirs = causal_conv(x, w, jnp.ones((2, 3, 8)), segment_ids=seg)
    assert float(mine[0, 0, 0]) == 4.0 and float(theirs[0, 0, 0]) == 1.0


# ----------------------------------------------------------------- the model

_SHAPES = {k: (2, 64) for k in ("input_ids", "segment_ids", "positions")}


def granite_form(cfg: MambaHybridConfig) -> dict:
    """A MambaHybridConfig written the way ``granite4_h_micro.json`` writes
    a cut: the published file's keys with the tiny sizes."""
    with open(os.path.join(_ROOT, "benchmark", "configs", "granite4_h_micro.json")) as fh:
        form = json.load(fh)
    form.update(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        shared_intermediate_size=cfg.shared_intermediate_size,
        intermediate_size=cfg.shared_intermediate_size,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        num_hidden_layers=cfg.num_layers, layer_types=list(cfg.layer_types),
        mamba_n_heads=cfg.mamba_n_heads, mamba_d_head=cfg.mamba_d_head,
        mamba_d_state=cfg.mamba_d_state, mamba_d_conv=cfg.mamba_d_conv,
        mamba_chunk_size=cfg.mamba_chunk_size,
        mamba_expand=cfg.ssm_width // cfg.hidden_size,
        **{k: getattr(cfg, k) for k in (
            "attention_multiplier", "embedding_multiplier", "residual_multiplier",
            "logits_scaling", "rms_norm_eps")},
    )
    return form


def packed_batch(cfg, b=2, s=64, seed=1, median=20, shortest=4):
    from sparknet_tpu.data.text import packed_dataset, packed_feed

    ds = packed_dataset(
        vocab_size=cfg.vocab_size, n_tokens=64 * s, seq_len=s, median_len=median,
        min_len=shortest, max_len=s, seed=seed,
    )
    return {k: jnp.asarray(v) for k, v in next(iter(packed_feed(ds, b, seed=seed))).items()}


def _tiny():
    cfg = MambaHybridConfig.tiny()
    model = MambaHybridLM(cfg, _SHAPES)
    params, _ = model.init(jax.random.PRNGKey(3))
    return cfg, model, shaken(params, 3.0), packed_batch(cfg)


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


def test_granite_form_round_trips_and_refuses_what_it_does_not_model(tiny):
    cfg, model, params, _ = tiny
    form = granite_form(cfg)
    assert MambaHybridConfig.from_published(
        form, loss_chunk=cfg.loss_chunk, ssm_segment=cfg.ssm_segment) == cfg
    for key, value in [
        ("num_local_experts", 4), ("position_embedding_type", "rope"),
        ("tie_word_embeddings", False), ("mamba_n_groups", 2),
        ("mamba_proj_bias", True), ("mamba_expand", 3),
    ]:
        with pytest.raises(ValueError, match=key):
            MambaHybridConfig.from_published(dict(form, **{key: value}))
    assert set(params["layer_00"]) == {
        "attn_norm", "ffn_norm", "in_proj", "conv_w", "conv_b", "dt_bias",
        "A_log", "D", "ssm_norm", "out_proj", "mlp_in", "mlp_out"}
    assert set(params["layer_01"]) == {
        "attn_norm", "ffn_norm", "q_w", "k_w", "v_w", "o_w", "mlp_in", "mlp_out"}
    assert model.input_names == ["input_ids", "labels", "segment_ids", "positions"]
    assert model.counters == SSD_COUNTERS + (
        "doc_count", "loss_positions", "attn_pairs_full", "flash_tiles_docs_full")
    with pytest.raises(ValueError, match="layer type 'kda'"):
        MambaHybridLM(dataclasses.replace(cfg, layer_types=("kda",)), _SHAPES)


def test_the_tied_head_is_counted_once(tiny):
    cfg, model, params, _ = tiny
    assert set(params["head"]) == {"norm"}  # the matrix is the embedding's
    counted = model.num_params(params)
    h = cfg.hidden_size
    width, n, heads = cfg.ssm_width, cfg.mamba_d_state, cfg.mamba_n_heads
    mlp = 3 * h * cfg.shared_intermediate_size
    mamba = (h * (2 * width + 2 * n + heads) + 5 * (width + 2 * n) + 3 * heads
             + width + width * h + mlp + 2 * h)
    attention = 2 * h * h + 2 * h * (h // 2) + mlp + 2 * h
    assert counted == 2 * mamba + attention + cfg.vocab_size * h + h


def test_initialisation_follows_mamba2(tiny):
    cfg, model, _, _ = tiny
    params, _ = model.init(jax.random.PRNGKey(0))
    lp = params["layer_00"]
    np.testing.assert_allclose(jnp.exp(lp["A_log"]), jnp.arange(1.0, cfg.mamba_n_heads + 1))
    np.testing.assert_array_equal(lp["D"], 1.0)
    dt = jax.nn.softplus(lp["dt_bias"])
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 1e-1 * 1.001
    assert float(jnp.abs(lp["conv_w"]).max()) <= 0.5
    specs = model.param_specs()["layer_00"]
    assert {k for k, (_, decay) in specs.items() if decay == 0.0} == {
        "attn_norm", "ffn_norm", "ssm_norm", "A_log", "dt_bias", "D", "conv_b"}


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("segment", [32, 64], ids=["two_segments", "one_segment"])
def test_loss_and_every_gradient_match_the_plain_reference(tiny, remat, segment):
    cfg, _, params, batch = tiny
    model = MambaHybridLM(dataclasses.replace(cfg, remat=remat, ssm_segment=segment), _SHAPES)
    reference = plain.make_loss(granite_form(cfg))
    with jax.default_matmul_precision("highest"):
        system = lambda p: model.apply(p, {}, batch, train=True)[0]["loss"]
        loss, grads = jax.value_and_grad(system)(params)
        want_loss, want = jax.value_and_grad(lambda p: reference(p, batch))(params)
    assert abs(float(loss) - float(want_loss)) < 2e-6
    for layer in want:
        for name, w in want[layer].items():
            scale = float(jnp.abs(w).max())
            assert scale > 0, (layer, name)  # every leaf takes part
            assert np.all(np.isfinite(grads[layer][name])), (layer, name)
            np.testing.assert_allclose(
                grads[layer][name], w, atol=2e-4 * scale, err_msg=f"{layer}.{name}")


def test_the_reference_reads_positions_from_the_ids(tiny):
    _, _, _, batch = tiny
    np.testing.assert_array_equal(
        plain.document_positions(batch["segment_ids"]), batch["positions"])


def _gate_dropped(model_cls):
    class NoGate(model_cls):
        def _gated_norm(self, lp, y, z):
            return decoder.rms_norm(y, lp["ssm_norm"], self.cfg.rms_norm_eps)
    return NoGate


_FAULTS = {
    "state_not_reset": "scan_ids",
    "convolution_across_boundaries": "conv_ids",
    "d_dropped": "d",
    "z_gate_dropped": "gate",
    "residual_multiplier_at_1": dict(residual_multiplier=1.0),
    "attention_multiplier_at_head_size": "scale",
}


def plant(fault, cfg, monkeypatch, model_cls=MambaHybridLM):
    """(configuration, model class) of the program with ``fault`` planted."""
    change = _FAULTS[fault]
    if change == "scan_ids":
        whole = decoder.ssd_scan
        monkeypatch.setattr(decoder, "ssd_scan", lambda *a, segment_ids, state_segment, **kw:
                            whole(*a, **kw))
    elif change == "conv_ids":
        whole = decoder.causal_conv
        monkeypatch.setattr(decoder, "causal_conv", lambda *a, segment_ids, history_ids, **kw:
                            whole(*a, **kw))
    elif change == "d":
        whole = decoder.ssd_scan
        monkeypatch.setattr(decoder, "ssd_scan", lambda x, dl, a, b, c, d, **kw:
                            whole(x, dl, a, b, c, None, **kw))
    elif change == "gate":
        model_cls = _gate_dropped(model_cls)
    elif change == "scale":
        cfg = dataclasses.replace(cfg, attention_multiplier=cfg.head_dim ** -0.5)
    else:
        cfg = dataclasses.replace(cfg, **change)
    return cfg, model_cls


@pytest.fixture(scope="module")
def telling():
    """Weights and a batch at which every mechanism shows in the tiny loss:
    gain 20 (at 3 the logits over 32 channels are so flat that no fault
    moves the loss by 1e-3), ``dt_bias`` lifted by 2 (a fresh ``delta`` of
    1e-3 .. 1e-1 writes so little into the state that ``D x`` is all of
    ``y``) and documents of median 8 tokens (14 in the batch: the taps and
    the state cross a boundary at many positions)."""
    cfg, model, _, _ = _tiny()
    params = shaken(model.init(jax.random.PRNGKey(3))[0], 20.0)
    params = {
        layer: {n: w + 2.0 if n == "dt_bias" else w for n, w in leaves.items()}
        for layer, leaves in params.items()
    }
    return cfg, model, params, packed_batch(cfg, median=8, shortest=2)


@pytest.mark.parametrize("fault", sorted(_FAULTS))
def test_reference_tells_each_mechanism(telling, fault, monkeypatch):
    """A program that carries the state or the convolution across a
    boundary, drops ``D`` or the ``z`` gate, or takes a multiplier as 1
    does not agree with the reference: each moves the tiny loss by >=
    1e-3."""
    cfg, model, params, batch = telling
    want = float(plain.make_loss(granite_form(cfg))(params, batch))
    sound = float(model.apply(params, {}, batch)[0]["loss"])
    broken_cfg, broken_cls = plant(fault, cfg, monkeypatch)
    got = float(broken_cls(broken_cfg, _SHAPES).apply(params, {}, batch)[0]["loss"])
    assert abs(sound - want) < 1e-5
    assert abs(got - want) > 1e-3, (fault, got, want)


def test_counters_reach_the_blobs_and_the_registry(tiny):
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.telemetry.registry import REGISTRY

    cfg, model, params, batch = tiny
    blobs = jax.jit(lambda p: model.apply(p, {}, batch)[0])(params)
    docs = int(blobs["doc_count"])
    assert docs == int((batch["positions"] == 0).sum()) > 2
    # documents begun inside the batch, in each of the two Mamba layers
    assert int(blobs["ssd_state_resets"]) == 2 * (docs - 2)
    assert int(blobs["ssd_chunks"]) == ssd_chunks(64, 8) == 8
    assert float(blobs["ssd_chunks_in_kernel"]) == 0.0
    assert 0.0 < float(blobs["ssd_decay_min"]) < 1.0
    args = lm_app.parser().parse_args([
        "--config", "tiny_mamba", "--seq-len", "64", "--batch-size", "2",
        "--pack-documents", "--doc-median", "20", "--doc-min", "4", "--doc-max", "64",
        "--synthetic-tokens", "4096"])
    solver, feed, _ = lm_app.build(args)
    metrics = solver.step(iter(feed), 1)
    read = REGISTRY.sources()["train_step"].snapshot()
    for name in solver.train_net.counters:
        assert read[name] == float(metrics[name]), name
    assert float(metrics["ssd_state_resets"]) == 2 * (float(metrics["doc_count"]) - 2)


def test_forced_kernels_walk_every_chunk_and_agree(monkeypatch):
    """Two Mamba layers whose scan the kernels take (4 heads of 64, a state
    of 128, one chunk of 128 a segment, two segments a sequence), the
    kernels in interpret mode: under "flash" ``ssd_chunks_in_kernel`` is
    ``ssd_chunks``, under "reference" 0, and the loss and every leaf's
    gradient agree (the scan's own cases: ``tests/test_ssd_kernel.py``)."""
    from functools import partial

    monkeypatch.setattr(decoder, "ssd_scan", partial(ssd_scan, interpret=True))
    cfg = MambaHybridConfig.tiny(
        layer_types=(MAMBA, MAMBA), mamba_d_head=64, mamba_d_state=128,
        mamba_chunk_size=128, ssm_segment=128)
    shapes = {k: (2, 256) for k in _SHAPES}
    batch = packed_batch(cfg, s=256, median=60)
    forced = MambaHybridLM(cfg, shapes, attention_impl="flash")
    params = shaken(forced.init(jax.random.PRNGKey(3))[0], 3.0)

    def run(model):
        loss = lambda p: (lambda out: (out["loss"], out))(model.apply(p, {}, batch, train=True)[0])
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    with jax.default_matmul_precision("highest"):
        (loss_k, out_k), grads_k = run(forced)
        (loss_p, out_p), grads_p = run(MambaHybridLM(cfg, shapes, attention_impl="reference"))
    assert float(out_k["ssd_chunks"]) == float(out_k["ssd_chunks_in_kernel"]) == 2.0
    assert float(out_p["ssd_chunks"]) == 2.0 and float(out_p["ssd_chunks_in_kernel"]) == 0.0
    assert float(out_k["ssd_state_resets"]) == float(out_p["ssd_state_resets"]) > 0
    np.testing.assert_allclose(loss_k, loss_p, rtol=2e-6)
    for layer in grads_p:
        for name, w in grads_p[layer].items():
            np.testing.assert_allclose(
                grads_k[layer][name], w, atol=2e-4 * max(float(jnp.abs(w).max()), 1e-12),
                err_msg=f"{layer}.{name}")


def test_the_compiled_step_carries_the_new_scopes_in_their_nesting(tiny):
    """The scope chains of the compiled step's instructions: the Mamba
    mixer's parts under ``attn.ssm`` in forward, backward and recompute,
    the attention layer under ``attn.full``."""
    from sparknet_tpu.utils import profiling

    _, _, params, batch = tiny
    model = MambaHybridLM(dataclasses.replace(MambaHybridConfig.tiny(), remat=True), _SHAPES)
    text = jax.jit(jax.grad(lambda p: model.apply(p, {}, batch)[0]["loss"])).lower(
        params).compile().as_text()
    table = profiling.scope_table(text, profiling.declared_scopes())
    chains = {(e.chain, e.pass_) for e in table.values()}
    for inner in ("attn.proj", "ssm.conv", "ssd.scan", "norm"):
        for pass_ in ("forward", "backward", "recompute"):
            assert any(c[:1] == ("attn.ssm",) and inner in c and p == pass_
                       for c, p in chains), (inner, pass_)
    assert any(c[:1] == ("attn.full",) and "attn.proj" in c for c, _ in chains)
    assert any(c[:1] == ("mlp.dense",) for c, _ in chains)


def test_a_sequence_is_whole_segments():
    with pytest.raises(ValueError, match="whole Mamba segments"):
        cfg = MambaHybridConfig.tiny(ssm_segment=24)
        model = MambaHybridLM(cfg, _SHAPES)
        model.apply(model.init(jax.random.PRNGKey(0))[0], {}, packed_batch(cfg))


def test_lm_app_trains_the_mamba_hybrid_and_prints_the_counters(capsys):
    from sparknet_tpu.apps import lm_app

    lm_app.main([
        "--config", "tiny_mamba", "--max-iter", "4", "--display", "2",
        "--seq-len", "64", "--batch-size", "2", "--pack-documents",
        "--doc-median", "20", "--doc-min", "4", "--doc-max", "64",
        "--synthetic-tokens", "4096", "--remat"])
    out = capsys.readouterr().out
    assert "ssd_state_resets = " in out and "ssd_chunks = 8" in out
    assert "flash_tiles_docs_full = " in out and "window" not in out.split("LmApp:")[1]
    assert "the pool's mean attn_pairs a sequence full=" in out


def test_a_published_file_drives_the_app(tmp_path):
    from sparknet_tpu.apps import lm_app

    path = tmp_path / "granite_tiny.json"
    path.write_text(json.dumps(granite_form(MambaHybridConfig.tiny())))
    args = lm_app.parser().parse_args(["--config", str(path)])
    cfg = lm_app.make_config(args)
    assert isinstance(cfg, MambaHybridConfig) and lm_app.model_class(cfg) is MambaHybridLM
    assert cfg.layer_types == (MAMBA, ATTENTION, MAMBA)
    assert lm_app.flash_tiles(cfg, 1024) == {
        "attention_unmasked": 0, "attention_masked": 3}
