"""The TPU's own compiler on the main path's kernels at real widths, for a
chip that is described and not attached: interpret mode cannot refuse a
block the tiling does not allow or a kernel that wants too much VMEM; this
can, and costs no chip time.  Nothing runs, so nothing here is a time or a
result.  Every such compile of the repository lives in this one file, and
the topology is described inside a fixture (one process at a time may load
the TPU's library: see the on-chip-measurement guide, section 2)."""

import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


_ATTENTION = {
    # heads, KV heads, sequence, head size (of q and k, of v), window, batch
    "ling_mla_layer": (32, 32, 16384, (192, 128), None, 1),
    "laguna_sliding_layer": (64, 8, 8192, 128, 512, 2),
    "laguna_full_layer": (48, 8, 8192, 128, None, 2),
    "bert_base_layer": (12, 12, 512, 64, None, 64),
    # as bert_mlm calls it: a key mask passed (else the kernels take none)
    # and dropout's hash on every tile
    "bert_base_layer_key_mask_dropout": (12, 12, 512, 64, None, 64),
    # as mellum_train_packed8k calls them: segment ids, so two scalar
    # prefetches and the keys' document ends beside q, k, v
    "mellum_full_layer_documents": (32, 4, 8192, 128, None, 4),
    "mellum_sliding_layer_documents": (32, 4, 8192, 128, 1024, 4),
    # as granite_train_packed16k calls them: the banded kernels at head size 64
    "granite_attention_layer_documents": (32, 8, 16384, 64, None, 1),
}


@pytest.mark.parametrize("case", sorted(_ATTENTION))
def test_flash_kernels_compile_for_v5e_at_real_widths(case, one_chip, no_compile_cache):
    from sparknet_tpu.ops.attention import flash_attention

    heads, kv_heads, seq, d, window, batch = _ATTENTION[case]
    causal = not case.startswith("bert_base_layer")
    masked = case.endswith("key_mask_dropout")
    packed = case.endswith("documents")
    d, dv = d if isinstance(d, tuple) else (d, d)
    q = jax.ShapeDtypeStruct((batch, heads, seq, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((batch, kv_heads, seq, d), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((batch, kv_heads, seq, dv), jnp.bfloat16, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((batch, seq), jnp.bool_, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)

    def grads(q, k, v, mask, rng, ids):
        extra = dict(kv_mask=mask, dropout_rate=0.1, dropout_rng=rng) if masked else {}
        if packed:
            extra["segment_ids"] = ids
        out = lambda *a: flash_attention(*a, causal=causal, window=window, **extra)
        return jax.grad(lambda *a: out(*a).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(q, k, v, mask, rng, ids).compile().as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"):
        assert kernel in text, kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 3


_HELD = {
    # tokens (batch, sequence), hidden, expert width, experts, held
    "laguna": ((2, 8192), 2048, 512, 256, 32),
    "mellum": ((4, 8192), 2304, 896, 64, 16),
}


@pytest.mark.parametrize("case", sorted(_HELD))
def test_held_experts_compile_to_the_grouped_product_for_v5e(case, one_chip, no_compile_cache):
    """One sparse layer's held experts at a cell's shapes, the kernels
    forced as a TPU takes them: the grouped products are the Pallas kernels
    of ``ops/gmm.py`` in every pass — forward the two ``gmm`` products, and
    in the chunk's backward its recomputed first product, the cotangent's
    product with the down weights, the rows' gradient (``gmm``) and both
    weight gradients (``tgmm``) — all under ``moe.experts`` and outside
    ``moe.rows``, and no ``ragged-dot`` of XLA's is left; a chunk's rows
    reach their tokens through ``moe_combine`` in the forward pass (the
    backward pass's scatter-add stays)."""
    from sparknet_tpu.parallel.moe import held_experts_ffn, route_sigmoid
    from sparknet_tpu.utils import profiling

    tokens, h, f, experts, held = _HELD[case]
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    params = {
        "router_w": shape((h, experts), jnp.float32),
        "experts_gate_up": shape((held, h, 2 * f), jnp.float32),
        "experts_down": shape((held, f, h), jnp.float32),
    }

    def grads(x, params):
        routed = lambda x, p: held_experts_ffn(
            x, p, experts_held=(0, held), top_k=8, compute_dtype=jnp.bfloat16,
            router=lambda xt, p: route_sigmoid(xt, p["router_w"], 8, 2.5),
            force="flash",
        )[0]
        return jax.value_and_grad(  # the value keeps the forward pass's sum
            lambda x, p: routed(x, p).astype(jnp.float32).sum(), (0, 1)
        )(x, params)

    text = jax.jit(grads).lower(shape((*tokens, h), jnp.bfloat16), params).compile().as_text()
    table = profiling.scope_table(text, profiling.declared_scopes())
    grouped = {name: e for name, e in table.items() if "ragged-dot" in name}
    assert all(e.kernel for e in grouped.values()), sorted(grouped)  # none of XLA's
    passes = lambda kind: sorted(e.pass_ for n, e in grouped.items() if n.startswith(kind + "."))
    assert passes("ragged-dot-gmm") == ["backward"] * 3 + ["forward"] * 2
    assert passes("ragged-dot-tgmm") == ["backward"] * 2
    for e in grouped.values():
        assert "moe.experts" in e.chain and "moe.rows" not in e.chain, e
    calls = [
        ln for ln in text.splitlines()
        if " custom-call(" in ln and "moe_combine" in ln.split(" = ")[0]
    ]
    assert len(calls) == 1  # the forward loop's


@pytest.mark.parametrize("force", ["flash", "reference"], ids=["kernels", "jax_numpy"])
def test_kda_scan_compiles_for_v5e_at_real_widths(force, one_chip, no_compile_cache):
    """One segment of a KDA layer of ``ling3_flash`` (1024 tokens, 32 heads
    of 128, chunks of 64), forward and backward, in both forms: the Pallas
    kernels, each with the state ``f32[1,32,128,128]`` among its operands
    or results (what the benchmark's reader of ``kda_scan_ms`` matches),
    and ``jax.numpy``, matrix products and a loop with no kernel of ours."""
    from sparknet_tpu.ops.kda import kda_scan

    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    wide = lambda t: shape((1, 32, 1024, 128), t)

    def grads(q, k, v, g, beta, state):
        def total(*a):
            out, end = kda_scan(
                *a[:5], chunk=64, initial_state=a[5], return_state=True, force=force
            )
            return out.sum() + end.sum()

        return jax.grad(total, range(6))(q, k, v, g, beta, state)

    text = jax.jit(grads).lower(
        wide(jnp.bfloat16), wide(jnp.bfloat16), wide(jnp.bfloat16), wide(jnp.float32),
        shape((1, 32, 1024), jnp.float32), shape((1, 32, 128, 128), jnp.float32),
    ).compile().as_text()
    assert "kda.scan" in text  # the scope reaches the compiled program's metadata
    if force == "reference":
        assert " while(" in text and "tpu_custom_call" not in text
        return
    calls = [line for line in text.splitlines() if " custom-call(" in line and "kda_scan" in line]
    for name in ("kda_scan_fwd", "kda_scan_bwd"):
        mine = [line for line in calls if name in line.split(" = ")[0]]
        assert mine and all("f32[1,32,128,128]" in line for line in mine), name


_ROTARY = {
    # batch, heads, lanes of a head that rotate, positions by sequence
    "laguna_sliding_q": (2, 64, 128, False),
    "laguna_full_q": (2, 48, 64, False),
    "laguna_k": (2, 8, 128, False),
    "mellum_q_documents": (4, 32, 128, True),
    "mellum_k_documents": (4, 4, 128, True),
}


@pytest.mark.parametrize("case", sorted(_ROTARY))
def test_rope_kernels_compile_for_v5e_at_real_widths(case, one_chip, no_compile_cache):
    """``ops.rope.rope_to_heads`` at a cell's q or k (8192 tokens, heads of
    128), forward and backward: two kernels, neither under a name the
    attention readers match, the gradient back in bfloat16."""
    from sparknet_tpu.ops.rope import rope_to_heads, uses_rope_kernel

    batch, heads, rot, packed = _ROTARY[case]
    assert uses_rope_kernel(8192, 128, rot, "flash")
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    x = shape((batch, 8192, heads * 128), jnp.float32)
    table = shape((batch if packed else 1, 8192, 128), jnp.float32)

    def grads(x, cos, sin):
        total = lambda x: (rope_to_heads(x, cos, sin, rot, jnp.bfloat16).astype(jnp.float32) ** 2).sum()
        return jax.grad(total)(x).astype(jnp.bfloat16)

    text = jax.jit(grads).lower(x, table, table).compile().as_text()
    calls = [ln.split(" = ")[0] for ln in text.splitlines() if " custom-call(" in ln]
    assert len(calls) == 2 and "flash_attention" not in text
    assert any("rope_to_heads" in c for c in calls) and any("rope_from_heads" in c for c in calls)


def test_the_rope_kernels_sit_under_attn_rope_in_all_three_passes(one_chip, no_compile_cache):
    """A small ``DecoderLM`` step with the layers checkpointed, compiled
    for v5e as a TPU takes it: ``utils.profiling.scope_table`` (what
    ``Solver.step_scopes()`` reads the compiled step with; on this backend
    its own program holds no custom call) finds the rotary kernels as
    kernels under ``attn.rope`` inside a mixer's scope, forward, recomputed
    and backward, apart from the flash kernels by name."""
    from sparknet_tpu.models.decoder import DecoderConfig, DecoderLM
    from sparknet_tpu.utils import profiling

    cfg = DecoderConfig.tiny(head_dim=128, remat=True)
    model = DecoderLM(cfg, {"input_ids": (2, 128)}, attention_impl="flash")
    put = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = put(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0]))
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32, sharding=one_chip)

    def step(p, ids):
        return jax.grad(
            lambda p_: model.apply(p_, {}, {"input_ids": ids, "labels": ids}, train=True)[0]["loss"]
        )(p)

    text = jax.jit(step).lower(params, ids).compile().as_text()
    table = profiling.scope_table(text, profiling.declared_scopes())
    rotary = {name: e for name, e in table.items() if name.startswith("rope_")}
    assert rotary and all(e.kernel for e in rotary.values())
    for e in rotary.values():
        assert e.chain[0] in ("attn.full", "attn.window") and e.chain[-1] == "attn.rope", e
    assert {e.pass_ for n, e in rotary.items() if n.startswith("rope_to_heads")} == {"forward", "recompute"}
    assert {e.pass_ for n, e in rotary.items() if n.startswith("rope_from_heads")} == {"backward"}
    flash = [name for name, e in table.items() if e.kernel and "flash_attention" in name]
    assert flash and not any("flash_attention" in name for name in rotary)
    # every kernel under a mixer is one or the other: mixer_ms - mixer_glue_ms
    kernels = {name for name, e in table.items() if e.kernel and e.chain[:1] and e.chain[0].startswith("attn")}
    assert kernels == set(rotary) | set(flash)


@pytest.mark.parametrize("force", ["flash", "reference"], ids=["kernels", "jax_numpy"])
def test_ssd_scan_compiles_for_v5e_at_real_widths(force, one_chip, no_compile_cache):
    """One segment of a Mamba-2 layer of ``granite4_h_micro`` (2048 tokens,
    64 heads of 64, a state of 128, chunks of 256, a packed segment's ids),
    forward and backward, in both forms: the Pallas kernels
    ``ssd_scan_fwd`` and ``ssd_scan_bwd``, and ``jax.numpy``, a loop with
    no kernel of ours; the scope reaches the compiled program."""
    from sparknet_tpu.ops.ssd import ssd_scan

    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)

    def grads(x, delta, a, b, c, d, ids, state):
        def total(x, delta, a, b, c, d, state):
            y, end = ssd_scan(
                x, delta, a, b, c, d, chunk=256, segment_ids=ids, state_segment=ids[:, 0],
                initial_state=state, return_state=True, force=force,
            )
            return y.sum() + end.sum()

        return jax.grad(total, range(7))(x, delta, a, b, c, d, state)

    text = jax.jit(grads).lower(
        shape((1, 2048, 64, 64), jnp.bfloat16), shape((1, 2048, 64), jnp.float32),
        shape((64,), jnp.float32), shape((1, 2048, 128), jnp.bfloat16),
        shape((1, 2048, 128), jnp.bfloat16), shape((64,), jnp.float32),
        shape((1, 2048), jnp.int32), shape((1, 64, 64, 128), jnp.float32),
    ).compile().as_text()
    assert "ssd.scan" in text
    calls = [ln.split(" = ")[0] for ln in text.splitlines() if "tpu_custom_call" in ln]
    if force == "reference":
        assert " while(" in text and not calls
        return
    assert len(calls) == 2
    assert any("ssd_scan_fwd" in c for c in calls) and any("ssd_scan_bwd" in c for c in calls)


def test_the_ssd_kernels_sit_under_ssd_scan_in_all_three_passes(one_chip, no_compile_cache):
    """A small ``MambaHybridLM`` step on packed documents with the layers
    checkpointed, compiled for v5e: ``utils.profiling.scope_table`` finds
    the scan's kernels under ``attn.ssm`` / ``ssd.scan`` — the forward
    kernel forward and recomputed, the backward kernel backward — and no
    other kernel, so ``ssd_scan_ms`` reads them whole."""
    from sparknet_tpu.models.decoder import MAMBA, MambaHybridConfig, MambaHybridLM
    from sparknet_tpu.utils import profiling

    cfg = MambaHybridConfig.tiny(
        layer_types=(MAMBA, MAMBA), mamba_d_head=64, mamba_d_state=128,
        mamba_chunk_size=128, ssm_segment=128, remat=True,
    )
    names = ("input_ids", "labels", "segment_ids", "positions")
    model = MambaHybridLM(cfg, {k: (1, 256) for k in names}, attention_impl="flash")
    put = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = put(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0]))
    batch = {k: jax.ShapeDtypeStruct((1, 256), jnp.int32, sharding=one_chip) for k in names}

    def step(p, batch):
        return jax.grad(lambda p_: model.apply(p_, {}, batch, train=True)[0]["loss"])(p)

    text = jax.jit(step).lower(params, batch).compile().as_text()
    table = profiling.scope_table(text, profiling.declared_scopes())
    kernels = {name: e for name, e in table.items() if e.kernel}
    assert kernels and all(n.startswith("ssd_scan_") for n in kernels), sorted(kernels)
    for e in kernels.values():
        assert e.chain[0] == "attn.ssm" and e.chain[-1] == "ssd.scan", e
    assert {e.pass_ for n, e in kernels.items() if n.startswith("ssd_scan_fwd")} == {"forward", "recompute"}
    assert {e.pass_ for n, e in kernels.items() if n.startswith("ssd_scan_bwd")} == {"backward"}


def test_the_conv_hybrid_step_runs_the_flash_kernels_at_head_64_under_attn_full(
    one_chip, no_compile_cache
):
    """A small ``ConvHybridLM`` step on packed documents (heads of 64, as
    ``lfm2_8b_a1b``'s; two conv layers round an attention layer) with the
    layers checkpointed, compiled for v5e: the flash kernels with segment
    ids sit under ``attn.full`` — forward and recomputed forward, dq and dkv
    backward — and no kernel sits under ``attn.conv``: the gated taps are
    XLA's."""
    from sparknet_tpu.models.decoder import ConvHybridConfig, ConvHybridLM
    from sparknet_tpu.utils import profiling

    cfg = ConvHybridConfig.tiny(
        hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=512, moe_intermediate_size=128, remat=True,
    )
    assert cfg.head_dim == 64
    names = ("input_ids", "labels", "segment_ids", "positions")
    model = ConvHybridLM(cfg, {k: (2, 256) for k in names}, attention_impl="flash")
    put = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = put(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0]))
    batch = {k: jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip) for k in names}

    def step(p, batch):
        return jax.grad(lambda p_: model.apply(p_, {}, batch, train=True)[0]["loss"])(p)

    text = jax.jit(step).lower(params, batch).compile().as_text()
    table = profiling.scope_table(text, profiling.declared_scopes())
    flash = {name: e for name, e in table.items() if e.kernel and "flash_attention" in name}
    assert flash and all(e.chain[0] == "attn.full" for e in flash.values()), flash
    passes = lambda kind: {e.pass_ for n, e in flash.items() if n.startswith(kind)}
    assert passes("flash_attention_fwd") == {"forward", "recompute"}
    assert passes("flash_attention_dq") == passes("flash_attention_dkv") == {"backward"}
    assert not any(e.kernel for e in table.values() if e.chain[:1] == ("attn.conv",))
    assert any("conv.short" in e.chain for e in table.values())


_LRN = {
    # NHWC input, dtype: shapes the cell's step does not hold
    "batch16_norm2": ((16, 27, 27, 256), jnp.bfloat16),  # 16 images a tile
    "caffenet_float32": ((10, 13, 13, 256), jnp.float32),
    "googlenet_c192": ((128, 56, 56, 192), jnp.bfloat16),  # batch form, 512 lanes
    "googlenet_c64_float32": ((128, 28, 28, 64), jnp.float32),
    "c512": ((256, 27, 27, 512), jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(_LRN))
def test_lrn_kernels_compile_for_v5e(case, one_chip, no_compile_cache):
    """``ops/lrn.py``'s two kernels, forward and backward, at shapes of the
    zoo's other LRN nets, in both orientations and dtypes."""
    from sparknet_tpu.ops.lrn import lrn_nhwc

    shape, dtype = _LRN[case]

    def grad(x):
        y = lambda t: lrn_nhwc(t, size=5, alpha=1e-4, beta=0.75, k=1.0).astype(jnp.float32)
        return jax.grad(lambda t: jnp.sum(y(t) ** 2))(x)

    text = jax.jit(grad).lower(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)).compile().as_text()
    calls = [ln.split(" = ")[0] for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any("lrn_fwd" in c for c in calls) and any("lrn_bwd" in c for c in calls), calls


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """The described v5e:2x2's four chips as a ``dp`` mesh."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices), ("dp",))


def test_the_lrn_kernels_run_per_shard_in_a_shard_map_and_not_in_a_partitioned_jit(
    four_chips, no_compile_cache
):
    """Over four chips XLA's partitioner cannot split a Mosaic kernel: under
    ``jit`` with the batch sharded (as ``--parallel sync`` builds AlexNet's
    step) the LRN compiles to its ``jax.numpy`` form, with no gather of the
    batch; inside a ``shard_map`` to the two kernels on each shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparknet_tpu.ops.lrn import lrn_nhwc

    def grad(x):
        y = lambda t: lrn_nhwc(t, size=5, alpha=1e-4, beta=0.75, k=1.0).astype(jnp.float32)
        return jax.grad(lambda t: jnp.sum(y(t) ** 2))(x)

    x = jax.ShapeDtypeStruct((512, 27, 27, 96), jnp.bfloat16)
    sharded = NamedSharding(four_chips, P("dp"))
    jit = jax.jit(grad, in_shardings=sharded, out_shardings=sharded).lower(x).compile().as_text()
    assert "tpu_custom_call" not in jit and "all-gather" not in jit
    per_shard = jax.jit(
        jax.shard_map(grad, mesh=four_chips, in_specs=P("dp"), out_specs=P("dp"))
    ).lower(x).compile().as_text()
    calls = [ln.split(" = ")[0] for ln in per_shard.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2 and any("lrn_fwd" in c for c in calls) and any("lrn_bwd" in c for c in calls)


_NORM1, _NORM2 = 1024 * 55 * 55 * 96, 1024 * 27 * 27 * 256  # alexnet_live's LRN inputs


@pytest.fixture(scope="module")
def alexnet_steps(one_chip):
    """``alexnet_live``'s step — the Solver's own program through
    ``lower_step``, batch 1024, bfloat16, with the Solver's compiler option
    — compiled for v5e twice: ``{"kernels": text, "jax_numpy": text}``, the
    LRN layers steered to the Pallas kernels and to the ``jax.numpy`` form
    (on this backend the rule picks the second by itself).  Module-scoped:
    two whole-step compiles, read by two tests."""
    import functools

    from jax.experimental.compilation_cache import compilation_cache

    from sparknet_tpu.apps.imagenet_app import ZOO
    from sparknet_tpu.nets import layers
    from sparknet_tpu.ops import lrn
    from sparknet_tpu.proto import caffe_pb
    from sparknet_tpu.solver.trainer import Solver

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    shapes = {"data": (1024, 227, 227, 3), "label": (1024,)}
    solver = Solver(
        caffe_pb.load_solver(f"{ZOO}/bvlc_alexnet_solver.prototxt"), shapes,
        net_param=caffe_pb.load_net(f"{ZOO}/bvlc_alexnet_train_val.prototxt"),
        test_input_shapes={"data": (2, 227, 227, 3), "label": (2,)},
        compute_dtype=jnp.bfloat16,
    )
    put = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    solver.params, solver.state, solver.opt_state, solver.rng = map(
        put, (solver.params, solver.state, solver.opt_state, solver.rng)
    )
    batch = put({k: jax.ShapeDtypeStruct(v, jnp.float32 if k == "data" else jnp.int32)
                 for k, v in shapes.items()})
    texts = {}
    with pytest.MonkeyPatch.context() as mp:
        for form, force in (("kernels", "flash"), ("jax_numpy", "reference")):
            mp.setattr(layers, "uses_lrn_kernel", functools.partial(lrn.uses_lrn_kernel, force=force))
            jax.clear_caches()  # the step traces again under the other rule
            texts[form] = solver.lower_step(batch).compile(
                # trainer._step_compiler_options' on a TPU backend
                compiler_options={"xla_tpu_scoped_vmem_limit_kib": "32768"}
            ).as_text()
    yield texts
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_the_lrn_kernels_sit_under_lrn_norm1_and_norm2_both_ways(alexnet_steps):
    """In ``alexnet_live``'s step the forward kernel sits under each LRN
    layer's scope in the forward pass and the backward kernel in the
    backward pass, and no other kernel is in the step; the ``jax.numpy``
    form's step holds none."""
    from sparknet_tpu.utils import profiling

    table = profiling.scope_table(alexnet_steps["kernels"], profiling.declared_scopes())
    kernels = sorted(
        (e.chain, e.pass_, "lrn_fwd" if "lrn_fwd" in n else "lrn_bwd" if "lrn_bwd" in n else n)
        for n, e in table.items() if e.kernel
    )
    assert kernels == [
        (("lrn.norm1",), "backward", "lrn_bwd"), (("lrn.norm1",), "forward", "lrn_fwd"),
        (("lrn.norm2",), "backward", "lrn_bwd"), (("lrn.norm2",), "forward", "lrn_fwd"),
    ]
    assert "tpu_custom_call" not in alexnet_steps["jax_numpy"]


def test_the_lrn_kernels_add_no_copy_of_an_lrn_sized_tensor(alexnet_steps):
    """The step holds no more ``copy`` or ``transpose`` instructions on a
    tensor of norm1's or norm2's number of elements, in any shape, than the
    ``jax.numpy`` form's step (three, all of norm2; the kernels' step keeps
    two of norm2's, where conv2 gives the tensor batch-minor and pool2 takes
    it channel-minor), none of norm1's (read as (N*H*W, C) rows it would add
    one), and every convolution keeps its layout."""
    import math
    import re

    def moves(text):
        shapes = re.findall(r" = \w+\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(", text)
        sizes = [math.prod(int(d) for d in s.split(",")) for s in shapes]
        return sorted(n for n in sizes if n in (_NORM1, _NORM2))

    def convolutions(text):
        return sorted(re.findall(r" = (\w+\[[\d,]+\]\{[^}]*\}) convolution\(", text))

    kernels, jax_numpy = alexnet_steps["kernels"], alexnet_steps["jax_numpy"]
    assert len(moves(kernels)) <= len(moves(jax_numpy)), (moves(kernels), moves(jax_numpy))
    assert _NORM1 not in moves(kernels)
    assert convolutions(kernels) == convolutions(jax_numpy)
