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
    """One sparse layer's held experts at a cell's shapes, the kernel
    forced as a TPU takes it: the products are XLA's grouped ones, forward
    and backward; a chunk's rows reach their tokens through ``moe_combine``
    in the forward pass (the backward pass's scatter-add stays)."""
    from sparknet_tpu.parallel.moe import held_experts_ffn, route_sigmoid

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
    assert text.count("ragged-dot") >= 6  # gate+up and down, and both gradients of each
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
