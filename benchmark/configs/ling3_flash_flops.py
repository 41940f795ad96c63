"""Model FLOPs of one Ling-3.0-flash training step, and the operations and
bytes its kernels' rooflines divide by: from shapes, so they read the same
work whatever implements it.

Conventions (``benchmark/flops.py``): a multiply-add is two FLOPs; a matrix
product counts forward once and backward twice; nothing recomputed counts.

- KDA's recurrence, per token and head: three products of ``d_k x d_v``
  multiply-adds forward (the state against the key, the rank-one update,
  the state against the query; the per-channel decay is elementwise and
  counts nothing) and twice that backward.  A chunked form does more
  arithmetic than this (the chunk matrices and the triangular inverse) and
  reads low against it; none can read above 100 %.
- MLA's attention counts the pairs the causal mask leaves, ``j <= i``:
  forward a score product over ``qk_nope + qk_rope`` and a context product
  over ``v_head_dim``, backward twice both.
- The expert products count the slots an even router sends to the experts
  held here, ``tokens * num_experts_per_tok * held / routed``.
"""

from typing import Dict, List, Mapping, Sequence, Tuple

Shapes = Mapping[str, Sequence[int]]


def layer_kinds(config: Mapping) -> List[Tuple[str, str]]:
    """(mixer, ffn) of each layer held: ``kda`` or ``mla``, ``dense`` or
    ``sparse``, from the published indices ``deployment.layers_kept``."""
    n = config["num_hidden_layers"]
    kept = config.get("deployment", {}).get("layers_kept", range(n))
    return [
        (
            "mla" if (i + 1) % config["layer_group_size"] == 0 else "kda",
            "dense" if i < config["first_k_dense_replace"] else "sparse",
        )
        for i in kept
    ]


def held_slots_per_token(config: Mapping) -> float:
    routed = config.get("deployment", {}).get(
        "num_experts_routed", config["num_experts"]
    )
    return config["num_experts_per_tok"] * config["num_experts"] / routed


def matmul_macs_per_token(config: Mapping) -> Dict[str, float]:
    """Forward multiply-adds a token, by part, over the layers held."""
    h, heads, d = (
        config["hidden_size"], config["num_attention_heads"], config["head_dim"]
    )
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    rank = config["kv_lora_rank"]
    routed = config.get("deployment", {}).get(
        "num_experts_routed", config["num_experts"]
    )
    macs = dict.fromkeys(
        ("kda_projections", "kda_convolutions", "kda_recurrence",
         "mla_projections", "dense_ffn", "router", "shared_expert", "experts",
         "head"), 0.0,
    )
    for mixer, ffn in layer_kinds(config):
        if mixer == "kda":
            # q, k, v, o, the decay gate and the output gate; beta
            macs["kda_projections"] += 6 * h * heads * d + h * heads
            macs["kda_convolutions"] += (
                3 * config["short_conv_kernel_size"] * heads * d
            )
            macs["kda_recurrence"] += 3 * heads * d * d
        else:
            macs["mla_projections"] += (
                h * heads * qk + h * (rank + config["qk_rope_head_dim"])
                + rank * heads * (config["qk_nope_head_dim"] + config["v_head_dim"])
                + heads * config["v_head_dim"] * h
            )
        if ffn == "sparse":
            macs["router"] += h * routed
            macs["shared_expert"] += (
                3 * h * config["moe_shared_expert_intermediate_size"]
                * config["num_shared_experts"]
            )
            macs["experts"] += (
                held_slots_per_token(config) * 3 * h * config["moe_intermediate_size"]
            )
        else:
            macs["dense_ffn"] += 3 * h * config["intermediate_size"]
    macs["head"] = h * config["vocab_size"]
    return macs


def mla_macs(config: Mapping, batch: int, seq: int) -> float:
    """Forward multiply-adds of the MLA layers' scores and context over the
    causal pairs, for the batch."""
    per_pair = (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"]
    )
    layers = sum(mixer == "mla" for mixer, _ffn in layer_kinds(config))
    pairs = seq * (seq + 1) // 2
    return float(layers * batch * config["num_attention_heads"] * pairs * per_pair)


def train_step(config: Mapping, shapes: Shapes) -> float:
    """Training FLOPs of one step on ``input_ids: (B, S)``."""
    b, s = shapes["input_ids"]
    matmuls = 3 * 2 * b * s * sum(matmul_macs_per_token(config).values())
    return float(matmuls + 3 * 2 * mla_macs(config, b, s))


def kda_scan_work(config: Mapping, shapes: Shapes) -> Tuple[float, float]:
    """(FLOPs, bytes) a step of the KDA layers' recurrence, forward and
    backward: the three products a token and head, and q, k, v and o in
    bfloat16, the log decay and beta in float32, and the gradient of each,
    moved once."""
    b, s = shapes["input_ids"]
    heads, d = config["num_attention_heads"], config["head_dim"]
    layers = sum(mixer == "kda" for mixer, _ffn in layer_kinds(config))
    flops = 3 * 2 * b * s * layers * 3 * heads * d * d
    per_token_head = 4 * 2 * d + 4 * d + 4
    return float(flops), float(2 * b * s * layers * heads * per_token_head)


def mla_attention_work(config: Mapping, shapes: Shapes) -> Tuple[float, float]:
    """(FLOPs, bytes) a step of the flash kernels (forward, dq, dkv) of the
    MLA layers: the products over the causal pairs; q, k and v read and o
    written forward, q, k, v, o and do read and dq, dk, dv written
    backward, each once, in bfloat16."""
    b, s = shapes["input_ids"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    layers = sum(mixer == "mla" for mixer, _ffn in layer_kinds(config))
    elements = (2 * qk + 2 * dv) + (2 * qk + 3 * dv + 2 * qk + dv)
    nbytes = 2 * b * s * layers * config["num_attention_heads"] * elements
    return 3 * 2 * mla_macs(config, b, s), float(nbytes)
