"""Model FLOPs of one Laguna-XS.2 training step, and the operations and
bytes its kernels' rooflines divide by: from shapes (and, for the grouped
products, the step's own count of held slots), so they read the same work
whatever implements it.

Conventions (``benchmark/flops.py``): a multiply-add is two FLOPs; a matrix
product counts forward once and backward twice; nothing recomputed counts.
Attention counts only the pairs the mask leaves — ``j <= i`` on full layers,
and ``i - j < sliding_window`` besides on sliding ones: two products forward
(scores, context), four backward.  A kernel that masks where it could skip,
or recomputes, reads low against these; none can read above 100 %.  The
model FLOPs of a step count the slots an even router sends to the experts
held here, ``tokens * num_experts_per_tok * held / routed``; the grouped
products' roofline counts the slots the traced step really held.
"""

from typing import Dict, Mapping, Sequence, Tuple

Shapes = Mapping[str, Sequence[int]]
SLIDING = "sliding_attention"


def _layers(config: Mapping):
    n = config["num_hidden_layers"]
    return zip(
        config["layer_types"][:n], config["mlp_layer_types"][:n],
        config["num_attention_heads_per_layer"][:n],
    )


def seen_pairs(seq: int, window: int = None) -> int:
    """(query, key) pairs of one head and sequence with ``j <= i`` and,
    given a window, ``i - j < window``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def held_slots_per_token(config: Mapping) -> float:
    routed = config.get("deployment", {}).get(
        "num_experts_routed", config["num_experts"]
    )
    return config["num_experts_per_tok"] * config["num_experts"] / routed


def matmul_macs_per_token(config: Mapping) -> Dict[str, float]:
    """Forward multiply-adds a token, by part, over the layers held."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    routed = config.get("deployment", {}).get(
        "num_experts_routed", config["num_experts"]
    )
    macs = dict.fromkeys(
        ("attention_projections", "dense_ffn", "router", "shared_expert",
         "experts", "head"), 0.0,
    )
    for _kind, mlp, heads in _layers(config):
        macs["attention_projections"] += 2 * h * heads * d + 2 * h * kv * d
        if mlp == "sparse":
            macs["router"] += h * routed
            macs["shared_expert"] += 3 * h * config["shared_expert_intermediate_size"]
            macs["experts"] += (
                held_slots_per_token(config) * 3 * h * config["moe_intermediate_size"]
            )
        else:
            macs["dense_ffn"] += 3 * h * config["intermediate_size"]
    macs["head"] = h * config["vocab_size"]
    return macs


def attention_macs(config: Mapping, batch: int, seq: int, kind: str) -> float:
    """Multiply-adds of ONE product (scores, say) over the seen pairs of
    every layer of ``kind``, for the batch."""
    window = config["sliding_window"] if kind == SLIDING else None
    return float(sum(
        batch * heads * seen_pairs(seq, window) * config["head_dim"]
        for layer_kind, _mlp, heads in _layers(config) if layer_kind == kind
    ))


def train_step(config: Mapping, shapes: Shapes) -> float:
    """Training FLOPs of one step on ``input_ids: (B, S)``."""
    b, s = shapes["input_ids"]
    matmuls = 3 * 2 * b * s * sum(matmul_macs_per_token(config).values())
    attention = 2 * (2 + 4) * sum(
        attention_macs(config, b, s, kind)
        for kind in ("full_attention", SLIDING)
    )
    return float(matmuls + attention)


def attention_kernels_work(
    config: Mapping, shapes: Shapes, kind: str
) -> Tuple[float, float]:
    """(FLOPs, bytes) a step of the flash kernels (forward, dq, dkv) of the
    layers of ``kind``: six products over the seen pairs; q, k and v read
    and o written forward, q, k, v, o and do read and dq, dk, dv written
    backward, each once, in bfloat16 (grouped K and V counted once, not per
    query head)."""
    b, s = shapes["input_ids"]
    d, kv = config["head_dim"], config["num_key_value_heads"]
    flops = 2 * 6 * attention_macs(config, b, s, kind)
    tensors = sum(
        6 * heads + 6 * kv
        for layer_kind, _mlp, heads in _layers(config) if layer_kind == kind
    )
    return flops, float(2 * b * s * d * tensors)


def expert_products_work(
    config: Mapping, shapes: Shapes, slots: float = None
) -> Tuple[float, float]:
    """(FLOPs, bytes) a step of the grouped products over the experts held:
    ``slots`` held slots, summed over the sparse layers (the step's own
    count; an even router's share where not given), through gate, up and
    down, forward and twice backward; each sparse layer's held weights
    read forward and for the input's gradient and their gradient written,
    and each slot's rows read or written once per product, in bfloat16."""
    b, s = shapes["input_ids"]
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    sparse = sum(mlp == "sparse" for _k, mlp, _h in _layers(config))
    if slots is None:
        slots = sparse * b * s * held_slots_per_token(config)
    flops = 3 * 2 * slots * 3 * h * f
    weights = sparse * config["num_experts"] * 3 * h * f
    rows = slots * (h + 2 * f + f + h)
    return float(flops), float(2 * 3 * (weights + rows))
