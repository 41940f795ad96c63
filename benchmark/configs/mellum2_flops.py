"""Model FLOPs of one Mellum2 training step on packed documents, and the
operations and bytes its flash kernels' roofline divides by.

Conventions (``benchmark/flops.py``): a multiply-add is two FLOPs; a matrix
product counts forward once and backward twice; nothing recomputed counts.
Attention counts only the pairs the three masks leave — same document, ``j <=
i`` and, on sliding layers, ``i - j < sliding_window``: two products forward
(scores, context), four backward.

A batch's pairs are 0.45-1.75 x the traffic's mean, so the work here is the
**mean** step's, to be set against a mean time over the traced steps: the
batch times the mean pairs of a sequence of the seeded pool, which the
program counts once from the pool's documents at set-up and keeps as the
registry's ``attn_pairs_pool`` gauges (``apps/lm_app.packing_note``); the
experts' work is an even router's, a token's ``num_experts_per_tok`` slots
times the share of the routed experts held here.  (The program's per-step
counters ``attn_pairs_full``, ``attn_pairs_window`` and ``moe_slots_held``
say what one step had; the harness cannot sum them over the traced steps.)
Where a program has no such gauge (no packed feed was built), the pairs are
those of one unbroken document a sequence.  A kernel that masks where it
could skip, or recomputes, reads low against these; none can read above
100 %.
"""

from typing import Dict, Mapping, Optional, Sequence, Tuple

Shapes = Mapping[str, Sequence[int]]
FULL, SLIDING = "full_attention", "sliding_attention"


def _kinds(config: Mapping):
    return config["layer_types"][: config["num_hidden_layers"]]


def _routed(config: Mapping) -> int:
    return config.get("deployment", {}).get(
        "num_experts_routed", config["num_experts"]
    )


def unbroken_pairs(seq: int, window: int = None) -> int:
    """(query, key) pairs of one sequence that is one document."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def pool_pairs(kind: str) -> Optional[float]:
    """The program's ``attn_pairs_pool`` gauge of ``kind`` (``full`` or
    ``window``): mean pairs a sequence of the packed pool in one layer of
    that kind.  None where the program has no such gauge."""
    try:
        from sparknet_tpu.telemetry.registry import REGISTRY
    except ImportError:
        return None
    series = REGISTRY.families().get("attn_pairs_pool", {}).get("series", {})
    gauge = series.get((("kind", kind),))
    return gauge.snapshot()["value"] if gauge else None


def seen_pairs(config: Mapping, shapes: Shapes) -> Dict[str, float]:
    """Pairs of a mean batch in ONE layer of each kind: the batch times the
    pool's mean sequence, else what unbroken sequences would give."""
    b, s = shapes["input_ids"]
    full, window = pool_pairs("full"), pool_pairs("window")
    return {
        FULL: b * (unbroken_pairs(s) if full is None else full),
        SLIDING: b * (
            unbroken_pairs(s, config["sliding_window"])
            if window is None else window
        ),
    }


def held_slots(config: Mapping, shapes: Shapes) -> float:
    """Slots an even router sends to the experts held here in a step,
    summed over the layers."""
    b, s = shapes["input_ids"]
    share = config["num_experts"] / _routed(config)
    return config["num_hidden_layers"] * b * s * config["num_experts_per_tok"] * share


def matmul_macs_per_token(config: Mapping) -> Dict[str, float]:
    """Forward multiply-adds a token outside the experts, over the layers
    held: attention's four projections, the router, the head."""
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    layers = config["num_hidden_layers"]
    return {
        "attention_projections": layers * (2 * h * heads * d + 2 * h * kv * d),
        "router": layers * h * _routed(config),
        "head": h * config["vocab_size"],
    }


def attention_macs(config: Mapping, pairs: Mapping[str, float]) -> float:
    """Multiply-adds of ONE product (scores, say) over the seen pairs of
    every layer, all heads."""
    per_pair = config["num_attention_heads"] * config["head_dim"]
    return float(sum(pairs[kind] for kind in _kinds(config)) * per_pair)


def train_step(config: Mapping, shapes: Shapes) -> float:
    """Training FLOPs of a mean step on ``input_ids: (B, S)``."""
    b, s = shapes["input_ids"]
    dense = b * s * sum(matmul_macs_per_token(config).values())
    experts = held_slots(config, shapes) * 3 * config["hidden_size"] * config["moe_intermediate_size"]
    attention = (2 + 4) * attention_macs(config, seen_pairs(config, shapes))
    return float(2 * (3 * (dense + experts) + attention))


def doc_attention_work(config: Mapping, shapes: Shapes) -> Tuple[float, float]:
    """(FLOPs, bytes) a mean step of the flash kernels (forward, dq, dkv) of
    all layers: six products over :func:`seen_pairs`; q, k and v read and o
    written forward, q, k, v, o and do read and dq, dk, dv written
    backward, each once, in bfloat16 (K and V once a KV head)."""
    b, s = shapes["input_ids"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    flops = 2 * 6 * attention_macs(config, seen_pairs(config, shapes))
    tensors = config["num_hidden_layers"] * (6 * heads + 6 * kv)
    return flops, float(2 * b * s * config["head_dim"] * tensors)
