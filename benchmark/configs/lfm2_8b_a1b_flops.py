"""Model FLOPs of one LFM2-8B-A1B training step on packed documents, and the
operations and bytes its short convolution's roofline divides by: from
shapes, so they read the same work whatever implements it.

Conventions (``benchmark/flops.py``): a multiply-add is two FLOPs; a matrix
product counts forward once and backward twice; nothing recomputed counts.

- The layers are the published ones ``deployment.layers_kept`` names, each
  of its published kind; one below ``num_dense_layers`` has the dense
  SwiGLU, the others the router and the experts.
- The short convolution: ``conv_L_cache`` multiply-adds a channel and token
  (its two gates are elementwise and count nothing, as a norm does).
- The experts' work is an even router's: a token's ``num_experts_per_tok``
  slots times the share of the routed experts held here.
- Attention counts the pairs the two masks leave, same document and ``j <=
  i``: two products forward (scores, context), four backward.  A batch's
  pairs are 0.45-1.75 x the traffic's mean, so the work is the **mean**
  step's: the batch times the mean pairs of a sequence of the seeded pool,
  the program's ``attn_pairs_pool`` gauge (``mellum2_flops.pool_pairs``);
  where a program has none, one unbroken document a sequence.
"""

from typing import Dict, List, Mapping, Sequence, Tuple

from benchmark.configs.mellum2_flops import pool_pairs, unbroken_pairs

Shapes = Mapping[str, Sequence[int]]
CONV, FULL = "conv", "full_attention"


def kept(config: Mapping) -> List[Tuple[int, str]]:
    """(published index, kind) of each layer held."""
    n = config["num_hidden_layers"]
    indices = config.get("deployment", {}).get("layers_kept", range(n))
    return [(i, config["layer_types"][i]) for i in indices]


def _routed(config: Mapping) -> int:
    return config.get("deployment", {}).get("num_experts_routed", config["num_experts"])


def matmul_macs_per_token(config: Mapping) -> Dict[str, float]:
    """Forward multiply-adds a token outside the experts, by part, over the
    layers held."""
    h = config["hidden_size"]
    kv_width = config["num_key_value_heads"] * h // config["num_attention_heads"]
    macs = dict.fromkeys(
        ("conv_projections", "conv_taps", "attention_projections", "dense_mlp",
         "router", "head"), 0.0,
    )
    for index, kind in kept(config):
        if kind == CONV:
            macs["conv_projections"] += h * 3 * h + h * h
            macs["conv_taps"] += config["conv_L_cache"] * h
        else:
            macs["attention_projections"] += 2 * h * h + 2 * h * kv_width
        if index < config["num_dense_layers"]:
            macs["dense_mlp"] += 3 * h * config["intermediate_size"]
        else:
            macs["router"] += h * _routed(config)
    macs["head"] = h * config["vocab_size"]
    return macs


def held_slots(config: Mapping, shapes: Shapes) -> float:
    """Slots an even router sends to the experts held here in a step,
    summed over the sparse layers."""
    b, s = shapes["input_ids"]
    sparse = sum(index >= config["num_dense_layers"] for index, _ in kept(config))
    share = config["num_experts"] / _routed(config)
    return sparse * b * s * config["num_experts_per_tok"] * share


def attention_macs(config: Mapping, shapes: Shapes) -> float:
    """Forward multiply-adds of ONE product (scores, say) over the pairs of
    a mean batch, every attention layer, all heads."""
    b, s = shapes["input_ids"]
    full = pool_pairs("full")
    pairs = b * (unbroken_pairs(s) if full is None else full)
    layers = sum(kind == FULL for _, kind in kept(config))
    return float(layers * pairs * config["hidden_size"])


def train_step(config: Mapping, shapes: Shapes) -> float:
    """Training FLOPs of a mean step on ``input_ids: (B, S)``."""
    b, s = shapes["input_ids"]
    dense = b * s * sum(matmul_macs_per_token(config).values())
    experts = held_slots(config, shapes) * 3 * config["hidden_size"] * config["moe_intermediate_size"]
    return float(2 * 3 * (dense + experts) + 2 * (2 + 4) * attention_macs(config, shapes))


def short_conv_work(config: Mapping, shapes: Shapes) -> Tuple[float, float]:
    """(FLOPs, bytes) a step of the conv layers' gated short convolution,
    forward and backward: the taps' multiply-adds a channel and token; the
    gates ``B`` and ``C``, the input ``x`` and the output ``y`` in bfloat16,
    and the gradient of each, moved once."""
    b, s = shapes["input_ids"]
    h = config["hidden_size"]
    layers = sum(kind == CONV for _, kind in kept(config))
    flops = 3 * 2 * b * s * layers * config["conv_L_cache"] * h
    return float(flops), float(2 * 2 * b * s * layers * 4 * h)
