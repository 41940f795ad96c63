"""Model FLOPs of one Granite 4.0-H Micro training step on packed documents,
and the operations and bytes its state-space scan's roofline divides by:
from shapes, so they read the same work whatever implements it.

Conventions (``benchmark/flops.py``): a multiply-add is two FLOPs; a matrix
product counts forward once and backward twice; nothing recomputed counts.

- The Mamba-2 recurrence, per token and head: the state's update ``delta x
  B^T`` and its read ``S C``, ``mamba_d_head x mamba_d_state``
  multiply-adds each forward (the decay and the ``D`` skip are elementwise
  and count nothing), twice that backward.  A chunked form does more
  arithmetic than this (the chunk's ``C B^T`` and its decays) and reads low
  against it; none can read above 100 %.
- The depthwise convolution: ``mamba_d_conv`` multiply-adds a channel and
  token.
- Attention counts the pairs the two masks leave, same document and ``j <=
  i``: two products forward (scores, context), four backward.  A batch's
  pairs are 0.45-1.75 x the traffic's mean, so the work is the **mean**
  step's: the batch times the mean pairs of a sequence of the seeded pool,
  the program's ``attn_pairs_pool`` gauge (``mellum2_flops.pool_pairs``);
  where a program has none, one unbroken document a sequence.
"""

from typing import Dict, Mapping, Sequence, Tuple

from benchmark.configs.mellum2_flops import pool_pairs, unbroken_pairs

Shapes = Mapping[str, Sequence[int]]
MAMBA, ATTENTION = "mamba", "attention"


def _kinds(config: Mapping):
    return config["layer_types"][: config["num_hidden_layers"]]


def matmul_macs_per_token(config: Mapping) -> Dict[str, float]:
    """Forward multiply-adds a token, by part, over the layers held."""
    h = config["hidden_size"]
    width = config["mamba_n_heads"] * config["mamba_d_head"]
    n, heads = config["mamba_d_state"], config["mamba_n_heads"]
    kv_width = config["num_key_value_heads"] * h // config["num_attention_heads"]
    macs = dict.fromkeys(
        ("mamba_projections", "mamba_convolutions", "mamba_recurrence",
         "attention_projections", "mlp", "head"), 0.0,
    )
    for kind in _kinds(config):
        if kind == MAMBA:
            macs["mamba_projections"] += h * (2 * width + 2 * n + heads) + width * h
            macs["mamba_convolutions"] += config["mamba_d_conv"] * (width + 2 * n)
            macs["mamba_recurrence"] += 2 * width * n
        else:
            macs["attention_projections"] += 2 * h * h + 2 * h * kv_width
        macs["mlp"] += 3 * h * config["shared_intermediate_size"]
    macs["head"] = h * config["vocab_size"]
    return macs


def attention_macs(config: Mapping, shapes: Shapes) -> float:
    """Forward multiply-adds of ONE product (scores, say) over the pairs of
    a mean batch, every attention layer, all heads."""
    b, s = shapes["input_ids"]
    full = pool_pairs("full")
    pairs = b * (unbroken_pairs(s) if full is None else full)
    layers = sum(kind == ATTENTION for kind in _kinds(config))
    return float(layers * pairs * config["hidden_size"])


def train_step(config: Mapping, shapes: Shapes) -> float:
    """Training FLOPs of a mean step on ``input_ids: (B, S)``."""
    b, s = shapes["input_ids"]
    matmuls = 3 * 2 * b * s * sum(matmul_macs_per_token(config).values())
    return float(matmuls + 2 * (2 + 4) * attention_macs(config, shapes))


def ssd_scan_work(config: Mapping, shapes: Shapes) -> Tuple[float, float]:
    """(FLOPs, bytes) a step of the Mamba-2 layers' recurrence, forward and
    backward: the state's update and read a token and head; ``x``, ``B``
    and ``C`` in bfloat16, ``delta`` and ``y`` in float32, and the gradient
    of each, moved once."""
    b, s = shapes["input_ids"]
    width = config["mamba_n_heads"] * config["mamba_d_head"]
    n = config["mamba_d_state"]
    layers = sum(kind == MAMBA for kind in _kinds(config))
    flops = 3 * 2 * b * s * layers * 2 * width * n
    per_token = 2 * width + 2 * 2 * n + 4 * config["mamba_n_heads"] + 4 * width
    return float(flops), float(2 * b * s * layers * per_token)
