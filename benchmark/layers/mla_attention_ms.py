"""Kernels: device milliseconds per step in the Pallas flash kernels
(forward, dq, dkv) of the MLA layers, told by their query tensor
``[B, H, S, qk_nope + qk_rope]`` (``hybrid_ops.mla_attention_ms``)."""

from benchmark.layers import hybrid_ops


def read(run):
    return hybrid_ops.mla_attention_ms(run)
