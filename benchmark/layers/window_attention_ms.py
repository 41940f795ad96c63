"""Kernels: device milliseconds per step in the Pallas flash kernels
(forward, dq, dkv) of the sliding-window layers, told by their head count
(``decoder_ops.attention_ms``)."""

from benchmark.layers import decoder_ops


def read(run):
    return decoder_ops.attention_ms(run, "sliding_attention")
