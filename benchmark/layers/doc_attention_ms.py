"""Kernels: device milliseconds per step in the Pallas flash kernels
(forward, the recomputed forward, dq, dkv) on packed documents, every layer
of a decoder whose window and full layers share one head count
(``packed_ops.doc_attention_ms``)."""

from benchmark.layers import packed_ops


def read(run):
    return packed_ops.doc_attention_ms(run)
