"""Expert layer: device milliseconds per step in the sparse layers'
operations other than the grouped products — router, top-k, sort, gather,
weighting, scatter-add: the latency- and bandwidth-bound part
(``decoder_ops.routing_ms``)."""

from benchmark.layers import decoder_ops


def read(run):
    return decoder_ops.routing_ms(run)
