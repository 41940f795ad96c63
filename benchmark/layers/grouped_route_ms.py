"""Expert layer: device milliseconds per step in the grouped router of the
hybrid decoder's sparse layers (``hybrid_ops.grouped_route_ms``): scores,
the selection by groups and the weights, forward, recomputed forward and
backward.  The gathers, grouped products and scatter-adds that follow are
``held_experts_ffn``'s and are read in ``laguna_train_s8k``."""

from benchmark.layers import hybrid_ops


def read(run):
    return hybrid_ops.grouped_route_ms(run)
