"""Expert layer: device milliseconds per step under ``moe.route``
(``parallel/moe.held_experts_ffn``): router, top-k, the sorts of the slots."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.under("moe.route"))
