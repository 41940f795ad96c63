"""Expert layer: device milliseconds per step under ``moe.rows`` (inside
``moe.experts``): the row gathers, the weighting and masking of a chunk's
rows, ``moe_combine`` and the scatter-adds."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.under("moe.rows"))
