"""Compiled step: device milliseconds per step under the gated short
convolutions' scope (outermost ``attn.conv``), all passes: the two
projections, the gates and the taps, and the copies around them."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.outermost("attn.conv"))
