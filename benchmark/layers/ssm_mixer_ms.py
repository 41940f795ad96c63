"""Compiled step: device milliseconds per step under the Mamba-2 mixers'
scope (outermost ``attn.ssm``), all passes: the two projections, the
convolution, the state-space scan, the gated norm and the copies around
them."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.outermost("attn.ssm"))
