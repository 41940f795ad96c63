"""Compiled step: device milliseconds per step under the token mixers' scopes
(outermost scope ``attn``, ``attn.full``, ``attn.window``, ``attn.kda``,
``attn.mla``): kernels, projections, rotary and the copies around them."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.outermost("attn"))
