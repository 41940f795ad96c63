"""Kernels: device milliseconds per step in the Mamba-2 layers' chunked
state-space scan (scope ``ssd.scan``), all layers, forward, recomputed
forward and backward, whatever it lowers to."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.under("ssd.scan"))
