"""Kernels: device milliseconds per step in the conv layers' gates and taps
(scope ``conv.short``): ``B * x``, the depthwise causal convolution with
its document mask and ``C * z``, all layers, forward, recomputed forward
and backward, whatever they lower to."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.under("conv.short"))
