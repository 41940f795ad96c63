"""Compiled step: device milliseconds per step in a prototxt net's LRN and
pooling layers (outermost scope ``lrn.<name>`` or ``pooling.<name>``), all
passes: what a fused LRN-and-pool pass would move."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.outermost("lrn.", "pooling."))
