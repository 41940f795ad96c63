"""Kernels: device milliseconds per step in the KDA layers' gated
delta-rule scan, all layers, forward, recomputed forward and backward,
whatever it lowers to (``hybrid_ops.kda_scan_ms``)."""

from benchmark.layers import hybrid_ops


def read(run):
    return hybrid_ops.kda_scan_ms(run)
