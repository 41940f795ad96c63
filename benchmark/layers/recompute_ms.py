"""Compiled step: device milliseconds per step in instructions of the
recompute pass (``rematted_computation`` on their path), under any scope:
what the checkpoints cost in time."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, lambda _chain, pass_, _kernel: pass_ == "recompute")
