"""Kernels: device milliseconds per step in the grouped matrix products
over the experts held (``jax.lax.ragged_dot``: gate and up, down, and their
gradients), in every sparse layer."""

from benchmark.layers import decoder_ops


def read(run):
    return decoder_ops.expert_products_ms(run)
