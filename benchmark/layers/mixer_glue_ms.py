"""Compiled step: the token mixers' device milliseconds per step
(``mixer_ms``) outside their Pallas kernels: projections, rotary, norms and
copies around the flash kernels."""

from benchmark.layers import scope_ops


def read(run):
    mixer = scope_ops.outermost("attn")
    return scope_ops.ms(
        run, lambda chain, pass_, kernel: not kernel and mixer(chain, pass_, kernel)
    )
