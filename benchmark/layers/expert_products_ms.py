"""Expert layer: device milliseconds per step under ``moe.experts`` and not
under ``moe.rows``: the grouped products and their activation."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(
        run, lambda chain, _pass, _kernel: "moe.experts" in chain
        and "moe.rows" not in chain,
    )
