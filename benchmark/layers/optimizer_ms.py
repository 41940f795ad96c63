"""Compiled step: device milliseconds per step under the ``optimizer`` scope
(``solver/trainer.make_train_step``): the update of every parameter."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(run, scope_ops.under("optimizer"))
