"""Compiled step: the share of the traced steps' device time, containers
left out, that lies in instructions under a scope the program declared
(``layers/scope_ops.py``): what the scope-read metrics can speak of."""

from benchmark.layers import scope_ops


def read(run):
    reduced = scope_ops.scope_time(run)
    return None if reduced is None else reduced["coverage"]
