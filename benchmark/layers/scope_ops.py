"""What the scope-read metrics share: a traced run's device time summed by
the program's own scopes.

The program names the parts of its step as jax traces it
(``sparknet_tpu.utils.profiling.scope``: ``attn.full``, ``moe.experts``,
``optimizer``, a prototxt layer as ``convolution.conv1``), and the compiled
step's text says which instruction lies under which scope and in which pass
(forward, backward, recompute).  ``profiling.step_scopes()`` hands out that
table for the step program the harness lowered (``run.step_program``, before
any reader runs), and ``profiling.by_scope`` sums the trace's seconds per
operation by it.  Nothing here matches a shape.

The rule of every reader over this file: None where the run has no trace or
the program publishes no table (an older program has no ``step_scopes``);
otherwise the sum, which may be 0.0.  The whole table goes once on
``bench:`` lines, with one more where under 95 % of the device's time lies
under a declared scope.  This file is no metric's reader.
"""

_KEY = "scope_time"  # where a run keeps its sums, made once


def _say(message):
    print(f"bench: {message}", flush=True)


def scope_time(run):
    """``profiling.by_scope`` of the run's traced steps, or None."""
    trace = run.get("trace")
    if not trace:
        return None
    if _KEY not in run:
        run[_KEY] = _reduce(trace)
    return run[_KEY]


def _reduce(trace):
    try:
        from sparknet_tpu.utils import profiling
    except ImportError:
        return None
    step_scopes = getattr(profiling, "step_scopes", None)
    table = step_scopes() if step_scopes else None
    if table is None:
        _say("scopes: the program publishes no scope table of its step")
        return None
    reduced = profiling.by_scope(trace["op_seconds"], table, trace["steps"])
    _say(
        f"scopes: device time by scope chain, ms a step over "
        f"{trace['steps']} traced steps of {trace['program']}"
    )
    for line in profiling.scope_lines(reduced, depth=None):
        _say(f"scopes: {line}")
    if reduced["coverage"] < 95.0:
        _say(
            f"scopes: only {reduced['coverage']:.2f}% of the device's time "
            f"lies under a declared scope (unscoped {reduced['unscoped']:.3f} "
            f"ms, unjoined {reduced['unjoined']:.3f} ms a step)"
        )
    return reduced


def ms(run, keep):
    """Device ms a step in the instructions whose ``(chain, pass, kernel)``
    ``keep`` takes: None without a table, else the sum, 0.0 included."""
    reduced = scope_time(run)
    if reduced is None:
        return None
    return sum(
        value for (chain, pass_, kernel), value in reduced["rows"].items()
        if keep(chain, pass_, kernel)
    )


def under(*names):
    """``keep`` for a chain that holds any of ``names``."""
    return lambda chain, _pass, _kernel: any(n in chain for n in names)


def outermost(*prefixes):
    """``keep`` for a chain whose outermost scope starts with a prefix."""
    return lambda chain, _pass, _kernel: chain[0].startswith(prefixes)
