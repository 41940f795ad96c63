"""The comparison that decides whether the system's forward pass is right.

Before any step is taken, the first batch is cut into (up to) eight groups
of examples and the loss of each group is computed twice: by the system's
own network, in the compute type and with the kernels it is served with,
and by the configuration's plain reference (``configs/<name>_reference.py``:
``jax.numpy``, float32, no kernels, no code of the program) under full
matmul precision.  Every group has to agree: rounding errors are random, so
one mean over the whole batch can agree by chance where eight do not.  A configuration file
names its reference, the gain and the tolerance: ``"reference": {"forward":
"module:loss", "weight_gain": ..., "abs_tolerance": ...}``.

Both sides run on the same *shaken* copy of the seed's weights, not on the
weights themselves.  At the seed the loss says almost nothing about the
network: AlexNet's gaussian 0.01 fillers shrink the signal layer by layer
until the logits differ by 0.005 and every loss reads ln(1000) = 6.908
within 0.007, so constant logits, 8-bit arithmetic or a dropped layer
would pass any tolerance that bfloat16 passes; BERT's attention scores are
so small that the softmax is flat.  Multiplying every matrix by the gain
brings the logits (and BERT's scores) to a spread of a few units, where
the loss moves with every layer; shaking the vectors by 0.1 takes the
biases off 0 and the norm scales off 1, where a reference that wired them
wrongly would still agree.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List

import jax

GROUPS = 8


def shaken(params, gain: float):
    """The parameter tree with every matrix, filter bank and embedding table
    (two or more axes) multiplied by ``gain`` and every vector (biases, norm
    scales) moved by 0.1 x a standard normal drawn from a fixed key, leaf by
    leaf in the tree's own order.  Deterministic, so both sides of the
    comparison make the same copy inside their own program."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        gain * x if x.ndim >= 2
        else x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)
    ])


def compare(
    solver, batch, plain: Callable, gain: float, tolerance: float
) -> Dict[str, Any]:
    """``plain`` is the reference's ``loss(params, batch)``; the system's
    side is the dropout-free forward of its training net, as
    ``solver.trainer.make_eval_step`` runs it.  The tolerance is absolute
    and the configuration's own: seven or more standard deviations of the
    differences seen on the chip between the bfloat16 system and the
    float32 reference, and under what 8-bit weights or one skipped layer
    move the loss (``tests/benchmark`` holds both to it)."""
    net = solver.train_net
    size = len(next(iter(batch.values())))
    groups = math.gcd(size, GROUPS)
    grouped = jax.tree_util.tree_map(
        lambda x: x.reshape(groups, size // groups, *x.shape[1:]), batch
    )

    def system_losses(params, state, grouped):
        params = shaken(params, gain)

        def one(group):
            blobs, _ = net.apply(params, state, group, train=False, rng=None)
            return net.loss_and_metrics(blobs)[0]

        return jax.lax.map(one, grouped)

    def plain_losses(params, grouped):
        params = shaken(params, gain)
        return jax.lax.map(lambda group: plain(params, group), grouped)

    def run_once(fn, *args) -> List[float]:
        # compiled ahead of time and dropped: neither program stays on the
        # device beside a step that fills it
        return [float(x) for x in jax.jit(fn).lower(*args).compile()(*args)]

    system = run_once(system_losses, solver.params, solver.state, grouped)
    with jax.default_matmul_precision("highest"):
        reference = run_once(plain_losses, solver.params, grouped)
    worst = max(abs(s - r) for s, r in zip(system, reference))
    return {
        "system_losses": system,
        "reference_losses": reference,
        "abs_diff": worst,
        "weight_gain": gain,
        "abs_tolerance": tolerance,
        "ok": worst <= tolerance,  # a nan is not
    }
