"""check_determinism — bitwise replay verification of a training run.

The reference inherits Spark's execution model, where the failure/race
story is "recompute from lineage and get the same answer". The
TPU-native framework makes the same promise through functional purity:
every batch, shuffle, augmentation, and dropout mask derives from
explicit seeds, so replaying N steps from the same state must reproduce
the weights BIT FOR BIT. This tool enforces that promise — it is the
race detector for this execution model (a nondeterministic data race,
an unseeded RNG, or a host-order dependence shows up as a bitwise
mismatch).

    python -m sparknet_tpu.tools.check_determinism \
        --solver solver.prototxt [--iters 5] [--synthetic] [--restore S]

Exit code 0 and "deterministic: true" when the replay matches; exit 1
with the first mismatching parameter otherwise.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def _run(args, iters: int):
    """One fresh build+train of `iters` steps; returns final params."""
    import jax

    from ..apps import cifar_app

    solver, train_feed, _test_feed = cifar_app.build(args)
    if args.restore:
        solver.restore(args.restore, feed=train_feed)
    solver.step(train_feed, iters)
    return jax.device_get(solver.params)


def compare_trees(a, b):
    """[(path, max_abs_diff)] over mismatching leaves; [] if bitwise
    equal. A leaf present in only one tree (structural divergence) is
    itself a mismatch, reported with diff=inf."""
    bad = []
    for layer in sorted(set(a) | set(b)):
        pa, pb = a.get(layer, {}), b.get(layer, {})
        for name in sorted(set(pa) | set(pb)):
            if name not in pa or name not in pb:
                bad.append((f"{layer}/{name}", float("inf")))
                continue
            x, y = np.asarray(pa[name]), np.asarray(pb[name])
            if x.shape != y.shape:
                bad.append((f"{layer}/{name}", float("inf")))
            elif x.tobytes() != y.tobytes():
                diff = float(
                    np.abs(x.astype(np.float64) - y.astype(np.float64)).max()
                )
                bad.append((f"{layer}/{name}", diff))
    return bad


def main(argv=None) -> int:
    from ..apps import cifar_app

    ap = argparse.ArgumentParser(
        prog="check_determinism", parents=[cifar_app.arg_parser()],
        conflict_handler="resolve",
    )
    ap.add_argument("--iters", type=int, default=5,
                    help="steps to run in each replay")
    args = ap.parse_args(argv)
    args.max_iter = None  # the replay length is --iters, not the solver's

    first = _run(args, args.iters)
    second = _run(args, args.iters)
    bad = compare_trees(first, second)
    if not bad:
        print(f"deterministic: true ({args.iters} steps replayed bitwise)")
        return 0
    print("deterministic: FALSE — mismatching parameters:")
    for path, diff in bad[:10]:
        print(f"  {path}: max|Δ|={diff:.3e}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
