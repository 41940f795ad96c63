#!/usr/bin/env python
"""Reproduce the reference's core scientific claim: the τ-local SGD
communication/staleness tradeoff (SURVEY.md §1 — τ independent steps
per worker, then average; the knob SparkNet's architecture exists to
exploit).

Sweeps τ ∈ {1, 5, 25, 50} × dp ∈ {2, 8} running the zoo's LeNet on
deterministic synthetic MNIST-shaped batches (the env ships no real
datasets — SURVEY.md §0; LeNet is light enough on CPU that hundreds of
iterations per config fit in one sweep), and reports loss vs iteration
AND vs wall-clock, plus time-to-threshold.

Expected shape of the result (the paper's Figure): larger τ buys fewer
sync barriers, but pays a staleness penalty per iteration; the best
time-to-threshold sits at a moderate τ. On this *intra-host* virtual
mesh the sync is nearly free, so only the penalty side is directly
measurable; the benefit side is reported through the paper's own cost
model — total time = measured compute time + C × (iterations / τ) for
a per-sync cost C (the reference paid ~seconds per weight
broadcast+collect round on EC2). The table prints time-to-threshold
for C ∈ {0, 1, 5} s so the crossover is visible from measured curves.

Usage:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/tau_sweep.py --iters 300 --batch 64

Emits one JSON line per config:
  {"dp": D, "tau": T, "it_per_sec": R,
   "curve": [[iter, seconds, loss], ...]}
then a markdown summary table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _HERE)

# virtual CPU mesh, same forcing as tests/conftest.py: this sweep models
# sync cost from counts, it does not time a device
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

ZOO = os.path.join(_HERE, "sparknet_tpu", "models", "prototxt")


def synthetic_batches(global_bs: int, n_distinct: int = 20, seed: int = 0):
    """Deterministic cycle of fixed (data, label) batches: random but
    *memorisable*, so the loss curve separates optimisers that make
    per-iteration progress from ones that don't. MNIST-shaped for the
    LeNet net below (light enough on CPU that the sync barrier is a
    visible fraction of the step, as DCN would be on a real cluster)."""
    rng = np.random.default_rng(seed)
    batches = [
        {
            "data": rng.normal(size=(global_bs, 28, 28, 1)).astype(np.float32),
            "label": rng.integers(0, 10, global_bs).astype(np.int32),
        }
        for _ in range(n_distinct)
    ]
    while True:
        yield from batches


def run_config(dp: int, tau: int, iters: int, global_bs: int, record: int):
    from sparknet_tpu.parallel import ParallelSolver, make_mesh
    from sparknet_tpu.proto import caffe_pb

    sp = caffe_pb.load_solver(os.path.join(ZOO, "lenet_solver.prototxt"))
    sp.base_lr = 0.01
    sp.lr_policy = "fixed"
    sp.max_iter = iters + tau  # never trip the schedule's end
    mesh = make_mesh({"dp": dp}, jax.devices()[:dp])
    shapes = {"data": (global_bs, 28, 28, 1), "label": (global_bs,)}
    solver = ParallelSolver(
        sp, shapes, solver_dir=ZOO, mesh=mesh, mode="local", tau=tau
    )
    feed = synthetic_batches(global_bs)

    # first round carries the XLA compile; record the curve from t0 =
    # end of round 1 so configs compare on steady-state wall-clock
    m = solver.step(feed, tau)
    float(m["loss"])  # fence
    t0 = time.perf_counter()
    curve = [[solver.iter, 0.0, float(m["loss"])]]
    chunk = max(tau, record)
    while solver.iter < iters:
        n = min(chunk, iters - solver.iter)
        m = solver.step(feed, n)
        loss = float(m["loss"])  # fence (host sync)
        curve.append([solver.iter, round(time.perf_counter() - t0, 3), loss])
    it_per_sec = (curve[-1][0] - curve[0][0]) / max(curve[-1][1], 1e-9)
    return {
        "dp": dp, "tau": tau, "global_batch": global_bs,
        "it_per_sec": round(it_per_sec, 2), "curve": curve,
    }


def time_to(curve, threshold: float, tau: int = 1, sync_cost: float = 0.0):
    """First modeled wall-clock at which loss <= threshold:
    measured compute seconds + sync_cost per completed round."""
    it0 = curve[0][0]
    for it, sec, loss in curve:
        if loss <= threshold:
            rounds = (it - it0) / tau
            return sec + sync_cost * rounds
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--record", type=int, default=25)
    ap.add_argument("--taus", default="1,5,25,50")
    ap.add_argument("--dps", default="2,8")
    ap.add_argument("--threshold", type=float, default=1.8,
                    help="loss level for the time-to-threshold column")
    ap.add_argument("--sync-costs", default="0,1,5",
                    help="comma list of modeled per-sync costs (seconds)")
    args = ap.parse_args()
    taus = [int(t) for t in args.taus.split(",")]
    dps = [int(d) for d in args.dps.split(",")]

    results = []
    for dp in dps:
        for tau in taus:
            r = run_config(dp, tau, args.iters, args.batch, args.record)
            results.append(r)
            print(json.dumps(r), flush=True)

    costs = [float(c) for c in args.sync_costs.split(",")]
    cost_cols = " | ".join(f"t@C={c:g}s" for c in costs)
    print(f"\n| dp | tau | compute it/s | final loss @{args.iters} | "
          f"{cost_cols} |")
    print("|---" * (4 + len(costs)) + "|")
    for r in results:
        cells = []
        for c in costs:
            t = time_to(r["curve"], args.threshold, r["tau"], c)
            cells.append("-" if t is None else f"{t:.1f}")
        print(
            f"| {r['dp']} | {r['tau']} | {r['it_per_sec']} | "
            f"{r['curve'][-1][2]:.3f} | " + " | ".join(cells) + " |"
        )
    print(f"\n(t@C = modeled seconds to loss<={args.threshold}: measured "
          f"compute + C per sync round — the reference's EC2 broadcast+"
          f"collect cost the paper amortises with tau)")


if __name__ == "__main__":
    main()
