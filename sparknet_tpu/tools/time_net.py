"""time_net — ``caffe time`` twin: benchmark a prototxt's train step.

Reports average forward, forward+backward(+update) step time and
throughput for a net/solver prototxt on the current backend, plus
XLA-cost-analysis FLOPs and MFU when the backend reports them.

    python -m sparknet_tpu.tools.time_net \
        --solver .../cifar10_quick_solver.prototxt [--batch-size N] \
        [--iters 50] [--bf16]
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

import jax
import jax.numpy as jnp


def synth_batch(shapes):
    """The synthetic batch every timing mode shares (same values, so the
    per-layer table and the whole-step numbers measure identical work)."""
    rng = np.random.default_rng(0)
    return {
        "data": jnp.asarray(rng.normal(size=shapes["data"]), jnp.float32),
        "label": jnp.asarray(
            rng.integers(0, 10, size=shapes["label"]), jnp.int32
        ),
    }


def time_solver(solver, shapes, iters: int = 50, warmup: int = 3):
    from ..utils.profiling import cost_numbers, device_peak_flops

    batch = synth_batch(shapes)

    def feed():
        while True:
            yield batch

    m = solver.step(feed(), warmup)
    float(m["loss"])  # device fence

    t0 = time.perf_counter()
    m = solver.step(feed(), iters)
    float(m["loss"])
    train_dt = (time.perf_counter() - t0) / iters

    # forward only (TEST-phase eval step), fenced once like the train
    # loop so the two numbers share a methodology
    m = solver._eval_step(solver.params, solver.state, batch)  # compile
    float(next(iter(m.values())))
    t0 = time.perf_counter()
    for _ in range(iters):
        m = solver._eval_step(solver.params, solver.state, batch)
    float(next(iter(m.values())))
    fwd_dt = (time.perf_counter() - t0) / iters

    # mirror Solver.step's batch layout: iter_size micro-batches stack
    # on a leading axis (and each timed step consumes iter_size * bs)
    iter_size = max(1, solver.sp.iter_size)
    flops_batch = batch
    if iter_size > 1:
        flops_batch = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * iter_size), batch
        )
    flops = cost_numbers(solver.lower_step(flops_batch).compile())[0]
    peak = device_peak_flops()
    items_per_step = shapes["data"][0] * iter_size
    out = {
        "platform": jax.devices()[0].platform,
        "batch": shapes["data"][0],
        "forward_ms": round(1000 * fwd_dt, 3),
        "train_step_ms": round(1000 * train_dt, 3),
        "items_per_sec": round(items_per_step / train_dt, 1),
    }
    if flops:
        out["train_tflops"] = round(flops / train_dt / 1e12, 2)
        if peak:
            out["mfu"] = round(flops / train_dt / peak, 4)
    return out


def time_per_layer(net, params, state, batch, iters: int = 10):
    """Per-layer forward/backward timings, like ``caffe time``'s layer
    table: each layer's ``apply`` is jitted and timed in isolation on
    its real input blobs (captured from one full forward), and its
    backward as the VJP w.r.t. inputs+params at the same point.  Each
    number is ``iters`` dispatches fenced once, so it includes the
    host's dispatch; the device's own share of a step is in the
    benchmark's trace (PERF.md §3)."""
    from ..nets.layers import DATA_LAYER_TYPES, LAYER_IMPLS, ApplyCtx
    from ..utils.profiling import cost_numbers

    blobs = dict(batch)
    rows = []
    for li, lp in enumerate(net.layers):
        if lp.type in DATA_LAYER_TYPES:
            continue
        impl = LAYER_IMPLS[lp.type]
        # a real per-layer key: Dropout and friends sample masks in
        # TRAIN mode and need one (rng=None would crash on them)
        ctx = ApplyCtx(
            train=True,
            rng=jax.random.fold_in(jax.random.PRNGKey(0), li),
            compute_dtype=net.compute_dtype,
        )
        inputs = [blobs[b] for b in lp.bottom]
        p = params.get(lp.name, {})
        st = state.get(lp.name)

        def fwd(p_, inputs_):
            outs, _ = impl.apply(lp, p_, st, inputs_, ctx)
            return outs

        # compile ONCE (AOT) and use the executable for both the timing
        # loop and cost analysis
        jfwd = jax.jit(fwd).lower(p, inputs).compile()
        outs = jfwd(p, inputs)
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(iters):
            outs = jfwd(p, inputs)
        jax.block_until_ready(outs)
        fwd_ms = 1000 * (time.perf_counter() - t0) / iters

        # cost analysis separates compute-bound from HBM-bound layers:
        # arithmetic intensity = FLOPs / bytes accessed (a layer far
        # below the device's FLOP:byte ratio is bandwidth-limited no
        # matter how its math is written)
        f, by = cost_numbers(jfwd)
        gflop = f / 1e9 if f else None
        gbyte = by / 1e9 if by else None

        bwd_ms = None
        # float outputs only: losses/metrics and feature maps; index
        # outputs (ArgMax) and no-output layers (Silence) have no VJP
        if outs and all(jnp.issubdtype(o.dtype, jnp.floating) for o in outs):
            fidx = [
                i for i, x in enumerate(inputs)
                if jnp.issubdtype(x.dtype, jnp.floating)
            ]

            def scalar(p_, finputs):
                full = list(inputs)
                for i, x in zip(fidx, finputs):
                    full[i] = x
                outs_ = fwd(p_, full)
                return sum(jnp.sum(o.astype(jnp.float32)) for o in outs_)

            if p or fidx:
                jbwd = jax.jit(jax.grad(scalar, argnums=(0, 1)))
                finputs = [inputs[i] for i in fidx]
                g = jbwd(p, finputs)
                jax.block_until_ready(g)
                t0 = time.perf_counter()
                for _ in range(iters):
                    g = jbwd(p, finputs)
                jax.block_until_ready(g)
                bwd_ms = 1000 * (time.perf_counter() - t0) / iters

        rows.append((lp.name, lp.type, fwd_ms, bwd_ms, gflop, gbyte))
        for top, out in zip(lp.top, outs):
            blobs[top] = out
    return rows


def main(argv=None):
    from ..proto import caffe_pb
    from ..solver.trainer import Solver

    ap = argparse.ArgumentParser(description="caffe-time twin")
    ap.add_argument("--solver", required=True)
    ap.add_argument("--batch-size", type=int, default=0)
    ap.add_argument("--crop", type=int, default=0,
                    help="input H=W (defaults to the net's data shape)")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--per-layer", action="store_true",
                    help="also print per-layer forward/backward ms "
                         "(caffe time's layer table)")
    args = ap.parse_args(argv)

    sp = caffe_pb.load_solver(args.solver)
    solver_dir = os.path.dirname(os.path.abspath(args.solver))
    from ..apps.cifar_app import _batch_size, _data_layer
    from ..solver.trainer import resolve_model_path

    net_path = sp.net or sp.train_net
    if net_path:
        net_param = caffe_pb.load_net(resolve_model_path(net_path, solver_dir))
    elif sp.net_param is not None:  # inline net_param {...}
        net_param = sp.net_param
    else:
        raise ValueError(f"{args.solver}: no net/train_net path or net_param")
    layer = _data_layer(net_param, "TRAIN")
    bs = args.batch_size or _batch_size(layer, 32)
    crop = args.crop
    if not crop:
        tp = layer.transform_param if layer else None
        crop = int(tp.get("crop_size", 0)) if tp else 0
    crop = crop or 32
    shapes = {"data": (bs, crop, crop, 3), "label": (bs,)}
    solver = Solver(
        sp, shapes, net_param=net_param, solver_dir=solver_dir,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )
    out = time_solver(solver, shapes, iters=args.iters)
    for k, v in out.items():
        print(f"{k}: {v}")
    if args.per_layer:
        batch = synth_batch(shapes)
        rows = time_per_layer(
            solver.train_net, solver.params, solver.state, batch,
            iters=max(3, args.iters // 5),
        )
        print(f"{'layer':<28}{'type':<22}{'fwd ms':>10}{'bwd ms':>10}"
              f"{'GFLOP':>9}{'GB':>8}{'F/B':>7}")
        for name, ltype, fwd_ms, bwd_ms, gflop, gbyte in rows:
            f = f"{fwd_ms:.3f}"
            b = f"{bwd_ms:.3f}" if bwd_ms is not None else "-"
            gf = f"{gflop:.2f}" if gflop is not None else "-"
            gb = f"{gbyte:.3f}" if gbyte is not None else "-"
            ai = (f"{gflop / gbyte:.0f}"
                  if gflop is not None and gbyte else "-")
            print(f"{name:<28}{ltype:<22}{f:>10}{b:>10}"
                  f"{gf:>9}{gb:>8}{ai:>7}")
        out["per_layer"] = [
            {"layer": n, "type": t, "forward_ms": round(f, 3),
             "backward_ms": None if b is None else round(b, 3),
             "gflop": None if gf is None else round(gf, 3),
             "gbytes": None if gb is None else round(gb, 4)}
            for n, t, f, b, gf, gb in rows
        ]
    return out


if __name__ == "__main__":
    main()
