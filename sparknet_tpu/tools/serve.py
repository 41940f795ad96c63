"""serve — batched inference over a deploy prototxt, single process or
a replicated tier.

The caffe-era spelling of a model server: point it at a zoo deploy
net plus trained weights and it holds the compiled executables
resident, micro-batching a request stream through them.

    # one process (engine + batcher + HTTP)
    python -m sparknet_tpu.tools.serve \
        --model deploy.prototxt --weights model.npz --port 8080 \
        [--buckets 1,8,32] [--batch-mode continuous|fill] \
        [--compile-cache DIR] [--snapshot-watch TARGET] [--data-cache NS]

    # the production shape: a front router over N replica processes
    python -m sparknet_tpu.tools.serve \
        --model deploy.prototxt --weights model.npz --port 8080 \
        --replicas 2 --compile-cache /var/cache/sparknet \
        --snapshot-watch runs/cifar/snap

With ``--replicas N`` the process becomes a **router**
(``serve/router.py``): it spawns N engine replicas (ephemeral ports,
discovered via portfiles), load-balances ``/classify`` by least
outstanding requests, retries a dying replica's in-flight requests on
a peer, respawns dead replicas under per-replica restart budgets
(``supervise/pool.py``), and rolls weight hot-swaps one replica at a
time.  The HTTP surface is identical either way — clients cannot tell
one process from a tier (docs/SERVING.md).

Weights may be a ``.caffemodel``, a ``.npz`` WeightCollection, or a
full ``.solverstate.npz`` training snapshot (params + BN stats are
extracted). ``--bench N`` skips the HTTP server and instead runs the
offline closed-loop load generator for N requests, printing one
JSON record — the serving twin of training img/s.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None):
    from ..utils import compile_cache

    compile_cache.enable()
    from ..serve.replica import add_engine_args

    ap = argparse.ArgumentParser(
        prog="serve", description="batched deploy-net inference server"
    )
    add_engine_args(ap)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument(
        "--replicas", type=int, default=0, metavar="N",
        help="run as a router over N engine-replica child processes "
             "(0: single process)",
    )
    ap.add_argument(
        "--quant-ab", type=float, default=0.0, metavar="FRAC",
        help="router mode: live quantization A/B — odd-indexed "
             "replicas serve the --quant variant, even stay f32, and "
             "FRAC of /classify traffic prefers the quantized group "
             "(docs/QUANTIZATION.md)",
    )
    ap.add_argument(
        "--autoscale-max", type=int, default=0, metavar="N",
        help="router mode: enable the autoscale control loop "
             "(autoscale/controller.py) — --replicas is the floor, N "
             "the ceiling; 0 disables autoscaling (static width)",
    )
    ap.add_argument(
        "--admission", choices=["auto", "on", "off"], default="auto",
        help="router mode: per-class SLO admission control at the "
             "front door (batch sheds 429 first; auto: on exactly "
             "when --autoscale-max is set)",
    )
    ap.add_argument(
        "--run-dir", default=None,
        help="router mode: where portfiles/logs land (default: a "
             "temp dir)",
    )
    ap.add_argument(
        "--deploy-dir", default=None, metavar="DIR",
        help="router mode: close the loop (deploy/controller.py) — "
             "replicas tee served traffic into DIR/log, an incremental "
             "trainer emits candidates into DIR/candidates, and each "
             "candidate is eval-gated, rolled, watched, and "
             "auto-rolled-back on SLO burn or agreement regression",
    )
    ap.add_argument(
        "--deploy-train-net", default=None, metavar="PATH",
        help="TRAIN .prototxt for the deploy trainer (Input data/label "
             "+ loss twin of --model); required with --deploy-dir",
    )
    ap.add_argument(
        "--deploy-interval-s", type=float, default=1.0,
        help="deploy controller tick cadence",
    )
    ap.add_argument(
        "--deploy-no-trainer", action="store_true",
        help="deploy loop without the supervised trainer child "
             "(candidates arrive from elsewhere; tests/smokes)",
    )
    ap.add_argument(
        "--health-interval-s", type=float, default=0.5,
        help="router health-sweep cadence",
    )
    ap.add_argument(
        "--portfile", default=None,
        help="publish the bound address (JSON) — lets scripts find an "
             "ephemeral --port 0",
    )
    ap.add_argument(
        "--bench", type=int, default=0, metavar="N",
        help="offline mode: run the closed-loop load generator for N "
             "requests and print one JSON record instead of serving",
    )
    ap.add_argument("--bench-concurrency", type=int, default=4)
    ap.add_argument(
        "--bench-sizes",
        type=lambda t: [int(v) for v in t.split(",") if v.strip()],
        default=[1, 2, 5, 8, 3],
        help="request row-counts the load generator cycles through",
    )
    args = ap.parse_args(argv)

    if args.quant_ab:
        if not (args.quant and args.quant != "f32"):
            ap.error("--quant-ab needs --quant bf16|int8 (the variant "
                     "the A/B fraction steers to)")
        if args.replicas < 2:
            ap.error("--quant-ab needs --replicas >= 2 (at least one "
                     "replica per variant)")

    if args.autoscale_max and args.autoscale_max < max(args.replicas, 1):
        ap.error("--autoscale-max must be >= --replicas (it is the "
                 "ceiling, --replicas the floor)")
    if args.autoscale_max and args.replicas < 1:
        ap.error("--autoscale-max needs router mode (--replicas >= 1)")

    if args.deploy_dir:
        if args.replicas < 1:
            ap.error("--deploy-dir needs router mode (--replicas >= 1):"
                     " the rollback is a tier-wide roll")
        if not args.deploy_train_net:
            ap.error("--deploy-dir needs --deploy-train-net (the TRAIN "
                     "prototxt the incremental trainer optimizes)")
        if getattr(args, "tee_dir", None):
            ap.error("--deploy-dir owns the tee (DIR/log); drop "
                     "--tee-dir")

    if args.replicas > 0:
        # one process per chip: on a TPU host every replica child gets
        # exactly one chip and this router process stays off JAX
        from ..utils import chips

        n_chips = chips.local_chip_count()
        width = max(args.replicas, args.autoscale_max)
        if n_chips and width > n_chips:
            ap.error(
                f"{width} replica processes need {width} TPU chips and "
                f"this host has {n_chips}: each replica initialises JAX "
                f"and holds one chip (--replicas/--autoscale-max <= "
                f"{n_chips}, or JAX_PLATFORMS=cpu for a CPU tier)"
            )
        if n_chips and args.deploy_dir:
            ap.error(
                "--deploy-dir on a TPU host: the eval gate builds its "
                "engines inside this router process and the trainer "
                "child needs a chip as well, so the loop would take "
                "chips its replicas hold (one process per chip; "
                "ROADMAP R7/D6 — run the closed loop with "
                "JAX_PLATFORMS=cpu)"
            )
        return _run_router(args, n_chips)

    from ..serve.loadgen import run_loadgen
    from ..serve.replica import build_stack, write_portfile

    engine, batcher, metrics, server = build_stack(args)

    if args.bench:
        record = run_loadgen(
            engine,
            n_requests=args.bench,
            sizes=args.bench_sizes,
            concurrency=args.bench_concurrency,
            batcher=batcher,
            metrics=metrics,
        )
        batcher.drain()
        print(json.dumps(record))
        return record

    if args.portfile:
        write_portfile(args.portfile, server, engine,
                       server.compile_cache_info)
    print(
        f"serving {args.model} on http://{server.host}:{server.port} "
        f"(buckets={engine.buckets}, mode={args.batch_mode}, "
        f"max_latency_us={args.max_latency_us})"
    )
    server.serve_forever()
    return server


def _replica_argv(args, run_dir: str, index: int, spawn: int):
    """The child command for replica ``index``, spawn ``spawn`` — a
    fresh portfile per spawn so the router can tell a respawn's port
    from its predecessor's."""
    argv = [
        sys.executable, "-m", "sparknet_tpu.serve.replica",
        "--model", args.model,
        "--buckets", ",".join(str(b) for b in args.buckets),
        "--max-batch", str(args.max_batch),
        "--max-latency-us", str(args.max_latency_us),
        "--max-queue", str(args.max_queue),
        "--batch-mode", args.batch_mode,
        "--top-k", str(args.top_k),
        "--port", "0",
        "--portfile", _portfile(run_dir, index, spawn),
    ]
    if args.weights:
        argv += ["--weights", args.weights]
    if args.bf16:
        argv.append("--bf16")
    # quantization A/B: odd-indexed replicas serve the quant variant,
    # even-indexed stay f32 — the router's health scrape learns each
    # side's mode and --quant-ab steers the split.  Without --quant-ab
    # every replica serves --quant uniformly.
    quant = getattr(args, "quant", None)
    if quant and quant != "f32":
        if getattr(args, "quant_ab", 0.0) > 0.0:
            if index % 2 == 1:
                argv += ["--quant", quant]
        else:
            argv += ["--quant", quant]
    if args.compile_cache:
        argv += ["--compile-cache", args.compile_cache]
    if args.data_cache:
        argv += ["--data-cache", args.data_cache]
    if getattr(args, "session_cache_mb", None) is not None:
        argv += ["--session-cache-mb", str(args.session_cache_mb)]
    # closed loop: every replica tees its served traffic into the
    # shared deploy log (deploy/tee.py is multi-writer safe: each
    # writer owns distinctly-seeded shard names via its pid)
    tee = getattr(args, "tee_dir", None)
    if getattr(args, "deploy_dir", None):
        tee = os.path.join(args.deploy_dir, "log")
    if tee:
        argv += ["--tee-dir", tee]
    # NOTE: --snapshot-watch is deliberately NOT forwarded — under a
    # router the roll is router-driven, one replica at a time
    return argv


def _portfile(run_dir: str, index: int, spawn: int) -> str:
    return os.path.join(run_dir, f"replica-{index}-s{spawn}.json")


def _run_router(args, n_chips: int):
    import tempfile

    from ..serve.replica import write_portfile
    from ..serve.router import Router
    from ..supervise.pool import ChildPool
    from ..utils import chips

    run_dir = args.run_dir or tempfile.mkdtemp(prefix="sparknet_serve_")
    os.makedirs(run_dir, exist_ok=True)
    pool = ChildPool(
        lambda i, s: _replica_argv(args, run_dir, i, s),
        args.replicas,
        # replica i (every spawn of it) owns chip i
        make_env=(lambda i, s: chips.one_chip_env(i)) if n_chips else None,
        name="serve-replica",
    )
    admit_on = (
        args.admission == "on"
        or (args.admission == "auto" and args.autoscale_max > 0)
    )
    admission = None
    if admit_on:
        from ..autoscale.admission import AdmissionPolicy

        admission = AdmissionPolicy()
    router = Router(
        args.replicas,
        pool=pool,
        portfile_for=lambda i, s: _portfile(run_dir, i, s),
        host=args.host,
        port=args.port,
        model_name=os.path.basename(args.model),
        health_interval_s=args.health_interval_s,
        watch=args.snapshot_watch,
        quant_ab=getattr(args, "quant_ab", 0.0),
        admission=admission,
    )
    controller = None
    if args.autoscale_max > 0:
        from ..autoscale.controller import AutoscaleController
        from ..autoscale.policy import AutoscalePolicy

        controller = AutoscaleController(
            router,
            AutoscalePolicy(
                min_replicas=args.replicas,
                max_replicas=args.autoscale_max,
            ),
        )
    deploy = None
    if args.deploy_dir:
        from ..deploy.controller import DeployController

        deploy = DeployController(
            router,
            deploy_dir=args.deploy_dir,
            model=args.model,
            train_net=args.deploy_train_net,
            boot_weights=args.weights,
            interval_s=args.deploy_interval_s,
            run_trainer=not args.deploy_no_trainer,
        )
        router.deploy = deploy
    pool.start()
    router.start()
    if controller is not None:
        controller.start()
    if deploy is not None:
        deploy.start()  # after router.start(): the probe replays need
        # the router's bound port
    if args.portfile:
        # reuse the replica portfile shape; the router has no engine
        write_portfile(
            args.portfile, router,
            type("E", (), {"warmup_s": None, "generation": 0})(), None,
        )
    ok = router.wait_healthy(timeout_s=300.0)
    auto = (
        f", autoscale {args.replicas}..{args.autoscale_max}"
        if controller is not None else ""
    )
    print(
        f"router on http://{router.host}:{router.port} — "
        f"{len(pool.alive())}/{args.replicas} replicas "
        f"{'healthy' if ok else 'NOT all healthy'} "
        f"(run_dir={run_dir}"
        f"{auto}{', admission on' if admission else ''}"
        f"{', deploy loop on' if deploy is not None else ''})",
        flush=True,
    )
    try:
        import time

        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        if controller is not None:
            controller.stop()
        router.stop()
    return router


if __name__ == "__main__":
    main()
