"""Engine-replica child process — one stateful backend of the tier.

``python -m sparknet_tpu.serve.replica`` is what the router's
:class:`~sparknet_tpu.supervise.pool.ChildPool` spawns N of: a full
single-process serving stack (engine + batcher + HTTP server) that

- binds an **ephemeral** port and publishes it through an atomically
  written ``--portfile`` (JSON: host/port/pid/warmup_s/compile_cache)
  — the router discovers respawned replicas by re-reading the file a
  fresh spawn writes;
- enables the **persistent compile cache** before warmup
  (``--compile-cache ROOT`` -> ``ROOT/<net-fingerprint>/``), so a
  respawn deserializes executables instead of recompiling — the
  portfile carries entry counts before/after warmup, making a
  cache-hit restart machine-checkable;
- can watch a snapshot prefix/dir itself (``--snapshot-watch``) for
  standalone use, though under a router the *router* drives the roll
  and replicas only take explicit ``/reload``;
- can attach **read-only** to a PR 8 decoded-batch cache namespace
  (``--data-cache NS``): ``/classify`` accepts ``cache_key`` bodies
  and the ``data_cache`` counters ride the replica's ``/metrics``.

Kept deliberately free of router knowledge: a replica is just a
server; the tier semantics (dispatch, retry, eject, roll) live in one
place, ``serve/router.py``.  Request tracing follows the same rule:
the replica records its hop spans (server/batcher/engine/serialize,
``telemetry/reqtrace.py``) and returns them inline in the
``X-Sparknet-Spans`` response header — stitching is the router's job.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def add_engine_args(ap: argparse.ArgumentParser) -> None:
    """The engine/batcher flags shared verbatim by the replica entry
    and ``tools/serve`` (single-process and router modes)."""

    def int_list(text: str):
        vals = [int(v) for v in text.split(",") if v.strip()]
        if not vals:
            raise argparse.ArgumentTypeError(f"empty int list: {text!r}")
        return vals

    ap.add_argument("--model", required=True, help="deploy .prototxt")
    ap.add_argument(
        "--weights", default=None,
        help=".caffemodel | .npz | .solverstate.npz",
    )
    ap.add_argument(
        "--buckets", type=int_list, default=[1, 8, 32],
        help="batch-size buckets to pre-compile (requests pad up)",
    )
    ap.add_argument(
        "--max-batch", type=int, default=0,
        help="rows per engine call (default: largest bucket)",
    )
    ap.add_argument(
        "--max-latency-us", type=int, default=2000,
        help="longest a request waits for batch co-riders",
    )
    ap.add_argument(
        "--max-queue", type=int, default=256,
        help="queued-request bound (backpressure -> HTTP 503)",
    )
    ap.add_argument(
        "--batch-mode", choices=("fill", "continuous"),
        default="continuous",
        help="admission policy: continuous (deadline-aware, the "
             "default) or fill (fill-then-flush, the A/B baseline)",
    )
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--bf16", action="store_true",
                    help="shorthand for --quant bf16 (kept for "
                         "back-compat)")
    ap.add_argument(
        "--quant", choices=("f32", "bf16", "int8"), default=None,
        help="quantized inference variant (serve/quantize.py): bf16 "
             "weights-as-arguments, or per-channel int8 weights with "
             "in-graph activation quantization; the compile caches "
             "key the mode so precisions never alias",
    )
    ap.add_argument(
        "--compile-cache", default=None, metavar="DIR",
        help="persistent compile cache root; executables land in "
             "DIR/<net-fingerprint>/ and restarts skip AOT warmup",
    )
    ap.add_argument(
        "--snapshot-watch", default=None, metavar="TARGET",
        help="snapshot prefix or run dir: hot-swap to each newer "
             "manifest-verified solverstate automatically",
    )
    ap.add_argument(
        "--data-cache", default=None, metavar="NS",
        help="attach read-only to a decoded-batch cache namespace "
             "(PR 8); /classify then accepts cache_key bodies",
    )
    ap.add_argument(
        "--session-cache-mb", type=float, default=None, metavar="MB",
        help="per-session decode-state cache budget for recurrent "
             "nets (serve/session.py; default SPARKNET_SESSION_CACHE_MB"
             " or 64; 0 disables — every request replays its prefix)",
    )
    ap.add_argument(
        "--layout", default=None, metavar="AXES",
        help="multi-device replica layout, e.g. dp=2,tp=2: weights "
             "shard per the training rule table (docs/PARALLELISM.md) "
             "and the compile cache keys include the layout",
    )
    ap.add_argument(
        "--tee-dir", default=None, metavar="DIR",
        help="deploy traffic tee (deploy/tee.py): append served "
             "rows + labels into a packed shard log under DIR — the "
             "incremental trainer's input; bounded and non-blocking "
             "(drops counted, never backpressures requests)",
    )


def build_stack(args, *, watch_in_server: bool = True):
    """args -> (engine, batcher, metrics, server) — the one place the
    serving stack is assembled (replica, single-process CLI and tests
    share it)."""
    import jax.numpy as jnp

    from .batcher import MicroBatcher
    from .compile_cache import cache_entries, enable_persistent_cache
    from .engine import InferenceEngine
    from .metrics import ServeMetrics
    from .server import InferenceServer

    layout = None
    if getattr(args, "layout", None):
        from ..parallel import partition

        layout = partition.parse_layout(args.layout, rules="tp")
    session_mb = getattr(args, "session_cache_mb", None)
    if session_mb is not None:
        # the engine's SessionCache reads the env at construction —
        # set it before the engine exists (0 = the disabled singleton)
        if session_mb <= 0:
            os.environ["SPARKNET_SESSION_CACHE"] = "0"
        else:
            os.environ["SPARKNET_SESSION_CACHE_MB"] = str(session_mb)
    quant = getattr(args, "quant", None) or (
        "bf16" if getattr(args, "bf16", False) else None
    )
    metrics = ServeMetrics(args.buckets)
    engine = InferenceEngine.from_files(
        args.model,
        args.weights,
        buckets=args.buckets,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        metrics=metrics,
        layout=layout,
        quant=quant,
    )
    cache_info = None
    if args.compile_cache:
        # before warmup, after the net exists: the fingerprint names
        # the per-net directory, warmup populates (or hits) it
        cache_info = enable_persistent_cache(
            args.compile_cache, engine.fingerprint
        )
    engine.warmup()
    if cache_info is not None:
        cache_info = dict(
            cache_info,
            entries_after=cache_entries(cache_info["dir"]),
            warmup_s=engine.warmup_s,
        )
    batcher = MicroBatcher(
        engine,
        max_batch=args.max_batch,
        max_latency_us=args.max_latency_us,
        max_queue=args.max_queue,
        metrics=metrics,
        mode=args.batch_mode,
    )
    data_cache = None
    if args.data_cache:
        from ..data.cache import ShmBatchCache

        data_cache = ShmBatchCache(namespace=args.data_cache, readonly=True)
    tee = None
    if getattr(args, "tee_dir", None):
        from ..deploy.tee import TeeWriter

        tee = TeeWriter(args.tee_dir)
    server = InferenceServer(
        engine,
        batcher=batcher,
        metrics=metrics,
        host=args.host,
        port=args.port,
        model_name=os.path.basename(args.model),
        default_top_k=args.top_k,
        data_cache=data_cache,
        watch=args.snapshot_watch if watch_in_server else None,
        compile_cache_info=cache_info,
        tee=tee,
    )
    return engine, batcher, metrics, server


def write_portfile(path: str, server, engine, cache_info) -> None:
    """Atomic (tmp + rename): the router may read mid-write."""
    doc = {
        "host": server.host,
        "port": server.port,
        "pid": os.getpid(),
        "warmup_s": getattr(engine, "warmup_s", None),
        "generation": getattr(engine, "generation", 0),
        "quant": getattr(engine, "quant", "f32"),
        "compile_cache": cache_info,
    }
    from ..utils import safeio

    safeio.atomic_write_json(
        path, doc, site="records", indent=None, fsync=False
    )


def main(argv=None) -> int:
    from ..telemetry import reqtrace
    from ..utils import compile_cache

    compile_cache.enable()
    # request tracing rides the inherited env (the router's operator
    # sets SPARKNET_REQTRACE once for the whole tier); re-resolve it
    # explicitly so a respawn under a scrubbed env behaves the same
    reqtrace.configure_from_env()
    ap = argparse.ArgumentParser(
        prog="sparknet-serve-replica",
        description="one engine replica of the serving tier",
    )
    add_engine_args(ap)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 (default): ephemeral — see --portfile")
    ap.add_argument("--portfile", default=None,
                    help="where to publish the bound address (JSON)")
    args = ap.parse_args(argv)

    engine, batcher, metrics, server = build_stack(args)
    # the supervisor stops replicas with SIGTERM (supervise/pool.py);
    # exit through serve_forever's cleanup so the deploy tee seals its
    # in-flight shard instead of abandoning a .writing file to the
    # next open's recover_log sweep
    import signal

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    if args.portfile:
        write_portfile(args.portfile, server, engine,
                       server.compile_cache_info)
    print(
        f"replica pid={os.getpid()} serving {args.model} on "
        f"http://{server.host}:{server.port} "
        f"(warmup {engine.warmup_s}s, mode={args.batch_mode})",
        flush=True,
    )
    server.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
