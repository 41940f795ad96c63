"""ImageNetApp — ImageNet end-to-end training entrypoint.

Behavioral twin of the reference's ``ImageNetApp`` (SURVEY.md §2;
``spark-submit`` there, ``python -m sparknet_tpu.apps.imagenet_app``
here): picks an architecture from the zoo (AlexNet / GoogLeNet /
ResNet-50 — the BASELINE.json ImageNetApp configs — plus
VGG-16), loads ImageNet
(folder / tar-shard / npz layouts, or synthetic), applies the net's
``transform_param`` (256→crop, mirror, mean), and trains — single chip
or across the mesh (``--parallel sync`` gradient all-reduce, or
``--parallel local`` for the reference's τ-local-SGD averaging).
"""

from __future__ import annotations

import argparse
import functools
import os
from typing import Dict, Iterator

import jax.numpy as jnp
import numpy as np

import jax

from ..data.imagenet import imagenet_dataset
from ..data.preprocess import Transformer
from ..nets import weights as W
from ..proto import caffe_pb
from ..solver.trainer import Solver, resolve_model_path
from ..parallel import ParallelSolver, make_mesh, multihost
from .cifar_app import (
    _batch_size,
    _data_layer,
    build_packed,
    comm_config_from,
    make_native_feed,
    print_data_cache_line,
    record_loader_meta,
    resolve_packed,
    train_loop,
)

ZOO = os.path.join(os.path.dirname(__file__), "..", "models", "prototxt")

ARCH_SOLVERS = {
    "alexnet": "bvlc_alexnet_solver.prototxt",
    "googlenet": "bvlc_googlenet_quick_solver.prototxt",
    "resnet50": "resnet50_solver.prototxt",
    "vgg16": "vgg16_solver.prototxt",
}


def make_feed(
    ds, transformer: Transformer, batch_size: int, seed: int = 0,
    workers: int = 0,
) -> Iterator[Dict[str, jnp.ndarray]]:
    # yield host numpy (not device arrays): the solver/device_put layer
    # owns placement, and pre-committed device arrays would force a
    # D2H round-trip in ParallelSolver's local mode (stack_round_batches)
    def transform(batch, rng):
        return {
            "data": np.asarray(transformer(batch["data"], rng), np.float32),
            "label": np.asarray(batch["label"], np.int32),
        }

    if workers > 0:
        # multiprocess assembly + preprocessing (data/pipeline.py); the
        # batch stream is bit-identical to the serial feed below
        from ..data.pipeline import ParallelBatchPipeline

        return ParallelBatchPipeline(
            ds, batch_size, workers=workers, shuffle=True, seed=seed,
            transform=transform,
        )
    return ds.batches(batch_size, shuffle=True, seed=seed, transform=transform)


def make_device_feed(
    ds, transformer: Transformer, batch_size: int, seed: int = 0
) -> Iterator[Dict[str, np.ndarray]]:
    """Feed for device-side augmentation: yields the raw uint8 source
    batch plus the augmentation *plan* (crop offsets / flip bits drawn
    from the same per-batch lineage RNG as :func:`make_feed`); the
    pixel work happens inside the jitted train step
    (``Solver(batch_transform=transformer.device_fn())``). Host cost
    drops to shuffle + memcpy; H2D ships uint8 (~3x smaller than
    float32 crops)."""

    def transform(batch, rng):
        data = np.ascontiguousarray(batch["data"])
        out = {"data": data, "label": np.asarray(batch["label"], np.int32)}
        out.update(transformer.plan(len(data), data.shape[1:3], rng))
        return out

    return ds.batches(batch_size, shuffle=True, seed=seed, transform=transform)


def lrn_elems_in_kernel(net) -> int:
    """Σ N·H·W·C over ``net``'s LRN layers that run as the Pallas kernels
    (``ops.lrn.uses_lrn_kernel``; 0 off a TPU), set once as the registry's
    ``lrn_elems_in_kernel`` gauge."""
    from ..nets import layers
    from ..telemetry.registry import REGISTRY

    n = sum(
        int(np.prod(net.blob_shapes[lp.bottom[0]])) for lp in net.layers
        if lp.type == "LRN"
        and layers.uses_lrn_kernel(net.blob_shapes[lp.bottom[0]], layers.LRN._geom(lp)[4])
    )
    REGISTRY.gauge("lrn_elems_in_kernel").set(n)
    return n


def make_args(**overrides) -> argparse.Namespace:
    """Programmatic equivalent of the CLI (tests, notebooks)."""
    args = parser().parse_args([])
    for k, v in overrides.items():
        if not hasattr(args, k):
            raise TypeError(f"unknown ImageNetApp arg {k!r}")
        setattr(args, k, v)
    return args


def build(args):
    solver_path = args.solver or os.path.join(ZOO, ARCH_SOLVERS[args.arch])
    sp = caffe_pb.load_solver(solver_path)
    solver_dir = os.path.dirname(os.path.abspath(solver_path))
    if args.max_iter:
        sp.max_iter = args.max_iter

    net_path = sp.net or sp.train_net
    if net_path:
        net_path = resolve_model_path(net_path, solver_dir)
    net_param = caffe_pb.load_net(net_path) if net_path else sp.net_param

    train_layer = _data_layer(net_param, "TRAIN")
    test_layer = _data_layer(net_param, "TEST")
    train_bs = args.batch_size or _batch_size(train_layer, 32)
    test_bs = args.batch_size or _batch_size(test_layer, train_bs)

    data_dir = None if args.synthetic else args.data_dir
    classes = args.synthetic_classes
    # Packed shard dirs first (--data-format packed / auto-detected
    # sparknet-pack manifest — streaming readers + optional decoded-
    # batch cache, docs/DATA.md), then Caffe-native sources
    # (LMDB/ImageData/HDF5) named in the prototxt (CifarApp's policy)
    packed_mean = None
    train_ds = test_ds = None
    use_packed, _ = resolve_packed(args)
    if use_packed:
        train_ds, test_ds, packed_mean = build_packed(args)
        data_dir = None  # a missing packed test split falls back below
    elif not args.synthetic:
        from ..data.caffe_layers import dataset_from_layer

        train_ds = dataset_from_layer(train_layer, solver_dir)
        test_ds = dataset_from_layer(test_layer, solver_dir)
    train_native = train_ds is not None
    test_native = test_ds is not None
    if train_ds is None:
        train_ds = imagenet_dataset(
            data_dir, train=True, synthetic_n=args.synthetic_n,
            synthetic_classes=classes,
        )
    if test_ds is None:
        test_ds = imagenet_dataset(
            data_dir, train=False, synthetic_n=args.synthetic_n,
            synthetic_classes=classes,
        )

    # multi-host: per-host data shards + local feed rows, global solver
    # batch (see cifar_app.build)
    nproc = jax.process_count()
    feed_train_bs, feed_test_bs = train_bs, test_bs
    if nproc > 1:
        if args.parallel == "none" and not getattr(args, "layout", None):
            raise ValueError("multi-host launch requires --parallel sync|local")
        if train_bs % nproc or test_bs % nproc:
            raise ValueError(
                f"batch sizes ({train_bs}/{test_bs}) must divide across "
                f"{nproc} processes"
            )
        train_ds = multihost.host_shard(train_ds)
        test_ds = multihost.host_shard(test_ds)
        feed_train_bs, feed_test_bs = train_bs // nproc, test_bs // nproc

    # missing mean .binaryproto -> the Caffe zoo's BGR channel means
    from .cifar_app import make_transformer, source_data_shape

    from ..data.imagenet import BGR_MEAN

    fallback_mean = (
        (lambda: packed_mean) if packed_mean is not None else lambda: BGR_MEAN
    )
    train_tf = make_transformer(
        train_layer, True, solver_dir, fallback_mean
    )
    test_tf = make_transformer(
        test_layer, False, solver_dir, fallback_mean
    )

    # same source-shape policy as CifarApp (crop wins H/W, channels
    # from the source); built-in loaders resize to 256 -> default 224
    ch, cw, cc = source_data_shape(
        train_ds, train_tf.crop_size, train_native, (224, 224)
    )
    eh, ew, ec = source_data_shape(
        test_ds, test_tf.crop_size, test_native, (ch, cw)
    )
    shapes = {"data": (train_bs, ch, cw, cc), "label": (train_bs,)}
    test_shapes = {"data": (test_bs, eh, ew, ec), "label": (test_bs,)}

    kw = dict(
        test_input_shapes=test_shapes,
        net_param=net_param,
        solver_dir=solver_dir,
        seed=args.seed,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        remat=getattr(args, "remat", False),
    )
    device_augment = getattr(args, "device_augment", False)
    layout_spec = getattr(args, "layout", None)
    if args.parallel == "none" and not layout_spec:
        if device_augment:
            kw["batch_transform"] = train_tf.device_fn()
        if getattr(args, "grad_compress", None):
            raise ValueError(
                "--grad-compress requires --parallel sync|local"
            )
        solver = Solver(sp, shapes, **kw)
    else:
        if device_augment:
            raise ValueError(
                "--device-augment currently requires --parallel none "
                "(the parallel solvers build their own train steps)"
            )
        if layout_spec:
            solver = ParallelSolver(
                sp, shapes, layout=layout_spec,
                mode="local" if args.parallel == "local" else "sync",
                tau=args.tau, comm_config=comm_config_from(args), **kw
            )
        else:
            solver = ParallelSolver(
                sp, shapes, mesh=make_mesh(), mode=args.parallel,
                tau=args.tau, comm_config=comm_config_from(args), **kw
            )
    if getattr(args, "weights", None):
        solver.load_weights(args.weights)  # Caffe --weights finetuning
    if device_augment:
        if getattr(args, "native_loader", "auto") == "on":
            # reject the conflicting pair rather than silently dropping
            # the explicitly-requested C++ loader (same
            # can't-believe-it-took-effect policy as ParallelSolver)
            raise ValueError(
                "--device-augment and --native-loader on are exclusive: "
                "device augmentation replaces the loader's host-side "
                "pixel work (leave --native-loader at auto/off)"
            )
        feed_fn = make_device_feed
    elif getattr(args, "native_loader", "auto") == "off":
        feed_fn = make_feed
    else:
        # auto/on: falls back if lib won't build
        feed_fn = functools.partial(
            make_native_feed, threads=getattr(args, "data_workers", -1)
        )
    if feed_fn is make_device_feed:
        # device augmentation already cut the host work to shuffle +
        # memcpy — worker processes would only add transport cost
        train_feed = feed_fn(train_ds, train_tf, feed_train_bs, seed=args.seed)
    else:
        from .cifar_app import resolve_feed_workers

        train_feed = feed_fn(
            train_ds, train_tf, feed_train_bs, seed=args.seed,
            workers=resolve_feed_workers(args, nproc),
        )
    # test feed stays serial (eval cadence; cheap center crop)
    test_feed = make_feed(test_ds, test_tf, feed_test_bs, seed=args.seed + 1)
    record_loader_meta(solver, train_feed)
    return solver, train_feed, test_feed


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="ImageNet training (ImageNetApp)")
    ap.add_argument("--arch", choices=sorted(ARCH_SOLVERS), default="alexnet")
    ap.add_argument("--solver", default=None,
                    help="explicit solver prototxt (overrides --arch)")
    ap.add_argument("--data-dir", default=os.environ.get("IMAGENET_DIR"))
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--synthetic-n", type=int, default=2048)
    ap.add_argument("--synthetic-classes", type=int, default=1000)
    ap.add_argument("--max-iter", type=int, default=0)
    ap.add_argument("--batch-size", type=int, default=0)
    ap.add_argument("--layout", default=None, metavar="AXES",
                    help="unified sharding layout, e.g. dp=2,tp=2 "
                         "(regex partition rule table; docs/PARALLELISM.md)")
    ap.add_argument("--parallel", choices=("none", "sync", "local"),
                    default="none")
    ap.add_argument("--grad-compress", choices=("none", "bf16", "int8"),
                    default=None,
                    help="compress the gradient/weight-delta all-reduce "
                         "with error-feedback residuals (also "
                         "SPARKNET_GRAD_COMPRESS; needs --parallel "
                         "sync|local; docs/COMMUNICATION.md)")
    ap.add_argument("--tau", default="10",
                    help="local-SGD sync period (the SparkNet τ knob): "
                         "an integer or 'auto' (telemetry-driven "
                         "controller)")
    ap.add_argument("--device-augment", action="store_true",
                    help="apply crop/mirror/mean on device inside the "
                         "jitted step (host ships uint8 + the aug plan); "
                         "stream-identical to the python feed")
    ap.add_argument("--native-loader", nargs="?", const="on", default="auto",
                    choices=("auto", "on", "off"),
                    help="C++ prefetching data loader: auto (default — "
                         "use it when the library builds), on, or off")
    ap.add_argument("--data-workers", type=int, default=-1,
                    help="preprocessing workers for the train feed: "
                         "threads of the native loader, processes of the "
                         "python feed (-1 auto: SPARKNET_DATA_WORKERS or "
                         "cpu-count aware; 0 serial). The batch stream "
                         "is bit-identical for any count")
    ap.add_argument("--data-format", choices=("auto", "packed"),
                    default=None,
                    help="input format: packed = stream sparknet-pack "
                         "shard files under --data-dir (CRC-checked "
                         "records, global shuffle, shard-level resume); "
                         "auto (default) detects a packed manifest (also "
                         "SPARKNET_DATA_FORMAT; docs/DATA.md)")
    ap.add_argument("--data-cache", nargs="?", const="default", default=None,
                    metavar="NS",
                    help="cross-job decoded-batch cache namespace for "
                         "the packed train feed (named shared memory, "
                         "shared with co-located jobs; also "
                         "SPARKNET_DATA_CACHE / SPARKNET_CACHE_MB; "
                         "docs/DATA.md)")
    ap.add_argument("--bf16", action="store_true",
                    help="bfloat16 compute (TPU-native matmul dtype)")
    ap.add_argument("--remat", action="store_true",
                    help="per-layer rematerialization: recompute "
                         "intra-layer intermediates in backward instead "
                         "of keeping them in HBM (bigger batches on "
                         "deep nets)")
    ap.add_argument("--restore", default=None, metavar="SOLVERSTATE",
                    help="resume from a .solverstate.npz snapshot")
    ap.add_argument("--auto-resume", action="store_true",
                    help="resume from the newest snapshot_prefix "
                         "solverstate if one exists (preemption recovery)")
    ap.add_argument("--weights", default=None, metavar="CAFFEMODEL",
                    help="initialise weights from a .caffemodel (finetune)")
    ap.add_argument("--profile-dir", default=None,
                    help="dump a jax.profiler trace of the training loop")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="host-side span trace + step-time breakdown: "
                         "write Chrome trace-event JSON (Perfetto-"
                         "loadable; pipeline workers and supervised "
                         "children merge in by pid/tid) and print the "
                         "per-phase step-time table (also "
                         "SPARKNET_TRACE; docs/OBSERVABILITY.md)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="batches staged ahead on device (0 disables)")
    ap.add_argument("--snapshot-format", choices=("npz", "orbax"),
                    default="npz",
                    help="solverstate on-disk format")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'pipeline.worker_crash@batch=37:worker=1' "
                         "(also SPARKNET_CHAOS; docs/ROBUSTNESS.md)")
    ap.add_argument("--supervise", action="store_true",
                    help="run under the job supervisor: automatic "
                         "relaunch with --auto-resume on failure, "
                         "restart budget + backoff + flap detection "
                         "(also SPARKNET_SUPERVISE=1; docs/MULTIHOST.md)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    from ..utils import compile_cache

    compile_cache.enable()
    args = parser().parse_args(argv)
    from .cifar_app import maybe_supervise

    code = maybe_supervise(
        "sparknet_tpu.apps.imagenet_app", argv, args,
        solver_path=args.solver or os.path.join(ZOO, ARCH_SOLVERS[args.arch]),
    )
    if code is not None:
        if code:
            raise SystemExit(code)
        return None

    from .. import chaos

    chaos.install_from(args.chaos)  # --chaos wins over SPARKNET_CHAOS
    multihost.initialize()  # no-op without SPARKNET_COORDINATOR
    solver, train_feed, test_feed = build(args)
    from ..solver.snapshot import solverstate_suffix

    solver.snapshot_suffix = solverstate_suffix(args.snapshot_format)
    from ..solver.snapshot import apply_auto_resume, resolve_prefix

    solver.sp.snapshot_prefix = resolve_prefix(solver.sp.snapshot_prefix)
    apply_auto_resume(args, solver.sp.snapshot_prefix)
    # elastic resume (supervisor degrade path — see cifar_app.main)
    weights_only = os.environ.get("SPARKNET_ELASTIC_RESUME", "") == "1"
    if args.restore:
        if args.auto_resume:
            # torn newest snapshot -> previous one (see cifar_app.main)
            from ..solver.snapshot import restore_with_fallback

            args.restore = restore_with_fallback(
                solver, solver.sp.snapshot_prefix, args.restore,
                feed=train_feed, weights_only=weights_only,
            )
        else:
            solver.restore(args.restore, train_feed,
                           weights_only=weights_only)
    # wrap AFTER restore (see cifar_app.main)
    from ..data.prefetch import maybe_prefetch

    raw_train_feed = train_feed
    train_feed = maybe_prefetch(train_feed, args, args.parallel)
    lrn_elems = lrn_elems_in_kernel(solver.train_net)
    if multihost.is_primary():
        if args.restore:
            print(f"Restoring previous solver status from {args.restore} "
                  f"(iter {solver.iter})")
        print(
            f"ImageNetApp: net={solver.net_param.name} "
            f"params={W.num_params(solver.params)} max_iter={solver.sp.max_iter} "
            f"lrn_elems_in_kernel={lrn_elems}"
        )
    from .. import telemetry
    from ..utils.profiling import trace

    # --trace / SPARKNET_TRACE / SPARKNET_TIMELINE wiring (see
    # cifar_app.main; docs/OBSERVABILITY.md)
    telemetry.install_for_training(solver, args.trace, args.profile_dir)
    try:
        with trace(args.profile_dir):
            result = train_loop(
                solver,
                telemetry.first_batch_lowered(
                    train_feed, solver, args.profile_dir
                ),
                test_feed,
            )
    except BaseException as e:
        # supervised runs leave a machine-readable failure record for
        # the supervisor's attribution (see cifar_app.main)
        from ..supervise import records as _records

        _records.write_crash_record(e)
        raise
    finally:
        # stop a multiprocess feed's workers/shm and report its waits
        # (host-bound vs device-bound) — see cifar_app.main
        # the staging thread first (data/prefetch.py): it must be out
        # of the raw feed, and of jax, before either goes away under it
        if train_feed is not raw_train_feed:
            train_feed.close()
        pm = getattr(raw_train_feed, "metrics", None)
        if pm is not None and multihost.is_primary():
            print(f"input pipeline: {pm.json_line()}")
        print_data_cache_line()  # decoded-batch cache counters
        getattr(raw_train_feed, "close", lambda: None)()
        if chaos.active() and multihost.is_primary():
            print(f"chaos: {chaos.METRICS.json_line()}")
        # after the feed close: worker span sidecars are on disk for
        # the merged Chrome trace (see cifar_app.main)
        telemetry.finish_run()
    multihost.stop_heartbeat()  # graceful leave (see cifar_app.main)
    return result


if __name__ == "__main__":
    main()
