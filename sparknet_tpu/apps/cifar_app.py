"""CifarApp — CIFAR-10 end-to-end training entrypoint.

Behavioral twin of the reference's ``CifarApp`` (SURVEY.md §2; launched
via spark-submit there, via ``python -m sparknet_tpu.apps.cifar_app``
here): reads a Caffe solver prototxt, loads CIFAR-10 (binary/pickle
layouts, or a deterministic synthetic set with ``--synthetic``), applies
the net's ``transform_param`` preprocessing, trains with test-interval
evaluation and snapshotting, and prints Caffe-style progress lines.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..data.cifar import cifar10_dataset
from ..data.preprocess import Transformer
from ..nets import weights as W
from ..parallel import ParallelSolver, make_mesh, multihost
from ..proto import caffe_pb
from ..solver.trainer import Solver, resolve_model_path


def _dataset_mean(ds) -> np.ndarray:
    """Per-pixel mean over a dataset's "data" rows — Caffe's
    compute_image_mean, regenerated when the .binaryproto is absent."""
    total = None
    count = 0
    for i in range(ds.num_partitions):
        part = ds.collect_partition(i)["data"].astype(np.float64)
        total = part.sum(0) if total is None else total + part.sum(0)
        count += len(part)
    return (total / max(count, 1)).astype(np.float32)


def _data_layer(net: caffe_pb.NetParameter, phase: str):
    for l in net.layers_for_phase(phase):
        if l.type in ("Data", "Input", "MemoryData", "ImageData", "HDF5Data"):
            return l
    return None


def _batch_size(layer, default: int) -> int:
    for field in (
        "data_param", "memory_data_param", "image_data_param",
        "hdf5_data_param",
    ):
        sub = layer.sub(field) if layer else None
        if sub is not None and sub.get("batch_size") is not None:
            return int(sub.get("batch_size"))
    return default


def source_data_shape(ds, crop_size, native, default_hw):
    """(h, w, c) the net will see from this data source: a crop fixes
    H,W; channels always come from the source itself, so grayscale
    LMDB/ImageData/HDF5 nets (e.g. MNIST LeNet) get 1-channel inputs.
    Native sources answer via ``ShardedDataset.sample_shape()`` — a
    cheap single-record probe (LMDB: one datum; ImageData: image
    header; HDF5: metadata), not a partition decode.  Shared by both
    image apps and the ``caffe`` CLI twin."""
    if native:
        h, w, c = ds.sample_shape()
    else:
        (h, w), c = default_hw, 3
    if crop_size:
        h = w = crop_size
    return int(h), int(w), int(c)


def make_transformer(layer, train: bool, solver_dir: str, fallback_mean=None):
    """transform_param -> Transformer, resolving ``mean_file``: a real
    .binaryproto wins; otherwise ``fallback_mean()`` supplies the mean
    (per-pixel (H,W,C) image or per-channel vector).  Shared by both
    image apps and the ``caffe test`` tool."""
    t = Transformer.from_message(
        layer.transform_param if layer else None, train=train
    )
    tp = layer.transform_param if layer else None
    if tp is not None and tp.get("mean_file") is not None:
        mf = resolve_model_path(str(tp.get("mean_file")), solver_dir)
        if os.path.exists(mf):
            from ..proto.caffemodel import load_binaryproto_mean

            t.mean_image = load_binaryproto_mean(mf)
        elif fallback_mean is not None:
            m = fallback_mean()
            if m is not None:
                m = np.asarray(m, np.float32)
                if m.ndim == 1:
                    t.mean_values = m
                else:
                    t.mean_image = m
    return t


def resolve_packed(args):
    """``--data-format`` / ``SPARKNET_DATA_FORMAT`` -> (use_packed,
    packed_dir).  ``packed`` demands a ``--data-dir`` holding a
    ``sparknet-pack`` output; ``auto`` (the default) uses the packed
    path exactly when the data dir carries a packed manifest — existing
    command lines never change behavior.  Shared by both image apps
    (docs/DATA.md)."""
    fmt = (
        getattr(args, "data_format", None)
        or os.environ.get("SPARKNET_DATA_FORMAT", "").strip()
        or "auto"
    )
    ddir = getattr(args, "data_dir", None)
    if fmt == "packed":
        if not ddir:
            raise ValueError(
                "--data-format packed requires --data-dir pointing at a "
                "sparknet-pack output directory"
            )
        return True, ddir
    if fmt == "auto" and ddir and not getattr(args, "synthetic", False):
        from ..data.records import is_packed

        if is_packed(ddir):
            return True, ddir
    return False, None


def build_packed(args):
    """The packed-format data plane for an image app's ``build``:
    streaming shard readers (+ the cross-job decoded-batch cache when
    ``--data-cache`` names a namespace) for train, packed test split
    when the pack wrote one (None otherwise — caller falls back), and
    the per-pixel mean ``sparknet-pack`` stored at pack time."""
    from ..data import records as _records
    from ..data.cache import cache_from_args

    _, packed_dir = resolve_packed(args)
    cache = cache_from_args(args)
    train_ds = _records.packed_dataset(packed_dir, train=True, cache=cache)
    test_ds = None
    if _records.has_packed_split(packed_dir, "test"):
        # the eval feed re-reads the same small stream at test_interval
        # cadence — no cache: eval must never evict training batches
        test_ds = _records.packed_dataset(packed_dir, train=False)
    return train_ds, test_ds, train_ds.mean()


def print_data_cache_line(log=print) -> None:
    """One ``data cache:`` JSON line (hit/miss/evict/torn counters) when
    a decoded-batch cache was active this run — same discipline as the
    ``chaos:`` / ``input pipeline:`` lines; check.sh asserts on it."""
    from ..telemetry import REGISTRY

    src = REGISTRY.sources().get("data_cache")
    if src is not None and multihost.is_primary():
        log(f"data cache: {src.json_line()}")


def make_native_feed(
    ds, transformer: Transformer, batch_size: int, seed: int = 0,
    workers: int = 0, threads: Optional[int] = None,
):
    """Feed served by the C++ prefetching loader (sparknet_tpu.native):
    shuffle + crop/mirror/mean + batch assembly in native worker threads,
    Python only wraps the ready batch's buffer (lent until the last
    reference to it dies, never copied). ``threads`` is the app's
    ``--data-workers`` as given: N >= 1 loader threads, else a count from
    the cores (``native.resolve_threads``). Falls back to :func:`make_feed`
    (which honours ``workers`` — the multiprocess python pipeline) when
    the library can't be built, or when the dataset won't fit the
    loader's in-RAM cache (it materialises every partition —
    ``SPARKNET_NATIVE_CACHE_MB``, default 2048, bounds that)."""
    from .. import native

    if not native.available():
        return make_feed(ds, transformer, batch_size, seed, workers=workers)
    cap = float(os.environ.get("SPARKNET_NATIVE_CACHE_MB", "2048")) * 1e6
    parts, total = [], 0
    for i in range(ds.num_partitions):
        p = ds.collect_partition(i)
        total += sum(np.asarray(v).nbytes for v in p.values())
        if total > cap:
            print(
                f"native loader: dataset exceeds "
                f"SPARKNET_NATIVE_CACHE_MB={cap / 1e6:.0f} — using the "
                f"python feed (partitions stay lazy)"
            )
            return make_feed(
                ds, transformer, batch_size, seed, workers=workers
            )
        parts.append(p)
    images = np.concatenate([p["data"] for p in parts])
    labels = np.concatenate([p["label"] for p in parts])
    return native.NativeLoader(
        images, labels, batch_size,
        crop=transformer.crop_size,
        train=transformer.train,
        mirror=transformer.mirror,
        mean_image=transformer.mean_image,
        mean_channel=transformer.mean_values,
        scale=transformer.scale,
        seed=seed,
        num_threads=threads,
    )


def make_feed(
    ds, transformer: Transformer, batch_size: int, seed: int = 0,
    workers: int = 0,
) -> Iterator[Dict[str, jnp.ndarray]]:
    # host numpy out: placement is the solver's job (see imagenet_app)
    def transform(batch, rng):
        return {
            "data": np.asarray(transformer(batch["data"], rng), np.float32),
            "label": np.asarray(batch["label"], np.int32),
        }

    if workers > 0:
        # multiprocess assembly + preprocessing; the batch stream is
        # bit-identical to the serial feed below for any worker count
        from ..data.pipeline import ParallelBatchPipeline

        return ParallelBatchPipeline(
            ds, batch_size, workers=workers, shuffle=True, seed=seed,
            transform=transform,
        )
    return ds.batches(batch_size, shuffle=True, seed=seed, transform=transform)


def build(args) -> tuple:
    sp = caffe_pb.load_solver(args.solver)
    solver_dir = os.path.dirname(os.path.abspath(args.solver))
    if args.max_iter:
        sp.max_iter = args.max_iter

    net_path = sp.net or sp.train_net
    if net_path:
        net_path = resolve_model_path(net_path, solver_dir)
    net_param = caffe_pb.load_net(net_path) if net_path else sp.net_param

    train_layer = _data_layer(net_param, "TRAIN")
    test_layer = _data_layer(net_param, "TEST")
    train_bs = args.batch_size or _batch_size(train_layer, 100)
    test_bs = _batch_size(test_layer, train_bs)

    data_dir = None if args.synthetic else args.data_dir
    # Packed shard dirs win first (--data-format packed, or auto +
    # a sparknet-pack manifest under --data-dir: streaming readers,
    # optional cross-job decoded-batch cache — docs/DATA.md); then
    # Caffe-native sources (LMDB/ImageData/HDF5) referenced by the
    # prototxt when present on disk — full data_param fidelity
    mean = None
    train_ds = test_ds = None
    use_packed, _ = resolve_packed(args)
    if use_packed:
        train_ds, test_ds, mean = build_packed(args)
        data_dir = None  # a missing packed test split falls back below
    elif not args.synthetic:
        from ..data.caffe_layers import dataset_from_layer

        train_ds = dataset_from_layer(train_layer, solver_dir)
        test_ds = dataset_from_layer(test_layer, solver_dir)
    train_native = train_ds is not None
    test_native = test_ds is not None
    if train_ds is None:
        train_ds, mean = cifar10_dataset(
            data_dir, train=True, synthetic_n=args.synthetic_n
        )
    if test_ds is None:
        test_ds, _ = cifar10_dataset(
            data_dir, train=False, synthetic_n=args.synthetic_n
        )

    # A mean regenerated from data must cover the FULL dataset and be
    # computed once — before host sharding (all hosts must subtract the
    # same mean) and shared by the train/test transformers.
    def needs_regenerated_mean(layer):
        tp = layer.transform_param if layer else None
        if tp is None or tp.get("mean_file") is None:
            return False
        return not os.path.exists(
            resolve_model_path(str(tp.get("mean_file")), solver_dir)
        )

    if mean is None and (
        needs_regenerated_mean(train_layer) or needs_regenerated_mean(test_layer)
    ):
        mean = _dataset_mean(train_ds)

    # multi-host: each process feeds its shard; batch sizes in the
    # solver stay GLOBAL (prototxt semantics), feeds serve local rows
    nproc = jax.process_count()
    feed_train_bs, feed_test_bs = train_bs, test_bs
    if nproc > 1:
        if train_bs % nproc or test_bs % nproc:
            raise ValueError(
                f"batch sizes ({train_bs}/{test_bs}) must divide across "
                f"{nproc} processes"
            )
        train_ds = multihost.host_shard(train_ds)
        test_ds = multihost.host_shard(test_ds)
        feed_train_bs, feed_test_bs = train_bs // nproc, test_bs // nproc

    # missing .binaryproto -> the precomputed full-dataset mean
    train_tf = make_transformer(train_layer, True, solver_dir, lambda: mean)
    test_tf = make_transformer(test_layer, False, solver_dir, lambda: mean)

    th, tw, tc = source_data_shape(
        train_ds, train_tf.crop_size, train_native, (32, 32)
    )
    eh, ew, ec = source_data_shape(
        test_ds, test_tf.crop_size, test_native, (32, 32)
    )
    shapes = {"data": (train_bs, th, tw, tc), "label": (train_bs,)}
    test_shapes = {"data": (test_bs, eh, ew, ec), "label": (test_bs,)}

    kw = dict(
        test_input_shapes=test_shapes,
        net_param=net_param,
        solver_dir=solver_dir,
        seed=args.seed,
    )
    parallel = getattr(args, "parallel", "none")
    layout_spec = getattr(args, "layout", None)
    if parallel == "none" and not layout_spec:
        if nproc > 1:
            raise ValueError("multi-host launch requires --parallel sync|local")
        if getattr(args, "grad_compress", None):
            # single-device training has no gradient communication to
            # compress — reject, per the can't-take-effect policy
            raise ValueError(
                "--grad-compress requires --parallel sync|local"
            )
        solver = Solver(sp, shapes, **kw)
    elif layout_spec:
        # unified rule-table path (docs/PARALLELISM.md): the layout IS
        # the parallelism — dp/tp/ep shapes are table entries, and
        # --parallel local keeps τ-local SGD over a dp-only layout
        solver = ParallelSolver(
            sp, shapes,
            layout=layout_spec,
            mode="local" if parallel == "local" else "sync",
            tau=getattr(args, "tau", 1),
            comm_config=comm_config_from(args), **kw
        )
    else:
        solver = ParallelSolver(
            sp, shapes, mesh=make_mesh(), mode=parallel,
            tau=getattr(args, "tau", 1),
            comm_config=comm_config_from(args), **kw
        )
    if getattr(args, "weights", None):
        solver.load_weights(args.weights)  # Caffe --weights finetuning
    feed_fn = (
        make_feed
        if getattr(args, "native_loader", "auto") == "off"
        # auto/on: falls back if the lib won't build
        else functools.partial(
            make_native_feed, threads=getattr(args, "data_workers", -1)
        )
    )
    workers = resolve_feed_workers(args, nproc)
    train_feed = feed_fn(
        train_ds, train_tf, feed_train_bs, seed=args.seed, workers=workers
    )
    # test feed stays serial: eval runs at test_interval cadence and its
    # center-crop transform is cheap — not worth worker processes
    test_feed = make_feed(test_ds, test_tf, feed_test_bs, seed=args.seed + 1)
    record_loader_meta(solver, train_feed)
    return solver, train_feed, test_feed


def comm_config_from(args):
    """``--grad-compress`` (app flag) + ``SPARKNET_COMM`` /
    ``SPARKNET_GRAD_COMPRESS`` / ``SPARKNET_COMM_BUCKET_MB`` (env) ->
    the parallel solver's :class:`CommConfig`.  Shared by all three
    apps (docs/COMMUNICATION.md)."""
    from ..parallel import comm

    return comm.resolve_config(
        compress=getattr(args, "grad_compress", None) or None
    )


def resolve_feed_workers(args, nproc: int) -> int:
    """Effective input-pipeline worker count for an app's train feed:
    ``--data-workers`` / ``SPARKNET_DATA_WORKERS`` / cpu-count auto
    (``data.pipeline.resolve_data_workers``). Auto stays serial under
    multi-host (forking next to the coordinator/heartbeat fabric is only
    done when asked explicitly); an explicit count is always honoured —
    the batch stream is bit-identical either way, so the choice is about
    throughput, never about results.  Shared by both image apps."""
    from ..data.pipeline import resolve_data_workers

    requested = getattr(args, "data_workers", -1)
    workers = resolve_data_workers(requested)
    if nproc > 1 and (requested is None or requested < 0):
        return 0
    return workers


def record_loader_meta(solver, train_feed) -> None:
    """Record the EFFECTIVE loader (``--native-loader auto`` may have
    fallen back) in the solverstate, so an ``--auto-resume`` in a
    changed environment (lib no longer builds, cache cap differs) warns
    about the silently different shuffle/augmentation RNG stream
    instead of hiding it — and print it, one ``train feed:`` line, so a
    run cannot mistake one feed for another."""
    from .. import native

    if isinstance(train_feed, native.NativeLoader):
        loader, how = "native", "native loader (C++ worker threads)"
    else:
        loader = "python"
        workers = getattr(train_feed, "workers", 0)
        how = (
            f"python feed, {workers} worker processes" if workers
            else "python feed, serial"
        )
        why_not = native.unavailable_reason()
        if why_not:
            how += f" (native loader unavailable: {why_not})"
    solver.env_meta["loader"] = loader
    if multihost.is_primary():
        print(f"train feed: {how}")


def train_loop(
    solver: Solver, train_feed, test_feed, log=print, timer=None
) -> Dict[str, float]:
    from .. import chaos
    from ..telemetry import aggregate as _aggregate
    from ..telemetry import anomaly as _anomaly
    from ..telemetry import flight as _flight
    from ..telemetry import timeline as _ttl
    from ..telemetry import trace as _trace
    from ..utils.profiling import StepTimer

    # per-iteration phase attribution: NULL unless the app enabled it
    # (--trace / SPARKNET_TIMELINE; telemetry.install_for_training)
    tl = getattr(solver, "timeline", _ttl.NULL)

    # supervisor.child_crash injection site (checked once per loop
    # chunk, i.e. at test/snapshot boundaries — not per iteration);
    # disabled chaos is the usual cached-None single test
    chaos_plan = chaos.get_plan()

    sp = solver.sp
    if not multihost.is_primary():
        # every process computes (collectives are SPMD); only process 0
        # speaks and writes — the reference's driver-side duties
        log = lambda *a, **k: None
    # flight recorder (telemetry/flight.py): every loop log line also
    # lands in the bounded ring for the crash dump — identity when the
    # recorder is off, so non-primary ranks keep their postmortem
    # context even though their stdout stays quiet
    log = _flight.tee_log(log)
    # live-reshard control surface (parallel/reshard.py): a request
    # file named by SPARKNET_RESHARD_REQUEST (or reshard_request.json
    # in a supervised child's run dir) migrates the job to a new
    # layout in place at a chunk boundary; None — zero per-iteration
    # cost — unless configured AND this solver can reshard
    from ..parallel import reshard as _reshard

    reshard_watch = _reshard.RequestWatcher.create(solver, log=log)
    if timer is None:
        shapes = solver.train_net.blob_shapes
        data_name = "data" if "data" in shapes else next(iter(shapes), None)
        timer = StepTimer(
            items_per_step=shapes[data_name][0] if data_name else 0,
            unit="images",
        )
    t0 = time.time()
    last_test: Dict[str, float] = {}

    def write_snapshot() -> None:
        path = f"{sp.snapshot_prefix}_iter_{solver.iter}.npz"
        state_path = (
            f"{sp.snapshot_prefix}_iter_{solver.iter}"
            f"{solver.snapshot_suffix}"
        )
        with tl.phase("snapshot"):
            # collective (gathers host-sharded optimizer slots); every
            # process participates, only process 0 writes the files.
            # Disk-full degrades to skip-with-counter (prune+retry
            # first) instead of crashing training — the prior chain
            # stays the bit-exact resume point (docs/ROBUSTNESS.md)
            saved = solver.save_or_skip(state_path, prefix=sp.snapshot_prefix)
            if multihost.is_primary() and saved:
                try:
                    W.save_npz(path, solver.params)
                except OSError as e:
                    from ..utils import safeio

                    safeio.count_fault("snapshot", safeio.classify(e))
                # keep-last-k (SPARKNET_SNAPSHOT_KEEP): bounds disk
                # growth while leaving older snapshots for torn-file
                # fallback
                from ..solver.snapshot import prune_snapshots

                prune_snapshots(sp.snapshot_prefix)
        log(f"Snapshotting to {path}")
        log(f"Snapshotting solver state to {state_path}")

    from ..solver.preempt import preempt_message, preemption_grace
    from ..telemetry import training_loop as _telemetry_loop

    # telemetry bracket: timeline wall clock + the periodic
    # ``telemetry:`` line (SPARKNET_TELEMETRY_INTERVAL_S, default off)
    # so long supervised runs surface numbers before exit
    with _telemetry_loop(tl, emit=log), preemption_grace(solver):
        # Caffe's pre-loop gate (Solver::Step):
        # iter % test_interval == 0 && (iter > 0 || test_initialization)
        # — a fresh solver tests once before training unless
        # test_initialization: false; a solver RESUMED exactly on a test
        # boundary re-runs that boundary's test before continuing.
        if sp.test_interval and (
            (solver.iter == 0 and sp.test_initialization)
            or (solver.iter > 0 and solver.iter % sp.test_interval == 0)
        ):
            with tl.phase("eval"):
                last_test = solver.test(test_feed)
            for k, v in last_test.items():
                log(f"    Test net output: {k} = {v:.4f}")
        while solver.iter < sp.max_iter:
            if chaos_plan is not None:
                rule = chaos_plan.match(
                    "supervisor.child_crash", iter=solver.iter
                )
                if rule is not None:
                    # simulated hard host death at a boundary the
                    # snapshot cadence may just have served: write the
                    # machine-readable record (the child's crash path),
                    # then die too hard for any cleanup — exactly what
                    # the supervisor must recover from
                    from ..supervise import records as _records

                    _records.write_failure_record(
                        process_id=multihost.process_index(),
                        kind="chaos.child_crash",
                        reason=(
                            f"chaos supervisor.child_crash at iteration "
                            f"{solver.iter}"
                        ),
                    )
                    os._exit(int(rule.params.get("exit_code", 9)))
            # stop at the nearest of: next test boundary, next snapshot
            # boundary, a requested reshard's at_iter, max_iter — so
            # neither cadence skips the others'.
            targets = [sp.max_iter]
            for interval in (sp.test_interval, sp.snapshot):
                if interval:
                    targets.append((solver.iter // interval + 1) * interval)
            if reshard_watch is not None:
                reshard_watch.add_targets(targets, solver.iter)
            nxt = min(targets)
            prev_iter = solver.iter
            timer.update(0)  # reset window: exclude eval/snapshot time

            def _log_iter(it, mm):
                loss = mm.get("loss", float("nan"))
                if loss == loss:  # NaN never feeds the spike detector
                    _anomaly.observe_loss(loss)
                log(f"Iteration {it}, loss = {loss:.5f}")

            t_chunk = time.time()
            m = solver.step(train_feed, nxt - solver.iter, log_fn=_log_iter)
            if sp.display:
                if m:  # host sync: the window measures completed compute
                    jax.block_until_ready(next(iter(m.values())))
                timer.update(solver.iter - prev_iter)
                log(f"    speed: {timer.format()}")
                if solver.iter > prev_iter:
                    # step-time spike stream (EMA+MAD, display cadence)
                    _anomaly.observe_step(
                        (time.time() - t_chunk) / (solver.iter - prev_iter)
                    )
            if solver.stop_requested:
                solver.stop_requested = False  # consumed: solver reusable
                if sp.snapshot_prefix:
                    write_snapshot()
                log(preempt_message(solver.iter, bool(sp.snapshot_prefix)))
                break
            at_end = solver.iter >= sp.max_iter
            if (
                sp.test_interval and solver.iter % sp.test_interval == 0
            ) or at_end:
                with tl.phase("eval"):
                    last_test = solver.test(test_feed)
                for k, v in last_test.items():
                    log(f"    Test net output: {k} = {v:.4f}")
            if (
                sp.snapshot
                and sp.snapshot_prefix
                and (solver.iter % sp.snapshot == 0 or at_end)
            ):
                write_snapshot()
            # reshard AFTER the boundary's snapshot: the snapshot at
            # the migration point carries the pre-reshard layout, so a
            # replay from it under the new layout reproduces the
            # resharded run bitwise (scripts/reshard_smoke.py pins it)
            if reshard_watch is not None and not at_end:
                reshard_watch.poll()
    done_iters = solver.iter
    dt = time.time() - t0
    log(
        f"Optimization Done. {done_iters} iters in {dt:.1f}s "
        f"({done_iters / max(dt, 1e-9):.1f} it/s)"
    )
    # communication record (ParallelSolver only): one `comm:` JSON line
    # (bucket plan + wire-byte estimate, same discipline as the chaos:
    # and supervisor: lines) and, under --tau auto, the controller's
    # decision log as a `tau:` line + a machine-readable report next to
    # the snapshots (docs/COMMUNICATION.md)
    # layout record (unified sharding path): mesh shape, rule count,
    # sharded/replicated leaf split and the layout fingerprint — one
    # `layout:` JSON line, same discipline as comm:/chaos:
    if getattr(solver, "layout_report", None):
        import json as _json

        lrep = solver.layout_report()
        if lrep:
            log(f"layout: {_json.dumps(lrep)}")
    if hasattr(solver, "comm_report"):
        import json as _json

        report = solver.comm_report()
        tc = getattr(solver, "tau_controller", None)
        if tc is not None:
            report.pop("tau_controller", None)  # the tau: line carries it
            log(f"tau: {tc.json_line()}")
            if multihost.is_primary() and sp.snapshot_prefix:
                path = tc.write_report(sp.snapshot_prefix)
                if path:
                    log(f"tau controller report written to {path}")
        log(f"comm: {_json.dumps(report)}")
    if tl.enabled:
        # the paper's τ-vs-communication accounting, read off the live
        # loop: input wait / H2D / multihost sync / fenced compute /
        # eval / snapshot, exclusive times (docs/OBSERVABILITY.md)
        log("telemetry: step-time breakdown")
        for line in tl.table().splitlines():
            log(f"  {line}")
        drops = _trace.dropped_spans()
        serr = _trace.sidecar_errors()
        if drops or serr:
            # the trace's own losses stop being silent truncation: ring
            # evictions and unreadable sidecars print with the table
            log(
                f"  trace: {drops} span(s) dropped (ring buffer), "
                f"{serr} sidecar merge error(s)"
            )
    # the cluster view (telemetry/aggregate.py): when the heartbeat
    # piggyback merged per-rank snapshots, rank 0 prints the
    # cluster-wide phase table — per-rank skew instead of rank-local
    # numbers (docs/OBSERVABILITY.md "Cluster level")
    _aggregate.self_ingest()
    agg = _aggregate.get_aggregator()
    if agg is not None and agg.has_data() and multihost.is_primary():
        log("cluster: phase table (per-rank shares of loop wall time)")
        for line in agg.table().splitlines():
            log(f"  {line}")
    return last_test


def arg_parser() -> argparse.ArgumentParser:
    """The CifarApp CLI surface; importable (add_help=False-compatible
    via ``parents=``) so wrapper tools accept the same flags."""
    ap = argparse.ArgumentParser(description="CIFAR-10 training (CifarApp)",
                                 add_help=False)
    ap.add_argument(
        "--solver",
        default=os.path.join(
            os.path.dirname(__file__), "..", "models", "prototxt",
            "cifar10_quick_solver.prototxt",
        ),
    )
    ap.add_argument("--data-dir", default=os.environ.get("CIFAR10_DIR"))
    ap.add_argument("--synthetic", action="store_true",
                    help="use the deterministic synthetic dataset")
    ap.add_argument("--synthetic-n", type=int, default=10000)
    ap.add_argument("--max-iter", type=int, default=0)
    ap.add_argument("--batch-size", type=int, default=0)
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
                         "the packed train feed: co-located jobs reading "
                         "the same stream share decoded batches over "
                         "named shared memory instead of re-decoding "
                         "(also SPARKNET_DATA_CACHE; budget "
                         "SPARKNET_CACHE_MB; docs/DATA.md)")
    ap.add_argument("--parallel", choices=("none", "sync", "local"),
                    default="none")
    ap.add_argument("--layout", default=None, metavar="AXES",
                    help="unified sharding layout, e.g. dp=2,tp=2: one "
                         "mesh + the regex partition rule table replaces "
                         "the per-strategy trainers — any dp×tp×ep shape "
                         "is a table entry (combine with --parallel local "
                         "for τ-local SGD over a dp-only layout; "
                         "docs/PARALLELISM.md)")
    ap.add_argument("--tau", default="10",
                    help="local-SGD sync period (the SparkNet τ knob): "
                         "an integer, or 'auto' for the telemetry-"
                         "driven controller — widens when rounds are "
                         "sync-bound, narrows when the loss diverges "
                         "between syncs (docs/COMMUNICATION.md)")
    ap.add_argument("--grad-compress", choices=("none", "bf16", "int8"),
                    default=None,
                    help="compress the gradient/weight-delta all-reduce "
                         "(bf16 cast or int8 with a shared per-bucket "
                         "scale), with error-feedback residuals carried "
                         "in opt state (also SPARKNET_GRAD_COMPRESS; "
                         "requires --parallel sync|local)")
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
                    help="solverstate on-disk format (orbax writes "
                         "sharded device arrays directly)")
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


def maybe_supervise(module: str, argv, args, solver_path=None):
    """``--supervise`` / ``SPARKNET_SUPERVISE=1`` wiring, shared by the
    apps: re-exec this invocation as supervised child process(es)
    (docs/MULTIHOST.md "Recovery") and return the supervisor's exit
    code — or None when supervision is off, which costs exactly one
    flag test on the way into the normal train path.  Children run
    with ``SPARKNET_SUPERVISE=0``, so the branch can never recurse."""
    if not (
        getattr(args, "supervise", False)
        or os.environ.get("SPARKNET_SUPERVISE", "") not in ("", "0")
    ):
        return None
    from ..supervise.supervisor import supervise_app

    prefix = None
    solver_path = solver_path or getattr(args, "solver", None)
    if solver_path:
        # the supervisor verifies the snapshot chain between launches;
        # a text parse of the solver prototxt names the prefix without
        # paying any backend/model build in the supervising process
        from ..solver.snapshot import resolve_prefix

        prefix = resolve_prefix(
            caffe_pb.load_solver(solver_path).snapshot_prefix or ""
        ) or None
    raw = list(sys.argv[1:] if argv is None else argv)
    return supervise_app(module, raw, prefix)


def main(argv=None):
    from ..utils import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(parents=[arg_parser()],
                                 description="CIFAR-10 training (CifarApp)")
    args = ap.parse_args(argv)

    code = maybe_supervise("sparknet_tpu.apps.cifar_app", argv, args)
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
    # elastic resume (supervisor degrade path): restore weights but
    # re-init optimizer slots — the snapshot's slots may be laid out
    # for a dp width this relaunch no longer has
    weights_only = os.environ.get("SPARKNET_ELASTIC_RESUME", "") == "1"
    if args.restore:
        if args.auto_resume:
            # auto-resume owns the snapshot chain: a torn newest file
            # falls back to the previous one instead of aborting
            from ..solver.snapshot import restore_with_fallback

            args.restore = restore_with_fallback(
                solver, solver.sp.snapshot_prefix, args.restore,
                feed=train_feed, weights_only=weights_only,
            )
        else:
            # an explicitly-named --restore must fail loudly on a torn
            # file: silently restoring something else isn't recovery
            solver.restore(args.restore, train_feed,
                           weights_only=weights_only)
    # wrap AFTER restore: align_feed fast-forwards skipped batches,
    # which must stay host-side (and skippable), not device transfers
    from ..data.prefetch import maybe_prefetch

    raw_train_feed = train_feed
    train_feed = maybe_prefetch(train_feed, args, args.parallel)
    if multihost.is_primary():
        if args.restore:
            print(f"Restoring previous solver status from {args.restore} "
                  f"(iter {solver.iter})")
        print(
            f"CifarApp: net={solver.net_param.name} params="
            f"{W.num_params(solver.params)} max_iter={solver.sp.max_iter}"
        )
    from .. import telemetry
    from ..utils.profiling import trace

    # --trace / SPARKNET_TRACE / SPARKNET_TIMELINE: span tracer +
    # step-time attribution (docs/OBSERVABILITY.md)
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
        # supervised runs leave a machine-readable failure record (who,
        # why, last completed iteration) for the supervisor's
        # attribution; a no-op when unsupervised
        from ..supervise import records as _records

        _records.write_crash_record(e)
        raise
    finally:
        # a multiprocess train feed owns worker processes + shm slots;
        # stop them even when the loop raises (and report its per-stage
        # waits — the host-bound vs device-bound answer — on the way out)
        # the staging thread first (data/prefetch.py): it must be out
        # of the raw feed, and of jax, before either goes away under it
        if train_feed is not raw_train_feed:
            train_feed.close()
        pm = getattr(raw_train_feed, "metrics", None)
        if pm is not None and multihost.is_primary():
            print(f"input pipeline: {pm.json_line()}")
        # cross-job decoded-batch cache counters, before the feed close
        # drops the (weakly registered) cache source
        print_data_cache_line()
        getattr(raw_train_feed, "close", lambda: None)()
        if chaos.active() and multihost.is_primary():
            # fires + recoveries, one JSON line — the chaos run's
            # observable record (tests assert exact counts on it)
            print(f"chaos: {chaos.METRICS.json_line()}")
        # AFTER the feed close: the joined workers' span sidecars are
        # on disk, so the merged Chrome trace includes them
        telemetry.finish_run()
    # training is done: leave the liveness fabric gracefully so the
    # last host to finish isn't mistaken for a dead peer
    multihost.stop_heartbeat()
    return result


if __name__ == "__main__":
    main()
