"""data/pipeline.py: the multiprocess input pipeline's contracts.

Determinism (the acceptance proof): the parallel feed's batch stream is
bit-identical to the serial ``ds.batches`` stream for 1, 2, and 4
workers, and ``skip(n)``-then-iterate equals iterate-then-slice.
Shutdown: close() leaves no child processes and no /dev/shm segments
(the session fixture in conftest.py re-asserts this globally after the
whole run). Errors surface at their serial stream position.
"""

import glob
import multiprocessing
import os
import time

import numpy as np
import pytest

from sparknet_tpu.data.pipeline import (
    ParallelBatchPipeline,
    PipelineMetrics,
    SHM_PREFIX,
    default_data_workers,
    resolve_data_workers,
)
from sparknet_tpu.data.rdd import ShardedDataset

pytestmark = pytest.mark.skipif(
    "fork" not in multiprocessing.get_all_start_methods(),
    reason="pipeline workers require the fork start method",
)


def _ds(n=96, parts=4):
    rng = np.random.default_rng(0)
    return ShardedDataset.from_arrays(
        {
            "data": rng.normal(size=(n, 8, 8, 3)).astype(np.float32),
            "label": np.arange(n, dtype=np.int32),
        },
        parts,
    )


def _aug(batch, r):
    # draws from the per-batch rng: catches any transform-RNG drift
    # between the serial path and a worker's
    return {
        "data": batch["data"]
        + r.normal(size=batch["data"].shape).astype(np.float32),
        "label": batch["label"],
    }


def _assert_same_stream(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert sorted(x) == sorted(y)
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


def _assert_no_leaks():
    """No stray pipeline children or shm segments right now (close()
    joins before returning, so no settling loop is needed)."""
    stray = [
        p for p in multiprocessing.active_children()
        if p.name.startswith(SHM_PREFIX)
    ]
    assert not stray, f"leaked pipeline workers: {stray}"
    if os.path.isdir("/dev/shm"):
        segs = glob.glob(f"/dev/shm/{SHM_PREFIX}_*")
        assert not segs, f"leaked shm segments: {segs}"


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_parallel_feed_bit_identical_to_serial(workers):
    ds = _ds()
    serial = list(
        ds.batches(8, shuffle=True, seed=3, epochs=2, transform=_aug)
    )
    with ParallelBatchPipeline(
        ds, 8, workers=workers, shuffle=True, seed=3, epochs=2,
        transform=_aug,
    ) as pipe:
        got = list(pipe)
    _assert_same_stream(serial, got)
    _assert_no_leaks()


def test_skip_then_iterate_equals_iterate_then_slice():
    ds = _ds()
    serial = list(
        ds.batches(8, shuffle=True, seed=3, epochs=2, transform=_aug)
    )
    with ParallelBatchPipeline(
        ds, 8, workers=3, shuffle=True, seed=3, epochs=2, transform=_aug
    ) as pipe:
        pipe.skip(7)  # pre-start skip: O(1), offsets every worker
        got = [next(pipe) for _ in range(5)]
    _assert_same_stream(serial[7:12], got)

    # post-start skip degrades to consume-and-discard but stays correct
    with ParallelBatchPipeline(
        ds, 8, workers=3, shuffle=True, seed=3, epochs=2, transform=_aug
    ) as pipe:
        first = next(pipe)
        pipe.skip(4)
        after = next(pipe)
    _assert_same_stream([serial[0], serial[5]], [first, after])


def test_infinite_stream_early_close_no_leaks():
    ds = _ds()
    serial_it = ds.batches(8, shuffle=True, seed=3, transform=_aug)
    serial = [next(serial_it) for _ in range(10)]
    pipe = ParallelBatchPipeline(
        ds, 8, workers=4, shuffle=True, seed=3, transform=_aug
    )
    got = [next(pipe) for _ in range(10)]
    pipe.close()
    _assert_same_stream(serial, got)
    _assert_no_leaks()
    with pytest.raises(StopIteration):
        next(pipe)  # closed pipelines don't resurrect workers


def test_worker_error_surfaces_at_serial_position():
    ds = _ds(n=40, parts=2)

    def boom(batch, r):
        if batch["label"][0] >= 20:
            raise RuntimeError("late explosion")
        return batch

    serial_n = 0
    try:
        for _ in ds.batches(4, shuffle=False, seed=0, transform=boom):
            serial_n += 1
    except RuntimeError:
        pass

    pipe = ParallelBatchPipeline(
        ds, 4, workers=2, shuffle=False, seed=0, transform=boom
    )
    n = 0
    with pytest.raises(RuntimeError, match="late explosion"):
        for _ in pipe:
            n += 1
    assert n == serial_n  # every batch before the failure was yielded
    _assert_no_leaks()


def test_slot_overflow_falls_back_to_pickle():
    ds = _ds(n=32, parts=2)
    serial = list(
        ds.batches(8, shuffle=False, seed=0, epochs=1, transform=_aug)
    )
    # slots too small for any batch: every worker batch takes the
    # pickled-queue fallback; the stream must not change
    with ParallelBatchPipeline(
        ds, 8, workers=2, shuffle=False, seed=0, epochs=1,
        transform=_aug, slot_bytes=8,
    ) as pipe:
        got = list(pipe)
        fallbacks = pipe.metrics.shm_fallbacks
    _assert_same_stream(serial, got)
    assert fallbacks == len(serial) - 1  # all but the serial probe batch


def test_metrics_snapshot_shape_and_occupancy():
    ds = _ds()
    with ParallelBatchPipeline(
        ds, 8, workers=2, shuffle=True, seed=0, epochs=1, transform=_aug
    ) as pipe:
        n = len(list(pipe))
        snap = pipe.metrics.snapshot()
    assert snap["batches"] == n
    assert snap["rows"] == n * 8
    assert snap["shm_fallbacks"] == 0
    for stage in ("produce", "worker_wait", "consumer_wait"):
        assert set(snap[stage]) == {
            "count", "mean_ms", "p50_ms", "p95_ms", "p99_ms"
        }
    # backpressure: the reorder buffer can never exceed the slot count
    # (slots release at in-order consumption, so workers*depth bounds it)
    assert snap["reorder_depth"]["max"] <= 2 * 2
    assert isinstance(pipe.metrics.json_line(), str)


def _straggler_aug(batch, r):
    # batches owned by one residue class stall: the OTHER workers must
    # not run unboundedly ahead while the sequence waits on them
    if int(batch["label"][0]) % 3 == 0:
        time.sleep(0.05)
    return {"data": batch["data"], "label": batch["label"]}


def test_backpressure_bounded_under_straggler():
    ds = ShardedDataset.from_arrays(
        {
            "data": np.zeros((240, 4), np.float32),
            "label": np.arange(240, dtype=np.int32),
        },
        2,
    )
    with ParallelBatchPipeline(
        ds, 8, workers=3, depth=2, shuffle=False, seed=0, epochs=1,
        transform=_straggler_aug,
    ) as pipe:
        n = len(list(pipe))
        depth_max = pipe.metrics.reorder_depth.max
    assert n == 30
    assert depth_max <= 3 * 2, depth_max


def test_training_through_pipeline_bit_identical():
    """Weights after training on the parallel feed == weights after the
    serial feed (the end-to-end determinism the resume/A-B contract
    rides on); composes with prefetch_to_device like the apps do."""
    import jax

    from sparknet_tpu.data.prefetch import prefetch_to_device
    from sparknet_tpu.proto import caffe_pb
    from sparknet_tpu.solver.trainer import Solver

    net_txt = """
name: "pipe"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 3
          weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
"""
    sp_txt = 'base_lr: 0.1\nlr_policy: "fixed"\nmomentum: 0.9\nmax_iter: 6\n'
    rng = np.random.default_rng(11)
    ds = ShardedDataset.from_arrays(
        {
            "data": rng.normal(size=(48, 6)).astype(np.float32),
            "label": rng.integers(0, 3, 48).astype(np.int32),
        },
        3,
    )

    def feed(workers):
        if workers:
            return ParallelBatchPipeline(
                ds, 8, workers=workers, shuffle=True, seed=5
            )
        return ds.batches(8, shuffle=True, seed=5)

    results = []
    for workers in (0, 2):
        sp = caffe_pb.load_solver(sp_txt, is_path=False)
        sp.net_param = caffe_pb.load_net(net_txt, is_path=False)
        solver = Solver(sp, {"data": (8, 6), "label": (8,)})
        raw = feed(workers)
        solver.step(prefetch_to_device(raw, size=2), 6)
        getattr(raw, "close", lambda: None)()
        results.append(jax.device_get(solver.params))
    a, b = results
    for layer in a:
        for name in a[layer]:
            np.testing.assert_array_equal(a[layer][name], b[layer][name])
    _assert_no_leaks()


def test_worker_count_resolution():
    assert resolve_data_workers(0) == 0
    assert resolve_data_workers(3) == 3
    env = os.environ.get("SPARKNET_DATA_WORKERS")
    try:
        os.environ["SPARKNET_DATA_WORKERS"] = "5"
        assert default_data_workers() == 5
        assert resolve_data_workers(-1) == 5
        assert resolve_data_workers(None) == 5
        os.environ["SPARKNET_DATA_WORKERS"] = "0"
        assert default_data_workers() == 0
        del os.environ["SPARKNET_DATA_WORKERS"]
        # cpu-count aware: bounded, serial on tiny hosts
        assert 0 <= default_data_workers() <= 4
    finally:
        if env is None:
            os.environ.pop("SPARKNET_DATA_WORKERS", None)
        else:
            os.environ["SPARKNET_DATA_WORKERS"] = env
    with pytest.raises(ValueError):
        ParallelBatchPipeline(_ds(), 8, workers=0)


def test_app_feed_constructor_uses_pipeline():
    """The apps' make_feed(workers=N) returns the pipeline and the
    stream equals the serial make_feed stream (the --data-workers /
    SPARKNET_DATA_WORKERS wiring, without running a whole app)."""
    from sparknet_tpu.apps.imagenet_app import make_feed
    from sparknet_tpu.data.preprocess import Transformer

    rng = np.random.default_rng(2)
    ds = ShardedDataset.from_arrays(
        {
            "data": rng.integers(0, 255, (40, 12, 12, 3)).astype(np.uint8),
            "label": np.arange(40, dtype=np.int32),
        },
        2,
    )
    tf = Transformer(crop_size=8, mirror=True, train=True, mean_values=[3.0])
    serial = make_feed(ds, tf, 8, seed=4)
    par = make_feed(ds, tf, 8, seed=4, workers=2)
    assert isinstance(par, ParallelBatchPipeline)
    try:
        a = [next(serial) for _ in range(6)]
        b = [next(par) for _ in range(6)]
    finally:
        par.close()
    _assert_same_stream(a, b)
