"""Native data runtime (C++ via ctypes) vs the pure-Python path."""

import numpy as np
import pytest

from sparknet_tpu import native
from sparknet_tpu.data.cifar import _decode_binary
from sparknet_tpu.data.preprocess import Transformer

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no g++?)"
)


def test_cifar_decode_matches_python():
    rng = np.random.default_rng(0)
    raw = bytes(rng.integers(0, 256, 3073 * 7).astype(np.uint8))
    ni, nl = native.cifar_decode(raw)
    pi, pl = _decode_binary(raw)
    np.testing.assert_array_equal(ni, pi)
    np.testing.assert_array_equal(nl, pl)


def test_transform_center_crop_matches_python():
    """Deterministic settings (TEST phase): native == Transformer."""
    rng = np.random.default_rng(1)
    images = rng.integers(0, 256, (6, 32, 32, 3)).astype(np.uint8)
    mean = rng.normal(size=(32, 32, 3)).astype(np.float32)
    t = Transformer(scale=0.5, mean_image=mean, crop_size=28, train=False)
    ref = t(images, np.random.default_rng(0))
    out = native.transform_batch(
        images, crop=28, train=False, mean_image=mean, scale=0.5
    )
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-5)


def test_transform_mean_channel_and_threads():
    rng = np.random.default_rng(2)
    images = rng.integers(0, 256, (16, 8, 8, 3)).astype(np.uint8)
    mc = np.array([104.0, 117.0, 123.0], np.float32)
    a = native.transform_batch(images, mean_channel=mc, num_threads=1)
    b = native.transform_batch(images, mean_channel=mc, num_threads=8)
    np.testing.assert_array_equal(a, b)  # thread count can't change output
    np.testing.assert_allclose(
        a, images.astype(np.float32) - mc, rtol=1e-6
    )


def test_transform_train_crop_in_bounds_and_seed_deterministic():
    rng = np.random.default_rng(3)
    images = rng.integers(0, 256, (32, 16, 16, 3)).astype(np.uint8)
    a = native.transform_batch(images, crop=8, train=True, mirror=True, seed=7)
    b = native.transform_batch(images, crop=8, train=True, mirror=True, seed=7)
    c = native.transform_batch(images, crop=8, train=True, mirror=True, seed=8)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)  # different seed, different crops
    assert a.shape == (32, 8, 8, 3)


def test_loader_epoch_coverage_and_determinism():
    """One epoch visits each sample at most once (Feistel shuffle is a
    permutation); two loaders with the same seed produce identical
    streams regardless of thread count."""
    n = 64
    rng = np.random.default_rng(4)
    images = rng.integers(0, 256, (n, 8, 8, 3)).astype(np.uint8)
    labels = np.arange(n, dtype=np.int32)  # label == sample id

    def stream(threads):
        ld = native.NativeLoader(
            images, labels, batch_size=8, train=False, seed=5,
            num_threads=threads,
        )
        try:
            return [next(ld) for _ in range(16)]  # 2 epochs
        finally:
            ld.close()

    s1, s2 = stream(1), (stream(4))
    for b1, b2 in zip(s1, s2):
        np.testing.assert_array_equal(b1["label"], b2["label"])
        np.testing.assert_array_equal(b1["data"], b2["data"])
    # epoch 0 = batches 0..7: every sample exactly once
    seen = np.concatenate([b["label"] for b in s1[:8]])
    assert sorted(seen.tolist()) == list(range(n))
    # epoch 1 differs in order from epoch 0
    seen2 = np.concatenate([b["label"] for b in s1[8:]])
    assert sorted(seen2.tolist()) == list(range(n))
    assert seen.tolist() != seen2.tolist()


def test_loader_transform_matches_native_transform():
    """Loader batches equal sn_transform_batch on the same permuted rows
    (data path consistency), including mean subtraction."""
    n = 32
    rng = np.random.default_rng(6)
    images = rng.integers(0, 256, (n, 12, 12, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, n).astype(np.int32)
    mc = np.array([10.0, 20.0, 30.0], np.float32)
    ld = native.NativeLoader(
        images, labels, batch_size=4, crop=8, train=False,
        mean_channel=mc, scale=0.25, seed=9, num_threads=2,
    )
    try:
        batch = next(ld)
    finally:
        ld.close()
    # reconstruct: which source rows were batch 0? labels identify them
    # only statistically; instead just verify value semantics on one row
    # by matching against all candidate source rows
    cand = native.transform_batch(
        images, crop=8, train=False, mean_channel=mc, scale=0.25
    )
    for row in batch["data"]:
        assert any(
            np.allclose(row, cand[j], atol=1e-5) for j in range(n)
        )


def test_loader_rejects_batch_larger_than_dataset():
    images = np.zeros((4, 8, 8, 3), np.uint8)
    labels = np.zeros((4,), np.int32)
    with pytest.raises(ValueError):
        native.NativeLoader(images, labels, batch_size=8)


def test_transform_both_means_and_scalar_mean_value():
    """Both mean_image and mean_channel subtract (preprocess.py parity);
    a single mean_value broadcasts to all channels."""
    rng = np.random.default_rng(7)
    images = rng.integers(0, 256, (3, 8, 8, 3)).astype(np.uint8)
    mean_img = rng.normal(size=(8, 8, 3)).astype(np.float32)
    mc1 = np.array([50.0], np.float32)  # scalar mean_value
    t = Transformer(scale=2.0, mean_image=mean_img, mean_values=mc1,
                    train=False)
    ref = t(images, np.random.default_rng(0))
    out = native.transform_batch(
        images, train=False, mean_image=mean_img, mean_channel=mc1, scale=2.0
    )
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_crop_larger_than_image_raises():
    images = np.zeros((2, 8, 8, 3), np.uint8)
    with pytest.raises(ValueError):
        native.transform_batch(images, crop=16)
    with pytest.raises(ValueError):
        native.NativeLoader(images, np.zeros(2, np.int32), 1, crop=16)


# ------------------------------------------------- the loader's counters

def _counted_loader(threads, side=48, **kw):
    rng = np.random.default_rng(9)
    images = rng.integers(0, 256, (64, side, side, 3)).astype(np.uint8)
    labels = np.arange(64, dtype=np.int32)
    return native.NativeLoader(
        images, labels, batch_size=16, crop=side - 8, train=True, mirror=True,
        seed=2, num_threads=threads, **kw,
    )


def test_loader_stats_are_monotone_and_count_the_batches_taken():
    ld = _counted_loader(2)
    try:
        assert set(ld.stats()) == set(native.STATS)
        before = ld.stats()
        for taken in range(1, 13):
            next(ld)
            now = ld.stats()
            assert all(now[k] >= before[k] for k in native.STATS), (before, now)
            assert now["batches_taken"] == taken
            # nothing is handed out before it is built, and the workers
            # run at most the window (4) plus one batch each ahead
            assert taken <= now["batches_built"] <= taken + 4 + 2
            before = now
        assert before["build_ns"] > 0 and before["copy_ns"] > 0
    finally:
        ld.close()
    assert ld.stats() == ld.stats()  # the last reading, once closed
    assert ld.stats()["batches_taken"] == 12


def test_a_slow_consumer_reads_as_worker_backpressure():
    import time

    ld = _counted_loader(2, queue_cap=2)
    try:
        next(ld)
        time.sleep(0.3)  # the window fills; both workers park on it
        for _ in range(3):
            next(ld)
        stats = ld.stats()
    finally:
        ld.close()
    # two workers parked for most of the sleep, the consumer for none
    assert stats["put_wait_ns"] > 0.3e9, stats
    assert stats["get_wait_ns"] < 0.1e9, stats
    assert stats["depth_on_arrival"] >= 2  # it found batches waiting,
    # which the input pipeline: line shows as the queue's depth
    assert ld.metrics.snapshot()["reorder_depth"]["max"] >= 1
    assert ld.metrics.snapshot()["worker_wait"]["count"] >= 4


def test_one_thread_and_a_fast_consumer_read_as_loader_blocked():
    from sparknet_tpu.telemetry import timeline

    tl = timeline.Timeline(fence=False)
    timeline.set_current(tl)
    try:
        ld = _counted_loader(1, side=200)  # a batch takes milliseconds
        try:
            for _ in range(24):
                next(ld)
            stats = ld.stats()
        finally:
            ld.close()
    finally:
        timeline.set_current(None)
    # the consumer outruns the one worker: it waits for every batch about
    # as long as the batch takes to build, and the worker never waits
    assert stats["get_wait_ns"] > 0.5 * stats["build_ns"], stats
    assert stats["put_wait_ns"] < 0.2 * stats["build_ns"], stats
    snap = ld.metrics.snapshot()
    assert snap["consumer_wait"]["count"] == 24
    assert snap["produce"]["count"] == stats["batches_built"]
    # the same deltas reached the current timeline, as background rows
    seconds = tl.phase_seconds()
    assert seconds["feed.loader_blocked"] == pytest.approx(
        1e-9 * stats["get_wait_ns"], rel=1e-6
    )
    assert seconds["feed.produce"] == pytest.approx(
        1e-9 * stats["build_ns"], rel=1e-6
    )
    assert tl.snapshot()["background"]["feed.produce"]["count"] == (
        stats["batches_built"]
    )
    # and the copy out of the queue, the other part of a next() of it
    assert seconds["feed.copy_out"] == pytest.approx(
        1e-9 * stats["copy_ns"], rel=1e-6
    )
    assert tl.snapshot()["background"]["feed.copy_out"]["count"] == 24
    assert tl.snapshot()["phases"] == {}


def test_loader_metrics_are_a_registry_source_and_survive_close():
    import json

    from sparknet_tpu.data.pipeline import PipelineMetrics
    from sparknet_tpu.data.prefetch import maybe_prefetch
    from sparknet_tpu.telemetry import REGISTRY

    ld = _counted_loader(2)
    assert isinstance(ld.metrics, PipelineMetrics)
    assert REGISTRY.sources()["native_loader"] is ld.metrics
    # maybe_prefetch hands the staging thread the loader's own metrics
    import argparse

    feed = maybe_prefetch(ld, argparse.Namespace(prefetch=2), "none")
    for _ in range(5):
        next(feed)
    feed.close()
    ld.close()
    line = json.loads(ld.metrics.json_line())  # the apps' input pipeline: line
    assert line["rows"] == 16 * line["batches"] and line["batches"] >= 5
    assert line["prefetch"]["hits"] + line["prefetch"]["misses"] == 5
    for block in ("produce", "worker_wait", "consumer_wait"):
        assert line[block]["count"] > 0, (block, line)
    assert line["prefetch"]["wait"]["count"] == 5
    with pytest.raises(StopIteration):
        next(ld)  # closed


def test_a_library_without_the_newest_symbol_is_stale(tmp_path, monkeypatch):
    """make goes by mtimes; a copied tree's say nothing.  A library on disk
    that lacks sn_loader_stats is rebuilt (make -B), never loaded."""
    assert not native._is_stale()  # the one this process loaded
    old = tmp_path / "libsparknet_data.so"
    with open(native._LIB_PATH, "rb") as fh:
        old.write_bytes(fh.read().replace(b"sn_loader_stats", b"sn_loader_stat_"))
    monkeypatch.setattr(native, "_LIB_PATH", str(old))
    assert native._is_stale()
    monkeypatch.setattr(native, "_LIB_PATH", str(tmp_path / "absent.so"))
    assert not native._is_stale()  # not there: make builds it
    ran = []
    monkeypatch.setattr(native, "_LIB_PATH", str(old))
    monkeypatch.setattr(
        native.subprocess, "run", lambda argv, **kw: ran.append(argv)
    )
    assert native._build() is None and ran[0][-1] == "-B"
