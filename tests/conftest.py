"""Test env: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; all sharding tests run on
``xla_force_host_platform_device_count=8`` CPU devices, mirroring how the
driver dry-runs the multi-chip path.

``SPARKNET_TEST_TPU=1`` keeps the real backend instead, for the
hardware-gated tests — on the chip:
``SPARKNET_TEST_TPU=1 python -m pytest tests/test_attention.py -k on_hardware``.

The suite is compile-bound (every jit traces + XLA-compiles), so the
persistent compilation cache is on, placed by the one rule in
``sparknet_tpu/utils/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR``
where it is set, else ``<checkout>/.jax_cache/`` (gitignored).  The
variable is exported either way so that the many subprocess-spawning
tests — app CLIs, multi-host clusters — share the directory, and every
compile is persisted (jaxlib 0.9.0 round-trips the near-instant ones
that crashed its predecessor's serializer).  Delete the dir to force
cold compiles; ``SPARKNET_TEST_NO_CACHE=1`` disables it.
"""

import os

if os.environ.get("SPARKNET_TEST_TPU", "") not in ("", "0"):
    import jax  # real accelerator: leave the backend alone
else:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"  # subprocess tests inherit it

    import jax

if os.environ.get("SPARKNET_TEST_NO_CACHE", "") in ("", "0"):
    from sparknet_tpu.utils import compile_cache

    os.environ.setdefault(compile_cache.ENV, compile_cache.enable())
else:
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    jax.config.update("jax_enable_compilation_cache", False)


import glob
import multiprocessing

import pytest


@pytest.fixture(autouse=True, scope="session")
def assert_no_pipeline_leaks(tmp_path_factory):
    """Tier-1 runs on CPU and must stay leak-free: after the whole
    session, no input-pipeline worker process may still be alive — the
    originals AND the chaos-era *respawned* replacements (named
    ``{SHM_PREFIX}-worker-{r}-r{n}``; a supervisor that forgets its
    respawns would pass a naive check) — and no shared-memory slot may
    survive in /dev/shm, including the replacement slots respawns add
    (``..._r{n}`` names).  data/pipeline.py names everything with the
    SHM_PREFIX, so stray ones are attributable.

    The cross-job decoded-batch cache (data/cache.py) persists named
    ``{SHM_CACHE_PREFIX}_*`` segments ON PURPOSE across jobs — but a
    test run is a closed world: every test that opens a cache namespace
    must ``clear()`` it, and any segment that survives the session is
    an orphan this fixture names."""
    yield
    import re

    from sparknet_tpu.data.cache import SHM_CACHE_PREFIX
    from sparknet_tpu.data.pipeline import SHM_PREFIX

    stray = [
        p for p in multiprocessing.active_children()
        if p.name.startswith(SHM_PREFIX)
    ]
    respawned = [p for p in stray if re.search(r"-r\d+$", p.name)]
    assert not stray, (
        f"input-pipeline workers leaked past tests: {stray}"
        + (f" (orphaned respawned workers: {respawned})" if respawned else "")
    )
    if os.path.isdir("/dev/shm"):
        segs = glob.glob(f"/dev/shm/{SHM_PREFIX}_*")
        assert not segs, f"shared-memory segments leaked past tests: {segs}"
        cache_segs = glob.glob(f"/dev/shm/{SHM_CACHE_PREFIX}_*")
        assert not cache_segs, (
            f"decoded-batch cache segments leaked past tests (a test "
            f"opened a cache namespace without clear()): {cache_segs}"
        )
    # storage-fault hygiene (utils/safeio.py): every atomic writer
    # must either publish (rename) or unlink its staging file, even
    # under injected ENOSPC/EIO, and an abandoned tee shard must be
    # renamed ``.writing.quarantined`` — so NO bare ``*.tmp*`` or
    # ``*.writing`` file may survive the suite anywhere under pytest's
    # session temp root.
    base = str(tmp_path_factory.getbasetemp())
    stale = []
    for root, _dirs, files in os.walk(base):
        for name in files:
            if name.endswith(".writing") or ".tmp" in name:
                stale.append(os.path.join(root, name))
    assert not stale, (
        f"staging files leaked past tests (a writer failed without "
        f"cleaning up its tmp, or a torn tee shard was not "
        f"quarantined): {stale[:20]}"
    )


TEST_ALARM_S = 300.0


@pytest.fixture(autouse=True)
def _per_test_alarm(request):
    """A test that hangs costs itself, not the run's clock: after
    ``TEST_ALARM_S`` seconds a SIGALRM fails the one test, by name.
    (``pytest-timeout`` is not installed.)  Main thread only — a signal
    handler runs there, and so does every test body; it interrupts a
    blocking ``wait``/``join``/``communicate``, which is where a
    subprocess test hangs."""
    import signal
    import threading

    if (
        not hasattr(signal, "setitimer")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return

    def fail(_signum, _frame):
        pytest.fail(
            f"{request.node.nodeid} still running after {TEST_ALARM_S:.0f} s "
            f"(tests/conftest.py per-test alarm)",
            pytrace=True,
        )

    before = signal.signal(signal.SIGALRM, fail)
    signal.setitimer(signal.ITIMER_REAL, TEST_ALARM_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


# A test this repository may not edit yet and a later change made untrue.
# ``tests/benchmark`` is one of BENCHMARK.json's ``paths``: only a
# ``benchmark`` PR may edit a file there, and any other PR that does is
# refused; and a PR's new entries go at the END of BENCHMARK.json's lists
# (one put in the middle reads as a change to what was there).  Since PR 35:
# ``test_ling``'s manifest test also asserts that ``ling_train_s16k``'s entries
# are the LAST of ``workloads`` and of ``per_layer``, which held until the next
# cell was appended.  Nothing else of it is muted:
# ``test_mellum.test_the_cell_before_this_one_keeps_all_but_its_place_at_the_end``
# calls it, holds that this assertion is the first and only one to fail, and
# asserts what follows it.  A ``benchmark`` PR drops the ``[-5:]`` / ``[-1]``
# assertion, this entry and that test.  Likewise ``test_scope_metrics``'
# manifest test, which asserts that the ten scope metrics are the LAST of
# ``per_layer``: the state-space cell's three were appended after them.
# ``test_granite.test_the_scope_metrics_keep_all_but_their_place_at_the_end``
# holds it as the other holds ``test_ling``'s.  The same for
# ``test_granite``'s manifest test and that holder itself, which assert that
# the state-space cell's entries end the lists: the convolutional hybrid's
# were appended after them, and ``test_lfm2`` holds both the same way.
_OVERTAKEN = {
    "tests/benchmark/test_ling.py::test_manifest_entries_are_the_issues":
        "asserts its cell's entries are the last of BENCHMARK.json's lists; "
        "PR 35 appended a cell (PERF.md section 7 row 6)",
    "tests/benchmark/test_scope_metrics.py::test_manifest_entries_are_the_issues_appended_last":
        "asserts the scope metrics are the last of BENCHMARK.json's per_layer; "
        "the state-space cell's three were appended after them (PERF.md "
        "section 7 row 6)",
    "tests/benchmark/test_granite.py::test_manifest_entries_are_appended_after_the_others":
        "asserts its cell's entries are the last of BENCHMARK.json's lists; "
        "the convolutional hybrid's cell was appended after them (PERF.md "
        "section 7 row 6)",
    "tests/benchmark/test_granite.py::test_the_scope_metrics_keep_all_but_their_place_at_the_end":
        "asserts the state-space cell's three metrics end BENCHMARK.json's "
        "per_layer; the convolutional hybrid's three were appended after "
        "them (PERF.md section 7 row 6)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        reason = _OVERTAKEN.get(item.nodeid)
        if reason:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=False))
