#!/usr/bin/env python
"""Quantized-serving smoke (ISSUE 12 satellite, run by scripts/check.sh).

The quantization story's load-bearing guarantees in one short CPU run:

1. build an f32 engine from the cifar10_quick deploy net, snapshot its
   weights (manifest-verified solverstate — the scale-capture source);
2. bring up an **int8 1-replica tier** (engine + batcher + HTTP
   server) from that snapshot and prove the hot-swap path: ``/reload``
   to a newer solverstate bumps the generation, ``/healthz`` and the
   ``/classify`` response both carry ``"quant": "int8"`` next to
   ``gen`` (the machine-checkable A/B surface);
3. assert f32-vs-int8 **top-1 agreement >= 99.5%** on a fixed batch
   (the <0.5% disagreement bar) and int8's resident weight bytes at
   most two thirds of f32's, on every check run;
4. assert the **persistent compile cache cannot alias precisions**:
   the f32 and int8 fingerprints differ, each fingerprint-keyed cache
   directory exists and holds its own entries;
5. lint: the fusion audit reads ONLY recorded traces — neither
   ``scripts/fusion_audit.py`` nor ``serve/quantize.py`` may grow an
   ad-hoc ``perf_counter`` clock, and the frozen allowlist must not
   have been bumped for them.

Exit 0 on success; any assertion prints the evidence and exits 1.
"""

from __future__ import annotations

import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

DEPLOY = os.path.join(
    REPO, "sparknet_tpu", "models", "prototxt",
    "cifar10_quick_deploy.prototxt",
)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import jax

    from sparknet_tpu.serve.compile_cache import (
        cache_entries,
        enable_persistent_cache,
    )
    from sparknet_tpu.serve.engine import InferenceEngine
    from sparknet_tpu.serve.quantize import tree_bytes
    from sparknet_tpu.solver import snapshot as snap

    tmp = tempfile.mkdtemp(prefix="quant_smoke_")
    cache_root = os.path.join(tmp, "compile_cache")

    # ---- 5 first (pure text checks, no jax warmup needed to fail fast)
    audit_src = open(os.path.join(HERE, "fusion_audit.py")).read()
    assert "perf_counter" not in audit_src, (
        "fusion_audit.py grew an ad-hoc clock — all fusion-audit "
        "timing must come from the recorded trace/timeline files"
    )
    quant_src = open(os.path.join(
        REPO, "sparknet_tpu", "serve", "quantize.py"
    )).read()
    assert "perf_counter" not in quant_src, (
        "serve/quantize.py grew an ad-hoc clock — route timing "
        "through telemetry/"
    )
    allow = open(os.path.join(HERE, "perf_counter_allowlist.txt")).read()
    assert "quantize" not in allow and "fusion" not in allow, (
        "the perf_counter allowlist was bumped for quant/fusion code "
        "— ISSUE 12 requires it unchanged"
    )

    # ---- f32 reference + the verified snapshot the scales come from
    f32 = InferenceEngine.from_files(DEPLOY, buckets=(1, 8))
    cc32 = enable_persistent_cache(cache_root, f32.fingerprint)
    f32.warmup()
    w0 = os.path.join(tmp, "w_iter_10.solverstate.npz")
    w1 = os.path.join(tmp, "w_iter_20.solverstate.npz")
    params = jax.device_get(f32.params)
    state = jax.device_get(f32.state)
    snap.save_state(w0, params=params, state=state)
    snap.save_state(w1, params=params, state=state)

    # ---- the int8 1-replica tier
    int8 = InferenceEngine.from_files(DEPLOY, w0, buckets=(1, 8),
                                      quant="int8")
    assert int8.fingerprint != f32.fingerprint, (
        f"int8 and f32 engines share a fingerprint "
        f"({f32.fingerprint}) — precision compile caches would alias"
    )
    # resident weights: int8 holds about a quarter of f32's bytes plus
    # the scale vectors; 1.5x is the floor a deployment is promised
    held = tree_bytes(f32.params) / tree_bytes(int8.params)
    assert held >= 1.5, (
        f"int8 weights are only {held:.2f}x smaller than f32's"
    )
    cc8 = enable_persistent_cache(cache_root, int8.fingerprint)
    int8.warmup()
    assert cc32["dir"] != cc8["dir"], (cc32, cc8)
    e32 = cache_entries(cc32["dir"])
    e8 = cache_entries(cc8["dir"])
    assert e32 > 0 and e8 > 0, (
        f"expected entries in BOTH precision cache dirs, got "
        f"f32={e32} ({cc32['dir']}) int8={e8} ({cc8['dir']})"
    )

    from sparknet_tpu.serve.server import InferenceServer

    server = InferenceServer(int8, port=0).start()
    try:
        client = server.client(timeout=60)
        st, hz = client.healthz()
        assert st == 200 and hz.get("quant") == "int8", hz
        gen0 = hz.get("generation", 0)

        # hot-swap a NEW snapshot into the running int8 tier: scales
        # re-captured from the verified file, generation bumps
        st, doc = client.reload(w1)
        assert st == 200 and doc.get("generation", 0) > gen0, doc

        rng = np.random.default_rng(0)
        probe = rng.normal(size=(64, 32, 32, 3)).astype(np.float32)
        st, resp = client.classify(probe.tolist(), top_k=1)
        assert st == 200 and resp.get("quant") == "int8", resp
        got = np.asarray(resp["indices"])[:, 0]
        want, _ = f32.topk(probe, 1)
        agree = float((got == want[:, 0]).mean())
        assert agree >= 0.995, (
            f"int8 top-1 agreement {agree:.4f} < 0.995 vs f32"
        )
        print(
            "quant smoke: OK — int8 tier hot-swapped to gen "
            f"{doc['generation']} (quant tag on healthz+classify), "
            f"top-1 agreement {agree:.3f} on {len(probe)} rows, "
            f"weights {held:.2f}x smaller, "
            f"precision-distinct cache dirs "
            f"(f32 {e32} entries, int8 {e8} entries), "
            "no new ad-hoc clocks"
        )
        return 0
    finally:
        server.stop()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
