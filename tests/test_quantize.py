"""Quantized inference (serve/quantize.py, ISSUE 12): per-channel
scale capture from real snapshots, int8 pack/unpack bit-stability
across processes, f32-vs-int8 top-1 agreement, fingerprint uniqueness
across (arch, layout, precision) and the engine/server/router quant
surfaces."""

import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest
import jax

from sparknet_tpu.nets.xlanet import XLANet
from sparknet_tpu.proto import caffe_pb
from sparknet_tpu.serve import quantize
from sparknet_tpu.serve.compile_cache import net_fingerprint
from sparknet_tpu.serve.engine import InferenceEngine
from sparknet_tpu.solver import snapshot as snap

REPO = os.path.join(os.path.dirname(__file__), "..")

TOY_DEPLOY = """
name: "toy"
input: "data"
input_shape { dim: 1 dim: 3 dim: 8 dim: 8 }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
        convolution_param { num_output: 4 kernel_size: 3 pad: 1
          weight_filler { type: "gaussian" std: 0.2 } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
        pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
        inner_product_param { num_output: 5
          weight_filler { type: "gaussian" std: 0.2 } } }
layer { name: "prob" type: "Softmax" bottom: "ip1" top: "prob" }
"""

TOY2_DEPLOY = TOY_DEPLOY.replace("num_output: 5", "num_output: 6")


def toy_net(text=TOY_DEPLOY, seed=7):
    net = XLANet(caffe_pb.load_net(text, is_path=False), "TEST")
    params, state = net.init(jax.random.PRNGKey(seed))
    return net, params, state


def toy_engine(quant=None, buckets=(4, 8), seed=7, warm=True):
    net, params, state = toy_net(seed=seed)
    eng = InferenceEngine(net, params, state, buckets=buckets,
                          quant=quant)
    return eng.warmup() if warm else eng


def toy_rows(n, seed=0):
    return (
        np.random.default_rng(seed).normal(size=(n, 8, 8, 3))
        .astype(np.float32)
    )


# ------------------------------------------------------------ scale capture
def test_weight_scale_is_per_output_channel_absmax():
    net, params, state = toy_net()
    scales = quantize.capture_scales(net, params)
    assert set(scales) == {"conv1", "ip1"}
    w = np.asarray(params["conv1"]["weight"])  # HWIO
    want = np.abs(w).reshape(-1, w.shape[-1]).max(0) / 127.0
    np.testing.assert_allclose(scales["conv1"], want, rtol=1e-6)
    assert scales["conv1"].shape == (4,)
    assert scales["ip1"].shape == (5,)


def test_quantize_dequantize_error_bounded_by_half_scale():
    net, params, state = toy_net()
    q = quantize.quantize_tree(net, params)
    assert np.asarray(q["conv1"]["weight"]).dtype == np.int8
    deq = quantize.dequantize_tree(q)
    for lname in ("conv1", "ip1"):
        w = np.asarray(params[lname]["weight"])
        err = np.abs(np.asarray(deq[lname]["weight"]) - w)
        step = np.asarray(q[lname][quantize.SCALE_KEY])
        assert (err <= step / 2 + 1e-7).all(), lname
        # biases ride through untouched
        np.testing.assert_array_equal(
            np.asarray(deq[lname]["bias"]),
            np.asarray(params[lname]["bias"]),
        )


def test_scale_capture_from_verified_snapshot(tmp_path):
    """The hot-swap capture path: scales come from the newest
    manifest-INTACT solverstate — a torn newer file is skipped."""
    net, params, state = toy_net()
    prefix = str(tmp_path / "w")
    good = f"{prefix}_iter_10.solverstate.npz"
    snap.save_state(good, params=jax.device_get(params),
                    state=jax.device_get(state))
    # a torn newer snapshot must be skipped, not quantized
    torn = f"{prefix}_iter_20.solverstate.npz"
    with open(good, "rb") as fh:
        raw = fh.read()
    with open(torn, "wb") as fh:
        fh.write(raw[: len(raw) // 2])
    qparams, qstate, it = quantize.quantize_snapshot(net, prefix)
    assert it == 10
    want = quantize.quantize_tree(net, jax.device_get(params))
    np.testing.assert_array_equal(
        np.asarray(qparams["conv1"]["weight"]),
        np.asarray(want["conv1"]["weight"]),
    )


def test_int8_pack_bit_stable_across_processes(tmp_path):
    """The packed tree round-trips the snapshot format bit-exactly in
    a DIFFERENT process (no float re-derivation on load)."""
    net, params, state = toy_net()
    q = quantize.quantize_tree(net, params)
    path = str(tmp_path / "q_iter_1.solverstate.npz")
    snap.save_state(path, params=jax.device_get(q))

    def digest(tree):
        h = hashlib.sha256()
        for kp, leaf in sorted(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            key=lambda t: jax.tree_util.keystr(t[0]),
        ):
            a = np.asarray(leaf)
            h.update(jax.tree_util.keystr(kp).encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
        return h.hexdigest()

    child = subprocess.run(
        [sys.executable, "-c", (
            "import sys, hashlib, numpy as np, jax\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "from sparknet_tpu.solver import snapshot as snap\n"
            "st = snap.load_state(sys.argv[1])\n"
            "h = hashlib.sha256()\n"
            "for kp, leaf in sorted("
            "jax.tree_util.tree_flatten_with_path(st['params'])[0],"
            "key=lambda t: jax.tree_util.keystr(t[0])):\n"
            "    a = np.asarray(leaf)\n"
            "    h.update(jax.tree_util.keystr(kp).encode())\n"
            "    h.update(str(a.dtype).encode())\n"
            "    h.update(a.tobytes())\n"
            "print(h.hexdigest())\n"
        ), path],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert child.returncode == 0, child.stderr
    assert child.stdout.strip() == digest(q)


# --------------------------------------------------------------- agreement
def test_int8_and_bf16_outputs_within_tolerance():
    """Quantized outputs against the f32 engine on the same seeded rows,
    compared as probabilities, not as arg-max: with random weights the
    closest f32 top-2 margin on these 128 rows is 9e-4, far inside any
    quantizer's rounding, so the top class flips on rounding (126/128
    agree under jax 0.9) and an agreement rate measures the seed, not
    the quantizer (model-configs guide, section 3).

    Tolerances (absolute, on softmax outputs in [0, 1]): int8 carries
    per-channel weight and per-row activation rounding of about 1/127
    through two layers — observed max 0.016, bound 0.05; bf16 keeps 8
    mantissa bits — observed max 0.004, bound 0.01.  Both bounds are
    about 3x the observed error and an order of magnitude below what
    the next coarser format (int4, fp8) would produce, so computing in
    a lower precision than the mode states fails."""
    ref = np.asarray(toy_engine().infer(toy_rows(128)))
    for quant, atol in (("int8", 0.05), ("bf16", 0.01)):
        out = np.asarray(toy_engine(quant=quant).infer(toy_rows(128)))
        assert np.isfinite(out).all(), quant
        err = float(np.abs(out - ref).max())
        assert err <= atol, (quant, err)
        assert err > 0.0, quant  # the quantized path really ran


def test_int8_padded_rows_bit_identical():
    """Per-ROW activation scales: a request's outputs can't depend on
    the engine's zero padding or bucket co-riders (the serving
    row-independence contract, held for int8 like f32)."""
    eng = toy_engine(quant="int8", buckets=(4,))
    rows = toy_rows(4, seed=3)
    full = np.asarray(eng.infer(rows))
    part = np.asarray(eng.infer(rows[:2]))  # padded 2 -> 4
    np.testing.assert_array_equal(part, full[:2])


# ------------------------------------------------------------- fingerprints
def test_fingerprint_unique_across_arch_layout_precision():
    from sparknet_tpu.parallel import partition

    net, params, state = toy_net()
    net2, params2, state2 = toy_net(TOY2_DEPLOY)
    q = quantize.quantize_tree(net, params)
    lay = partition.parse_layout("dp=1", rules="tp")
    fps = {
        "f32": net_fingerprint(net, params, state, "float32"),
        "bf16": net_fingerprint(
            net, quantize.bf16_tree(params), state, "bfloat16",
            quant="bf16",
        ),
        "int8": net_fingerprint(net, q, state, "float32", quant="int8"),
        "arch2": net_fingerprint(net2, params2, state2, "float32"),
        "layout": net_fingerprint(
            net, params, state, "float32", layout=lay
        ),
    }
    assert len(set(fps.values())) == len(fps), fps


def test_engine_quant_modes_never_share_executable_keys():
    f32 = toy_engine(warm=False)
    int8 = toy_engine(quant="int8", warm=False)
    bf16 = toy_engine(quant="bf16", warm=False)
    assert len({f32.fingerprint, int8.fingerprint, bf16.fingerprint}) == 3
    # the in-memory executable cache key leads with the fingerprint
    f32._executable(4)
    int8._executable(4)
    keys = set(f32._cache) | set(int8._cache)
    assert len(keys) == 2


def test_quant_mode_validation():
    with pytest.raises(ValueError, match="quant mode"):
        toy_engine(quant="fp4", warm=False)
    from sparknet_tpu.parallel import partition

    net, params, state = toy_net()
    with pytest.raises(ValueError, match="layout"):
        InferenceEngine(
            net, params, state, buckets=(4,), quant="int8",
            layout=partition.parse_layout("dp=1", rules="tp"),
        )


# ----------------------------------------------------------------- hot swap
def test_int8_hot_swap_recaptures_scales(tmp_path):
    """swap_from_file on an int8 engine: scales re-captured from the
    verified snapshot (outputs track the new weights), generation
    bumps, and the merge base is the retained f32 reference — not the
    quantized tree."""
    eng = toy_engine(quant="int8", buckets=(4,))
    rows = toy_rows(4, seed=5)
    out0 = np.asarray(eng.infer(rows))

    # scaled-up weights -> different scales, different outputs
    net, params, state = toy_net()
    scaled = jax.tree_util.tree_map(
        lambda a: np.asarray(a) * 2.0, jax.device_get(params)
    )
    w = str(tmp_path / "w_iter_20.solverstate.npz")
    snap.save_state(w, params=scaled, state=jax.device_get(state))
    gen = eng.swap_from_file(w)
    assert gen == 1 and eng.quant == "int8"
    assert np.asarray(eng.params["conv1"]["weight"]).dtype == np.int8
    out1 = np.asarray(eng.infer(rows))
    assert not np.array_equal(out0, out1)
    # swapping the SAME file again is bit-stable (scale capture is
    # deterministic) and keeps bumping the generation
    gen2 = eng.swap_from_file(w)
    assert gen2 == 2
    np.testing.assert_array_equal(out1, np.asarray(eng.infer(rows)))


# ------------------------------------------------------- HTTP quant surface
def test_server_exposes_quant_on_healthz_and_classify():
    from sparknet_tpu.serve.server import InferenceServer

    eng = toy_engine(quant="int8", buckets=(4,))
    server = InferenceServer(eng, port=0).start()
    try:
        client = server.client(timeout=30)
        st, hz = client.healthz()
        assert st == 200 and hz["quant"] == "int8"
        st, resp = client.classify(toy_rows(2), top_k=2)
        assert st == 200 and resp["quant"] == "int8"
        assert "gen" in resp
    finally:
        server.stop()


def test_router_quant_ab_splits_and_records(tmp_path):
    """A 2-replica f32+int8 tier under quant_ab=0.5: the Bresenham
    draw splits a burst exactly in half, both variants answer, and
    the replica table carries the precision column's source field."""
    from sparknet_tpu.serve.loadgen import run_http_loadgen
    from sparknet_tpu.serve.router import Router
    from sparknet_tpu.serve.server import InferenceServer

    servers = [
        InferenceServer(toy_engine(buckets=(4,)), port=0).start(),
        InferenceServer(
            toy_engine(quant="int8", buckets=(4,)), port=0
        ).start(),
    ]
    router = Router(
        [(s.host, s.port) for s in servers], quant_ab=0.5
    )
    try:
        assert router.wait_healthy(timeout_s=30)
        router.start()
        lg = run_http_loadgen(
            router.host, router.port, (8, 8, 3),
            n_requests=40, sizes=(1, 2), concurrency=1,
        )
        assert lg["failed_requests"] == 0
        assert lg["served_quants"] == ["f32", "int8"]
        hz = router.healthz()
        assert hz["quant_ab"] == 0.5
        assert hz["quants"] == ["f32", "int8"]
        answered = {
            r["quant"]: r["forwarded"] for r in hz["replicas"]
        }
        assert answered == {"f32": 20, "int8": 20}, answered
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_router_quant_ab_falls_back_when_variant_down():
    """Variant preference never beats availability: with the int8
    replica dead, quant-preferring requests still answer on f32."""
    from sparknet_tpu.serve.router import Router
    from sparknet_tpu.serve.server import Client, InferenceServer

    f32_server = InferenceServer(toy_engine(buckets=(4,)), port=0).start()
    int8_server = InferenceServer(
        toy_engine(quant="int8", buckets=(4,)), port=0
    ).start()
    router = Router(
        [(f32_server.host, f32_server.port),
         (int8_server.host, int8_server.port)],
        quant_ab=1.0,  # EVERY request prefers the quant variant
        eject_after=1,
    )
    try:
        assert router.wait_healthy(timeout_s=30)
        router.start()
        int8_server.stop()
        client = Client(router.host, router.port, timeout=30, retries=4)
        oks = 0
        for _ in range(6):
            st, resp = client.classify(toy_rows(1), top_k=1)
            if st == 200:
                oks += 1
                assert resp["quant"] == "f32"
        assert oks == 6
    finally:
        router.stop()
        f32_server.stop()


def test_dash_replica_table_has_precision_column():
    from sparknet_tpu.telemetry import dash

    page = dash.render_html(
        {},
        router={
            "replicas_healthy": 1,
            "replicas_total": 1,
            "generations": [0],
            "router": {},
            "replicas": [{
                "index": 0, "healthy": True, "addr": "x:1",
                "outstanding": 0, "generation": 0, "quant": "int8",
                "forwarded": 3, "latency": {},
            }],
        },
    )
    assert "<th>precision</th>" in page
    assert "<td>int8</td>" in page
