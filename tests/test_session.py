"""Session-aware serving (ISSUE 13): the decode stepper, the
per-session state cache, engine.generate, session-affinity routing and
the satellites (loadgen skew mode, dash panel).

The expensive chaos e2e (subprocess tier, SIGKILL of the session
holder) lives in scripts/session_smoke.py (check.sh); these tests pin
the same semantics fast with in-process servers and a toy char-level
decoder small enough that the step compiles in well under a second."""

import threading
import time

import numpy as np
import pytest
import jax

from sparknet_tpu.nets.xlanet import XLANet
from sparknet_tpu.proto import caffe_pb
from sparknet_tpu.serve import session as session_mod
from sparknet_tpu.serve.engine import InferenceEngine
from sparknet_tpu.serve.session import (
    DISABLED,
    DecodeStepper,
    SessionCache,
)

VOCAB = 12

TOY_CHAR = """
name: "toy_char"
input: "data"
input_shape { dim: 6 dim: 1 }
input: "cont"
input_shape { dim: 6 dim: 1 }
layer { name: "embed" type: "Embed" bottom: "data" top: "emb"
        embed_param { num_output: 4 input_dim: 12 bias_term: false
          weight_filler { type: "uniform" min: -0.3 max: 0.3 } } }
layer { name: "lstm" type: "LSTM" bottom: "emb" bottom: "cont" top: "hid"
        recurrent_param { num_output: 6
          weight_filler { type: "uniform" min: -0.3 max: 0.3 }
          bias_filler { type: "constant" } } }
layer { name: "ip" type: "InnerProduct" bottom: "hid" top: "logits"
        inner_product_param { num_output: 12 axis: 2
          weight_filler { type: "gaussian" std: 0.2 } } }
layer { name: "prob" type: "Softmax" bottom: "logits" top: "prob"
        softmax_param { axis: 2 } }
"""

TOY_RNN = TOY_CHAR.replace('type: "LSTM"', 'type: "RNN"')


def char_engine(seed=3, **kw):
    net = XLANet(caffe_pb.load_net(TOY_CHAR, is_path=False), "TEST")
    params, state = net.init(jax.random.PRNGKey(seed))
    return InferenceEngine(net, params, state, **kw).warmup()


# ------------------------------------------------------------- stepper
def _seq_vs_step(proto):
    net = XLANet(caffe_pb.load_net(proto, is_path=False), "TEST")
    params, state = net.init(jax.random.PRNGKey(0))
    stepper = DecodeStepper(net, "prob")
    T = 6
    toks = np.arange(T) % VOCAB
    cont = np.ones((T, 1), np.float32)
    cont[0] = 0
    blobs, _ = net.apply(
        params, state,
        {"data": jax.numpy.asarray(toks[:, None], jax.numpy.int32),
         "cont": jax.numpy.asarray(cont)},
        train=False, rng=None,
    )
    seq = np.asarray(blobs["prob"])
    step = jax.jit(stepper.step_fn)
    carry = stepper.init_carry(1)
    outs = []
    for t in toks:
        out, carry = step(
            params, state, carry,
            jax.numpy.asarray([t], jax.numpy.int32),
        )
        outs.append(np.asarray(out))
    return seq, np.stack(outs)


@pytest.mark.parametrize("proto", [TOY_CHAR, TOY_RNN],
                         ids=["lstm", "rnn"])
def test_stepper_matches_sequence(proto):
    """The single-token step replays the sequence net's own math: per-
    step outputs match the lax.scan path (ulp-level — XLA fuses the
    scan body differently; the serving bit-identity bar is hit-vs-cold
    through ONE executable, pinned below)."""
    seq, stepped = _seq_vs_step(proto)
    assert np.allclose(seq, stepped, rtol=1e-5, atol=1e-6)


def test_stepper_rejects_unsupported_nets():
    from tests.test_serving_tier import TOY_DEPLOY

    net = XLANet(caffe_pb.load_net(TOY_DEPLOY, is_path=False), "TEST")
    assert not DecodeStepper.supports(net)
    with pytest.raises(ValueError, match="no recurrent"):
        DecodeStepper(net, "prob")
    # a recurrent net with a step-unsafe layer (Flatten mixes the time
    # axis into the row) is rejected with the offending layer named
    bad = TOY_CHAR.replace(
        'layer { name: "ip" type: "InnerProduct" bottom: "hid" top: "logits"\n'
        '        inner_product_param { num_output: 12 axis: 2\n'
        '          weight_filler { type: "gaussian" std: 0.2 } } }',
        'layer { name: "flat" type: "Flatten" bottom: "hid" '
        'top: "logits" }',
    )
    assert 'Flatten' in bad  # the replace actually happened
    netp = caffe_pb.load_net(bad, is_path=False)
    with pytest.raises(ValueError, match="flat"):
        DecodeStepper(XLANet(netp, "TEST"), "prob")


def test_inner_product_axis2_matches_einsum():
    """The layers.py satellite: IP axis=2 contracts the trailing dim
    per (T, N) position — pinned against the plain einsum."""
    from sparknet_tpu.nets.layers import ApplyCtx, InnerProduct

    lp = caffe_pb.load_net(TOY_CHAR, is_path=False).layers[2]
    rng = np.random.default_rng(0)
    x = jax.numpy.asarray(
        rng.normal(size=(5, 2, 6)).astype(np.float32)
    )
    params = InnerProduct.init(lp, jax.random.PRNGKey(1), [(5, 2, 6)])
    (y,), _ = InnerProduct.apply(
        lp, params, None, [x],
        ApplyCtx(train=False, rng=None),
    )
    want = np.einsum("tnh,hv->tnv", np.asarray(x),
                     np.asarray(params["weight"]))
    assert y.shape == (5, 2, 12)
    assert np.allclose(np.asarray(y), want, rtol=1e-5, atol=1e-6)
    assert InnerProduct.infer(lp, [(5, 2, 6)]) == [(5, 2, 12)]


# ----------------------------------------------------- engine.generate
def test_generate_hit_vs_cold_bit_identical():
    """THE session bar: the same full prefix answered from the cache
    (hit) and recomputed from scratch (cold) must be bitwise equal —
    both paths run the one compiled step executable."""
    eng = char_engine()
    prefix = [1, 2, 3, 4, 5, 6, 7]
    r0 = eng.generate(prefix, session="a", steps=0)
    assert r0["cache_state"] == "cold"
    assert r0["steps_run"] == len(prefix)
    hit = eng.generate(prefix + [8], session="a", steps=2)
    assert hit["cache_state"] == "hit"
    assert hit["steps_run"] == 3  # 1 new + 2 generated, never O(prefix)
    cold = eng.generate(prefix + [8], steps=2)
    assert cold["cache_state"] == "cold"
    assert hit["probs"] == cold["probs"]
    assert hit["indices"] == cold["indices"]
    assert hit["tokens"] == cold["tokens"]


def test_generate_prefix_mismatch_rebuilds():
    """Reusing a session id with a DIFFERENT history must rebuild from
    the request's prefix (cache_state=rebuilt), answering exactly like
    a fresh cold request — never from the stale carry."""
    eng = char_engine()
    eng.generate([1, 2, 3], session="s")
    r = eng.generate([9, 8, 7], session="s")
    assert r["cache_state"] == "rebuilt"
    cold = eng.generate([9, 8, 7])
    assert r["probs"] == cold["probs"]
    assert eng.session_cache.snapshot()["rebuilt"] == 1


def test_hot_swap_invalidates_sessions():
    """Gen-tag invalidation: after a weight hot-swap, cached session
    state must be dropped (stale_gen) and the answer recomputed under
    the NEW weights — bit-equal to a fresh engine on those weights."""
    eng = char_engine(seed=3)
    other = char_engine(seed=11)
    prefix = [1, 2, 3, 4]
    eng.generate(prefix, session="s")
    gen = eng.swap(
        jax.device_get(other.params), jax.device_get(other.state)
    )
    r = eng.generate(prefix, session="s")
    assert r["cache_state"] == "stale_gen" and r["gen"] == gen
    want = other.generate(prefix)
    assert r["probs"] == want["probs"], "stale-gen state leaked"
    assert eng.session_cache.snapshot()["stale_gen"] == 1
    # and the rebuilt state at the new gen hits afterwards
    assert eng.generate(prefix + [5], session="s")["cache_state"] == "hit"


def test_session_cache_lru_bound(monkeypatch):
    """LRU-by-hit under the byte budget: the hot (recently hit)
    session survives, cold ones evict, resident bytes stay bounded."""
    cache = SessionCache(max_mb=2e-3)  # ~2 KB
    carry = {"lstm": (np.zeros((1, 6), np.float32),) * 2}
    toks = np.arange(4, dtype=np.int32)
    out = np.zeros((1, 12), np.float32)
    per = session_mod._tree_bytes(carry) + toks.nbytes + out.nbytes
    fits = cache.max_bytes // per
    assert fits >= 2
    cache.put("fp", "hot", 0, toks, carry, out)
    for i in range(fits * 3):
        # keep "hot" recently hit while colds pour in
        got, st = cache.take("fp", "hot", 0, toks)
        assert st == "hit"
        cache.put("fp", "hot", 0, toks, got.carry, got.last_out)
        cache.put("fp", f"cold{i}", 0, toks, carry, out)
    snap = cache.snapshot()
    assert snap["resident_bytes"] <= cache.max_bytes
    assert snap["evictions"] > 0
    got, st = cache.take("fp", "hot", 0, toks)
    assert st == "hit", "the hot session was evicted before cold ones"


def test_session_cache_disabled_zero_footprint(monkeypatch):
    """SPARKNET_SESSION_CACHE=0: the engine shares the no-op singleton
    — generate works (always cold-replays), nothing is stored, and
    non-recurrent engines use the same object."""
    monkeypatch.setenv("SPARKNET_SESSION_CACHE", "0")
    eng = char_engine()
    assert eng.session_cache is DISABLED
    r1 = eng.generate([1, 2, 3], session="s")
    r2 = eng.generate([1, 2, 3], session="s")
    assert r1["cache_state"] == r2["cache_state"] == "disabled"
    assert r1["probs"] == r2["probs"]
    assert DISABLED.snapshot() == {"enabled": False, "entries": 0}
    monkeypatch.delenv("SPARKNET_SESSION_CACHE")
    from tests.test_serving_tier import toy_net

    net, params, state = toy_net()
    assert InferenceEngine(net, params, state).session_cache is DISABLED


def test_generate_validation():
    eng = char_engine()
    with pytest.raises(ValueError, match="out of range"):
        eng.generate([99])
    with pytest.raises(ValueError, match="empty"):
        eng.generate([])
    with pytest.raises(ValueError, match="steps"):
        eng.generate([1], steps=-1)
    from tests.test_serving_tier import toy_net

    net, params, state = toy_net()
    with pytest.raises(ValueError, match="no recurrent"):
        InferenceEngine(net, params, state).generate([1])


# ------------------------------------------------ batcher submit_call
class _StubEngine:
    buckets = (8,)

    def infer_tagged(self, rows):
        return rows * 2.0, 0

    def bucket_for(self, n):
        return 8


def test_batcher_submit_call_fifo_and_shed():
    """Callable requests share the single worker with rows requests:
    results land in order, and an expired call is shed before running
    (DeadlineExceeded) exactly like rows."""
    from sparknet_tpu.serve.batcher import DeadlineExceeded, MicroBatcher

    order = []
    b = MicroBatcher(_StubEngine(), max_latency_us=100)
    futs = []
    for i in range(3):
        futs.append(b.submit(np.full((1, 2), float(i))))
        futs.append(b.submit_call(lambda i=i: order.append(i) or i))
    rows_out = [f.result(10) for f in futs[::2]]
    call_out = [f.result(10) for f in futs[1::2]]
    assert call_out == [0, 1, 2] and order == [0, 1, 2]
    assert [float(r[0][0]) for r in rows_out] == [0.0, 2.0, 4.0]
    # deadline shed: the shed check runs at flush time — park the
    # worker on a slow call first so the short-deadline call expires
    # in the queue behind it, then is dropped before running
    ran = []
    slow = b.submit_call(lambda: time.sleep(0.4))
    time.sleep(0.1)  # let the worker pick up the slow call alone
    shed = b.submit_call(lambda: ran.append(1), deadline_s=0.01)
    slow.result(10)
    with pytest.raises(DeadlineExceeded):
        shed.result(10)
    assert not ran
    b.drain()


# --------------------------------------------------- HTTP + router e2e
@pytest.fixture(scope="module")
def char_tier():
    """Two real char-rnn replicas (in-process servers) behind a
    Router — the affinity/migration fixture."""
    from sparknet_tpu.serve.router import Router
    from sparknet_tpu.serve.server import InferenceServer

    servers = [
        InferenceServer(char_engine(seed=3), port=0).start()
        for _ in range(2)
    ]
    router = Router(
        [(s.host, s.port) for s in servers],
        model_name="char", health_interval_s=0.1,
    )
    assert router.wait_healthy(timeout_s=30)
    router.start()
    yield servers, router
    router.stop()
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass


def test_server_generate_route(char_tier):
    """Single-replica surface: cold -> hit over the wire, session
    counters on /healthz, 400 on garbage."""
    servers, _ = char_tier
    c = servers[0].client()
    st, r1 = c.generate([1, 2, 3], session="route", steps=1)
    assert st == 200 and r1["cache_state"] == "cold"
    assert r1["session"] == "route" and r1["quant"] == "f32"
    st, r2 = c.generate([1, 2, 3] + r1["tokens"], session="route")
    assert st == 200 and r2["cache_state"] == "hit"
    st, hz = c.healthz()
    sc = hz["session_cache"]
    assert sc["enabled"] and sc["hits"] >= 1 and sc["entries"] >= 1
    st, err = c.generate([1000], session="route")
    assert st == 400 and "out of range" in err["error"]
    import http.client as hc

    conn = hc.HTTPConnection(servers[0].host, servers[0].port)
    conn.request("POST", "/generate", b"{}",
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 400
    conn.close()


def test_router_affinity_sticks_then_migrates(char_tier):
    """Affinity: every step of a session lands on the replica holding
    its state (hits, despite least-outstanding ties).  Ejecting the
    holder migrates the session: the answer is rebuilt cold on the
    peer, marked migrated, counted, and bit-equal to the cold path."""
    servers, router = char_tier
    c = router.client()
    sid = "aff-e2e"
    st, r = c.generate([5, 6, 7], session=sid, steps=1)
    assert st == 200 and r["cache_state"] == "cold"
    hist = [5, 6, 7] + r["tokens"]
    for _ in range(3):
        st, r = c.generate(hist, session=sid, steps=1)
        assert st == 200 and r["cache_state"] == "hit", r
        hist += r["tokens"]
    holders = [
        i for i, s in enumerate(servers)
        if s.engine.session_cache.snapshot()["entries"] > 0
    ]
    assert len(holders) == 1, "affinity scattered one session"
    before = router.metrics.snapshot()["session_migrations"]
    # eject the holder (stop its HTTP server: conn-refused -> retry)
    servers[holders[0]].stop()
    try:
        st, r = c.generate(hist, session=sid, steps=1)
        assert st == 200, r
        assert r.get("migrated") is True and r["cache_state"] == "cold"
        assert (
            router.metrics.snapshot()["session_migrations"] == before + 1
        )
        hist += r["tokens"]
        st, cold = c.generate(hist, steps=0)
        st2, again = c.generate(hist, session=sid, steps=0)
        assert cold["probs"] == again["probs"], "migrated state wrong"
    finally:
        # revive a server on the dead slot so the module fixture's
        # other tests see two healthy replicas again
        from sparknet_tpu.serve.server import InferenceServer

        servers[holders[0]] = InferenceServer(
            char_engine(seed=3), port=0
        ).start()
        with router._lock:
            rep = router.replicas[holders[0]]
            rep.host = servers[holders[0]].host
            rep.port = servers[holders[0]].port
        router.wait_healthy(timeout_s=30)


def test_loadgen_session_mode(char_tier):
    """Hot-session skew mode: Zipf weights are deterministic and
    normalized, the record carries per-state counts + hit rate +
    session_failed_requests, and zero requests fail."""
    from sparknet_tpu.serve.loadgen import run_http_loadgen, zipf_weights

    w = zipf_weights(8, 1.2)
    assert np.isclose(w.sum(), 1.0) and (np.diff(w) < 0).all()
    assert np.allclose(zipf_weights(8, 1.2), w)
    assert np.allclose(zipf_weights(4, 0.0), 0.25)
    _, router = char_tier
    rec = run_http_loadgen(
        router.host, router.port, (), n_requests=24, concurrency=2,
        sessions=4, session_zipf=1.2, seed=5,
    )
    assert rec["failed_requests"] == 0
    assert rec["session_failed_requests"] == 0
    s = rec["sessions"]
    assert s["count"] == 4 and s["zipf"] == 1.2
    assert s["states"].get("hit", 0) > 0
    assert 0 < s["hit_rate"] <= 1
    assert sum(n for _, n in s["hottest"]) <= 24


def test_dash_session_panel(char_tier):
    """The /dash session panel renders on both tiers: replica dash
    from the registry source, router dash from the aggregated replica
    scrapes + a sessions column in the replica table."""
    import urllib.request

    servers, router = char_tier
    c = router.client()
    c.generate([1, 2], session="dash", steps=1)
    page = urllib.request.urlopen(
        f"http://{servers[0].host}:{servers[0].port}/dash"
    ).read().decode()
    assert "Sessions" in page and "stale gen" in page
    # router view: wait one health sweep so replica session_cache
    # blocks arrive, then the tier page aggregates them
    deadline = time.time() + 10
    while time.time() < deadline:
        snap = router.snapshot()
        if any(
            (r.get("session_cache") or {}).get("entries")
            for r in snap["replicas"]
        ):
            break
        time.sleep(0.2)
    page = urllib.request.urlopen(
        f"http://{router.host}:{router.port}/dash"
    ).read().decode()
    assert "Sessions" in page and "<th>sessions</th>" in page
