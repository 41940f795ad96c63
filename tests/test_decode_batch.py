"""Continuous token-level batched decode (ISSUE 17): K sessions per
compiled step dispatch through ``engine.decode_batch``, the batcher's
``submit_decode`` windowing, coalescing, deadline sheds, accounting,
and the satellites (server A/B flag, healthz/dash surfaces).

The expensive chaos e2e (subprocess tier, mid-burst SIGKILL of the
session holder) lives in scripts/decode_batch_smoke.py (check.sh);
these tests pin the same semantics fast with the toy char decoder from
tests/test_session.py.  The load-bearing numeric fact, pinned below:
rows are bitwise independent across the batched widths (4/8/16) —
slot position, batch width and batch-mates never change a row's
answer — which is exactly why the width ladder floors at 4 instead
of 1 (XLA CPU fuses the width-1 step differently, at the ulp level).
"""

import os
import threading
import time

import numpy as np
import pytest

from tests.test_session import char_engine

from sparknet_tpu.serve.batcher import (
    DeadlineExceeded,
    MicroBatcher,
    decode_batching_enabled,
)
from sparknet_tpu.serve.engine import (
    DECODE_BUCKETS_DEFAULT,
    decode_buckets_from_env,
)
from sparknet_tpu.serve.metrics import ServeMetrics


@pytest.fixture(scope="module")
def eng():
    return char_engine(seed=3)


def _ok(results):
    bad = [r for r in results if isinstance(r, Exception)]
    assert not bad, bad
    return results


# ------------------------------------------------------- core equality
def test_decode_batch_matches_serial_bitwise(eng):
    """THE acceptance bar: a multi-row batched window returns, per
    row, exactly what a one-at-a-time replay through the same loop
    returns — tokens, probs, indices, accounting."""
    reqs = [
        {"tokens": [1 + i, 2, 3 + i], "steps": 2 + (i % 2)}
        for i in range(5)
    ]
    batched = _ok(eng.decode_batch([dict(r) for r in reqs]))
    serial = [
        _ok(eng.decode_batch([dict(r)]))[0] for r in reqs
    ]
    for b, s in zip(batched, serial):
        assert b["tokens"] == s["tokens"]
        assert b["probs"] == s["probs"]
        assert b["indices"] == s["indices"]
        assert b["steps_run"] == s["steps_run"]
        assert b["session_tokens"] == s["session_tokens"]


def test_decode_batch_sessions_match_serial(eng):
    """Same bar with live session state: batched rows put/take cache
    entries exactly like the serial path."""
    reqs = [
        {"tokens": [2 * i + 1, 4, 5], "steps": 2, "session": f"sb{i}"}
        for i in range(3)
    ]
    batched = _ok(eng.decode_batch([dict(r) for r in reqs]))
    for i in range(3):
        eng.session_cache.drop(eng.fingerprint, f"sb{i}")
    serial = [_ok(eng.decode_batch([dict(r)]))[0] for r in reqs]
    for b, s in zip(batched, serial):
        assert b["tokens"] == s["tokens"] and b["probs"] == s["probs"]
        assert b["cache_state"] == s["cache_state"] == "cold"
    for i in range(3):
        eng.session_cache.drop(eng.fingerprint, f"sb{i}")


def test_decode_rows_independent_of_width_and_slot(eng):
    """The width-4-floor rationale: a row's answer is bitwise
    identical whether it compiles at width 4, 8 or 16, and whatever
    slot or batch-mates it rides with."""
    req = {"tokens": [3, 1, 4], "steps": 3}
    other = {"tokens": [5, 9, 2], "steps": 3}
    saved = eng.decode_buckets
    try:
        outs = []
        for buckets in ((4,), (8,), (16,)):
            eng.decode_buckets = buckets
            outs.append(_ok(eng.decode_batch([dict(req)]))[0])
        eng.decode_buckets = saved
        # slot 0 vs slot 1, alone vs with a batch-mate
        outs.append(_ok(eng.decode_batch([dict(req), dict(other)]))[0])
        outs.append(_ok(eng.decode_batch([dict(other), dict(req)]))[1])
        ref = outs[0]
        for o in outs[1:]:
            assert o["tokens"] == ref["tokens"]
            assert o["probs"] == ref["probs"]
            assert o["indices"] == ref["indices"]
    finally:
        eng.decode_buckets = saved


def test_decode_batch_matches_generate(eng):
    """The single-session ``generate`` path stays the A/B baseline:
    identical greedy continuations, allclose distributions (width 1
    vs width >= 4 differ at the ulp level on CPU — same fusion story
    the docstring pins)."""
    req = {"tokens": [7, 8, 9], "steps": 4}
    b = _ok(eng.decode_batch([dict(req)]))[0]
    g = eng.generate([7, 8, 9], steps=4)
    assert b["tokens"] == g["tokens"]
    assert b["indices"] == g["indices"]
    assert np.allclose(b["probs"], g["probs"], rtol=1e-6, atol=1e-8)
    assert b["steps_run"] == g["steps_run"]


# --------------------------------------------------------- accounting
def test_decode_accounting_padded_steps_dont_count(eng):
    """Satellite 2 regression: padded/masked slots are never rows —
    ``steps_run``/``session_tokens`` stay exact per request, and the
    metrics ledger splits real rows from padding."""
    m = ServeMetrics()
    eng.metrics = m
    try:
        sid = "acct"
        cold = _ok(eng.decode_batch(
            [{"tokens": [1, 2, 3], "steps": 2, "session": sid}]
        ))[0]
        # a lone row padded to width 4 still ran exactly 5 steps
        assert cold["cache_state"] == "cold"
        assert cold["steps_run"] == 5 and cold["session_tokens"] == 5
        hist = [1, 2, 3] + cold["tokens"]
        hit = _ok(eng.decode_batch(
            [{"tokens": hist, "steps": 2, "session": sid}]
        ))[0]
        assert hit["cache_state"] == "hit"
        assert hit["steps_run"] == 2, (
            "hit must step only its NEW tokens — padded dispatches "
            f"leaked into steps_run: {hit}"
        )
        assert hit["session_tokens"] == len(hist) + 2
        snap = m.snapshot()["decode"]
        assert snap["rows"] == cold["steps_run"] + hit["steps_run"]
        assert snap["dispatches"] == 7
        assert snap["padded_rows"] == 7 * 4 - snap["rows"]
        assert snap["retired"] == 2 and snap["occupancy"] == 0.25
        assert snap["per_width"]["4"]["dispatches"] == 7
    finally:
        eng.metrics = None
        eng.session_cache.drop(eng.fingerprint, "acct")


def test_decode_full_prefix_hit_retires_without_dispatch(eng):
    """A request a cache hit already fully covers (steps=0, prefix
    resident) retires at admission: zero batched steps run."""
    m = ServeMetrics()
    eng.metrics = m
    try:
        sid = "instant"
        first = _ok(eng.decode_batch(
            [{"tokens": [4, 5, 6], "steps": 0, "session": sid}]
        ))[0]
        before = m.snapshot()["decode"]["dispatches"]
        again = _ok(eng.decode_batch(
            [{"tokens": [4, 5, 6], "steps": 0, "session": sid}]
        ))[0]
        assert again["cache_state"] == "hit" and again["steps_run"] == 0
        assert again["probs"] == first["probs"]
        assert m.snapshot()["decode"]["dispatches"] == before
    finally:
        eng.metrics = None
        eng.session_cache.drop(eng.fingerprint, "instant")


# --------------------------------------------------------- coalescing
def test_decode_same_session_rows_coalesce(eng):
    """Two rows for ONE session in a window would race one carry:
    the second defers until the first retires, then takes a HIT on
    the state the first just published — and the cache counts it."""
    sid = "co"
    before = eng.session_cache.snapshot()["coalesced"]
    out = _ok(eng.decode_batch([
        {"tokens": [1, 2, 3], "steps": 0, "session": sid},
        {"tokens": [1, 2, 3, 7], "steps": 0, "session": sid},
    ]))
    assert out[0]["cache_state"] == "cold" and out[0]["steps_run"] == 3
    assert out[1]["cache_state"] == "hit", (
        f"coalesced row must hit the freshly put carry: {out[1]}"
    )
    assert out[1]["steps_run"] == 1  # only the one new token
    assert eng.session_cache.snapshot()["coalesced"] == before + 1
    # equal to the uncontended answer
    eng.session_cache.drop(eng.fingerprint, sid)
    solo = _ok(eng.decode_batch([{"tokens": [1, 2, 3, 7], "steps": 0}]))
    assert out[1]["probs"] == solo[0]["probs"]
    eng.session_cache.drop(eng.fingerprint, sid)


# ---------------------------------------------------- shed + admission
def test_decode_per_token_deadline_shed(eng):
    """An expired row sheds AT A STEP BOUNDARY without disturbing its
    batch-mates; the shed is a DeadlineExceeded and counted."""
    m = ServeMetrics()
    eng.metrics = m
    try:
        out = eng.decode_batch([
            {"tokens": [1, 2, 3], "steps": 2},
            {"tokens": [4, 5, 6], "steps": 2,
             "deadline": time.perf_counter() - 1.0},
        ])
        assert isinstance(out[1], DeadlineExceeded)
        assert not isinstance(out[0], Exception)
        solo = _ok(eng.decode_batch([{"tokens": [1, 2, 3], "steps": 2}]))
        assert out[0]["probs"] == solo[0]["probs"]
        d = m.snapshot()["decode"]
        assert d["shed"] == 1 and d["retired"] == 2
        assert m.health() == "degraded"
    finally:
        eng.metrics = None


def test_decode_admit_hook_joins_running_window(eng):
    """Step-boundary admission: a request arriving mid-window becomes
    a fresh batch row and returns exactly its serial answer."""
    late = {"tokens": [9, 8, 7], "steps": 2}
    handed = []

    def admit(free_slots):
        assert free_slots > 0
        if not handed:
            handed.append(1)
            return [dict(late)]
        return None

    results = {}
    out = _ok(eng.decode_batch(
        [{"tokens": [1, 2, 3], "steps": 3}],
        admit=admit,
        on_result=lambda tag, v: results.setdefault(tag, v),
    ))
    assert len(out) == 2 and handed
    solo = _ok(eng.decode_batch([dict(late)]))[0]
    assert out[1]["probs"] == solo["probs"]
    assert out[1]["tokens"] == solo["tokens"]
    # on_result fired once per row, keyed by slot-default tags
    assert set(results) == {0, 1} and results[1]["probs"] == solo["probs"]


def test_decode_per_row_validation(eng):
    """A bad request fails ITS slot only — batch-mates answer."""
    out = eng.decode_batch([
        {"tokens": [1, 2], "steps": 1},
        {"tokens": [10**6], "steps": 0},
        {"tokens": [], "steps": 0},
    ])
    assert not isinstance(out[0], Exception)
    assert isinstance(out[1], ValueError) and "out of range" in str(out[1])
    assert isinstance(out[2], ValueError)


# ------------------------------------------------- batcher integration
def test_submit_decode_shares_windows_and_keeps_fifo(eng):
    """Concurrent submit_decode futures resolve with serial-equal
    answers; interleaved submit_call work still runs in FIFO order;
    the decode metrics block sees multi-row windows."""
    m = ServeMetrics()
    eng.metrics = m
    b = MicroBatcher(eng, metrics=m)
    try:
        reqs = [
            {"tokens": [i + 1, 5, 3], "steps": 2, "session": f"mb{i}"}
            for i in range(4)
        ]
        futs = [b.submit_decode(dict(r), block=True) for r in reqs]
        calls = [b.submit_call(lambda i=i: i) for i in range(2)]
        got = [f.result(60) for f in futs]
        assert [c.result(60) for c in calls] == [0, 1]
        for i in range(4):
            eng.session_cache.drop(eng.fingerprint, f"mb{i}")
        for g, r in zip(got, reqs):
            solo = _ok(eng.decode_batch([dict(r)]))[0]
            assert g["tokens"] == solo["tokens"]
            assert g["probs"] == solo["probs"]
        d = m.snapshot()["decode"]
        assert d["retired"] >= 4 and d["dispatches"] > 0
    finally:
        b.drain()
        eng.metrics = None
        for i in range(4):
            eng.session_cache.drop(eng.fingerprint, f"mb{i}")


def test_decode_flag_and_bucket_env(monkeypatch):
    """The A/B switch and the width-ladder override parse exactly."""
    monkeypatch.delenv("SPARKNET_DECODE_BATCH", raising=False)
    assert decode_batching_enabled() is True
    for off in ("0", "off", "OFF", "false", "no"):
        monkeypatch.setenv("SPARKNET_DECODE_BATCH", off)
        assert decode_batching_enabled() is False
    monkeypatch.setenv("SPARKNET_DECODE_BATCH", "1")
    assert decode_batching_enabled() is True

    monkeypatch.delenv("SPARKNET_DECODE_BUCKETS", raising=False)
    assert decode_buckets_from_env() == DECODE_BUCKETS_DEFAULT == (4, 8, 16)
    monkeypatch.setenv("SPARKNET_DECODE_BUCKETS", "8, 4,32")
    assert decode_buckets_from_env() == (4, 8, 32)
    monkeypatch.setenv("SPARKNET_DECODE_BUCKETS", "2")
    with pytest.raises(ValueError):
        decode_buckets_from_env()


# ------------------------------------------------------ server surface
@pytest.fixture(scope="module")
def char_server():
    from sparknet_tpu.serve.server import InferenceServer

    server = InferenceServer(char_engine(seed=3), port=0).start()
    yield server
    try:
        server.stop()
    except Exception:
        pass


def test_server_generate_batched_and_flag_off(char_server, monkeypatch):
    """/generate rides the batched decode loop by default (healthz
    decode block proves it ran); SPARKNET_DECODE_BATCH=0 falls back to
    the serial submit_call path live, with equal answers."""
    monkeypatch.delenv("SPARKNET_DECODE_BATCH", raising=False)
    c = char_server.client()
    st, on = c.generate([1, 2, 3], steps=2)
    assert st == 200 and len(on["tokens"]) == 2
    st, hz = c.healthz()
    dec = hz["decode"]
    assert dec["batching"] is True and dec["buckets"] == [4, 8, 16]
    assert dec["dispatches"] > 0 and dec["rows"] > 0
    before = dec["dispatches"]
    monkeypatch.setenv("SPARKNET_DECODE_BATCH", "0")
    st, off = c.generate([1, 2, 3], steps=2)
    assert st == 200 and off["tokens"] == on["tokens"]
    assert off["indices"] == on["indices"]
    st, hz = c.healthz()
    assert hz["decode"]["batching"] is False
    assert hz["decode"]["dispatches"] == before, (
        "flag-off generate still ran the batched loop"
    )
    # error mapping holds on the batched path
    monkeypatch.delenv("SPARKNET_DECODE_BATCH", raising=False)
    st, err = c.generate([10**6], steps=0)
    assert st == 400 and "out of range" in err["error"]


def test_dash_decode_tiles(char_server):
    """/dash Sessions panel gains the occupancy + tokens/sec +
    coalesced tiles once batched decode has run."""
    import urllib.request

    c = char_server.client()
    c.generate([2, 3, 4], session="dash-dec", steps=1)
    page = urllib.request.urlopen(
        f"http://{char_server.host}:{char_server.port}/dash"
    ).read().decode()
    assert "batch occupancy" in page
    assert "decode tokens/s" in page
    assert "coalesced" in page
