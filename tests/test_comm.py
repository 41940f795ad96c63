"""Communication layer (parallel/comm.py + tau_controller.py) tests.

Key contracts (ISSUE 6):
- lossless bucketed reduction is BITWISE identical to the monolithic
  per-leaf pmean it replaces (and so is the trained result);
- int8 runs are deterministic per seed;
- error-feedback residuals re-inject quantization error (the
  cumulative mean converges where no-feedback stays biased);
- the tau controller widens when sync-bound, narrows on divergence,
  and never leaves [tau_min, tau_max];
- residuals ride opt state through snapshot save/restore.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from sparknet_tpu.parallel import CommConfig, ParallelSolver, comm, make_mesh
from sparknet_tpu.parallel.local_sgd import RESIDUAL_KEY, RoundBuffer
from sparknet_tpu.parallel.tau_controller import TauController, parse_tau
from sparknet_tpu.proto import caffe_pb

TINY_NET = """
name: "tiny"
layer { name: "d" type: "Input" top: "data" top: "label" }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
        inner_product_param { num_output: 16
          weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2" bottom: "label" top: "loss" }
"""

SOLVER_TXT = "base_lr: 0.1 momentum: 0.9 lr_policy: 'fixed' weight_decay: 0.001"
SHAPES = {"data": (16, 8), "label": (16,)}


def tiny_net():
    return caffe_pb.load_net(TINY_NET, is_path=False)


def tiny_solver():
    return caffe_pb.load_solver(SOLVER_TXT, is_path=False)


def batch(seed, n=16):
    rng = np.random.default_rng(seed)
    return {
        "data": jnp.asarray(rng.normal(size=(n, 8)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 4, size=(n,)), jnp.int32),
    }


def make_local(cc, tau=2, seed=7, net=None):
    return ParallelSolver(
        tiny_solver(), SHAPES, net_param=net or tiny_net(), seed=seed,
        mesh=make_mesh(), mode="local", tau=tau, comm_config=cc,
    )


def run_local(cc, tau=2, n=6, seed=7):
    s = make_local(cc, tau=tau, seed=seed)
    s.step(iter([batch(i) for i in range(n)]), n)
    return jax.device_get(s.params), s


def assert_trees_equal(a, b, exact=True, rtol=0.0, atol=0.0):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    assert len(la) == len(lb)
    for (pa, xa), (_, xb) in zip(la, lb):
        xa, xb = np.asarray(xa), np.asarray(xb)
        if exact:
            assert np.array_equal(xa, xb), (pa, np.max(np.abs(xa - xb)))
        else:
            np.testing.assert_allclose(xa, xb, rtol=rtol, atol=atol,
                                       err_msg=str(pa))


# ------------------------------------------------------------ planning

def test_plan_buckets_bounds_order_and_coverage():
    leaves = [
        np.zeros(s, np.float32)
        for s in ((100,), (200,), (50,), (500,), (10,))
    ]
    plan = comm.plan_buckets(leaves, 1000)  # 250 floats per bucket
    covered = sorted(i for b in plan for i in b)
    assert covered == list(range(len(leaves)))  # every leaf exactly once
    # reverse flatten order: first bucket starts from the LAST leaf
    assert plan[0][0] == len(leaves) - 1
    for b in plan:
        nbytes = sum(leaves[i].nbytes for i in b)
        assert nbytes <= 1000 or len(b) == 1  # oversized leaf = own bucket
    # a leaf above the bound still lands somewhere, alone
    assert any(len(b) == 1 and 3 in b for b in plan)


def test_plan_buckets_never_mixes_dtypes():
    leaves = [np.zeros(4, np.float32), np.zeros(4, np.int32),
              np.zeros(4, np.float32)]
    plan = comm.plan_buckets(leaves, 1 << 20)
    for b in plan:
        assert len({np.asarray(leaves[i]).dtype for i in b}) == 1


def test_wire_bytes_and_histogram():
    leaves = [np.zeros(256, np.float32), np.zeros(64, np.float32)]
    plan = comm.plan_buckets(leaves, 1 << 20)
    h = comm.bucket_histogram(plan, leaves)
    assert h["buckets"] == 1 and h["total_bytes"] == 320 * 4
    assert comm.wire_bytes(plan, leaves, "none") == 320 * 4
    assert comm.wire_bytes(plan, leaves, "bf16") == 320 * 2
    assert comm.wire_bytes(plan, leaves, "int8") == 320 * 2 + 4  # int16 acc


def test_config_resolution_and_validation(monkeypatch):
    monkeypatch.setenv(comm.COMM_ENV, "monolithic")
    monkeypatch.setenv(comm.COMPRESS_ENV, "")
    assert comm.resolve_config().mode == "monolithic"
    monkeypatch.setenv(comm.COMM_ENV, "")
    monkeypatch.setenv(comm.COMPRESS_ENV, "int8")
    cfg = comm.resolve_config()
    assert cfg.compress == "int8" and cfg.for_sync() == "bucketed"
    assert cfg.for_local() == "bucketed"
    with pytest.raises(ValueError):
        CommConfig(mode="monolithic", compress="bf16")
    with pytest.raises(ValueError):
        CommConfig(mode="nope")
    with pytest.raises(ValueError):
        CommConfig(bucket_mb=0)
    # lossless auto: sync keeps the implicit program
    assert CommConfig().for_sync() == "monolithic"


# ----------------------------------------------------- in-mesh reduction

def test_bucketed_none_reduce_is_bitwise_per_leaf_pmean():
    mesh = make_mesh()
    tree = {
        "a": {"w": jnp.arange(300, dtype=jnp.float32).reshape(30, 10) / 7.0,
              "b": jnp.linspace(-1, 1, 10, dtype=jnp.float32)},
        "z": {"w": jnp.full((128,), 2.5, jnp.float32)},
    }
    cc = CommConfig(mode="bucketed", bucket_mb=0.0005)  # force >1 bucket

    def vary(t):
        widx = lax.axis_index("dp").astype(jnp.float32)
        t = comm.pcast_varying(t, "dp")
        return jax.tree_util.tree_map(lambda x: x * (1.0 + 0.1 * widx), t)

    def bucketed(t):
        r, _ = comm.reduce_bucketed(vary(t), "dp", 8, cc)
        return r

    def per_leaf(t):
        return jax.tree_util.tree_map(
            lambda x: lax.pmean(x, "dp"), vary(t)
        )

    f1 = jax.jit(jax.shard_map(
        bucketed, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))
    f2 = jax.jit(jax.shard_map(
        per_leaf, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))
    assert_trees_equal(f1(tree), f2(tree), exact=True)


@pytest.mark.parametrize("compress", ["bf16", "int8"])
def test_error_feedback_converges_where_biased_would_not(compress):
    """Reducing the SAME per-worker values round after round: with
    error feedback the cumulative mean of the reduced outputs converges
    to the exact mean (residual re-injection cancels quantization
    error); without residuals the same error repeats every round."""
    mesh = make_mesh()
    cc = CommConfig(compress=compress, bucket_mb=1.0)
    val = {"w": jnp.linspace(0.1, 1.7, 64, dtype=jnp.float32)}

    def worker(res):
        widx = lax.axis_index("dp").astype(jnp.float32)
        t = jax.tree_util.tree_map(
            lambda x: comm.pcast_varying(x, "dp") * (1.0 + 0.013 * widx),
            val,
        )
        red, new_res = comm.reduce_bucketed(t, "dp", 8, cc, residual=res)
        return red, new_res

    f = jax.jit(jax.shard_map(
        worker, mesh=mesh, in_specs=(P("dp"),), out_specs=(P(), P("dp")),
        check_vma=False))
    exact = np.asarray(val["w"]) * (1.0 + 0.013 * np.mean(np.arange(8)))
    res = jax.device_put(
        jax.tree_util.tree_map(
            lambda x: jnp.zeros((8,) + x.shape, jnp.float32), val
        ),
        jax.sharding.NamedSharding(make_mesh(), P("dp")),
    )
    total = np.zeros_like(exact)
    rounds = 8
    first_err = None
    for i in range(rounds):
        red, res = f(res)
        if first_err is None:
            first_err = np.max(np.abs(np.asarray(red["w"]) - exact))
        total += np.asarray(red["w"])
    ef_err = np.max(np.abs(total / rounds - exact))
    # repeating the round-1 output (no feedback) keeps the round-1
    # error; the EF cumulative mean must beat it clearly
    assert first_err > 0  # quantization really is lossy here
    assert ef_err < 0.35 * first_err, (ef_err, first_err)


# ------------------------------------------------- local-SGD end to end

def test_local_bucketed_none_bitwise_matches_monolithic():
    mono, _ = run_local(CommConfig(mode="monolithic"))
    buck, _ = run_local(CommConfig(mode="bucketed", bucket_mb=0.01))
    assert_trees_equal(mono, buck, exact=True)


def test_local_compressed_tracks_exact_average():
    exact, _ = run_local(CommConfig(mode="monolithic"))
    for compress in ("bf16", "int8"):
        got, s = run_local(CommConfig(compress=compress, bucket_mb=0.01))
        assert RESIDUAL_KEY in s.opt_state
        assert_trees_equal(exact, got, exact=False, rtol=0.02, atol=5e-3)


def test_local_int8_deterministic_per_seed():
    a, _ = run_local(CommConfig(compress="int8", bucket_mb=0.01))
    b, _ = run_local(CommConfig(compress="int8", bucket_mb=0.01))
    assert_trees_equal(a, b, exact=True)


def test_grad_allreduce_phase_attributed():
    from sparknet_tpu.telemetry import timeline as ttl

    s = make_local(CommConfig(mode="bucketed"))
    tl = ttl.Timeline(fence=True)
    s.timeline = tl
    tl.start()
    s.step(iter([batch(i) for i in range(4)]), 4)
    tl.stop()
    ph = tl.phase_seconds()
    assert "grad_allreduce" in ph and ph["grad_allreduce"] > 0
    assert "grad_allreduce" in tl.table()


# ------------------------------------------------------ sync DP bucketed

def test_sync_bucketed_matches_implicit():
    net = tiny_net()
    imp = ParallelSolver(
        tiny_solver(), SHAPES, net_param=net, seed=7, mesh=make_mesh(),
        mode="sync", comm_config=CommConfig(mode="monolithic"),
    )
    exp = ParallelSolver(
        tiny_solver(), SHAPES, net_param=net, seed=7, mesh=make_mesh(),
        mode="sync", comm_config=CommConfig(mode="bucketed", bucket_mb=0.01),
    )
    feed = [batch(i) for i in range(3)]
    imp.step(iter(list(feed)), 3)
    exp.step(iter(list(feed)), 3)
    assert_trees_equal(
        jax.device_get(imp.params), jax.device_get(exp.params),
        exact=False, rtol=2e-5, atol=1e-6,
    )


def test_sync_compressed_residual_lives_in_opt_state():
    s = ParallelSolver(
        tiny_solver(), SHAPES, net_param=tiny_net(), seed=7,
        mesh=make_mesh(), mode="sync",
        comm_config=CommConfig(compress="int8", bucket_mb=0.01),
    )
    assert RESIDUAL_KEY in s.opt_state
    lead = jax.tree_util.tree_leaves(s.opt_state[RESIDUAL_KEY])[0]
    assert lead.shape[0] == 8  # per-worker residual stack
    s.step(iter([batch(i) for i in range(2)]), 2)
    # after a step some worker quantized something away
    resid_mag = sum(
        float(jnp.sum(jnp.abs(x)))
        for x in jax.tree_util.tree_leaves(s.opt_state[RESIDUAL_KEY])
    )
    assert np.isfinite(resid_mag)


# -------------------------------------------------- snapshots + residual

def test_snapshot_roundtrip_carries_residual(tmp_path):
    cc = CommConfig(compress="bf16", bucket_mb=0.01)
    feed = [batch(i) for i in range(6)]
    a = make_local(cc)
    a.step(iter(list(feed[:2])), 2)
    path = str(tmp_path / "comm.solverstate.npz")
    a.save(path)
    b = make_local(cc, seed=11)  # different init: restore must win
    b.restore(path)
    assert RESIDUAL_KEY in b.opt_state
    a.step(iter(list(feed[2:])), 4)
    b.step(iter(list(feed[2:])), 4)
    assert_trees_equal(
        jax.device_get(a.params), jax.device_get(b.params), exact=True
    )


def test_restore_reconciles_residual_mismatch(tmp_path, capsys):
    # snapshot WITHOUT residuals -> restored into a compressed run
    plain = make_local(CommConfig(mode="bucketed"))
    plain.step(iter([batch(0), batch(1)]), 2)
    path = str(tmp_path / "plain.solverstate.npz")
    plain.save(path)
    lossy = make_local(CommConfig(compress="int8", bucket_mb=0.01))
    lossy.restore(path)
    assert RESIDUAL_KEY in lossy.opt_state  # injected zeros
    lossy.step(iter([batch(2)]), 1)  # and the compiled step accepts them
    # snapshot WITH residuals -> restored into a lossless run
    path2 = str(tmp_path / "lossy.solverstate.npz")
    lossy.save(path2)
    plain2 = make_local(CommConfig(mode="bucketed"))
    plain2.restore(path2)
    assert RESIDUAL_KEY not in plain2.opt_state  # dropped
    plain2.step(iter([batch(3)]), 1)


# ------------------------------------------------------- tau controller

def _snap(round_s=1.0, sync_s=0.0, loss=1.0):
    return dict(round_s=round_s, sync_s=sync_s, loss=loss)


def test_tau_controller_widens_when_sync_bound():
    c = TauController(tau=4, tau_min=1, tau_max=32, cooldown_rounds=0)
    taus = [c.observe_round(**_snap(sync_s=0.5, loss=1.0)) for _ in range(4)]
    assert taus == [8, 16, 32, 32]  # doubles, then pins at tau_max
    assert all(d["action"] in ("widen", "hold") for d in c.decisions)
    assert c.decisions[0]["reason"].startswith("sync share")


def test_tau_controller_narrows_on_divergence():
    c = TauController(tau=16, tau_min=2, tau_max=32, cooldown_rounds=0)
    c.observe_round(**_snap(sync_s=0.0, loss=1.0))  # establishes the EMA
    taus = [
        c.observe_round(**_snap(sync_s=0.0, loss=1.0 + 0.5 * k))
        for k in range(1, 5)
    ]
    assert taus[0] == 8 and min(taus) >= 2  # halves, floor respected
    assert any(d["action"] == "narrow" for d in c.decisions)
    # divergence wins even when also sync-bound
    c2 = TauController(tau=8, tau_min=1, tau_max=64, cooldown_rounds=0)
    c2.observe_round(**_snap(loss=1.0))
    assert c2.observe_round(**_snap(sync_s=0.9, loss=2.0)) == 4


def test_tau_controller_cooldown_and_bounds():
    c = TauController(tau=4, tau_min=4, tau_max=4)
    for k in range(5):
        t = c.observe_round(**_snap(sync_s=0.9, loss=1.0 + k))
        assert t == 4  # bounds pin tau regardless of signals
    c = TauController(tau=2, tau_min=1, tau_max=64, cooldown_rounds=2)
    assert c.observe_round(**_snap(sync_s=0.9, loss=1.0)) == 4
    # two cooldown rounds hold even though still sync-bound
    assert c.observe_round(**_snap(sync_s=0.9, loss=1.0)) == 4
    assert c.observe_round(**_snap(sync_s=0.9, loss=1.0)) == 4
    assert c.observe_round(**_snap(sync_s=0.9, loss=1.0)) == 8


def test_parse_tau():
    assert parse_tau(5) == (5, False)
    assert parse_tau("12") == (12, False)
    tau0, auto = parse_tau("auto")
    assert auto and tau0 >= 1
    with pytest.raises(ValueError):
        parse_tau("fast")


def test_tau_auto_end_to_end_records_decisions(tmp_path):
    s = make_local(CommConfig(mode="bucketed"), tau="auto")
    assert s.tau_controller is not None
    s.step(iter([batch(i) for i in range(64)]), 3 * s.tau)
    snap = s.tau_controller.snapshot()
    assert snap["rounds"] >= 2 and snap["decisions"]
    assert all(
        snap["tau_min"] <= d["next_tau"] <= snap["tau_max"]
        for d in snap["decisions"]
    )
    path = s.tau_controller.write_report(str(tmp_path / "run"))
    import json

    with open(path) as f:
        assert json.load(f)["decisions"]
    report = s.comm_report()
    assert report["tau_controller"]["rounds"] == snap["rounds"]
    assert report["buckets"]["buckets"] >= 1


# --------------------------------------------------------- round buffer

def test_round_buffer_bit_identical_and_counted():
    from sparknet_tpu.telemetry import REGISTRY

    buf = RoundBuffer()
    reuse0 = REGISTRY.counter("round_buffer", event="reuse").snapshot()
    alloc0 = REGISTRY.counter("round_buffer", event="alloc").snapshot()
    rounds = []
    for r in range(5):
        bl = [batch(10 * r + i) for i in range(3)]
        from sparknet_tpu.parallel import stack_round_batches

        want = jax.tree_util.tree_map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *bl
        )
        got = stack_round_batches(bl, buffer=buf)
        for k in want:
            assert np.array_equal(want[k], np.asarray(got[k])), (r, k)
        rounds.append(got)
    reuse = REGISTRY.counter("round_buffer", event="reuse").snapshot() - reuse0
    alloc = REGISTRY.counter("round_buffer", event="alloc").snapshot() - alloc0
    # depth-3 rotation per key, 2 keys (data/label), 5 rounds
    assert alloc == 2 * RoundBuffer.DEPTH
    assert reuse == 2 * (5 - RoundBuffer.DEPTH)
    # rotation depth really protects the last DEPTH-1 rounds: the last
    # three rounds' buffers are distinct objects
    assert len({id(rounds[r]["data"]) for r in (2, 3, 4)}) == 3
