#!/usr/bin/env python
"""Closed-loop deploy smoke (ISSUE 18, run by scripts/check.sh).

The whole model lifecycle in one short CPU run:

1. boot a 2-replica router tier with ``--deploy-dir`` (traffic tee +
   supervised incremental trainer + eval gate + rollback watch) on a
   tiny 8-feature MLP, gate enforcement ON;
2. drive closed-loop traffic the entire time — served rows tee into
   the training log, the trainer emits candidate solverstates, the
   gate verifies + agreement-checks each against the serving
   generation, and the controller rolls the first passing candidate
   (generation N+1) cleanly: its watch window passes and it becomes
   the new baseline;
3. the NEXT roll is chaos-regressed in the replicas
   (``deploy.regressed_weights`` fires AFTER the gate saw clean
   bytes); the watch replays the gate-time probe through the front
   door, sees the top-1 agreement collapse, and auto-rolls the tier
   back to the previous pinned generation (resident weights — no file
   I/O, no recompile);
4. assert: ZERO failed requests end to end, the rollback happened
   exactly once, the bad generation's digest is machine-checkably
   ineligible (ledger + a re-roll attempt is refused with HTTP 409),
   and post-rollback answers match the previous generation bitwise
   (zero bad-generation answers after rollback).

Exit 0 on success; any assertion prints the evidence and exits 1.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

TRAIN_NET = """
name: "tiny"
layer { name: "d" type: "Input" top: "data" top: "label" }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
        inner_product_param { num_output: 16
          weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
        bottom: "label" top: "loss" }
"""

DEPLOY_NET = """
name: "tiny"
input: "data"
input_shape { dim: 1 dim: 8 }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
        inner_product_param { num_output: 16
          weight_filler { type: "gaussian" std: 0.5 } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
        inner_product_param { num_output: 4
          weight_filler { type: "gaussian" std: 0.5 } } }
layer { name: "prob" type: "Softmax" bottom: "ip2" top: "prob" }
"""


def wait_for(pred, timeout_s, what, debug=None):
    deadline = time.time() + timeout_s
    next_debug = time.time() + 15.0
    while time.time() < deadline:
        got = pred()
        if got:
            return got
        if debug is not None and time.time() >= next_debug:
            next_debug = time.time() + 15.0
            try:
                print(f"... waiting for {what}: {debug()}", flush=True)
            except Exception:
                pass
        time.sleep(0.3)
    raise SystemExit(f"closed-loop smoke: timed out waiting for {what}")


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    tmp = tempfile.mkdtemp(prefix="closed_loop_smoke_")
    deploy_dir = os.path.join(tmp, "deploy")
    portfile = os.path.join(tmp, "router.json")
    log = open(os.path.join(tmp, "tier.log"), "w")
    train_net = os.path.join(tmp, "train.prototxt")
    deploy_net = os.path.join(tmp, "deploy.prototxt")
    with open(train_net, "w") as fh:
        fh.write(TRAIN_NET)
    with open(deploy_net, "w") as fh:
        fh.write(DEPLOY_NET)

    import numpy as np

    import jax
    from sparknet_tpu.serve.engine import InferenceEngine
    from sparknet_tpu.solver import snapshot as snap

    # boot generation: random weights are fine — the smoke tests the
    # lifecycle plumbing, not accuracy
    eng = InferenceEngine.from_files(deploy_net, buckets=(8,))
    boot = os.path.join(tmp, "boot_iter_1.solverstate.npz")
    snap.save_state(
        boot,
        params=jax.device_get(eng.params),
        state=jax.device_get(eng.state),
    )

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # the gate is REQUIRED: ungated bytes cannot reach a replica
        "SPARKNET_DEPLOY_GATE": "require",
        # roll 1 (swap index 0 in each replica) is clean; roll 2 hits
        # the silent post-gate weight regression the watch exists for
        "SPARKNET_CHAOS": "deploy.regressed_weights@after=1:times=1:frac=64",
        "SPARKNET_DEPLOY_WATCH_S": "2.5",
        "SPARKNET_DEPLOY_PROBE_N": "8",     # must fit the 8-row bucket
        "SPARKNET_DEPLOY_MIN_NEW": "8",
        # consecutive candidates are a few SGD steps apart — the gate
        # bar is relaxed so the story is decided by the WATCH, whose
        # regression bar stays far below the chaos-induced collapse
        # the clean roll's replay is bitwise-identical (0% disagree),
        # so a low bar cannot false-positive — and one flipped probe
        # row (12.5% of 8) is enough to catch the chaos regression
        "SPARKNET_DEPLOY_DISAGREE_PCT": "75",
        "SPARKNET_DEPLOY_REGRESS_PCT": "12",
    })
    proc = subprocess.Popen(
        [sys.executable, "-m", "sparknet_tpu.tools.serve",
         "--model", deploy_net, "--weights", boot,
         "--replicas", "2", "--port", "0", "--buckets", "1,8",
         "--portfile", portfile,
         "--run-dir", os.path.join(tmp, "run"),
         "--deploy-dir", deploy_dir,
         "--deploy-train-net", train_net,
         "--deploy-interval-s", "0.25"],
        cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
    )
    stop = threading.Event()
    try:
        wait_for(
            lambda: os.path.exists(portfile) or proc.poll() is not None,
            300, "router portfile",
        )
        if proc.poll() is not None:
            print(open(log.name).read()[-4000:])
            raise SystemExit("closed-loop smoke: tier died at boot")
        doc = json.load(open(portfile))

        from sparknet_tpu.deploy import gate
        from sparknet_tpu.serve.server import Client

        client = Client(doc["host"], doc["port"], timeout=60, retries=4)

        def healthy2():
            try:
                _, hz = client.healthz()
                return hz if hz.get("replicas_healthy") == 2 else None
            except Exception:
                return None

        wait_for(healthy2, 300, "2 healthy replicas")

        # ---- continuous traffic: every served row tees into the log;
        # the failure counter runs across BOTH rolls and the rollback
        stats = {"requests": 0, "failed": 0, "gens": set()}
        lock = threading.Lock()

        def drive(seed):
            rng = np.random.default_rng(seed)
            c = Client(doc["host"], doc["port"], timeout=60, retries=4)
            while not stop.is_set():
                rows = rng.normal(size=(8, 8)).astype(np.float32)
                try:
                    st, resp = c.classify(rows, top_k=1)
                except Exception:
                    st, resp = 599, {}
                with lock:
                    if st == 200:
                        stats["requests"] += 1
                        stats["gens"].add(resp.get("gen"))
                    else:
                        stats["failed"] += 1

        threads = [
            threading.Thread(target=drive, args=(s,), daemon=True)
            for s in range(3)
        ]
        for t in threads:
            t.start()

        def deploy_block():
            try:
                _, hz = client.healthz()
            except Exception:
                return None
            return hz.get("deploy")

        # ---- phase 1: a gated roll lands and SURVIVES its watch
        t0 = time.time()
        def dep_debug():
            d = deploy_block() or {}
            return json.dumps({
                "rolls": d.get("rolls"),
                "rollbacks": d.get("rollbacks"),
                "last_gated_iter": d.get("last_gated_iter"),
                "watch": d.get("watch"),
                "events": [
                    (e.get("action"), e.get("detail"))
                    for e in (d.get("events") or [])[-5:]
                ],
            }, default=str)

        dep = wait_for(
            lambda: (lambda d: d if d and d.get("rolls", 0) >= 1 else None)(
                deploy_block()
            ),
            300, "first gated roll (tee -> trainer -> gate -> roll)",
            debug=dep_debug,
        )
        print(f"closed-loop smoke: roll 1 after {time.time() - t0:.1f}s "
              f"(baseline {dep.get('baseline')})", flush=True)

        # ---- phase 2: the regressed roll 2 triggers auto-rollback
        dep = wait_for(
            lambda: (
                lambda d: d if d and d.get("rollbacks", 0) >= 1 else None
            )(deploy_block()),
            300, "chaos regression -> watch fire -> tier rollback",
            debug=dep_debug,
        )
        stop.set()
        for t in threads:
            t.join(60)

        watch = dep.get("watch") or {}
        fired = watch.get("fired_reason") or ""
        assert dep.get("rolls", 0) >= 2, (
            f"expected a clean roll + a regressed roll, got {dep}"
        )
        assert dep.get("rollbacks") == 1, f"rollbacks != 1: {dep}"
        assert fired.startswith("agreement_regressed"), (
            f"watch fired for {fired!r}, want agreement_regressed: {watch}"
        )
        actions = [e.get("action") for e in dep.get("events", [])]
        for want in ("roll", "watch_pass", "rollback"):
            assert want in actions, (
                f"deploy event {want!r} missing from timeline {actions}"
            )
        rollback_ms = dep.get("last_rollback_ms")
        assert rollback_ms is not None and rollback_ms < 10_000, (
            f"rollback latency unmeasured/absurd: {rollback_ms}"
        )
        with lock:
            failed, requests = stats["failed"], stats["requests"]
        assert requests > 0, "traffic driver never completed a request"
        assert failed == 0, (
            f"failed requests across rolls + rollback: {failed}"
        )

        # ---- the bad generation is machine-checkably ineligible
        bad = watch.get("source") or ""
        assert bad and os.path.exists(bad), f"watch.source gone: {bad!r}"
        bad_digest = gate.snapshot_digest(bad)
        ledger = json.load(
            open(os.path.join(deploy_dir, "candidates",
                              "DEPLOY_LEDGER.json"))
        )
        assert bad_digest in ledger.get("ineligible", {}), (
            f"rolled-back digest {bad_digest} not in ledger {ledger}"
        )
        ok, reason = gate.check_eligible(bad)
        assert not ok and "ineligible" in reason, (bad, reason)
        st, resp = client.reload(bad)   # re-roll attempt: refused
        assert st == 409, (
            f"re-rolling the rolled-back snapshot must 409, "
            f"got {st}: {resp}"
        )

        # ---- zero bad-generation answers after rollback: the tier
        # now answers exactly like the previous pinned generation
        prev = watch.get("previous") or ""
        assert prev and os.path.exists(prev), f"watch.previous: {prev!r}"
        ref = InferenceEngine.from_files(deploy_net, prev, buckets=(8,))
        probe = np.random.default_rng(123).normal(size=(8, 8)).astype(
            np.float32
        )
        want = np.argmax(np.asarray(ref.infer(probe)), axis=-1)
        st, resp = client.classify(probe, top_k=1)
        assert st == 200, f"post-rollback classify failed: {resp}"
        got = np.asarray([r[0] for r in resp["indices"]])
        bad_answers = int(np.sum(got != want))
        assert bad_answers == 0, (
            f"{bad_answers}/8 post-rollback answers disagree with the "
            f"restored generation {os.path.basename(prev)}"
        )

        # the tee actually fed the loop
        _, hz = client.healthz()
        teed = sum(
            (r.get("tee") or {}).get("offered", 0)
            for r in hz.get("replicas", [])
        )
        assert teed > 0, "replicas never teed a served sample"
        rolled_back = [
            r.get("rolled_back_from") for r in hz.get("replicas", [])
            if r.get("rolled_back_from")
        ]
        assert rolled_back, (
            f"no replica reports rolled_back_from: {hz.get('replicas')}"
        )

        print(
            "closed-loop smoke: OK — 0 failed requests across "
            f"{requests} reqs, {dep.get('rolls')} gated rolls, "
            f"auto-rollback in {rollback_ms:.0f} ms ({fired}), "
            f"bad generation {bad_digest[:8]} ledgered ineligible "
            f"(re-roll -> 409), 0 bad-generation answers after rollback"
        )
        return 0
    except BaseException:
        stop.set()
        try:
            sys.stdout.write(open(log.name).read()[-4000:])
        except Exception:
            pass
        raise
    finally:
        stop.set()
        if proc.poll() is None:
            proc.send_signal(signal.SIGINT)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
        log.close()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
