"""``caffe`` CLI twin — the reference-era binary's subcommand surface.

    python -m sparknet_tpu.tools.caffe train --solver=s.prototxt \
        [--weights=m.caffemodel] [--snapshot=state.solverstate.npz] [...]
    python -m sparknet_tpu.tools.caffe test  --model=net.prototxt \
        --weights=m.caffemodel [--iterations=50]
    python -m sparknet_tpu.tools.caffe time  --solver=s.prototxt [...]

``train`` routes to CifarApp's generic loop (any prototxt works — the
app name is historical), so every app flag passes through — including
``--data-workers=N`` / ``SPARKNET_DATA_WORKERS`` for the multiprocess
input pipeline (docs/PIPELINE.md; the training run prints the
pipeline's per-stage wait metrics on exit, the host-bound vs
device-bound answer) and ``--chaos=SPEC`` / ``SPARKNET_CHAOS`` for
deterministic fault injection (docs/ROBUSTNESS.md; e.g.
``SPARKNET_CHAOS=pipeline.worker_crash@batch=37 caffe train ...``
kills a pipeline worker mid-epoch and the run completes with
bit-identical weights, printing the ``chaos:`` recovery counters on
exit) and ``--supervise`` / ``SPARKNET_SUPERVISE=1`` for the job
supervisor (docs/MULTIHOST.md "Recovery": the training run becomes
child process(es) that are automatically relaunched with
``--auto-resume`` under a restart budget, capped backoff and flap
detection, with machine-readable failure records in the run dir and a
``supervisor:`` recovery-counter line on exit) and ``--trace=OUT.json``
/ ``SPARKNET_TRACE`` for the telemetry subsystem (docs/OBSERVABILITY.md:
the run writes a Perfetto-loadable Chrome trace — pipeline workers and
supervised children merged in by pid/tid — and prints the per-phase
step-time breakdown table, the paper's τ-vs-communication accounting;
on a multi-host run rank 0 additionally prints the cluster-merged
phase table with per-rank skew from the heartbeat telemetry piggyback,
and the anomaly detectors emit ``anomaly:`` JSON lines on stragglers,
step/loss spikes, and queue stalls).
Data-plane knobs pass through as well (docs/DATA.md):
``--data-format=packed`` streams a ``sparknet-pack`` output under
``--data-dir`` (CRC-checked shard records, seeded global shuffle,
shard-level O(1) resume) and ``--data-cache[=NS]`` attaches the
cross-job decoded-batch cache — a second co-located run of the same
stream reads decoded batches from named shared memory instead of
re-decoding every epoch, bit-identically (the run prints a
``data cache:`` hit/miss/evict line on exit).
``time`` routes to tools/time_net; ``test`` builds the
TEST-phase net and reports averaged metrics.  Both ``--flag=value``
and ``--flag value`` spellings are accepted, like the original binary.

Communication knobs pass through too (docs/COMMUNICATION.md):
``--parallel local --tau auto`` runs the telemetry-driven τ controller
(decision log on the ``tau:`` line + ``<prefix>_tau_controller.json``),
``--grad-compress bf16|int8`` compresses the round-end reduction with
error-feedback residuals, and the run prints one ``comm:`` JSON line
(bucket plan + wire-byte estimate).
"""

from __future__ import annotations

import sys
from typing import List


def _split_eq(argv: List[str]) -> List[str]:
    out: List[str] = []
    for a in argv:
        if a.startswith("--") and "=" in a:
            k, _, v = a.partition("=")
            out.extend([k, v])
        else:
            out.append(a)
    return out


def _drop_gpu_flag(args: List[str]) -> List[str]:
    """Accept-and-ignore Caffe's ``--gpu <id|all>``: device selection
    belongs to JAX/XLA here (the visible accelerator is used), but
    published caffe command lines must not argparse-error on it."""
    out: List[str] = []
    skip_value = False
    for a in args:
        if skip_value:
            skip_value = False
            # --gpu values are device ids or 'all', never dashed: a
            # dashed token here means the value was omitted — keep it
            # so argparse can report the real problem.
            if not a.startswith("--"):
                continue
        if a == "--gpu":
            skip_value = True
            continue
        out.append(a)
    return out


def _train(argv: List[str]):
    from ..apps import cifar_app

    args = _drop_gpu_flag(_split_eq(argv))
    # caffe spells resume as --snapshot=<state>; our apps as --restore
    args = ["--restore" if a == "--snapshot" else a for a in args]
    return cifar_app.main(args)


def _time(argv: List[str]):
    from . import time_net

    args = _drop_gpu_flag(_split_eq(argv))
    # caffe time spells the iteration count --iterations; time_net --iters
    args = ["--iters" if a == "--iterations" else a for a in args]
    return time_net.main(args)


def _test(argv: List[str]):
    import argparse
    import os

    import jax

    from ..proto import caffe_pb
    from ._common import batch_transform_fn, build_phase_net, load_weights

    ap = argparse.ArgumentParser(prog="caffe test")
    ap.add_argument("--model", required=True)
    ap.add_argument("--weights", default=None)
    ap.add_argument("--iterations", type=int, default=50)
    args = ap.parse_args(_drop_gpu_flag(_split_eq(argv)))

    net_param = caffe_pb.load_net(args.model)
    model_dir = os.path.dirname(os.path.abspath(args.model))
    test_net, ds, tf, bs = build_phase_net(net_param, model_dir, "TEST")
    if test_net is None:
        raise SystemExit("caffe test: the net's TEST data source was not found")
    params, state = test_net.init(jax.random.PRNGKey(0))
    if args.weights:
        params, state = load_weights(test_net, params, state, args.weights)

    feed = ds.batches(
        bs, shuffle=False, epochs=1, transform=batch_transform_fn(tf)
    )
    acc: dict = {}
    n = 0
    for batch in feed:
        if n >= args.iterations:
            break
        import jax.numpy as jnp

        blobs, _ = test_net.apply(
            params, state,
            {"data": jnp.asarray(batch["data"]),
             "label": jnp.asarray(batch["label"])},
            train=False, rng=None,
        )
        _, metrics = test_net.loss_and_metrics(blobs)
        for k, v in metrics.items():
            acc[k] = acc.get(k, 0.0) + float(v)
        n += 1
    for k, v in acc.items():
        print(f"{k} = {v / max(n, 1):.4f}")
    return {k: v / max(n, 1) for k, v in acc.items()}


def _device_query(argv: List[str]):
    """Twin of ``caffe device_query``: one line per visible accelerator."""
    import jax

    try:
        devices = jax.devices()
    except Exception as e:
        print(f"device_query: backend init failed: {type(e).__name__}: {e}")
        return []
    for d in devices:
        kind = getattr(d, "device_kind", d.platform)
        print(f"Device id: {d.id}  platform: {d.platform}  kind: {kind}")
    return devices


def main(argv=None):
    from ..utils import compile_cache

    compile_cache.enable()
    argv = list(sys.argv[1:] if argv is None else argv)
    cmds = {
        "train": _train,
        "test": _test,
        "time": _time,
        "device_query": _device_query,
    }
    if not argv or argv[0] not in cmds:
        print("usage: caffe train|test|time|device_query [--flag=value ...]")
        raise SystemExit(2)
    cmd, rest = argv[0], argv[1:]
    return cmds[cmd](rest)


if __name__ == "__main__":
    main()
