"""BertApp — BERT MLM pre-training entrypoint (pure-JAX model family).

BASELINE.json config #5. No reference counterpart (SURVEY.md §2 —
SparkNet predates transformers); the entrypoint shape mirrors
CifarApp/ImageNetApp: pick a config, build feeds, drive the Solver —
single chip or across the mesh (sync DP / τ-local SGD), AdamW with
linear warmup + poly decay.
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..data.text import mlm_dataset, mlm_feed
from ..models.bert import BertConfig, BertMLM
from ..parallel import ParallelSolver, make_mesh, multihost
from ..proto import caffe_pb
from ..solver.trainer import Solver

CONFIGS = {
    "base": BertConfig.bert_base,
    "small": BertConfig.bert_small,
    "tiny": BertConfig.bert_tiny,
}


def make_solver_param(args) -> caffe_pb.SolverParameter:
    """AdamW, linear warmup, poly(1.0) decay to zero — the standard BERT
    pre-training schedule, expressed in SolverParameter terms."""
    return caffe_pb.SolverParameter(
        base_lr=args.lr,
        lr_policy="poly",
        power=1.0,
        max_iter=args.max_iter,
        warmup_iter=max(1, args.max_iter // 100),
        momentum=0.9,
        momentum2=0.999,
        delta=1e-6,
        weight_decay=0.01,
        solver_type="ADAMW",
        display=args.display,
        random_seed=args.seed,
    )


def make_args(**overrides) -> argparse.Namespace:
    args = parser().parse_args([])
    for k, v in overrides.items():
        if not hasattr(args, k):
            raise TypeError(f"unknown BertApp arg {k!r}")
        setattr(args, k, v)
    return args


def make_config(args):
    """(BertConfig, seq_len) from the CLI flags — shared by the Solver
    path and the model-parallel modes so config knobs cannot drift."""
    import dataclasses

    cfg = CONFIGS[args.config]()
    overrides = {}
    if args.vocab_size:
        overrides["vocab_size"] = args.vocab_size
    if args.moe_experts:
        overrides.update(
            moe_num_experts=args.moe_experts,
            moe_top_k=args.moe_top_k,
            moe_dispatch=args.moe_dispatch,
            moe_capacity_factor=args.moe_capacity,
        )
    if args.remat:
        overrides["remat"] = True
    if args.max_position:
        # long-context: grow the position table past BERT's 512 (pair
        # with --attention flash [+ --remat]; the streamed kernels keep
        # VMEM O(block) at any S — S=32k fwd+bwd measured on v5e)
        overrides["max_position"] = args.max_position
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    seq = args.seq_len or min(128, cfg.max_position)
    if seq > cfg.max_position:
        raise ValueError(
            f"--seq-len {seq} exceeds max_position {cfg.max_position}; "
            f"raise --max-position"
        )
    return cfg, seq


def flash_tiles_note(tiles: Dict[str, int]) -> str:
    """The start-up line's words for ``tiles`` — the score tiles a
    batch-head executes in each flash kernel, by whether a mask runs over
    them (``ops.attention.flash_tile_kinds``; empty: the reference path) —
    set once as the registry's ``flash_tiles`` gauges, one a kind."""
    from ..telemetry.registry import REGISTRY

    for kind, n in tiles.items():
        REGISTRY.gauge("flash_tiles", kind=kind).set(n)
    return f"flash_tiles={tiles or 'none (reference attention)'}"


def build(args):
    cfg, seq = make_config(args)
    if args.attention in ("ring", "ulysses"):
        raise ValueError(
            f"--attention {args.attention} is a sequence-parallel "
            f"implementation: use --parallel sp (or tp with an sp mesh axis)"
        )
    bs = args.batch_size
    max_preds = max(1, int(seq * 0.15) + 1)

    ds, vsize = mlm_dataset(
        text_files=args.text_files or None,
        vocab_size=cfg.vocab_size,
        n_tokens=args.synthetic_tokens,
        seq_len=seq,
        seed=args.seed,
    )
    if vsize != cfg.vocab_size:  # corpus-built vocab may be smaller
        cfg = type(cfg)(**{**cfg.__dict__, "vocab_size": vsize})

    # multi-host: host-sharded data, local feed rows, global solver batch
    nproc = jax.process_count()
    feed_bs = bs
    if nproc > 1:
        if args.parallel == "none" and not getattr(args, "layout", None):
            raise ValueError("multi-host launch requires --parallel sync|local")
        if bs % nproc:
            raise ValueError(f"batch ({bs}) must divide across {nproc} processes")
        ds = multihost.host_shard(ds)
        feed_bs = bs // nproc

    shapes = {
        "input_ids": (bs, seq),
        "mlm_positions": (bs, max_preds),
    }
    model = BertMLM(
        cfg,
        shapes,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        attention_impl=args.attention or None,
    )
    sp = make_solver_param(args)
    layout_spec = getattr(args, "layout", None)
    if args.parallel == "none" and not layout_spec:
        if getattr(args, "grad_compress", None):
            raise ValueError(
                "--grad-compress requires --parallel sync|local"
            )
        solver = Solver(sp, shapes, model=model, seed=args.seed)
    elif layout_spec:
        from .cifar_app import comm_config_from

        # unified rule-table path: the "bert" ruleset (Megatron
        # column/row split + expert stacks) resolves against whatever
        # axes the layout names — dp=2,tp=2 and dp=2,ep=4 are the same
        # model, different table entries (docs/PARALLELISM.md)
        solver = ParallelSolver(
            sp, shapes, model=model, seed=args.seed,
            layout=layout_spec,
            mode="local" if args.parallel == "local" else "sync",
            tau=args.tau, comm_config=comm_config_from(args),
        )
    else:
        from .cifar_app import comm_config_from

        solver = ParallelSolver(
            sp, shapes, model=model, seed=args.seed,
            mesh=make_mesh(), mode=args.parallel, tau=args.tau,
            comm_config=comm_config_from(args),
        )
    feed = mlm_feed(ds, feed_bs, cfg.vocab_size, max_preds, seed=args.seed)
    return solver, feed, cfg


def parse_mesh(spec: str, default_axis: str):
    """"dp=2,tp=2,sp=2" -> axis dict (one size may be -1); empty spec
    puts every device on ``default_axis`` with a unit dp axis (the step
    factories always reduce over dp)."""
    if not spec:
        return {"dp": 1, default_axis: -1}
    axes = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        axes[k.strip()] = int(v)
    if "dp" not in axes:
        raise ValueError(
            f"--mesh {spec!r}: include a dp axis (dp=1 for none) — the "
            f"parallel train steps reduce gradients over dp"
        )
    return axes


def run_model_parallel(args) -> Dict[str, float]:
    """The tp/sp/pp/ep modes: token-level MLM loss over an explicit
    mesh, driven by the parallel step factories (the same ones the
    driver's multi-chip dryrun exercises) rather than the Solver class.

        bert_app --parallel sp --mesh dp=2,sp=4 --attention ring
        bert_app --parallel tp --mesh dp=2,tp=2,sp=2
        bert_app --parallel pp --mesh dp=2,pp=4 --pp-microbatches 2
        bert_app --parallel ep --mesh dp=2,ep=4 --moe-experts 4
    """
    import dataclasses

    from ..data.text import mlm_dataset, mlm_feed_tokens
    from ..nets import weights as W
    from ..parallel.mesh import make_mesh
    from ..solver.caffe_solver import init_opt_state
    from ..utils.profiling import StepTimer

    mode = args.parallel
    if jax.process_count() > 1:
        raise ValueError(
            f"--parallel {mode} is single-process (one controller over "
            f"the local mesh); multi-host launches use --parallel "
            f"sync|local"
        )
    if args.restore or args.auto_resume:
        raise ValueError(
            f"--restore/--auto-resume are Solver-path features; the "
            f"{mode} mode snapshots params only (no solver state yet)"
        )
    if args.snapshot_format != "npz":
        raise ValueError(
            f"--snapshot-format {args.snapshot_format} is a Solver-path "
            f"feature; the {mode} mode snapshots params-only .npz"
        )
    cfg, seq = make_config(args)
    bs = args.batch_size
    axes = parse_mesh(args.mesh, mode)
    # a fully-specified spec smaller than the device count uses a
    # prefix of the devices (e.g. dp=2,pp=2 on an 8-device host)
    sizes = list(axes.values())
    devices = None
    if -1 not in sizes:
        total = int(np.prod(sizes))
        devices = jax.devices()[:total]
    mesh = make_mesh(axes, devices)
    ds, vs = mlm_dataset(
        text_files=args.text_files or None, vocab_size=cfg.vocab_size,
        n_tokens=args.synthetic_tokens, seq_len=seq, seed=args.seed,
    )
    if vs != cfg.vocab_size:  # corpus-built vocab may be smaller
        cfg = dataclasses.replace(cfg, vocab_size=vs)
    shapes = {"input_ids": (bs, seq), "mlm_positions": (bs, 8)}
    sp_param = make_solver_param(args)
    cdt = jnp.bfloat16 if args.bf16 else jnp.float32

    if mode == "sp":
        from ..parallel.sequence import make_sp_train_step

        impl = args.attention or "ring"
        if impl not in ("ring", "ulysses"):
            raise ValueError(
                f"--parallel sp needs --attention ring|ulysses "
                f"(got {impl!r}); flash/reference cannot shard the "
                f"sequence axis"
            )
        model = BertMLM(cfg, shapes, compute_dtype=cdt,
                        attention_impl=impl, sp_axis="sp")
        step = make_sp_train_step(model, sp_param, mesh)
        if impl == "ring":
            from ..parallel.sequence import ring_engine

            s_local = seq // mesh.shape["sp"]
            print(f"BertApp[sp]: ring engine={ring_engine(s_local)} "
                  f"(S_local={s_local})")
    elif mode == "tp":
        from ..parallel.tensor import make_tp_train_step

        has_sp = "sp" in mesh.shape
        model = BertMLM(
            cfg, shapes, compute_dtype=cdt, tp_axis="tp",
            attention_impl="ring" if has_sp else None,
            sp_axis="sp" if has_sp else None,
        )
        step = make_tp_train_step(
            model, sp_param, mesh, dp_axis="dp", tp_axis="tp",
            sp_axis="sp" if has_sp else None,
        )
    elif mode == "pp":
        from ..parallel.pipeline import make_pp_train_step, stack_layer_params

        # --moe-experts composes: pp shards the layer stack, and an ep
        # mesh axis additionally shards the expert stacks
        ep = "ep" if (cfg.moe_num_experts > 0 and "ep" in axes) else None
        model = BertMLM(cfg, shapes, compute_dtype=cdt, ep_axis=ep)
        step = make_pp_train_step(
            model, sp_param, mesh, n_micro=args.pp_microbatches,
            dp_axis="dp", ep_axis=ep,
        )
    elif mode == "ep":
        from ..parallel.expert import make_ep_train_step

        if not cfg.moe_num_experts:
            raise ValueError("--parallel ep needs --moe-experts N")
        model = BertMLM(cfg, shapes, compute_dtype=cdt, ep_axis="ep")
        step = make_ep_train_step(model, sp_param, mesh, dp_axis="dp",
                                  ep_axis="ep")
    else:  # pragma: no cover — guarded by argparse choices
        raise ValueError(mode)

    from ..solver.snapshot import resolve_prefix

    args.snapshot_prefix = resolve_prefix(args.snapshot_prefix)
    params, _ = model.init(jax.random.PRNGKey(args.seed))
    if mode == "pp":
        stacked, rest = stack_layer_params(params, cfg.num_layers)
        params = {"layers": stacked, "rest": rest}
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(
        f"BertApp[{mode}]: mesh={dict(mesh.shape)} vocab={cfg.vocab_size} "
        f"layers={cfg.num_layers} hidden={cfg.hidden_size} params={n_params}"
    )
    opt_state = init_opt_state(sp_param, params)
    feed = mlm_feed_tokens(ds, bs, vs, seed=args.seed)
    timer = StepTimer(items_per_step=bs * seq, unit="tokens")
    rng = jax.random.PRNGKey(args.seed + 1)
    metrics: Dict[str, float] = {}
    display = args.display  # 0 = silent, like the Solver path
    last_report = 0
    for it in range(args.max_iter):
        batch = {k: jnp.asarray(v) for k, v in next(feed).items()}
        rng, srng = jax.random.split(rng)
        params, opt_state, m = step(
            params, opt_state, batch, jnp.asarray(it, jnp.int32), srng
        )
        done = it + 1
        if done == args.max_iter or (display and done % display == 0):
            metrics = {k: float(v) for k, v in m.items()}  # host sync
            if display:
                timer.update(done - last_report)  # honest partial windows
                last_report = done
                print(
                    f"Iteration {done}, "
                    + ", ".join(
                        f"{k} = {v:.5f}" for k, v in metrics.items()
                    )
                )
                print(f"    speed: {timer.format()}")
        if args.snapshot and (done % args.snapshot == 0
                              or done == args.max_iter):
            path = f"{args.snapshot_prefix}_{mode}_iter_{done}.npz"
            # pp params nest three deep ({layers, rest{layer{name}}});
            # save a two-level view load_npz can round-trip
            tree = jax.device_get(params)
            if mode == "pp":
                tree = {**tree["rest"], "pp_stacked_layers": tree["layers"]}
            W.save_npz(path, tree)
            print(f"Snapshotting params to {path}")
    return metrics


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="BERT MLM pre-training (BertApp)")
    ap.add_argument("--config", choices=sorted(CONFIGS), default="base")
    ap.add_argument("--vocab-size", type=int, default=0,
                    help="override config vocab size")
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--max-position", type=int, default=0,
                    help="override the position-embedding table size "
                         "(long-context; combine with --attention flash)")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--max-iter", type=int, default=1000)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--display", type=int, default=20)
    ap.add_argument("--text-files", nargs="*", default=None)
    ap.add_argument("--synthetic-tokens", type=int, default=1 << 16)
    ap.add_argument("--parallel",
                    choices=("none", "sync", "local", "tp", "sp", "pp", "ep"),
                    default="none",
                    help="none/sync/local drive the Solver; tp/sp/pp/ep "
                         "run the model-parallel token-loss steps over "
                         "--mesh")
    ap.add_argument("--mesh", default="",
                    help="axis spec for tp/sp/pp/ep, e.g. dp=2,tp=2,sp=2 "
                         "(one size may be -1 = all remaining devices)")
    ap.add_argument("--layout", default=None, metavar="AXES",
                    help="unified sharding layout for the Solver path, "
                         "e.g. dp=2,tp=2: the 'bert' regex rule table "
                         "maps params to PartitionSpecs and one GSPMD "
                         "jit program replaces the per-mode step "
                         "builders (docs/PARALLELISM.md)")
    ap.add_argument("--pp-microbatches", type=int, default=2)
    ap.add_argument("--tau", default="10",
                    help="local-SGD sync period: an integer or 'auto' "
                         "(telemetry-driven controller)")
    ap.add_argument("--grad-compress", choices=("none", "bf16", "int8"),
                    default=None,
                    help="compress the gradient/weight-delta all-reduce "
                         "with error-feedback residuals (also "
                         "SPARKNET_GRAD_COMPRESS; needs --parallel "
                         "sync|local; docs/COMMUNICATION.md)")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--attention",
                    choices=("flash", "reference", "ring", "ulysses"),
                    default=None,
                    help="flash/reference pick the single-device kernel; "
                         "ring/ulysses are the --parallel sp "
                         "implementations")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="replace dense FFNs with an N-expert MoE")
    ap.add_argument("--moe-top-k", type=int, default=1)
    ap.add_argument("--moe-dispatch", choices=("dense", "sort"),
                    default="sort",
                    help="sort = O(tokens) dispatch (use at scale); "
                         "dense = one-hot einsums (small models)")
    ap.add_argument("--moe-capacity", type=float, default=1.25,
                    help="per-expert capacity factor")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialise encoder layers (activation "
                         "memory ~ O(1) in depth; long-context knob)")
    ap.add_argument("--snapshot", type=int, default=0,
                    help="snapshot every N iters (Solver modes: full "
                         "solver state, resumable; tp/sp/pp/ep modes: "
                         "params-only npz)")
    ap.add_argument("--snapshot-prefix", default=os.path.join("runs", "bert"),
                    help="CWD-relative like Caffe's snapshot_prefix; the "
                         "default corrals artifacts under runs/")
    ap.add_argument("--restore", default=None, metavar="SOLVERSTATE",
                    help="resume from a .solverstate.npz snapshot")
    ap.add_argument("--auto-resume", action="store_true",
                    help="resume from the newest snapshot-prefix "
                         "solverstate if one exists (preemption recovery)")
    ap.add_argument("--profile-dir", default=None,
                    help="dump a jax.profiler trace of the training loop")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="host-side span trace + step-time breakdown "
                         "(Solver modes; Chrome trace-event JSON, also "
                         "SPARKNET_TRACE; docs/OBSERVABILITY.md)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="batches staged ahead on device (0 disables)")
    ap.add_argument("--snapshot-format", choices=("npz", "orbax"),
                    default="npz",
                    help="solverstate on-disk format (Solver modes)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None) -> Dict[str, float]:
    from ..utils import compile_cache

    compile_cache.enable()
    args = parser().parse_args(argv)
    multihost.initialize()  # no-op without SPARKNET_COORDINATOR
    if args.parallel in ("tp", "sp", "pp", "ep"):
        try:
            return run_model_parallel(args)
        finally:
            # single-process today (run_model_parallel enforces it), but
            # the goodbye must never depend on that staying true
            multihost.stop_heartbeat()
    solver, feed, cfg = build(args)
    from ..solver.snapshot import solverstate_suffix

    solver.snapshot_suffix = solverstate_suffix(args.snapshot_format)
    from ..solver.snapshot import apply_auto_resume, resolve_prefix

    args.snapshot_prefix = resolve_prefix(args.snapshot_prefix)
    apply_auto_resume(args, args.snapshot_prefix)
    if args.restore:
        solver.restore(args.restore, feed)
    # wrap AFTER restore (see cifar_app.main)
    from ..data.prefetch import maybe_prefetch

    feed = maybe_prefetch(feed, args, args.parallel)
    # every tile lies under the key mask the model passes
    from ..ops.attention import flash_tile_kinds, uses_flash

    seq = solver.train_net.seq_len
    tiles = flash_tiles_note(dict(zip(
        ("unmasked", "masked"),
        flash_tile_kinds(seq, seq, causal=False, key_mask=True),
    )) if uses_flash(args.attention or None) else {})
    primary = multihost.is_primary()
    if primary:
        if args.restore:
            print(f"Restoring previous solver status from {args.restore} "
                  f"(iter {solver.iter})")
        n_params = solver.train_net.num_params(solver.params)
        print(
            f"BertApp: config={args.config} vocab={cfg.vocab_size} "
            f"layers={cfg.num_layers} hidden={cfg.hidden_size} params={n_params} "
            f"{tiles}"
        )
    from ..utils.profiling import StepTimer, trace

    timer = StepTimer(
        items_per_step=args.batch_size * solver.train_net.seq_len,
        unit="tokens",
    )
    from .. import telemetry

    # --trace / SPARKNET_TRACE: span tracer + step-time attribution on
    # the Solver path (see cifar_app.main; docs/OBSERVABILITY.md)
    telemetry.install_for_training(solver, args.trace, args.profile_dir)
    t0 = time.time()
    metrics = {}
    try:
        # the telemetry bracket also runs the periodic telemetry: line
        # (SPARKNET_TELEMETRY_INTERVAL_S) like cifar_app.train_loop
        with trace(args.profile_dir), telemetry.training_loop(
            solver.timeline, emit=print
        ):
            metrics = _fit(
                solver,
                telemetry.first_batch_lowered(feed, solver, args.profile_dir),
                args, timer, primary,
            )
    finally:
        telemetry.finish_run()
    dt = time.time() - t0
    if primary:
        done_iters = solver.iter  # may be < max_iter after a preemption
        print(
            f"Optimization Done. {done_iters} iters in {dt:.1f}s "
            f"({done_iters / max(dt, 1e-9):.1f} it/s)"
        )
        tl = solver.timeline
        if tl.enabled:
            print("telemetry: step-time breakdown")
            for line in tl.table().splitlines():
                print(f"  {line}")
            drops = telemetry.trace.dropped_spans()
            if drops:
                print(f"  trace: {drops} span(s) dropped (ring buffer)")
        # cluster-merged phase table when the heartbeat piggyback ran
        # (same discipline as cifar_app.train_loop)
        telemetry.aggregate.self_ingest()
        agg = telemetry.aggregate.get_aggregator()
        if agg is not None and agg.has_data():
            print("cluster: phase table (per-rank shares of loop wall time)")
            for line in agg.table().splitlines():
                print(f"  {line}")
        # layout/comm/tau record lines, same discipline as
        # cifar_app.train_loop
        if getattr(solver, "layout_report", None):
            import json as _json

            lrep = solver.layout_report()
            if lrep:
                print(f"layout: {_json.dumps(lrep)}")
        if hasattr(solver, "comm_report"):
            import json as _json

            report = solver.comm_report()
            tc = getattr(solver, "tau_controller", None)
            if tc is not None:
                report.pop("tau_controller", None)
                print(f"tau: {tc.json_line()}")
                if args.snapshot_prefix:
                    path = tc.write_report(args.snapshot_prefix)
                    if path:
                        print(f"tau controller report written to {path}")
            print(f"comm: {_json.dumps(report)}")
    multihost.stop_heartbeat()  # graceful leave (see cifar_app.main)
    return metrics


def _fit(solver, feed, args, timer, primary) -> Dict[str, float]:
    from ..solver.preempt import preemption_grace

    with preemption_grace(solver):
        return _fit_loop(solver, feed, args, timer, primary)


def _fit_loop(solver, feed, args, timer, primary) -> Dict[str, float]:
    from ..telemetry import anomaly as _anomaly

    metrics: Dict[str, float] = {}
    while solver.iter < args.max_iter:
        # stop at the nearest of: next display chunk, next snapshot
        # boundary, max_iter — so the cadences can't skip each other
        # (same scheme as cifar_app.train_loop).
        targets = [args.max_iter]
        for interval in (args.display or 20, args.snapshot):
            if interval:
                targets.append((solver.iter // interval + 1) * interval)
        prev_iter = solver.iter
        timer.update(0)  # reset: exclude snapshot/feed-setup wall time
        def _log_iter(it, mm):
            # loss-spike stream (telemetry/anomaly.py) at display cadence
            _anomaly.observe_loss(float(mm["loss"]))
            if primary:
                print(
                    f"Iteration {it}, loss = {mm['loss']:.5f}, "
                    f"mlm_acc = {mm['mlm_acc']:.4f}"
                )

        m = solver.step(feed, min(targets) - solver.iter, log_fn=_log_iter)
        if m:  # a preempted chunk may return {} — keep the last real one
            metrics = {k: float(v) for k, v in m.items()}  # host sync
        if primary and args.display:
            print(f"    speed: {timer.update(solver.iter - prev_iter).format()}")
        preempted = solver.stop_requested
        if preempted:
            solver.stop_requested = False  # consumed: solver reusable
        at_end = solver.iter >= args.max_iter
        snap_now = preempted and args.snapshot_prefix
        if (
            args.snapshot and (solver.iter % args.snapshot == 0 or at_end)
        ) or snap_now:
            path = (
                f"{args.snapshot_prefix}_iter_{solver.iter}"
                f"{solver.snapshot_suffix}"
            )
            solver.save(path)  # collective; process 0 writes
            if primary:
                print(f"Snapshotting solver state to {path}")
        if preempted:
            if primary:
                from ..solver.preempt import preempt_message

                print(preempt_message(solver.iter, bool(snap_now)))
            break
    return metrics


if __name__ == "__main__":
    main()
