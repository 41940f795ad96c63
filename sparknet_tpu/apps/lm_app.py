"""LmApp — causal-LM pre-training entrypoint for the decoder family.

The entrypoint shape is BertApp's: pick a config, build the feed, drive
the :class:`~sparknet_tpu.solver.trainer.Solver` (single chip), AdamW with
BertApp's schedule, batches staged ahead by ``maybe_prefetch``.

    python -m sparknet_tpu.apps.lm_app --config tiny --max-iter 20
    python -m sparknet_tpu.apps.lm_app --config benchmark/configs/laguna_xs2.json \\
        --bf16 --remat --seq-len 8192 --batch-size 2 --synthetic-tokens 4194304
    python -m sparknet_tpu.apps.lm_app --config benchmark/configs/mellum2.json \\
        --bf16 --remat --seq-len 8192 --batch-size 4 --pack-documents

``--pack-documents`` trains on packed documents (``data.text.packed_feed``:
lengths ``clip(lognormal(--doc-median, --doc-sigma), --doc-min, --doc-max)``
tokens, concatenated and cut every ``--seq-len``): attention, rotary
positions and the loss keep inside each document, and the progress line
gains ``doc_count``, ``loss_positions``, ``attn_pairs_full``,
``attn_pairs_window`` and ``flash_tiles_docs_full`` / ``_window`` (the score
tiles the documents leave of the start-up line's ``flash_tiles``, which is
then the most a batch-head walks).  The ``train feed:`` line says what
traffic the run had, with the pool's mean pairs a sequence (the registry's
``attn_pairs_pool`` gauges: what a mean step's attention costs).

``--config`` is ``tiny``, ``tiny_hybrid``, ``tiny_mamba``, ``tiny_conv`` or
the path of a JSON file holding a published ``config.json``'s keys, as cut to
this chip's share if it is (``num_experts`` held of ``deployment.num_experts_routed``).
Its ``model_type`` picks the model: ``bailing_hybrid`` is
:class:`~sparknet_tpu.models.decoder.HybridLM` (KDA and MLA layers by
``layer_group_size``, ``HybridConfig.from_published``), ``granitemoehybrid``
:class:`~sparknet_tpu.models.decoder.MambaHybridLM` (Mamba-2 and attention
layers by ``layer_types``, ``MambaHybridConfig.from_published``),
``lfm2_moe`` :class:`~sparknet_tpu.models.decoder.ConvHybridLM` (gated short
convolutions and attention by ``layer_types``,
``ConvHybridConfig.from_published``), anything else :class:`~sparknet_tpu.models.decoder.DecoderLM`
(``DecoderConfig.from_published``: ``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``, ``rope_parameters`` ...).  Token ids come
from the config's ``vocab_size``.  The progress line carries the model's
counters (the sparse layers' ``moe_slots_held``, ``moe_slots_in_kernel``
(as many where the ``moe_combine`` kernel sums the experts' rows into their
tokens, 0 where a scatter-add does), ``moe_load_max_over_mean``,
``moe_slots_dropped``, ``moe_slots_in_gmm`` (as many where the Pallas
kernels of ``ops/gmm.py`` compute the grouped products, 0 where
``lax.ragged_dot`` does); ``rope_rows_in_kernel``, the rows of q and k that the
``rope_to_heads`` kernel rotated, 0 where ``apply_rope`` did; the hybrid's
``kda_chunks``, ``kda_chunks_in_kernel`` and ``kda_decay_min`` in its place;
the Mamba hybrid's ``ssd_chunks``, ``ssd_chunks_in_kernel``,
``ssd_state_resets`` and ``ssd_decay_min`` alone; the convolutional
hybrid's ``short_conv_resets`` and ``moe_bias_rerouted`` beside the sparse
layers' and ``rope_rows_in_kernel``);
the telemetry registry has them, as every
solver's newest step metrics, under its source ``train_step``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Dict

import jax.numpy as jnp

from ..data.text import (
    clm_dataset, clm_feed, packed_dataset, packed_feed, pool_pairs,
)
from ..models.decoder import (
    CONV, KDA, MAMBA, SLIDING, ConvHybridConfig, ConvHybridLM, DecoderConfig,
    DecoderLM, HybridConfig, HybridLM, MambaHybridConfig, MambaHybridLM,
)
from ..ops.attention import flash_tile_kinds, uses_flash
from ..solver.trainer import Solver
from .bert_app import flash_tiles_note, make_solver_param

# a published file's model_type -> its configuration; DecoderConfig otherwise
_CONFIGS = {
    "bailing_hybrid": HybridConfig, "granitemoehybrid": MambaHybridConfig,
    "lfm2_moe": ConvHybridConfig,
}
_TINY = {
    "tiny": DecoderConfig, "tiny_hybrid": HybridConfig,
    "tiny_mamba": MambaHybridConfig, "tiny_conv": ConvHybridConfig,
}


def make_config(args):
    if args.config in _TINY:
        cfg = _TINY[args.config].tiny()
    else:
        with open(args.config) as fh:
            published = json.load(fh)
        kind = _CONFIGS.get(published.get("model_type"), DecoderConfig)
        cfg = kind.from_published(published)
    return dataclasses.replace(cfg, remat=True) if args.remat else cfg


def model_class(cfg):
    """The model a configuration's class builds (by this module's names, so
    that a test can plant a subclass)."""
    if isinstance(cfg, HybridConfig):
        return HybridLM
    if isinstance(cfg, MambaHybridConfig):
        return MambaHybridLM
    if isinstance(cfg, ConvHybridConfig):
        return ConvHybridLM
    return DecoderLM


def build(args):
    """(solver, feed, cfg): one chip through the Solver."""
    cfg = make_config(args)
    shapes = {"input_ids": (args.batch_size, args.seq_len)}
    if args.pack_documents:
        if args.seq_len < args.doc_min:
            raise SystemExit(
                f"--pack-documents: --seq-len {args.seq_len} is shorter than "
                f"the shortest document (--doc-min {args.doc_min})"
            )
        ds = packed_dataset(
            vocab_size=cfg.vocab_size, n_tokens=args.synthetic_tokens,
            seq_len=args.seq_len, median_len=args.doc_median,
            sigma=args.doc_sigma, min_len=args.doc_min, max_len=args.doc_max,
            seed=args.seed,
        )
        make_feed = packed_feed
        shapes.update(segment_ids=shapes["input_ids"], positions=shapes["input_ids"])
    else:
        ds = clm_dataset(
            vocab_size=cfg.vocab_size, n_tokens=args.synthetic_tokens,
            seq_len=args.seq_len, seed=args.seed,
        )
        make_feed = clm_feed
    model = model_class(cfg)(
        cfg, shapes,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        attention_impl=args.attention or None,
    )
    if args.pack_documents:
        print(packing_note(args, cfg, ds))
    solver = Solver(make_solver_param(args), shapes, model=model, seed=args.seed)
    return solver, make_feed(ds, args.batch_size, seed=args.seed), cfg


def packing_note(args, cfg, ds) -> str:
    """The ``train feed:`` line of a run on packed documents: the packing's
    parameters, the first batch's documents, and what a sequence of this
    traffic costs an attention layer — the mean over the pool, steady where
    a batch's own pairs (the progress line's ``attn_pairs_*``) are 0.45-1.75
    x it; set once as the registry's ``attn_pairs_pool`` gauges, one a
    layer kind."""
    from ..telemetry.registry import REGISTRY

    first = next(iter(packed_feed(ds, args.batch_size, seed=args.seed)))
    pairs = {"full": pool_pairs(ds)}
    window = getattr(cfg, "sliding_window", None)  # None: no window layers
    if window is not None:
        pairs["window"] = pool_pairs(ds, window)
    for kind, mean in pairs.items():
        REGISTRY.gauge("attn_pairs_pool", kind=kind).set(mean)
    return (
        f"train feed: packed documents, lengths clip(lognormal(median "
        f"{args.doc_median:g}, sigma {args.doc_sigma:g}), {args.doc_min}, "
        f"{args.doc_max}) tokens, cut every {args.seq_len}; first batch "
        f"doc_count={int((first['positions'] == 0).sum())} "
        f"loss_positions={int((first['labels'] >= 0).sum())}; the pool's "
        f"mean attn_pairs a sequence "
        + " ".join(f"{kind}={mean:.0f}" for kind, mean in pairs.items())
    )


def flash_tiles(cfg, seq_len: int) -> Dict[str, int]:
    """Score tiles a batch-head of each kind of layer executes in each flash
    kernel, by whether a mask runs over them (``flash_tile_kinds``); the
    hybrids' KDA, Mamba and conv layers run no flash kernel."""
    tiles = {}
    for kind in dict.fromkeys(cfg.layer_types):
        if kind in (KDA, MAMBA, CONV):
            continue
        tiles[f"{kind}_unmasked"], tiles[f"{kind}_masked"] = flash_tile_kinds(
            seq_len, seq_len, causal=True,
            window=cfg.sliding_window if kind == SLIDING else None,
        )
    return tiles


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="Causal-LM pre-training (LmApp)")
    ap.add_argument("--config", default="tiny",
                    help="'tiny', 'tiny_hybrid', 'tiny_mamba', 'tiny_conv' or a JSON "
                         "file of published config keys")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-iter", type=int, default=1000)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--display", type=int, default=20)
    ap.add_argument("--synthetic-tokens", type=int, default=1 << 16)
    ap.add_argument("--pack-documents", action="store_true",
                    help="train on packed documents: attention, positions "
                         "and the loss keep inside each (data.text.packed_feed)")
    ap.add_argument("--doc-median", type=float, default=1024.0,
                    help="median document length, tokens (lognormal)")
    ap.add_argument("--doc-sigma", type=float, default=1.0)
    ap.add_argument("--doc-min", type=int, default=32)
    ap.add_argument("--doc-max", type=int, default=8192)
    # one choice, but not a knob of this app's: every app's args carry
    # ``parallel`` for ``maybe_prefetch``, and benchmark/run.py reads it
    ap.add_argument("--parallel", choices=("none",), default="none",
                    help="single chip through the Solver")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--attention", choices=("flash", "reference"),
                    default=None, help="default: flash on a TPU")
    ap.add_argument("--remat", action="store_true",
                    help="recompute each layer in the backward pass")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="batches staged ahead on device (0 disables)")
    ap.add_argument("--snapshot", type=int, default=0,
                    help="snapshot the solver state every N iters")
    ap.add_argument("--snapshot-prefix", default=os.path.join("runs", "lm"))
    ap.add_argument("--restore", default=None, metavar="SOLVERSTATE")
    ap.add_argument("--profile-dir", default=None,
                    help="dump a jax.profiler trace of the training loop")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="host-side span trace + step-time breakdown")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None) -> Dict[str, float]:
    from .. import telemetry
    from ..data.prefetch import maybe_prefetch
    from ..solver.snapshot import resolve_prefix
    from ..utils import compile_cache
    from ..utils.profiling import StepTimer, trace

    compile_cache.enable()
    args = parser().parse_args(argv)
    solver, feed, cfg = build(args)
    args.snapshot_prefix = resolve_prefix(args.snapshot_prefix)
    if args.restore:
        solver.restore(args.restore, feed)
        print(f"Restoring previous solver status from {args.restore} "
              f"(iter {solver.iter})")
    feed = maybe_prefetch(feed, args, args.parallel)  # after restore
    tiles = flash_tiles_note(
        flash_tiles(cfg, args.seq_len) if uses_flash(args.attention) else {}
    )
    if args.pack_documents:
        # the band's tiles; what a batch's documents leave of them is the
        # progress line's flash_tiles_docs_full / _window
        tiles = tiles.replace("flash_tiles=", "flash_tiles at most ", 1)
    experts = (
        f"experts_held={cfg.experts_held} of {cfg.num_experts} "
        if hasattr(cfg, "experts_held") else ""
    )
    print(
        f"LmApp: config={args.config} vocab={cfg.vocab_size} "
        f"layers={cfg.num_layers} hidden={cfg.hidden_size} {experts}"
        f"params={solver.train_net.num_params(solver.params)} {tiles}"
    )
    timer = StepTimer(
        items_per_step=args.batch_size * args.seq_len, unit="tokens"
    )
    telemetry.install_for_training(solver, args.trace, args.profile_dir)
    t0 = time.time()
    metrics: Dict[str, float] = {}
    try:
        with trace(args.profile_dir), telemetry.training_loop(
            solver.timeline, emit=print
        ):
            metrics = _fit(
                solver,
                telemetry.first_batch_lowered(feed, solver, args.profile_dir),
                args, timer,
            )
    finally:
        telemetry.finish_run()
    dt = time.time() - t0
    print(f"Optimization Done. {solver.iter} iters in {dt:.1f}s "
          f"({solver.iter / max(dt, 1e-9):.1f} it/s)")
    if solver.timeline.enabled:
        print("telemetry: step-time breakdown")
        for line in solver.timeline.table().splitlines():
            print(f"  {line}")
    return metrics


def _fit(solver, feed, args, timer) -> Dict[str, float]:
    """Step in chunks that end at the next display or snapshot boundary."""
    metrics: Dict[str, float] = {}

    def log_iter(it, mm):
        print(
            f"Iteration {it}, loss = {float(mm['loss']):.5f}, token_acc = "
            f"{float(mm['token_acc']):.4f}, " + ", ".join(
                f"{name} = {float(mm[name]):.4g}"
                for name in solver.train_net.counters
            )
        )

    while solver.iter < args.max_iter:
        targets = [args.max_iter]
        for interval in (args.display or 20, args.snapshot):
            if interval:
                targets.append((solver.iter // interval + 1) * interval)
        before = solver.iter
        timer.update(0)
        m = solver.step(feed, min(targets) - solver.iter, log_fn=log_iter)
        metrics = {k: float(v) for k, v in m.items()}  # host sync
        if args.display:
            print(f"    speed: {timer.update(solver.iter - before).format()}")
        if args.snapshot and (
            solver.iter % args.snapshot == 0 or solver.iter >= args.max_iter
        ):
            path = (f"{args.snapshot_prefix}_iter_{solver.iter}"
                    f"{solver.snapshot_suffix}")
            solver.save(path)
            print(f"Snapshotting solver state to {path}")
    return metrics


if __name__ == "__main__":
    main()
