"""What the hybrid decoder's readers share: finding the KDA scan's and the
MLA layers' operations among a traced run's device operations.

The trace names an operation by its HLO text (``decoder_ops``' header).

- MLA: the Pallas flash kernels carry their ``pallas_call`` name in the
  instruction's name and the query tensor ``bf16[B, H, S, qk_nope +
  qk_rope]`` among their shapes.
- The KDA scan (``sparknet_tpu/ops/kda.py``) is ``jax.numpy`` over chunks:
  it lowers to some hundreds of fusions and copies on head-split, chunked
  tensors and to a ``while`` that walks a segment's chunks.  Nothing else
  in the step has such tensors, so its operations are matched by what
  they work on (``scan_pattern``): a tensor whose dimensions hold, one
  after another, the chunks of a segment and the heads, ``N, [B,] H`` (a
  segment's stacked chunk matrices; XLA drops a batch of 1), or ``B, H,
  [1,] N, chunk``, or ``B, H, chunk`` / ``H, chunk`` (the recurrence's own
  operands), or the state ``[B, H, d_k, d_v]``.  Checked against the
  compiled step's metadata (the ``kda.scan`` scope, which the trace does
  not carry): the pattern finds the scope's operations and the backward
  pass's, which lose the scope's name, and none of the layer's
  projections, convolutions, gates or norms.  A container (``while``,
  ``conditional``, ``call``) is left out and its body's operations are
  counted, so no time counts twice (the leaves of a traced step add up
  to ``device_step_ms``).  The program states how it chunks
  (``HybridConfig.kda_chunk`` and ``kda_segment``); a program without
  them matches nothing, and so does a sequence that is not whole segments
  (shorter than one, its tensors would not be told from the projections').
- The grouped router (``parallel/moe.route_grouped``) has no name of its
  own either: its operations are those on tensors of tokens x routed
  experts, tokens x groups (x experts a group, x 2), tokens x groups kept
  and tokens x experts a token.  ``decoder_ops.routing_ms`` is not used
  here: at this configuration a chunk of gathered slots has as many rows
  (2560) as the model is wide, so its pattern takes every weight matrix
  for a routing tensor (441 ms of a 1349 ms step on the first trace).

A run without these (another configuration, an older program) matches
nothing and its reader returns None.  This file is no metric's reader.
"""

import re

from benchmark.layers.decoder_ops import _CONTAINERS, _per_step, roofline_share  # noqa: F401


def is_hybrid(config):
    return "layer_group_size" in config and "kv_lora_rank" in config


def mla_attention_ms(run):
    """Device ms a step in the flash kernels of the MLA layers."""
    config, shape = run["config"], run["shapes"].get("input_ids")
    if shape is None or not is_hybrid(config):
        return None
    tensor = "bf16[{},{},{},{}]".format(
        shape[0], config["num_attention_heads"], shape[1],
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
    )
    return _per_step(
        run, lambda name: "flash_attention" in name.split(" = ")[0]
        and " custom-call(" in name and tensor in name,
    )


def scan_pattern(config, batch, seq):
    """The regular expression that finds a tensor of the scan in an
    operation's text, or None where the program has no such scan."""
    try:
        from sparknet_tpu.models.decoder import HybridConfig
    except ImportError:
        return None
    cfg = HybridConfig.from_published(config)
    if not hasattr(cfg, "kda_segment"):
        return None
    if seq % cfg.kda_segment:
        return None  # the program refuses it, or runs one short segment
    b, h, d, c = batch, cfg.num_attention_heads, cfg.head_dim, cfg.kda_chunk
    n = cfg.kda_segment // c
    return re.compile("|".join([
        rf"[\[,]{n},(?:{b},)?{h},", rf"[\[,]{b},{h},(?:1,)?{n},{c}[,\]]",
        rf"[\[,]{b},{h},{c}[,\]]", rf"\[{b},{h},{d},{d}\]",
        rf"\[{h},{c},", rf"\[{h},{d},{d}\]",
    ]))


def kda_scan_ms(run):
    """Device ms a step in the KDA layers' scan: forward, recomputed
    forward and backward."""
    config, shape = run["config"], run["shapes"].get("input_ids")
    if shape is None or not is_hybrid(config):
        return None
    pattern = scan_pattern(config, *shape)
    if pattern is None:
        return None
    return _per_step(
        run, lambda name: not any(c in name for c in _CONTAINERS)
        and pattern.search(name) is not None,
    )


def grouped_route_ms(run):
    """Device ms a step in the grouped router of every sparse layer: the
    scores, the selection bias, the three ``top_k`` (two best a group,
    groups, experts), the weights and their gradients."""
    config, shape = run["config"], run["shapes"].get("input_ids")
    routed = config.get("deployment", {}).get("num_experts_routed")
    if shape is None or routed is None or not is_hybrid(config):
        return None
    tokens = shape[0] * shape[1]
    seconds = {
        routed, config["n_group"], config["topk_group"], config["num_experts_per_tok"],
    }
    pattern = re.compile(
        rf"\[{tokens},(?:{'|'.join(map(str, sorted(seconds)))})[,\]]"
    )
    return _per_step(
        run, lambda name: not any(c in name for c in _CONTAINERS)
        and pattern.search(name) is not None,
    )
