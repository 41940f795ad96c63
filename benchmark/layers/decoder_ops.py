"""What the decoder cells' readers share: finding the decoder's kernels and
routing operations among a traced run's device operations.

The trace names an operation by its HLO text: ``%name = shape opcode(operands
with their shapes), attributes``.  The Pallas flash kernels carry their
``pallas_call`` name (``flash_attention_fwd`` / ``_dq`` / ``_dkv``) in the
instruction's name and the query tensor ``bf16[B,H,S,D]`` among their
shapes, so the layers of one kind are told from the other by their head
count.  ``jax.lax.ragged_dot`` compiles to the TPU's grouped matrix product,
an instruction named ``ragged-dot...``.  Everything else of the expert layer
(router, top-k, sort, gather, scatter-add, the weighting) has no name of its
own and is matched by what it works on: tensors with the routing's
dimensions — tokens x routed experts, tokens x experts a token, the slots,
the rows of one chunk of gathered slots, which the program states
(``parallel/moe.held_chunk_rows``) and this file does not reckon again.  A
run without these (another configuration, an older program) matches nothing
and its reader returns None; a run that has grouped products but no tensor
of a chunk's rows says so on a ``bench:`` line and reads None too, since
its gathers and scatter-adds would be missing from the sum.  This file is
no metric's reader.
"""

import math
import re

_CONTAINERS = (" while(", " conditional(", " call(")


def _per_step(run, keep):
    trace = run.get("trace")
    if not trace:
        return None
    seconds = [s for name, s in trace["op_seconds"].items() if keep(name)]
    return 1e3 * sum(seconds) / trace["steps"] if seconds else None


def heads_of(config, kind):
    """The head count of the layers of ``kind``, if they have exactly one."""
    n = config.get("num_hidden_layers", 0)
    counts = {
        h for k, h in zip(
            config.get("layer_types", [])[:n],
            config.get("num_attention_heads_per_layer", [])[:n],
        ) if k == kind
    }
    return counts.pop() if len(counts) == 1 else None


def attention_ms(run, kind):
    """Device ms a step in the flash kernels of the layers of ``kind``."""
    shape = run["shapes"].get("input_ids")
    heads = heads_of(run["config"], kind)
    if shape is None or heads is None:
        return None
    tensor = "bf16[{},{},{},{}]".format(
        shape[0], heads, shape[1], run["config"]["head_dim"]
    )
    return _per_step(
        run, lambda name: "flash_attention" in name.split(" = ")[0]
        and " custom-call(" in name and tensor in name,
    )


def _grouped_product(name):
    return "ragged-dot" in name.split(" = ")[0]


def expert_products_ms(run):
    """Device ms a step in the grouped products over the experts held."""
    return _per_step(run, _grouped_product)


def routing_dims(config, shapes):
    """The routing's dimension strings for a batch: tokens x routed,
    tokens x chosen, slots, the slots padded to whole chunks, and the rows
    of a chunk, last (the program's own ``held_chunk_rows``).  None where
    the program has no expert layer that says how it chunks."""
    try:
        from sparknet_tpu.parallel.moe import held_chunk_rows
    except ImportError:
        return None
    b, s = shapes["input_ids"]
    tokens, k = b * s, config["num_experts_per_tok"]
    routed = config["deployment"]["num_experts_routed"]
    rows = held_chunk_rows(tokens * k, config["num_experts"], routed)
    padded = rows * math.ceil(tokens * k / rows)
    return [
        f"[{tokens},{routed}]", f"[{tokens},{k}]", f"[{tokens * k}]",
        f"[{padded}]", f"[{rows}]", f"[{rows},",
    ]


def routing_ms(run):
    """Device ms a step in the expert layers' operations other than the
    grouped products: router, top-k, sort, gather, weighting, scatter-add."""
    config, shapes = run["config"], run["shapes"]
    if "input_ids" not in shapes or "num_experts_routed" not in config.get(
        "deployment", {}
    ):
        return None
    dims = routing_dims(config, shapes)
    if dims is None:
        return None
    routing = lambda name: not _grouped_product(name) and not any(
        c in name for c in _CONTAINERS
    )
    chunk = dims[-1]
    if expert_products_ms(run) and not _per_step(
        run, lambda name: routing(name) and chunk in name
    ):
        print(
            f"bench: moe_route_ms: grouped products but no operation on a "
            f"chunk's {chunk} rows: the expert layer chunks otherwise than "
            f"parallel/moe.held_chunk_rows says; not read", flush=True,
        )
        return None
    pattern = re.compile("|".join(re.escape(d) for d in dims))
    return _per_step(run, lambda name: routing(name) and pattern.search(name))


def step_counter(name):
    """The program's own counter ``name`` of the newest step (the last of
    the traced ones), from the telemetry registry's ``train_step`` source.
    None where the program has no such counter."""
    try:
        from sparknet_tpu.telemetry.registry import REGISTRY
    except ImportError:
        return None
    source = REGISTRY.sources().get("train_step")
    return source.snapshot().get(name) if source else None


def held_slots(run):
    """Slots routed to held experts in a step, over all sparse layers
    (``moe_slots_held`` is the mean over them)."""
    held = step_counter("moe_slots_held")
    if held is None:
        return None
    n = run["config"]["num_hidden_layers"]
    return held * run["config"]["mlp_layer_types"][:n].count("sparse")


def roofline_share(run, work, ms):
    """100 x the least time the chip could take for ``work`` = (FLOPs,
    bytes) a step — the larger of FLOPs over the bf16 peak and bytes over
    the HBM peak — over the ``ms`` measured."""
    if not ms:
        return None
    flops, nbytes = work
    peaks = run["peaks"]
    least_s = max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * 1e3 * least_s / ms
