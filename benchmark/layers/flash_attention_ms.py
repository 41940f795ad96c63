"""Kernels: device milliseconds per step in the Pallas flash-attention
kernels (forward, dq, dkv).  The program gives them no stable name, so they
are matched by what they are: custom calls on tensors of the attention
shape ``[batch, heads, sequence, head size]``.  Nothing to read where the
batch has no ``input_ids`` or no such call ran."""


def read(run):
    trace = run.get("trace")
    shape = run["shapes"].get("input_ids")
    config = run["config"]
    if not trace or shape is None or "num_attention_heads" not in config:
        return None
    heads = config["num_attention_heads"]
    tensor = "[{},{},{},{}]".format(
        shape[0], heads, shape[1], config["hidden_size"] // heads
    )
    seconds = [
        s for name, s in trace["op_seconds"].items()
        if " custom-call(" in name and tensor in name
    ]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / trace["steps"]
