"""What the packed-document cell's readers share beside ``decoder_ops``:
finding the flash kernels of a decoder whose layers have one head count.
This file is no metric's reader."""

from benchmark.layers import decoder_ops


def doc_attention_ms(run):
    """Device ms a step in the flash kernels (forward, the recomputed
    forward, dq, dkv) of every layer: the instructions named
    ``flash_attention_fwd/_dq/_dkv`` on the query tensor ``bf16[B,H,S,D]``.
    Window and full layers are both in it: one head count, one shape."""
    config, shape = run["config"], run["shapes"].get("input_ids")
    if shape is None or not {"num_attention_heads", "head_dim"} <= set(config):
        return None
    tensor = "bf16[{},{},{},{}]".format(
        shape[0], config["num_attention_heads"], shape[1], config["head_dim"]
    )
    return decoder_ops._per_step(
        run, lambda name: "flash_attention" in name.split(" = ")[0]
        and " custom-call(" in name and tensor in name,
    )
