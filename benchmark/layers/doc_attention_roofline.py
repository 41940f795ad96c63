"""Kernels: the flash kernels on packed documents against their roofline:
the least time for six products over the pairs of a mean batch (the batch
times the mean sequence of the seeded pool, the program's ``attn_pairs_pool``
gauges; ``mellum2_flops.doc_attention_work``), every tensor moved once, over
``doc_attention_ms``, the traced steps' mean: mean work over mean time.  A
kernel that masks blocks it could skip, or recomputes, reads low.  None where
the program has no such gauge."""

from benchmark.layers import decoder_ops, packed_ops


def read(run):
    from benchmark.configs.mellum2_flops import doc_attention_work, pool_pairs

    ms = packed_ops.doc_attention_ms(run)
    if not ms or pool_pairs("full") is None:
        return None
    return decoder_ops.roofline_share(
        run, doc_attention_work(run["config"], run["shapes"]), ms
    )
