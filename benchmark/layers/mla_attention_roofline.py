"""Kernels: the MLA layers' flash kernels against their roofline: the
least time for the work counted from shapes
(``ling3_flash_flops.mla_attention_work``: the products over the causal
pairs, ``qk_nope + qk_rope + v_head_dim`` a pair forward and twice that
backward, every tensor moved once) over ``mla_attention_ms``.  A kernel
that computes tiles above the diagonal, or recomputes, reads low."""

from benchmark.layers import hybrid_ops


def read(run):
    ms = hybrid_ops.mla_attention_ms(run)
    if not ms:
        return None
    from benchmark.configs.ling3_flash_flops import mla_attention_work

    return hybrid_ops.roofline_share(
        run, mla_attention_work(run["config"], run["shapes"]), ms
    )
