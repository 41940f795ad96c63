"""Kernels: the sliding-window layers' flash kernels against their
roofline: the least time for the work counted from shapes
(``laguna_xs2_flops.attention_kernels_work``: six products over the pairs
the window leaves, every tensor moved once) over ``window_attention_ms``.
A kernel that masks blocks it could skip, or recomputes, reads low."""

from benchmark.layers import decoder_ops


def read(run):
    ms = decoder_ops.attention_ms(run, "sliding_attention")
    if not ms:
        return None
    from benchmark.configs.laguna_xs2_flops import attention_kernels_work

    work = attention_kernels_work(run["config"], run["shapes"], "sliding_attention")
    return decoder_ops.roofline_share(run, work, ms)
