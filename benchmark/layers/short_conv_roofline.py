"""Kernels: the gated short convolution against its roofline: the least
time for its own work counted from shapes
(``lfm2_8b_a1b_flops.short_conv_work``: the taps' multiply-adds, or the
gates, the input and the output and their gradients moved once in bfloat16,
whichever takes longer at the peaks; the bytes bound it) over
``short_conv_ms``.  A float32 pass, a recomputed forward and every pass
over an intermediate read low.  None where the run has no such
convolution."""

from benchmark.layers import decoder_ops, scope_ops


def read(run):
    config = run.get("config", {})
    if "conv_L_cache" not in config or "input_ids" not in run.get("shapes", {}):
        return None
    ms = scope_ops.ms(run, scope_ops.under("conv.short"))
    if not ms:
        return None
    from benchmark.configs.lfm2_8b_a1b_flops import short_conv_work

    return decoder_ops.roofline_share(run, short_conv_work(config, run["shapes"]), ms)
