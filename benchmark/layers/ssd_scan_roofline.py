"""Kernels: the Mamba-2 scan against its roofline: the least time for the
recurrence's own work counted from shapes
(``granite4_h_micro_flops.ssd_scan_work``: the state's update and read a
token and head forward and twice that backward, or x, B, C, delta, y and
their gradients moved once, whichever takes longer at the peaks) over
``ssd_scan_ms``.  A chunked form's extra arithmetic, a recomputed forward
and every pass over an intermediate read low.  None where the run has no
such scan."""

from benchmark.layers import decoder_ops, scope_ops


def read(run):
    config = run.get("config", {})
    if "mamba_d_state" not in config or "input_ids" not in run.get("shapes", {}):
        return None
    ms = scope_ops.ms(run, scope_ops.under("ssd.scan"))
    if not ms:
        return None
    from benchmark.configs.granite4_h_micro_flops import ssd_scan_work

    return decoder_ops.roofline_share(run, ssd_scan_work(config, run["shapes"]), ms)
