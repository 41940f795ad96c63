"""Kernels: the KDA scan against its roofline: the least time for the
recurrence's own work counted from shapes
(``ling3_flash_flops.kda_scan_work``: three products a token and head
forward and twice that backward, or q, k, v, g, beta, o and their gradients
moved once, whichever takes longer at the peaks) over ``kda_scan_ms``.  A
chunked form's extra arithmetic, a recomputed forward and every pass over
an intermediate read low."""

from benchmark.layers import hybrid_ops


def read(run):
    ms = hybrid_ops.kda_scan_ms(run)
    if not ms:
        return None
    from benchmark.configs.ling3_flash_flops import kda_scan_work

    return hybrid_ops.roofline_share(
        run, kda_scan_work(run["config"], run["shapes"]), ms
    )
