"""Kernels: the grouped products over the experts held against their
roofline: the least time for the slots the step really held (the program's
``moe_slots_held`` counter; ``laguna_xs2_flops.expert_products_work``) over
``moe_experts_ms``.  It follows the kernel, not the router: an uneven
router changes the slots counted with the time.  Recomputed products read
low."""

from benchmark.layers import decoder_ops


def read(run):
    ms = decoder_ops.expert_products_ms(run)
    slots = decoder_ops.held_slots(run)
    if not ms or not slots:
        return None
    from benchmark.configs.laguna_xs2_flops import expert_products_work

    return decoder_ops.roofline_share(
        run, expert_products_work(run["config"], run["shapes"], slots), ms
    )
