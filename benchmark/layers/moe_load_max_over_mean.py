"""Expert layer: the fullest held expert over the mean of the held ones, the
worst sparse layer of the newest step: the program's own counter
(``decoder_ops.step_counter``).  1 is an even router; the grouped products
take as long as their fullest expert's tiles.  None where the program has
no such counter."""

from benchmark.layers import decoder_ops


def read(run):
    return decoder_ops.step_counter("moe_load_max_over_mean")
