"""Compiled step: device milliseconds per step in the head and the loss, all
passes: the ``lm_head`` scope of the decoders (final norm, head product,
chunked logits, cross-entropy), the ``loss`` scope of BERT (MLM head and
cross-entropy), a prototxt ``SoftmaxWithLoss`` layer's own scope."""

from benchmark.layers import scope_ops


def read(run):
    return scope_ops.ms(
        run, lambda chain, _pass, _kernel: any(
            s in ("loss", "lm_head") or s.startswith("softmaxwithloss.")
            for s in chain
        ),
    )
