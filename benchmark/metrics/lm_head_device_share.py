"""Share of the chip's op time under the scope ``lm_head``: the product of
the last hidden states with the head's matrix, forward and backward. The
float32 softmax over the logits is the loss's and so outside it."""

from benchmark.metrics._scope_share import share


def read(records, trace, cell):
    return share(trace, "lm_head")
