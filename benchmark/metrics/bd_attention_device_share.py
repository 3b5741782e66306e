"""Share of the chip's op time under the scope ``bd_attention``: the
block-diffusion attention's projections, per-head q/k norms, rotary
embedding, the masked blockwise products and ``W_o``, forward,
rematerialised and backward (``feddrift_tpu/models/sdar_moe.py``)."""

from benchmark.metrics._scope_share import share


def read(records, trace, cell):
    return share(trace, "bd_attention")
