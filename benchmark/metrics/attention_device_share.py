"""Share of the chip's op time under the scope ``mla_attention``: the latent
attention's projections, rotary embedding and blockwise products, forward,
rematerialised and backward."""

from benchmark.metrics._scope_share import share


def read(records, trace, cell):
    return share(trace, "mla_attention")
