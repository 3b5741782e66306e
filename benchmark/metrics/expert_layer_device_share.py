"""Share of the chip's op time under the scope ``expert_layer``: router,
choice, the held routed experts' loop of row blocks and the shared experts,
forward, rematerialised and backward."""

from benchmark.metrics._scope_share import share


def read(records, trace, cell):
    return share(trace, "expert_layer")
