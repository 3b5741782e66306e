"""Share of the chip's op time that none of the family's scopes claims:
embedding, dense MLP, norms and residuals, the loss, and everything of the
round program that is no model code: the SGD update, the running weighted
sum, the casts at the apply boundary."""

from benchmark import xplane
from benchmark.metrics._scope_share import share


def read(records, trace, cell):
    return share(trace, xplane.OUTSIDE)
