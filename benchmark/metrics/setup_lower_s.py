"""Seconds of set-up in which JAX lowered a jaxpr to an MLIR module: the
union of the set-up's ``lower`` spans (``cat="compile"``)."""

from benchmark.metrics._setup_spans import stage_s


def read(records, trace, cell):
    return stage_s(records, "lower")
