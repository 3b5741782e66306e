"""Seconds of set-up in which JAX traced a Python function to a jaxpr: the
union of the set-up's ``trace`` spans (``cat="compile"``; a trace nested in
another is folded into the outermost, which counts it under ``nested``).
Where the compile cache serves every program, set-up still traces and
lowers each one."""

from benchmark.metrics._setup_spans import stage_s


def read(records, trace, cell):
    return stage_s(records, "trace")
