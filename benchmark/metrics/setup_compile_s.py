"""Seconds of set-up in ``compile_or_get_cached``: the union of the
set-up's ``compile`` spans (``cat="compile"``), XLA compiles and loads from
the persistent compilation cache alike; ``setup_cache_misses`` says how
many were compiles."""

from benchmark.metrics._setup_spans import stage_s


def read(records, trace, cell):
    return stage_s(records, "compile")
