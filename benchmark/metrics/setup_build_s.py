"""Seconds of the experiment's build that were host work: the duration of
the ``experiment_build`` span (``cat="setup"``; data, model, pool, the
data's copy to the device and the algorithm) less the union of the
``cat="compile"`` spans inside it."""

from benchmark.metrics._setup_spans import setup_spans, union_s


def read(records, trace, cell):
    setup = setup_spans(records)
    if setup is None:
        return None
    build = [s for s in setup
             if s["name"] == "experiment_build" and s["cat"] == "setup"][-1]
    a, b = build["ts"], build["ts"] + build["dur"]
    inside = [(max(s["ts"], a), min(s["ts"] + s["dur"], b)) for s in setup
              if s["cat"] == "compile"
              and s["ts"] < b and s["ts"] + s["dur"] > a]
    return build["dur"] * 1e-6 - union_s(inside)
