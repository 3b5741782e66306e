"""Programs that XLA built in set-up because the persistent compilation
cache did not hold them: the set-up's ``compile`` spans whose ``cache`` is
``miss`` (the cache was asked) or ``off`` (it was not). 0 on a run whose
every program came from the cache: it says whether a run was cold, and,
with ``fn`` on each span, which program the cache does not keep."""

from benchmark.metrics._setup_spans import setup_spans


def read(records, trace, cell):
    setup = setup_spans(records)
    if setup is None:
        return None
    return sum(1 for s in setup if s["cat"] == "compile"
               and s["name"] == "compile"
               and s.get("args", {}).get("cache") in ("miss", "off"))
