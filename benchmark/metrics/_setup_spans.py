"""What the five set-up metrics share: the program's spans of set-up.

The program arms its span recorder first thing in ``Experiment.__init__``
and records there the build (``cat="setup"``: ``experiment_build`` with its
children ``data``, ``pool_init``, ``data_to_device`` and ``algorithm``) and
JAX's own stages of every program it builds (``cat="compile"``: ``trace``,
``lower`` and ``compile``, the last with ``cache`` = ``hit``, ``miss`` or
``off``; ``feddrift_tpu/obs/compile_spans.py``). **Set-up** is the ring's
spans that end before the ``iteration`` span of the window's first time step
starts: the build, the harness's weights and the warm-up time steps. The
reference's and the comparison's compiles come after the window and are not
in it. Times are in seconds; a ring keeps ``ts`` and ``dur`` in
microseconds.
"""


def setup_spans(records):
    """The set-up spans, or None where there is nothing sound to read: no
    window, a ring that has dropped any span (set-up's are the first to
    go), or no ``experiment_build`` span (a program that does not record
    its set-up)."""
    steps = records.get("time_steps") or []
    if not steps:
        return None
    from feddrift_tpu.obs import spans
    recorder = spans.get_recorder()
    if getattr(recorder, "dropped", 1):
        return None
    ring = recorder.spans()
    first = steps[0]["t"]
    start = next((s["ts"] for s in ring
                  if s["name"] == "iteration" and s["cat"] == "runner"
                  and s.get("args", {}).get("iteration") == first), None)
    if start is None:
        return None
    setup = [s for s in ring if s["ts"] + s["dur"] <= start]
    if not any(s["name"] == "experiment_build" and s["cat"] == "setup"
               for s in setup):
        return None
    return setup


def union_s(intervals) -> float:
    """Seconds covered by the (start, end) microsecond intervals, each
    counted once however many overlap it."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total * 1e-6


def stage_s(records, stage):
    """Seconds of set-up covered by the ``cat="compile"`` spans called
    ``stage`` (``trace``, ``lower``, ``compile``)."""
    setup = setup_spans(records)
    if setup is None:
        return None
    return union_s((s["ts"], s["ts"] + s["dur"]) for s in setup
                   if s["cat"] == "compile" and s["name"] == stage)
