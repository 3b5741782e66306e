"""What the three span-reading metrics share: the window's time steps and
the program's spans recorded in them.

The program records a ``cat="round"`` span at each layer boundary of a round
(``dispatch`` in ``core/step.py``, ``device_compute`` at
``comm/multihost.py::fetch`` and at the runner's explicit blocks, the
runner's own segments), each with the time step (``iteration``) in its
``args``; its ``round_breakdown`` segments, which the driver copies into
``records["time_steps"]``, are the self times of those spans. The spans are
read from the process-wide recorder's ring, which outlives the
``Experiment``.
"""


def window(records):
    """(time steps, ``cat="round"`` spans recorded in them), or None where
    there is nothing sound to read: no time step, a time step without a
    ``device_compute`` segment (a program that does not measure the wait on
    every round), or a ring that may have dropped the start of the window
    (a recorder that does not say what it dropped counts as one that did)."""
    steps = records.get("time_steps") or []
    if not steps or not all("device_compute" in s["segments"] for s in steps):
        return None
    from feddrift_tpu.obs import spans
    recorder = spans.get_recorder()
    ring = [s for s in recorder.spans() if s["cat"] == "round"]
    window_t = {s["t"] for s in steps}

    def time_step(span):
        return span.get("args", {}).get("iteration")

    before = [s for s in ring
              if time_step(s) is not None and time_step(s) < min(window_t)]
    if getattr(recorder, "dropped", 1) and not before:
        return None
    return steps, [s for s in ring if time_step(s) in window_t]


def per_round(records, name):
    """``cat="round"`` spans called ``name`` per round of the window; None
    where there is none."""
    found = window(records)
    if found is None:
        return None
    steps, spans = found
    n = sum(1 for s in spans if s["name"] == name)
    return n / sum(s["rounds"] for s in steps) if n else None
