"""Host time of the drift algorithm per time step: the runner's
``drift_decision`` segment (``begin_iteration`` + ``end_iteration``)."""


def read(records, trace, cell):
    steps = records["time_steps"]
    if not steps or not all("drift_decision" in s["segments"] for s in steps):
        return None
    return 1e3 * sum(s["segments"]["drift_decision"] for s in steps) / len(steps)
