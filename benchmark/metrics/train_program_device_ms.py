"""Device time per round of the traffic's round program: the ``XLA
Modules`` events of the first chip whose names hold the ``round_program``
that the traffic file names, over the rounds of the traced window."""


def read(records, trace, cell):
    if trace is None or not trace["rounds"]:
        return None
    total = sum(s for name, s in trace["module_s"].items()
                if records["round_program"] in name)
    return 1e3 * total / trace["rounds"] if total > 0 else None
