"""Device time per round of the evaluation program: the ``XLA Modules``
events of the first chip whose names hold ``acc_matrix``, over the rounds of
the traced window. The per-round traffic dispatches it after every round (the
re-assignment reads it), at the evaluations and at the time-step boundary:
the assignment's sweep of M x C forwards, beside ``train_program_device_ms``."""


def read(records, trace, cell):
    if trace is None or not trace["rounds"]:
        return None
    total = sum(s for name, s in trace["module_s"].items()
                if "acc_matrix" in name)
    return 1e3 * total / trace["rounds"] if total > 0 else None
