"""1 - busy over the traced window, busy being the union of the op
intervals on each chip's ``XLA Ops`` line, averaged over the chips."""


def read(records, trace, cell):
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
