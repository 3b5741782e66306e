"""The longest ``run_iteration`` call of the window, by the benchmark's own
clock around the call. A window holds too few time steps for a percentile."""


def read(records, trace, cell):
    steps = records["time_steps"]
    return max(s["wall_s"] for s in steps) if steps else None
