"""The share of the window in which the host was not waiting for the device:
100 x (sum of the time steps' wall - sum of their ``device_compute`` segment)
/ sum of their wall. ``device_compute`` is the self time of the spans around
the calls that block on the device (every ``multihost.fetch`` and the
runner's explicit ``block_until_ready``): wait plus copy. What is left is
host work the device may or may not overlap: dispatch, preparation, the
drift decision, logging. Gaps inside a program count as waiting here and as
idle for ``device_idle_share``."""

from benchmark.metrics._round_spans import window


def read(records, trace, cell):
    found = window(records)
    if found is None:
        return None
    steps, _ = found
    wall = sum(s["wall_s"] for s in steps)
    waited = sum(s["segments"]["device_compute"] for s in steps)
    return 100.0 * (wall - waited) / wall
