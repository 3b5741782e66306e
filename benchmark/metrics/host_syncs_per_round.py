"""Blocking host-device round trips per round: the ``device_compute`` spans
of the window's time steps (one per ``multihost.fetch`` and per explicit
``block_until_ready`` of the runner) over its rounds."""

from benchmark.metrics._round_spans import per_round


def read(records, trace, cell):
    return per_round(records, "device_compute")
