"""What the whole-run tests share: a cell's files at the rehearsal's sizes and
one drive of everything in a run but the look for a chip (the driver's
set-up, warm-up, window, reference and comparison), held to the cell's own
limits."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402
from benchmark.drivers import train  # noqa: E402

MANIFEST = bench.load_manifest()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "resnet20.ifca_perround"
SEED = 2 ** 31 + 5


def files(cell_name=CELL, program=None):
    """``program`` is laid over the cell file's own group, the last word on
    the program's configuration."""
    cell, config, traffic, sizes = bench.load_cell(MANIFEST, cell_name,
                                                   rehearse=True)
    if program:
        sizes = bench.overlay(sizes, {"program": program})
    return cell, config, traffic, sizes


def drive(cell_name=CELL, seed=SEED, program=None):
    cell, config, traffic, sizes = files(cell_name, program)
    return train.run(manifest=MANIFEST, cell=cell, config=config,
                     traffic=traffic, sizes=sizes, seed=seed, seconds=0.5,
                     trace=False, rehearse=True, device=CPU, t_start=0.0)


def failed(result):
    return sorted(k for k, c in result["check"].items() if not c["ok"])
