"""What the whole-run tests share: a cell's files at the rehearsal's sizes and
one drive of everything in a run but the look for a chip (the driver's
set-up, warm-up, window, reference and comparison), held to the cell's own
limits; the faults that are planted under the timed path; and a temporary
copy of ``benchmark/`` to which a family of the tests' own, its
configurations, its cells and a per-layer metric are added as files and
appended manifest entries, none that is there being edited."""

import functools
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402
from benchmark.drivers import train  # noqa: E402

MANIFEST = bench.load_manifest()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "resnet20.ifca_perround"
SEED = 2 ** 31 + 5


def files(cell_name=CELL, program=None, manifest=MANIFEST):
    """``program`` is laid over the cell file's own group, the last word on
    the program's configuration."""
    cell, config, traffic, sizes = bench.load_cell(manifest, cell_name,
                                                   rehearse=True)
    if program:
        sizes = bench.overlay(sizes, {"program": program})
    return cell, config, traffic, sizes


def drive(cell_name=CELL, seed=SEED, program=None, manifest=MANIFEST):
    cell, config, traffic, sizes = files(cell_name, program, manifest)
    return train.run(manifest=manifest, cell=cell, config=config,
                     traffic=traffic, sizes=sizes, seed=seed, seconds=0.5,
                     trace=False, rehearse=True, device=CPU, t_start=0.0)


def failed(result):
    return sorted(k for k, c in result["check"].items() if not c["ok"])


# ----------------------------------------------------------------------
# the faults a one-chip training cell can have, planted in the program
def plant(monkeypatch, fault: str) -> None:
    """``state_unchanged``: ``train_round`` returns parameters and
    optimizer state as it got them. ``half_batch``: the loss and its
    gradient over the first half of every batch, the mean over the rest.
    ``assign_altered``: the accuracy matrix that the host's re-assignment
    reads comes back negated, so every client goes to its worst model."""
    import jax
    from feddrift_tpu.core import step
    if fault == "state_unchanged":
        real = step.TrainStep.train_round

        @functools.wraps(real)
        def unchanged(self, params, opt_states, *a, **kw):
            keep = jax.tree_util.tree_map(lambda l: l.copy(),
                                          (params, opt_states))
            out = real(self, params, opt_states, *a, **kw)
            return keep + tuple(out[2:])
        monkeypatch.setattr(step.TrainStep, "train_round", unchanged)
    elif fault == "half_batch":
        real = step.cross_entropy
        monkeypatch.setattr(
            step, "cross_entropy",
            lambda logits, labels: real(logits[: logits.shape[0] // 2],
                                        labels[: labels.shape[0] // 2]))
    elif fault == "assign_altered":
        real = step.TrainStep.acc_matrix

        def negated(self, *a, **kw):
            correct, loss, total = real(self, *a, **kw)
            return -correct, loss, total
        monkeypatch.setattr(step.TrainStep, "acc_matrix", negated)
    else:
        raise KeyError(fault)


# ----------------------------------------------------------------------
# a family, two configurations and two cells that arrive as files alone
FAMILY = "dense2_fixture"
DOOR_ARCH = {"family": FAMILY, "input": [3], "hidden": 10, "num_classes": 2}
DOOR_OPTIMIZERS = {
    "amsgrad": ("adam", {"kind": "amsgrad", "b1": 0.9, "b2": 0.999,
                         "eps": 1e-8}),
    "sgd": ("sgd", {"kind": "sgd"}),
}
# set from rehearsals on the CPU in float32 at the fixture's own size (sound
# on seeds 1-8, each fault on seeds 1-3): sound readings lie under 1e-4
# (the store gaps under 6e-3) and 0.0 for assign_regret; state_unchanged
# reads change_gap 1.0, half_batch change_gap 0.03-0.80 and train_loss_gap
# 0.03-0.87, assign_altered assign_regret 0.016-0.31 (DOOR_SEED: 0.17-0.22)
DOOR_LIMITS = {
    "amsgrad": {"train_loss_gap": 0.01, "first_grad_gap_median": 0.05,
                "moment_gap_median": 0.05, "change_gap": 0.05,
                "change_gap_median": 0.02, "assign_regret": 0.05,
                "param_store_gap": 0.3, "moment_store_gap": 0.3},
    "sgd": {"train_loss_gap": 0.01, "change_gap": 0.05,
            "change_gap_median": 0.02, "assign_regret": 0.05,
            "param_store_gap": 0.3},
}
DOOR_SEED = 2


def door_cell(kind: str) -> str:
    return f"dense2_{kind}.ifca_perround"


# a per-layer metric of the door's own: its entry lists one door cell, and its
# reader is a file that only the copy has
DOOR_METRIC = {"name": "door_rounds_per_time_step", "unit": "count",
               "better": "lower", "source": "program_span", "layer": "runner",
               "moves": "train_examples_per_s",
               "workloads": [door_cell("sgd")]}
DOOR_READER = '''"""Rounds a time step of the window: a reader that only
the tests' copy of ``benchmark/`` has."""


def read(records, trace, cell):
    steps = records.get("time_steps") or []
    if not steps:
        return None
    return sum(s["rounds"] for s in steps) / len(steps)
'''


def open_door(tmp: str, limits=DOOR_LIMITS) -> dict:
    """Copies ``benchmark/`` to ``tmp`` and ADDS, for each optimizer, a
    configuration ``sea_dense2_<kind>`` of the family ``dense2_fixture``
    (the program's ``fnn`` on ``sea``) and a cell of it under the traffic
    ``ifca_perround``, and the per-layer metric ``DOOR_METRIC`` with its
    reader ``metrics/<name>.py``, with their manifest entries appended in a
    ``BENCHMARK.json`` beside it. Returns that manifest."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads(json.dumps(MANIFEST))
    for kind, (client_optimizer, group) in DOOR_OPTIMIZERS.items():
        config = {
            "name": f"sea_dense2_{kind}", "source": "tests/benchmark",
            "arch": DOOR_ARCH,
            "program": {"model": "fnn", "dataset": "sea", "sample_num": 64,
                        "batch_size": 16, "epochs": 2,
                        "client_optimizer": client_optimizer, "lr": 0.05,
                        "wd": 0.001, "precision": "auto", "dtype": "float32",
                        "compute_dtype": "bfloat16", "remat": False},
            "optimizer": group, "reduced": {}, "assumed": ["a fixture"]}
        cell = {"name": door_cell(kind), "clients_per_chip": 4,
                "program": {"train_iterations": 6}, "limits": limits[kind]}
        for sub, body in (("configs", config), ("cells", cell)):
            with open(os.path.join(tmp, "benchmark", sub,
                                   f"{body['name']}.json"), "w") as f:
                json.dump(body, f, indent=1)
        manifest["configs"].append({
            "name": config["name"], "source": config["source"],
            "file": f"benchmark/configs/{config['name']}.json",
            "reduced": [], "why": "a fixture family of the tests"})
        manifest["workloads"].append({
            "name": cell["name"], "config": config["name"],
            "traffic": "ifca_perround", "chips": 1,
            "why": "the door is open: files alone"})
    with open(os.path.join(tmp, "benchmark", "metrics",
                           f"{DOOR_METRIC['name']}.py"), "w") as f:
        f.write(DOOR_READER)
    manifest["per_layer"].append(dict(DOOR_METRIC))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return manifest
