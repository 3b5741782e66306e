"""Readings for the limits of ``correct``, taken on the chip at a cell's own
size. The benchmark's own runs never run this.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 --what <mode>

``sound`` runs the program as the configuration states it through the
driver's set-up and comparison, with no window: the lower readings.
``control`` does the same with the program's own lower-precision path
switched on (``CONTROL``: bfloat16 parameters and moments, the nearest step
below the configurations' float32). ``faults`` puts the reference in the
program's place with one fault planted at a time and compares it with the
sound reference; ``look`` does so with no fault and the convolutions'
operands in bfloat16, which is what the configuration's compute precision
alone does to the numbers. Each mode prints one JSON line per seed: the
numbers of the comparison, then ``correct`` as the harness decides it by
the cell's limits, and the numbers that failed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROL = {"precision": "bf16_mixed"}
FAULTS = ("half_batch", "state_unchanged", "assign_altered")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", required=True,
                    choices=("sound", "control", "faults", "look"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import reference
    from benchmark import run as bench
    from benchmark.drivers import train
    manifest = bench.load_manifest()
    cell, config, traffic, sizes = bench.load_cell(manifest, args.workload,
                                                   args.rehearse)
    bench.setup_jax(args.rehearse)
    on_program = args.what in ("sound", "control")
    device = {"platform": "cpu", "kind": "cpu", "count": 1} if args.rehearse \
        else bench.require_chips(cell["chips"])

    def emit(seed, mode, numbers):
        checks = reference.compare(numbers, sizes["limits"])
        failed = sorted(k for k, c in checks.items() if not c["ok"])
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "mode": mode, **numbers, "correct": not failed,
                          "failed": failed}), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        if on_program:
            run_as = config if args.what == "sound" else bench.overlay(
                config, {"program": CONTROL})
            result = train.run(
                manifest=manifest, cell=cell, config=run_as, traffic=traffic,
                sizes=sizes, seed=seed, seconds=0.0, trace=False,
                rehearse=args.rehearse, device=device, t_start=0.0)
            emit(seed, args.what if args.what == "sound"
                 else f"control:{CONTROL['precision']}", result["numbers"])
            continue
        job = _reference_job(train, cell, config, traffic, sizes, seed)
        if args.what == "look":
            planted = [("look:bf16_compute", {"compute_dtype": "bfloat16"})]
        else:
            planted = [(f"fault:{f}", {"fault": f}) for f in FAULTS]
        for mode, kw in planted:
            seen = train.reference_as_program(*job, **kw)
            emit(seed, mode, train.check(*job, seen))
    return 0


def _reference_job(train, cell, config, traffic, sizes, seed):
    """(arch, hyper, init, x, y, job, traffic) as the driver hands them to
    the comparison, made without the program's ``Experiment``."""
    from benchmark import weights
    from feddrift_tpu.data.registry import make_dataset
    clients = int(sizes["clients_per_chip"]) * int(cell["chips"])
    cfg = train.experiment_config(config, traffic, sizes, seed, clients)
    follow = int(traffic["check"]["follow_time_steps"])
    ds = make_dataset(cfg)
    x, y = ds.x[:, : follow + 1], ds.y[:, : follow + 1]
    init = train.initial_models(
        weights.make_weights(config["arch"], seed, cfg.concept_num), traffic)
    hyper = dict(config["optimizer"], lr=cfg.lr, wd=cfg.wd)
    job = {"seed": cfg.seed, "batch": cfg.batch_size,
           "local_steps": cfg.epochs}
    return config["arch"], hyper, init, x, y, job, traffic


if __name__ == "__main__":
    sys.exit(main())
