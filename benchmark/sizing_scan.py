"""Compile-time HBM of a cell's round programs under the scanned client
axis (``client_axis="scan"``), with no chip.

    JAX_PLATFORMS=cpu python3 benchmark/sizing_scan.py --workload <cell> [--clients <C>]

What ``sizing.py`` does for the vmap body, for a configuration whose
``program`` group asks for the scanned body: ``sizing.py`` builds its
``TrainStep`` from a fixed list of arguments, without the program's
``client_axis``, so it would lower the vmap body (PERF.md section 7 names the
edit that folds this file into it). Lowers ``train_round`` and ``acc_matrix`` with
shapes only and compiles them for a described ``v5e:2x2`` device, one line a
program. A last line says what a run holds on the device beside a program:
the pool (M models at the pool's type), ``init_params`` (the program's reinit
target, one model) and the data, counted from the shapes; the harness holds
nothing there after time step 0 (``drivers/train.py::install_weights``).
Their sum with the larger program's temporaries is held against the same
limit. ``ModelPool.set_slot`` builds its pool beside the one it reads, so
while a slot is written (IFCA's re-draw at time step 0, before a round
program is loaded) a run holds one pool more: that is what set the decoder
cell's ``peak_bytes_in_use`` on the chip (PERF.md section 4). Nothing runs
here, so nothing here is a chip measurement.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

COMPILER_LIMIT = 15.75 * 2 ** 30


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--clients", type=int, default=None,
                    help="clients per chip to try; by default the cell's own")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import family_of, flops
    from benchmark.drivers.train import experiment_config
    from benchmark.run import load_cell, load_manifest
    from feddrift_tpu.core.precision import PrecisionPolicy
    from feddrift_tpu.core.step import TrainStep, make_optimizer
    from feddrift_tpu.data.drift_dataset import DriftDataset
    from feddrift_tpu.models import create_model

    jax.config.update("jax_enable_compilation_cache", False)
    cell, config, traffic, sizes = load_cell(load_manifest(), args.workload)
    prog = {**config["program"], **traffic["program"], **sizes["program"]}
    if prog.get("client_axis") != "scan" or int(cell["chips"]) != 1:
        raise SystemExit("sizing_scan.py sizes one-chip cells of the scanned "
                         "body; use sizing.py")
    C = args.clients or int(sizes["clients_per_chip"])
    M, T1, N = prog["concept_num"], prog["train_iterations"] + 1, \
        prog["sample_num"]
    shapes = family_of(config["arch"]).sample_shapes(config["arch"])
    (x_shape, x_dtype), (y_shape, y_dtype) = shapes["x"], shapes["y"]
    module = create_model(prog["model"], DriftDataset(
        x=np.zeros((1, 2, 1, *x_shape), x_dtype),
        y=np.zeros((1, 2, 1, *y_shape), y_dtype),
        num_classes=shapes["num_classes"],
        concepts=np.zeros((2, 1), np.int32), is_sequence=True),
        experiment_config(config, traffic, sizes, 0, C))
    # the apply boundary of runner._make_apply under "auto" on a TPU; the
    # module remats its own blocks
    cdt = jnp.dtype(prog["compute_dtype"])

    def cast(p):
        return jax.tree_util.tree_map(lambda l: l.astype(cdt), p)

    def apply_fn(p, x):
        return module.apply({"params": cast(p)}, x).astype(jnp.float32)

    def stats_fn(p, x):
        logits, counts = module.apply({"params": cast(p)}, x,
                                      return_stats=True)
        return logits.astype(jnp.float32), counts

    step = TrainStep(
        apply_fn=apply_fn, client_axis="scan",
        stats_fn=stats_fn if getattr(module, "returns_stats", False)
        else None,
        optimizer=make_optimizer(prog["client_optimizer"], prog["lr"],
                                 prog["wd"]),
        batch_size=prog["batch_size"], num_steps=prog["epochs"],
        num_classes=shapes["num_classes"], cost_capture="off",
        precision=PrecisionPolicy(name="auto", param_dtype=prog["dtype"],
                                  compute_dtype=prog["compute_dtype"]))

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=chip)

    one = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *x_shape), x_dtype))["params"])
    params = jax.tree_util.tree_map(
        lambda l: sds((M, *l.shape), prog["dtype"]), one)
    opt = jax.eval_shape(lambda p: step.init_opt_states(p, M, C), params)
    opt = jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype), opt)
    tw, sw, fm = sds((M, C, T1), "float32"), sds((M, C, N), "float32"), \
        sds((M, 1), "float32")
    programs = {
        "train_round": TrainStep._train_round_scan_jit.lower(
            step, params, opt, sds((2,), "uint32"),
            sds((C, T1, N, *x_shape), x_dtype),
            sds((C, T1, N, *y_shape), y_dtype), tw, sw, fm,
            sds((), "float32"), keep_client_params=False),
        "acc_matrix": TrainStep._acc_matrix_jit.lower(
            step, params, sds((C, N, *x_shape), x_dtype),
            sds((C, N, *y_shape), y_dtype), fm)}
    temporaries = 0
    for name, lowered in programs.items():
        ma = lowered.compile().memory_analysis()
        temporaries = max(temporaries, ma.temp_size_in_bytes)
        parts = {"arguments": ma.argument_size_in_bytes,
                 "outputs": ma.output_size_in_bytes,
                 "aliases": ma.alias_size_in_bytes,
                 "temporaries": ma.temp_size_in_bytes}
        total = parts["arguments"] + parts["outputs"] - parts["aliases"] \
            + parts["temporaries"]
        print(json.dumps({
            "workload": cell["name"], "program": name, "clients_per_chip": C,
            **{k: round(v / 1e9, 3) for k, v in parts.items()},
            "total_gb": round(total / 1e9, 3),
            "share_of_compiler_limit": round(total / COMPILER_LIMIT, 3),
            "within_rule": total <= 0.8 * COMPILER_LIMIT}), flush=True)

    count = flops.parameter_count(config["arch"])
    if count != sum(l.size // M for l in jax.tree_util.tree_leaves(params)):
        raise SystemExit("the configuration's arch does not describe the "
                         "program's model")
    width = jnp.dtype(prog["dtype"]).itemsize
    held = {"pool": M * width * count, "init_params": width * count,
            "data": C * T1 * N * (
                math.prod(x_shape) * jnp.dtype(x_dtype).itemsize
                + math.prod(y_shape) * jnp.dtype(y_dtype).itemsize)}
    total = sum(held.values()) + temporaries
    print(json.dumps({
        "workload": cell["name"], "held": "beside a program",
        "clients_per_chip": C, "parameters": count,
        "bytes_a_parameter_held": (M + 1) * width,
        **{f"{k}_gb": round(v / 1e9, 3) for k, v in held.items()},
        "held_gb": round(sum(held.values()) / 1e9, 3),
        "held_while_a_slot_is_written_gb": round(
            (sum(held.values()) + held["pool"]) / 1e9, 3),
        "largest_temporaries_gb": round(temporaries / 1e9, 3),
        "total_gb": round(total / 1e9, 3),
        "share_of_compiler_limit": round(total / COMPILER_LIMIT, 3),
        "within_rule": total <= 0.8 * COMPILER_LIMIT}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
