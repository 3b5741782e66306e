"""Compile-time HBM of a cell's timed round program, with no chip.

    JAX_PLATFORMS=cpu python3 benchmark/sizing.py --workload <cell> [--clients <C>]

lowers the program's own jitted round program with shapes only and compiles
it for a described ``v5e:2x2`` device (the ``on-chip-measurement`` guide,
section 2.3). It prints arguments + outputs - aliases + temporaries, which
is what the sizing rule in PERF.md holds to 80 % of the 15.75 GB the
compiler allows. Nothing runs, so nothing here is a chip measurement.
"""

from __future__ import annotations

import argparse
import json
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
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import numpy as np

    from benchmark import family_of
    from benchmark.drivers.train import experiment_config
    from benchmark.run import load_cell, load_manifest
    from feddrift_tpu.core.precision import PrecisionPolicy
    from feddrift_tpu.core.step import TrainStep, make_optimizer
    from feddrift_tpu.data.drift_dataset import DriftDataset
    from feddrift_tpu.models import create_model

    jax.config.update("jax_enable_compilation_cache", False)
    cell, config, traffic, sizes = load_cell(load_manifest(), args.workload)
    prog = {**config["program"], **traffic["program"], **sizes["program"]}
    chips = int(cell["chips"])
    per_chip = args.clients or int(sizes["clients_per_chip"])
    C = per_chip * chips
    M, R = prog["concept_num"], prog["comm_round"]
    T1, N = prog["train_iterations"] + 1, prog["sample_num"]
    fused = traffic["round_program"] == "train_iteration_eval"

    # one sample's shapes from the family's file; the module through the
    # program's own factory, over a data set of one sample of those shapes
    shapes = family_of(config["arch"]).sample_shapes(config["arch"])
    (x_shape, x_dtype), (y_shape, y_dtype) = shapes["x"], shapes["y"]
    classes = shapes["num_classes"]
    # (the factory reads the class count and x's shape; the program's data
    # set holds one label per sample, so y stands in with no trailing axes)
    module = create_model(prog["model"], DriftDataset(
        x=np.zeros((1, 2, 1, *x_shape), x_dtype),
        y=np.zeros((1, 2, 1), np.int32), num_classes=classes,
        concepts=np.zeros((2, 1), np.int32),
        is_sequence=np.issubdtype(np.dtype(x_dtype), np.integer)),
        experiment_config(config, traffic, sizes, 0, C))
    # the apply boundary of runner._make_apply under "auto" on a TPU
    cdt = jnp.dtype(prog["compute_dtype"])

    def apply_fn(p, x):
        pc = jax.tree_util.tree_map(lambda l: l.astype(cdt), p)
        return module.apply({"params": pc}, x.astype(cdt)).astype(jnp.float32)

    step = TrainStep(
        apply_fn=apply_fn,
        optimizer=make_optimizer(prog["client_optimizer"], prog["lr"],
                                 prog["wd"]),
        batch_size=prog["batch_size"], num_steps=prog["epochs"],
        num_classes=classes, cost_capture="off",
        precision=PrecisionPolicy(name="auto", param_dtype=prog["dtype"],
                                  compute_dtype=prog["compute_dtype"]))

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(topo.devices[:chips], ("clients",))
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype, spec=None):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=rep if spec is None
            else NamedSharding(mesh, spec))

    one = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *x_shape), x_dtype))["params"])
    params = jax.tree_util.tree_map(
        lambda l: sds((M, *l.shape), jnp.float32), one)
    opt = jax.eval_shape(lambda p: step.init_opt_states(p, M, C), params)
    opt = jax.tree_util.tree_map(
        lambda l: sds(l.shape, l.dtype,
                      P(None, "clients") if l.ndim >= 2 else None), opt)
    x = sds((C, T1, N, *x_shape), jnp.dtype(x_dtype), P("clients"))
    y = sds((C, T1, N, *y_shape), jnp.dtype(y_dtype), P("clients"))
    tw = sds((M, C, T1), jnp.float32)
    sw = sds((M, C, N), jnp.float32)
    fm = sds((M, *x_shape), jnp.float32)
    key = sds((2,), jnp.uint32)
    lr = sds((), jnp.float32)
    if fused:
        lowered = TrainStep._train_iteration_eval_jit.lower(
            step, params, opt, key, x, y, tw, sw, fm, lr, R,
            prog["frequency_of_the_test"], sds((), jnp.int32))
        name = "train_iteration_eval"
    else:
        lowered = TrainStep._train_round_jit.lower(
            step, params, opt, key, x, y, tw, sw, fm, lr,
            keep_client_params=False)
        name = "train_round"
    ma = lowered.compile().memory_analysis()
    parts = {"arguments": ma.argument_size_in_bytes,
             "outputs": ma.output_size_in_bytes,
             "aliases": ma.alias_size_in_bytes,
             "temporaries": ma.temp_size_in_bytes}
    total = parts["arguments"] + parts["outputs"] - parts["aliases"] \
        + parts["temporaries"]
    print(json.dumps({
        "workload": cell["name"], "program": name, "clients_per_chip":
        per_chip, "clients": C, "chips": chips,
        **{k: round(v / 1e9, 3) for k, v in parts.items()},
        "total_gb": round(total / 1e9, 3),
        "share_of_compiler_limit": round(total / COMPILER_LIMIT, 3),
        "within_rule": total <= 0.8 * COMPILER_LIMIT}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
