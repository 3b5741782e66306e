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

    from benchmark.run import load_cell, load_manifest
    from feddrift_tpu.core.precision import PrecisionPolicy
    from feddrift_tpu.core.step import TrainStep, make_optimizer
    from feddrift_tpu.models.resnet import ResNet18, ResNetCifar

    jax.config.update("jax_enable_compilation_cache", False)
    cell, config, traffic, sizes = load_cell(load_manifest(), args.workload)
    prog = {**config["program"], **traffic["program"], **sizes["program"]}
    chips = int(cell["chips"])
    per_chip = args.clients or int(sizes["clients_per_chip"])
    C = per_chip * chips
    M, R = prog["concept_num"], prog["comm_round"]
    T1, N = prog["train_iterations"] + 1, prog["sample_num"]
    fused = traffic["round_program"] == "train_iteration_eval"

    module = {"resnet18": ResNet18(num_classes=10),
              "resnet20": ResNetCifar(num_classes=10, depth=20)}[prog["model"]]
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
        num_classes=10, cost_capture="off",
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
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))["params"])
    params = jax.tree_util.tree_map(
        lambda l: sds((M, *l.shape), jnp.float32), one)
    opt = jax.eval_shape(lambda p: step.init_opt_states(p, M, C), params)
    opt = jax.tree_util.tree_map(
        lambda l: sds(l.shape, l.dtype,
                      P(None, "clients") if l.ndim >= 2 else None), opt)
    x = sds((C, T1, N, 32, 32, 3), jnp.float32, P("clients"))
    y = sds((C, T1, N), jnp.int32, P("clients"))
    tw = sds((M, C, T1), jnp.float32)
    sw = sds((M, C, N), jnp.float32)
    fm = sds((M, 32, 32, 3), jnp.float32)
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
