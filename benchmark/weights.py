"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the pool's starting weights itself, so that the program
and the plain reference start from the same numbers and neither takes them
from the other. He-normal convolutions, a LeCun-normal head, unit scales and
zero biases, float32 (the type the configuration stores parameters in).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import param_spec


@partial(jax.jit, static_argnames=("spec", "num_models"))
def _make(key, *, spec, num_models):
    out = {}
    for i, (name, shape, role) in enumerate(spec):
        if role == "scale":
            out[name] = jnp.ones((num_models, *shape), jnp.float32)
        elif role == "bias":
            out[name] = jnp.zeros((num_models, *shape), jnp.float32)
        else:
            fan_in = math.prod(shape[:-1])
            std = math.sqrt((2.0 if role == "conv" else 1.0) / fan_in)
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), (num_models, *shape), jnp.float32)
    return out


def make_weights(arch: dict, seed: int, num_models: int) -> dict:
    """``name -> [num_models, ...]``: distinct models, slot 0 first."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x5EED)
    return _make(key, spec=tuple(param_spec(arch)), num_models=num_models)


def to_program_tree(arch: dict, flat: dict) -> dict:
    """The flat parameter dict in the nesting the program's flax modules
    use, by the layout the configuration names."""
    layout = arch["layout"]

    def norm(prefix):
        return {"scale": flat[f"{prefix}/scale"], "bias": flat[f"{prefix}/bias"]}

    def block(name):
        b = {"Conv_0": {"kernel": flat[f"{name}/conv1"]},
             "_Norm_0": norm(f"{name}/norm1"),
             "Conv_1": {"kernel": flat[f"{name}/conv2"]},
             "_Norm_1": norm(f"{name}/norm2")}
        if f"{name}/proj" in flat:
            b["Conv_2"] = {"kernel": flat[f"{name}/proj"]}
            b["_Norm_2"] = norm(f"{name}/projnorm")
        return b

    stem = {"Conv_0": {"kernel": flat["stem/conv"]},
            "_Norm_0": norm("stem/norm")}
    head = {"Dense_0": {"kernel": flat["head/kernel"],
                        "bias": flat["head/bias"]}}
    names = [f"s{s}b{b}" for s, st in enumerate(arch["stages"])
             for b in range(st["blocks"])]
    if layout == "resnet18":
        tree = dict(stem)
        tree.update({f"BasicBlock_{i}": block(n) for i, n in enumerate(names)})
        tree.update(head)
        return tree
    if layout == "resnet_cifar":
        n0 = arch["stages"][0]["blocks"]
        trunk = dict(stem)
        trunk.update({f"BasicBlock_{i}": block(n)
                      for i, n in enumerate(names[:n0])})
        tail = {f"BasicBlock_{i}": block(n) for i, n in enumerate(names[n0:])}
        tail.update(head)
        return {"ResNetFeatures_0": trunk, "ResNetServerTail_0": tail}
    raise KeyError(f"unknown parameter layout {layout!r}")


def from_program_tree(arch: dict, tree: dict) -> dict:
    """Inverse of ``to_program_tree``: the program's nested parameters as
    the flat dict the reference reads."""
    names = [n for n, _, _ in param_spec(arch)]
    probe = to_program_tree(arch, {n: n for n in names})
    flat_names = jax.tree_util.tree_leaves(probe)
    leaves = jax.tree_util.tree_leaves(tree)
    if len(flat_names) != len(leaves):
        raise ValueError("the program's parameter tree does not match the "
                         "configuration's layout")
    return dict(zip(flat_names, leaves))
