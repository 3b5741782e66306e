"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the pool's starting weights itself, so that the program
and the plain reference start from the same numbers and neither takes them
from the other. The family's file says which parameters there are, how each
role is drawn from a key and how the program nests them; float32 (the type
the configurations store parameters in).
"""

from __future__ import annotations

from functools import partial

import jax

from benchmark import family_of, named


@partial(jax.jit, static_argnames=("family", "spec", "num_models"))
def _make(key, *, family, spec, num_models):
    draw = named("families", family).draw
    return {name: draw(role, jax.random.fold_in(key, i), shape, num_models)
            for i, (name, shape, role) in enumerate(spec)}


def make_weights(arch: dict, seed: int, num_models: int) -> dict:
    """``name -> [num_models, ...]``: distinct models, slot 0 first."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x5EED)
    return _make(key, family=arch["family"],
                 spec=tuple(family_of(arch).param_spec(arch)),
                 num_models=num_models)


def to_program_tree(arch: dict, flat: dict) -> dict:
    """The flat parameter dict in the nesting the program's modules use."""
    return family_of(arch).to_program_tree(arch, flat)


def from_program_tree(arch: dict, tree: dict) -> dict:
    """Inverse of ``to_program_tree``: the program's nested parameters as
    the flat dict the reference reads."""
    names = [n for n, _, _ in family_of(arch).param_spec(arch)]
    probe = to_program_tree(arch, {n: n for n in names})
    flat_names = jax.tree_util.tree_leaves(probe)
    leaves = jax.tree_util.tree_leaves(tree)
    if len(flat_names) != len(leaves):
        raise ValueError("the program's parameter tree does not match the "
                         "configuration's layout")
    return dict(zip(flat_names, leaves))
