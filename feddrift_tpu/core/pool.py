"""The model pool: M models as one pytree with a leading [M] axis.

Replaces the reference's Python list of ``torch.nn.Module``s
(fedavg_ens/FedAvgEnsAPI.py models list; per-model for-loops in trainers and
aggregators). Create/delete/merge become index updates on the stacked arrays,
so the pool shape stays static for XLA:

- ``reinitialize`` (reference model/utils.py:7-24: reset with a *fixed* torch
  seed, so every reinit yields identical params) == writing the stored
  ``init_params`` back into a slot;
- IFCA's distinct per-model init at iteration 0
  (FedAvgEnsAggregatorSoftCluster.py:66-69: reset_parameters *without*
  seeding) == ``distinct_init``;
- FedDrift's merge (FedAvgEnsDataLoader.py:1048-1072) == weighted lerp of two
  slots;
- "clone from original model" on LRU reuse (FedAvgEnsDataLoader.py:1031-1033)
  == ``copy_slot``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from feddrift_tpu.core.precision import cast_floating


@dataclass
class ModelPool:
    module: Any                 # flax nn.Module
    params: Any                 # pytree, leaves [M, ...]
    init_params: Any            # single-model pytree (the deterministic reinit target)
    num_models: int
    example_input: Any = None   # sample batch used for (re)initialisation

    # ------------------------------------------------------------------
    @classmethod
    def create(cls, module, sample_input, num_models: int, seed: int = 42,
               identical: bool = True,
               param_dtype: str | None = None) -> "ModelPool":
        """Initialise the pool.

        ``identical=True`` matches the reference start-up: every model is
        ``reinitialize``d with the same fixed seed (main_fedavg.py:324-329 +
        model/utils.py:20), so all M slots hold the same params.

        ``param_dtype`` (precision policy, core/precision.py): store the
        pool — and the deterministic-reinit target, which ``reinit_slot``
        writes back into slots — at this dtype. Flax initialises at f32;
        the cast here is the one storage boundary, so a bf16 pool is bf16
        from its very first leaf (None = keep the module's init dtype).
        """
        base_key = jax.random.PRNGKey(seed)
        # a module may ask for a jitted init (``jit_init``): the compiler
        # then drops the forward pass, which eagerly would run op by op at
        # the sample's full size (minutes for a large sequence model)
        init = jax.jit(module.init) if getattr(module, "jit_init", False) \
            else module.init
        init_params = init(base_key, sample_input)["params"]
        if param_dtype is not None:
            init_params = cast_floating(init_params, param_dtype)
        if identical:
            params = jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p[None], (num_models, *p.shape)).copy(),
                init_params)
        else:
            keys = jax.random.split(base_key, num_models)
            params = jax.vmap(
                lambda k: module.init(k, sample_input)["params"])(keys)
            if param_dtype is not None:
                params = cast_floating(params, param_dtype)
        return cls(module=module, params=params, init_params=init_params,
                   num_models=num_models, example_input=sample_input)

    # ------------------------------------------------------------------
    def apply(self, params, x):
        return self.module.apply({"params": params}, x)

    def slot(self, m: int):
        return jax.tree_util.tree_map(lambda p: p[m], self.params)

    def set_slot(self, m: int, new_params) -> None:
        self.params = jax.tree_util.tree_map(
            lambda pool, p: pool.at[m].set(p), self.params, new_params)

    def reinit_slot(self, m: int) -> None:
        """Deterministic reinit (reference reinitialize, model/utils.py:20-24)."""
        self.set_slot(m, self.init_params)

    def distinct_reinit_slot(self, m: int, seed: int) -> None:
        """Fresh random params (IFCA symmetry breaking, AggregatorSoftCluster.py:66-69)."""
        init = jax.jit(self.module.init) \
            if getattr(self.module, "jit_init", False) else self.module.init
        new = init(jax.random.PRNGKey(seed), self.example_input)["params"]
        # flax inits at f32; match the pool's stored dtype leaf-by-leaf so
        # a policy-typed pool never mixes dtypes across slots
        new = jax.tree_util.tree_map(
            lambda n, pool: n.astype(pool.dtype) if n.dtype != pool.dtype
            else n, new, self.params)
        self.set_slot(m, new)

    def copy_slot(self, dst: int, src: int) -> None:
        """dst := src (LRU reuse initialises from the drifted client's old
        model, FedAvgEnsDataLoader.py:1031-1033)."""
        self.set_slot(dst, self.slot(src))

    def merge_slots(self, base: int, second: int, w1: float, w2: float) -> None:
        """base := w1*base + w2*second; second := deterministic reinit
        (FedDrift merge, FedAvgEnsDataLoader.py:1059-1066)."""
        merged = jax.tree_util.tree_map(
            lambda p: w1 * p[base] + w2 * p[second], self.params)
        self.set_slot(base, merged)
        self.reinit_slot(second)
