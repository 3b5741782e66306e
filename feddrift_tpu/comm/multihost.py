"""Multi-host control-plane sync over JAX collectives (DCN/ICI).

The reference synchronizes hosts by MPI point-to-point sends of pickled
state_dicts (mpi_send_thread.py:27). In a TPU pod the equivalent is: every
host runs the same program and cross-host agreement on *array* state is a
collective. These wrappers delegate to jax.experimental.multihost_utils —
the supported implementation of the zero-on-non-source + all-reduce trick —
so every process compiles the identical program, which is a hard requirement
of JAX's multi-controller model.

Single-process (this environment, and all tests): the helpers are identity
functions, so the same experiment code runs unmodified from laptop sim to
pod.
"""

from __future__ import annotations

import jax

from feddrift_tpu import obs


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Join the multi-controller runtime (jax.distributed.initialize).

    On TPU pods all arguments auto-detect from the environment; on CPU/GPU
    clusters pass them explicitly. This replaces the reference's
    mpirun-launched process bootstrap (FedAvgEnsAPI.py:25-29: MPI rank/size);
    afterwards jax.devices() spans every host and the client mesh axis can be
    laid out across DCN.
    """
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def process_count() -> int:
    return jax.process_count()


def is_coordinator() -> bool:
    return jax.process_index() == 0


def broadcast_from_coordinator(tree):
    """Every host returns process 0's pytree value."""
    if jax.process_count() == 1:
        return tree
    obs.registry().counter("multihost_collectives",
                           op="broadcast").inc()
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(tree)


def _gather(tree):
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(tree)   # leading [P] axis


def all_hosts_mean(tree):
    """Mean of each host's pytree across hosts (metric aggregation)."""
    if jax.process_count() == 1:
        return tree
    g = _gather(tree)
    return jax.tree_util.tree_map(lambda l: l.mean(axis=0), g)


def broadcast_sum(tree):
    """Element-wise sum of every host's contribution."""
    if jax.process_count() == 1:
        return tree
    g = _gather(tree)
    return jax.tree_util.tree_map(lambda l: l.sum(axis=0), g)


def fetch(tree):
    """Device->host fetch of (possibly cross-process-sharded) arrays.

    Single-process: plain ``jax.device_get``.  Multi-controller: a global
    array sharded over the ``clients`` mesh axis has shards this process
    cannot address, so ``device_get``/``np.asarray`` would raise; the
    supported path is an allgather that materialises the full value on
    every host (the algorithms' host-side clustering logic then runs
    identically everywhere, keeping the SPMD programs in lockstep).
    """
    obs.registry().counter("multihost_fetches").inc()
    # the host blocks here until the device has produced the value, then
    # copies it: wait plus copy is the round's device_compute segment
    with obs.spans.span("device_compute", cat="round"):
        if jax.process_count() == 1:
            return jax.device_get(tree)
        return _allgather_tiled(tree)


def _allgather_tiled(tree):
    from jax.experimental import multihost_utils

    def _require_jax_array(leaf):
        # process_allgather(tiled=True) silently CONCATENATES host-local
        # numpy/scalar leaves across processes — a wrong-shaped result with
        # no error. Every fetch() call site passes device-backed arrays;
        # make any future misuse loud instead of wrong.
        if not isinstance(leaf, jax.Array):
            raise TypeError(
                "multihost.fetch() requires jax.Array leaves in "
                f"multi-process runs, got {type(leaf).__name__}; fetch "
                "numpy/host values with plain code, not a collective")
        return leaf

    tree = jax.tree_util.tree_map(_require_jax_array, tree)
    return multihost_utils.process_allgather(tree, tiled=True)
