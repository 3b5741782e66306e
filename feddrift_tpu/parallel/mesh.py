"""Device mesh and sharding layout.

The reference's distribution unit is one MPI process per client with pickled
point-to-point messages (fedml_core/distributed/communication/mpi/,
SURVEY.md §2c). The TPU-native equivalent: a ``jax.sharding.Mesh`` whose
``clients`` axis shards every client-indexed array; aggregation reductions
lower to XLA all-reduces over ICI (intra-pod) / DCN (multi-host under
``jax.distributed.initialize``). The model pool and its [M] axis stay
replicated on the legacy 1-D mesh — M is small (<= concept_num) and every
device needs every model.

With a 2-D ``(models, clients)`` mesh (cfg.mesh_shape, e.g.
``{"models": 2, "clients": 4}``) the [M, C, ...] stacks additionally shard
their leading M axis over model-shards, and params stay replicated within
each model-shard:

    x, y          [C, T1, N, ...]  -> P('clients', ...)
    time_w        [M, C, T1]       -> P('models', 'clients')
    sample_w      [M, C, N]        -> P('models', 'clients')
    opt_states    [M, C, ...]      -> P('models', 'clients')
    params        [M, ...]         -> P('models') / replicated per shard

C (and M) need not divide the device count; ``constrain_pool`` only places
an axis when the mesh names it AND the dim divides the mesh axis size —
otherwise that axis degrades to replicated, so a 1-device CPU mesh is a
no-op and results stay bitwise-identical.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(num_devices: int | None = None, axis_name: str = "clients",
              shape: dict[str, int] | None = None) -> Mesh:
    """Build the device mesh.

    Without ``shape``: the legacy 1-D ``(clients,)`` mesh over all (or the
    first ``num_devices``) devices. With ``shape`` (an ordered
    axis-name -> size dict, e.g. ``{"models": 2, "clients": 4}``): an N-D
    mesh over the first prod(sizes) devices, erroring when the host has
    fewer.
    """
    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    if shape:
        need = math.prod(shape.values())
        if need > len(devices):
            raise ValueError(
                f"mesh_shape {shape} needs {need} devices, "
                f"only {len(devices)} available")
        arr = np.asarray(devices[:need]).reshape(tuple(shape.values()))
        return Mesh(arr, tuple(shape))
    return Mesh(np.asarray(devices), (axis_name,))


def client_sharding(mesh: Mesh, rank: int, client_axis: int = 0) -> NamedSharding:
    """NamedSharding placing ``client_axis`` of a rank-``rank`` array on the
    clients mesh axis."""
    spec = [None] * rank
    spec[client_axis] = "clients"
    return NamedSharding(mesh, P(*spec))


def replicate(mesh: Mesh, tree):
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def shard_client_arrays(mesh: Mesh, tree, client_axis: int = 0):
    """Shard every leaf of ``tree`` along ``client_axis`` over the mesh."""
    def put(leaf):
        return jax.device_put(leaf, client_sharding(mesh, np.ndim(leaf), client_axis))
    return jax.tree_util.tree_map(put, tree)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 0


def pool_spec(mesh: Mesh, shape: tuple[int, ...],
              model_axis: int | None = 0,
              client_axis: int | None = None) -> P:
    """PartitionSpec for one [M, C, ...]-style leaf on ``mesh``.

    ``model_axis`` is placed on the "models" mesh axis and ``client_axis``
    on "clients" — each only when the mesh has that axis AND the array dim
    is divisible by the mesh axis size (GSPMD constraints with indivisible
    dims force halo padding; replicating is the safe degradation). On the
    legacy 1-D ``(clients,)`` mesh the model axis is therefore always
    replicated; on 1 device everything degrades to a no-op.
    """
    spec: list[str | None] = [None] * len(shape)
    for axis, name in ((model_axis, "models"), (client_axis, "clients")):
        if axis is None:
            continue
        n = _axis_size(mesh, name)
        if n > 1 and axis < len(shape) and shape[axis] % n == 0:
            spec[axis] = name
    return P(*spec)


def constrain_pool(mesh: Mesh | None, tree, model_axis: int | None = 0,
                   client_axis: int | None = None):
    """``with_sharding_constraint`` every leaf of a model-pool stack.

    Traceable (usable inside jit): annotates each leaf with the
    ``pool_spec`` layout so GSPMD propagates the 2-D ``(models, clients)``
    placement through the megastep scan instead of defaulting to
    replication. ``mesh=None``, a mesh naming neither axis, or a mesh where
    no named axis actually splits (every size <= 1 — the 1-device CPU case)
    returns the tree UNCHANGED: an "all-replicated" constraint is not free,
    it commits outputs to a NamedSharding and thereby changes downstream
    jit cache keys against uncommitted inputs (one silent recompile).
    """
    if mesh is None or not any(_axis_size(mesh, n) > 1
                               for n in ("models", "clients")):
        return tree

    def one(leaf):
        spec = pool_spec(mesh, leaf.shape, model_axis, client_axis)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(one, tree)


def place_pool(mesh: Mesh | None, tree, model_axis: int = 0):
    """Host-side committed placement of a model-pool stack.

    ``constrain_pool`` is the traceable in-program annotation; this is its
    ``device_put`` counterpart for pool snapshots built OUTSIDE jit — the
    serving engine places every hot-swapped generation with it before
    publishing, so readers never trigger a lazy transfer mid-request. Same
    degradation rule: ``mesh=None`` or a mesh where no named axis actually
    splits returns the tree unchanged (committing to a 1-device
    NamedSharding would flip the ``committed`` bit and retrace the serve
    program against its warm-up signature).
    """
    if mesh is None or not any(_axis_size(mesh, n) > 1
                               for n in ("models", "clients")):
        return tree

    def one(leaf):
        spec = pool_spec(mesh, np.shape(leaf), model_axis)
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(one, tree)
