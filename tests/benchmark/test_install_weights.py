"""What the harness leaves on the device (ISSUE 34), on the CPU at the
rehearsal's sizes: after ``install_weights`` the pool and the program's
reinit target hold the benchmark's model 0, the re-draw of the first time
step uploads slot m's start model from the host arrays that the reference
starts from, and no start model stays reachable on the device. Every
comparison is bit for bit against ``weights.make_weights``. No number from
here is a device number."""

import gc
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_driving as bd  # noqa: E402

from benchmark import family_of, weights  # noqa: E402
from benchmark.drivers import train  # noqa: E402

CELLS = {"mla_moe": "kanana2.ifca_perround",
         "resnet_basic": "resnet20.ifca_perround"}


def files_of(family: str, distinct: bool):
    """(config, traffic, sizes) of the family's cell, ``distinct_init`` set
    so in the traffic."""
    _cell, config, traffic, sizes = bd.files(CELLS[family])
    assert config["arch"]["family"] == family
    return config, dict(traffic, distinct_init=distinct), sizes


def build(config, traffic, sizes):
    """The cell's ``Experiment`` as the driver builds it, before the
    weights go in."""
    from feddrift_tpu.parallel.mesh import make_mesh
    from feddrift_tpu.simulation.runner import Experiment
    cfg = train.experiment_config(config, traffic, sizes, bd.SEED,
                                  int(sizes["clients_per_chip"]))
    return Experiment(cfg, mesh=make_mesh(num_devices=1))


class ParameterBytes:
    """Bytes of the live device buffers that have the shape of a parameter
    (one model's, a pool's or one slot's with its axis kept), over what was
    alive when the count began. By buffer: reading a pool leaf on the host
    leaves a second array object on the same one."""

    def __init__(self, arch, M):
        shapes = [tuple(s) for _, s, _ in family_of(arch).param_spec(arch)]
        self.shapes = {lead + s for s in shapes for lead in ((), (1,), (M,))}
        self.model = 4 * sum(int(np.prod(s)) for s in shapes)
        gc.collect()
        self.before = 0
        self.before = self()      # what earlier tests still hold

    def __call__(self) -> int:
        import jax
        buffers = {a.unsafe_buffer_pointer(): a.nbytes
                   for a in jax.live_arrays() if a.shape in self.shapes}
        return sum(buffers.values()) - self.before


def host(flat: dict) -> dict:
    """Copies: on the CPU ``np.asarray`` of a device array is a view that
    keeps it alive."""
    return {k: np.array(v) for k, v in flat.items()}


def slots(exp, arch):
    """The pool on the host, as the flat dict the reference reads."""
    return host(weights.from_program_tree(arch, exp.pool.params))


def slot(pool: dict, m: int) -> dict:
    return {k: v[m] for k, v in pool.items()}


def same(a: dict, b: dict) -> bool:
    return sorted(a) == sorted(b) and all(
        a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]) for k in a)


@pytest.mark.parametrize("distinct", [True, False])
@pytest.mark.parametrize("family", sorted(CELLS))
def test_the_pool_starts_from_the_seeds_weights_and_no_start_model_stays(
        family, distinct):
    import jax
    config, traffic, sizes = files_of(family, distinct)
    arch, M = config["arch"], traffic["program"]["concept_num"]
    held = ParameterBytes(arch, M)
    exp = build(config, traffic, sizes)
    ours = (M + 1) * held.model           # a pool and the reinit target
    assert held() == ours
    init = train.install_weights(exp, arch, traffic, bd.SEED)
    want = host(weights.make_weights(arch, bd.SEED, M))
    model = lambda m: slot(want, m)       # noqa: E731

    # model 0 in every slot and as the reinit target; the reference's start
    pool = slots(exp, arch)
    assert all(same(slot(pool, m), model(0)) for m in range(M))
    assert same(host(weights.from_program_tree(arch, exp.pool.init_params)),
                model(0))
    assert len(init) == M and all(
        same(init[m], model(m if distinct else 0)) for m in range(M))
    assert all(not isinstance(v, jax.Array) for p in init for v in p.values())
    assert held() == ours

    if not distinct:
        # the traffic draws none: the program's own re-draw is untouched
        assert "distinct_reinit_slot" not in vars(exp.pool)
        return
    # the re-draws of time step 0, as algorithms/softcluster.py makes them
    for m in range(M):
        exp.pool.distinct_reinit_slot(m, seed=bd.SEED + 7700 + m)
    pool = slots(exp, arch)
    assert all(same(slot(pool, m), model(m)) for m in range(M))
    assert [l.dtype for l in jax.tree_util.tree_leaves(exp.pool.params)] \
        == [np.dtype(np.float32)] * len(want)
    # no start model is reachable on the device: what is alive of a
    # parameter's shape beside the pool and the target is under one model
    assert held() - ours < held.model

    # after a time step of training the same call gives the same slot again
    exp.run_iteration(0)
    before = slots(exp, arch)
    trained = [m for m in range(M) if not same(slot(before, m), model(m))]
    assert trained                  # IFCA trains the models its clients chose
    exp.pool.distinct_reinit_slot(trained[0], seed=None)
    after = slots(exp, arch)
    assert same(slot(after, trained[0]), model(trained[0]))
    assert all(same(slot(after, m), slot(before, m))
               for m in range(M) if m != trained[0])
    train._free(exp)


@pytest.mark.parametrize("family", sorted(CELLS))
def test_installing_holds_a_pool_and_a_model_at_the_most(family, monkeypatch):
    """The program's first pool is gone before the weights are made, and
    their device copy before the new pool is: at every leaf put on the
    device, what is alive of a parameter's shape is under a pool and one
    model (the parent held two pools, the start models and the target)."""
    from feddrift_tpu.parallel import mesh
    config, traffic, sizes = files_of(family, True)
    arch, M = config["arch"], traffic["program"]["concept_num"]
    held = ParameterBytes(arch, M)
    exp = build(config, traffic, sizes)
    ours = (M + 1) * held.model
    seen = {"making": [], "placing": []}
    make, place = weights.make_weights, mesh.replicate

    def making(*a, **kw):
        seen["making"].append(held())
        return make(*a, **kw)

    def placing(m, tree):
        seen["placing"].append(held())
        return place(m, tree)
    monkeypatch.setattr(weights, "make_weights", making)
    monkeypatch.setattr(mesh, "replicate", placing)
    train.install_weights(exp, arch, traffic, bd.SEED)
    assert seen["making"] == [0]
    assert len(seen["placing"]) == len(family_of(arch).param_spec(arch))
    assert max(seen["placing"]) <= ours
    assert held() == ours
    train._free(exp)
