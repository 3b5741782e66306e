"""The door is open (ISSUE 27): a model family and a client optimizer arrive
as files alone. A family that lives in the tests (``dense2_fixture.py``: the
program's ``fnn`` on ``sea``) is put in ``sys.modules`` as
``benchmark.families.dense2_fixture``; its configurations and cells are
ADDED to a temporary copy of ``benchmark/`` (``bench_driving.open_door``),
and whole rehearsal runs come out ``correct`` under AMSGrad and under plain
SGD, and not ``correct`` with a fault planted, by the fixture cells' own
limits. No number from here is a device number."""

import filecmp
import hashlib
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_driving as bd  # noqa: E402
import dense2_fixture  # noqa: E402

from benchmark import flops, named, reference, weights  # noqa: E402
from benchmark import run as bench  # noqa: E402
from benchmark.drivers import train  # noqa: E402

KINDS = sorted(bd.DOOR_OPTIMIZERS)


@pytest.fixture()
def door(tmp_path, monkeypatch):
    """The fixture family under its name, and the harness reading its data
    files from the temporary copy. Returns the copy's manifest."""
    monkeypatch.setitem(sys.modules, f"benchmark.families.{bd.FAMILY}",
                        dense2_fixture)
    manifest = bd.open_door(str(tmp_path))
    monkeypatch.setattr(bench, "BENCH", str(tmp_path / "benchmark"))
    return manifest


# ----------------------------------------------------------------------
# whole runs through the command
LAUNCH = """
import sys
sys.path[:0] = [{copy!r}, {tests!r}, {root!r}]
import dense2_fixture
sys.modules["benchmark.families.dense2_fixture"] = dense2_fixture
from benchmark import run
assert run.ROOT == {copy!r}, run.ROOT
sys.exit(run.main(sys.argv[1:]))
"""


@pytest.mark.parametrize("kind", KINDS)
def test_a_family_and_an_optimizer_that_arrive_as_files_run_to_correct(
        kind, door, tmp_path):
    """``run.py --rehearse`` of the temporary copy, with nothing of
    ``benchmark/`` edited: every file of the repo's is in the copy byte for
    byte, and the copy has four files more."""
    copy = str(tmp_path)
    p = subprocess.run(
        [sys.executable, "-c",
         LAUNCH.format(copy=copy, tests=bd.HERE, root=bd.ROOT),
         "--workload", bd.door_cell(kind), "--seed", str(2 ** 31 + 9),
         "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=copy, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "rehearsal done: correct=True" in p.stderr
    # every run names the segments of its longest time step (PERF.md
    # section 7, row 15)
    assert "longest time step of the window" in p.stderr
    assert '"device_compute"' in p.stderr
    numbers = json.loads(next(l for l in p.stderr.splitlines()
                              if l.startswith("numbers "))[len("numbers "):])
    _cell, config, traffic, _sizes = bd.files(bd.door_cell(kind),
                                              manifest=door)
    assert list(numbers) == train.numbers_of(config, traffic)
    assert ("moment_gap" in numbers) == (kind == "amsgrad")
    added = _compare(os.path.join(bd.ROOT, "benchmark"),
                     os.path.join(copy, "benchmark"))
    assert sorted(added) == sorted(
        os.path.join(sub, f"{stem}.json") for k in KINDS for sub, stem in
        (("configs", f"sea_dense2_{k}"), ("cells", bd.door_cell(k))))


def _compare(ours, theirs, rel=""):
    """Files of ``theirs`` that ``ours`` has not; every file of ``ours`` has
    to be in ``theirs`` unchanged."""
    cmp = filecmp.dircmp(os.path.join(ours, rel), os.path.join(theirs, rel),
                         ignore=["__pycache__"])
    assert not cmp.left_only and not cmp.diff_files and not cmp.funny_files, \
        (rel, cmp.left_only, cmp.diff_files)
    _, mismatch, errors = filecmp.cmpfiles(
        cmp.left, cmp.right, cmp.common_files, shallow=False)
    assert not mismatch and not errors, (rel, mismatch, errors)
    added = [os.path.join(rel, f) for f in cmp.right_only]
    for sub in cmp.common_dirs:
        added += _compare(ours, theirs, os.path.join(rel, sub))
    return added


# ----------------------------------------------------------------------
# faults under plain SGD, by the fixture cell's own limits
def test_the_program_under_sgd_is_correct_by_the_numbers_sgd_can_give(door):
    result = bd.drive(bd.door_cell("sgd"), seed=bd.DOOR_SEED, manifest=door)
    assert result["correct"], result["check"]
    assert set(result["check"]) == set(bd.DOOR_LIMITS["sgd"])
    assert not {"moment_gap", "first_grad_gap", "moment_store_gap"} \
        & set(result["numbers"])
    assert result["numbers"]["change_gap"] < 1e-4


@pytest.mark.parametrize("fault,fails", [
    ("state_unchanged", {"change_gap", "change_gap_median", "train_loss_gap"}),
    ("half_batch", {"change_gap", "train_loss_gap"}),
    ("assign_altered", {"assign_regret"}),
])
def test_a_fault_planted_under_sgd_is_not_correct(door, monkeypatch, fault,
                                                  fails):
    """The faults of ``test_planted_faults.py`` that an optimizer with no
    state admits, with no moment to read them from."""
    bd.plant(monkeypatch, fault)
    result = bd.drive(bd.door_cell("sgd"), seed=bd.DOOR_SEED, manifest=door)
    assert not result["correct"]
    assert fails <= set(bd.failed(result)), result["check"]
    if fault == "state_unchanged":
        assert result["check"]["change_gap"]["value"] == pytest.approx(1.0)
    if fault == "assign_altered":      # nothing else is touched
        assert bd.failed(result) == ["assign_regret"]


# ----------------------------------------------------------------------
# what is refused as the cell is loaded
def test_a_limit_on_a_number_the_optimizer_cannot_give_is_refused_at_load(
        tmp_path, monkeypatch):
    monkeypatch.setitem(sys.modules, f"benchmark.families.{bd.FAMILY}",
                        dense2_fixture)
    limits = dict(bd.DOOR_LIMITS,
                  sgd=dict(bd.DOOR_LIMITS["sgd"], moment_gap_median=0.2))
    manifest = bd.open_door(str(tmp_path), limits=limits)
    monkeypatch.setattr(bench, "BENCH", str(tmp_path / "benchmark"))
    with pytest.raises(ValueError, match="moment_gap_median.*'sgd'"):
        bench.load_cell(manifest, bd.door_cell("sgd"))
    # the same limit under the optimizer that has the moment is fine
    bench.load_cell(manifest, bd.door_cell("amsgrad"))
    assert "moment_gap_median" in bd.DOOR_LIMITS["amsgrad"]


def test_a_family_or_an_optimizer_with_no_file_names_those_there_are(door):
    with pytest.raises(KeyError, match="resnet_basic"):
        named("families", "transformer")
    with pytest.raises(KeyError, match="amsgrad.*sgd"):
        named("optimizers", "lion")
    _cell, config, traffic, sizes = bd.files(bd.door_cell("sgd"),
                                             manifest=door)
    for group, stray in (("arch", {"family": "no_such"}),
                         ("optimizer", {"kind": "no_such"})):
        broken = bench.overlay(config, {group: stray})
        with pytest.raises(KeyError, match="no_such"):
            train.check_cell(broken, traffic, sizes)


# ----------------------------------------------------------------------
# the reference alone
@pytest.mark.parametrize("kind", KINDS)
def test_the_comparison_works_out_the_numbers_the_loader_holds_limits_to(
        door, kind):
    """The reference put in the program's place on the fixture family:
    ``check`` gives the numbers ``numbers_of`` says, in that order, and
    agrees with itself."""
    _cell, config, traffic, _sizes = bd.files(bd.door_cell(kind),
                                              manifest=door)
    rng = np.random.default_rng(3)
    C, T1, N, M = 4, 3, 16, traffic["program"]["concept_num"]
    x = rng.normal(size=(C, T1, N, 3)).astype(np.float32)
    y = (x.sum(-1) > 0).astype(np.int32)
    flat = {k: np.asarray(v) for k, v in
            weights.make_weights(config["arch"], 5, M).items()}
    init = [{k: v[m] for k, v in flat.items()} for m in range(M)]
    hyper = dict(config["optimizer"], lr=0.05, wd=0.001)
    job = {"seed": 5, "batch": 8, "local_steps": 2}
    args = (config["arch"], hyper, init, x, y, job, traffic)
    numbers = train.check(*args, train.reference_as_program(*args))
    assert list(numbers) == train.numbers_of(config, traffic)
    assert all(v < 1e-5 for v in numbers.values()), numbers
    unchanged = train.check(*args, train.reference_as_program(
        *args, fault="state_unchanged"))
    assert unchanged["change_gap"] == pytest.approx(1.0)


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_a_round_on_per_token_labels_agrees_with_a_hand_written_loop(
        monkeypatch, fault):
    """``y`` [C, T1, N, L]: the labels keep their trailing axis through the
    round, the evaluation and the logged losses; the loss is the mean over
    a batch's B x L tokens, ``half_batch`` leaves out half of the B."""
    monkeypatch.setitem(sys.modules, f"benchmark.families.{bd.FAMILY}",
                        dense2_fixture)
    arch = {"family": bd.FAMILY, "input": [3], "hidden": 5, "num_classes": 4}
    rng = np.random.default_rng(1)
    C, T1, N, L, B, steps, lr, seed = 2, 2, 8, 3, 4, 2, 0.1, 7
    x = rng.normal(size=(C, T1, N, L, 3)).astype(np.float32)
    y = rng.integers(0, 4, size=(C, T1, N, L)).astype(np.int32)
    flat = weights.make_weights(arch, seed, 1)
    init = [{k: np.asarray(v[0]) for k, v in flat.items()}]
    ref = reference.Reference(arch, {"kind": "sgd", "lr": lr}, init, x, y,
                              seed, batch=B, local_steps=steps, fault=fault)
    assert ref.labels == N * L
    time_w = np.array([[[1.0, 0.0], [1.0, 1.0]]], np.float32)   # [M, C, T1]
    ref.round(0, 0, time_w, c_pad=C)

    def forward(p, xb):
        h = np.maximum(xb @ p["fc1/kernel"] + p["fc1/bias"], 0.0)
        z = h @ p["fc2/kernel"] + p["fc2/bias"]
        z = z - z.max(-1, keepdims=True)
        return h, np.exp(z) / np.exp(z).sum(-1, keepdims=True)

    rkey = reference.round_key(seed, 0, 0)
    total, acc = 0.0, None
    for c in range(C):
        p = {k: v.astype(np.float64) for k, v in init[0].items()}
        idx = np.asarray(reference.batch_indices(
            reference.pair_key(rkey, 0, c, 1, C), time_w[0, c], N, B, steps))
        xf, yf = x[c].reshape(-1, L, 3), y[c].reshape(-1, L)
        for s in range(steps):
            xb, yb = xf[idx[s]].astype(np.float64), yf[idx[s]]
            if fault == "half_batch":
                xb, yb = xb[: B // 2], yb[: B // 2]
            h, prob = forward(p, xb)
            dz = prob.copy()
            np.put_along_axis(dz, yb[..., None], np.take_along_axis(
                dz, yb[..., None], -1) - 1.0, -1)
            dz /= yb.size                       # the mean over B x L tokens
            dh = (dz @ p["fc2/kernel"].T) * (h > 0)
            g = {"fc2/kernel": np.einsum("blh,blk->hk", h, dz),
                 "fc2/bias": dz.sum((0, 1)),
                 "fc1/kernel": np.einsum("blf,blh->fh", xb, dh),
                 "fc1/bias": dh.sum((0, 1))}
            p = {k: p[k] - lr * g[k] for k in p}
        n = float(time_w[0, c].sum()) * N
        acc = {k: n * v for k, v in p.items()} if acc is None \
            else {k: acc[k] + n * p[k] for k in acc}
        total += n
    for k, v in acc.items():
        np.testing.assert_allclose(np.asarray(ref.params[0][k]), v / total,
                                   rtol=2e-5, atol=2e-6, err_msg=k)
        assert float(np.abs(v / total - init[0][k]).max()) > 1e-4   # it moved

    # evaluation and the logged losses count tokens
    mine = {k: np.asarray(v, np.float64) for k, v in ref.params[0].items()}
    corr, loss = ref.eval_matrix(0)
    tot = 0.0
    for c in range(C):
        _, prob = forward(mine, x[c, 0].astype(np.float64))
        assert corr[0, c] == (prob.argmax(-1) == y[c, 0]).sum()
        nll = -np.log(np.take_along_axis(prob, y[c, 0][..., None], -1)).sum()
        assert loss[0, c] == pytest.approx(nll, rel=1e-5)
        tot += nll
    train_loss, _test_loss = ref.losses(0, [0, 0], [0, 0])
    assert train_loss == pytest.approx(tot / (C * N * L), rel=1e-5)


# ----------------------------------------------------------------------
# resnet_basic through the new path reads as it read on the parent
PINNED = {      # values of the parent (commit fe56978) on the CPU
    "cifar10_resnet20": dict(
        leaves=65, spec="8e5dec463027c6b1", macs=40_813_184, count=272_474,
        tree="cf8aab2a615902b1", stem=0.18461021780967712,
        at=((1, 2, 1, 2, 5), -0.34510132670402527),
        head=((1, 3, 7), 0.010295110754668713), proj_sum=1.9795601432560943),
    "cifar10_resnet18": dict(
        leaves=62, spec="7f8d5f1d185f9265", macs=555_422_720,
        count=11_173_962, tree="9e838b36aaaa5e82", stem=0.18461021780967712,
        at=((1, 2, 1, 2, 5), 0.2806510329246521),
        head=((1, 3, 7), 0.0845465287566185), proj_sum=29.901437270659244),
}


def _sha(obj) -> str:
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_resnet_basic_through_the_family_file_reads_as_on_the_parent(name):
    import jax
    import jax.numpy as jnp
    pin = PINNED[name]
    arch = bench.load_json("configs", f"{name}.json")["arch"]
    spec = reference.param_spec(arch)
    assert len(spec) == pin["leaves"] and _sha(spec) == pin["spec"]
    assert flops.forward_macs(arch) == pin["macs"]
    assert flops.parameter_count(arch) == pin["count"]
    seed, M = 2 ** 31 + 5, 2
    w = weights.make_weights(arch, seed, M)
    assert _sha(jax.tree_util.tree_leaves(
        weights.to_program_tree(arch, {k: k for k in w}))) == pin["tree"]
    # the parent's loop, as it stood in weights.py, under one jit as there:
    # bit for bit
    @jax.jit
    def parents(key):
        out = {}
        for i, (leaf, shape, role) in enumerate(spec):
            if role == "scale":
                out[leaf] = jnp.ones((M, *shape), jnp.float32)
            elif role == "bias":
                out[leaf] = jnp.zeros((M, *shape), jnp.float32)
            else:
                fan_in = math.prod(shape[:-1])
                std = math.sqrt((2.0 if role == "conv" else 1.0) / fan_in)
                out[leaf] = std * jax.random.normal(
                    jax.random.fold_in(key, i), (M, *shape), jnp.float32)
        return out
    want = parents(jax.random.fold_in(jax.random.PRNGKey(seed), 0x5EED))
    assert sorted(w) == sorted(want)
    for leaf in want:
        assert np.array_equal(np.asarray(w[leaf]), np.asarray(want[leaf])), \
            leaf
    # and the parent's numbers
    assert float(w["stem/conv"][0, 0, 0, 0, 0]) == pytest.approx(
        pin["stem"], rel=1e-6)
    assert float(w["stem/conv"][pin["at"][0]]) == pytest.approx(
        pin["at"][1], rel=1e-6)
    assert float(w["head/kernel"][pin["head"][0]]) == pytest.approx(
        pin["head"][1], rel=1e-6)
    assert float(np.asarray(w["s1b0/proj"], np.float64).sum()) \
        == pytest.approx(pin["proj_sum"], rel=1e-5)
