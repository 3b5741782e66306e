"""Plain reference of the federated round, and the comparison that decides
``correct``.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision: no
vmap over pairs, no donation, no mixed precision, one (model, client)
trajectory at a time. It imports nothing of the program and takes nothing
the program made: weights come from ``benchmark/weights.py``, data and the
round's keys from the seed. What it shares with the program is the
definition of the job: how a round's key becomes a batch (``batch_indices``),
the local loop, the sample-weighted mean over a model's clients.

What is the model's comes from the family's file, ``families/<family>.py``
(parameters, forward pass, loss and hits), found by ``arch["family"]``; the
client optimizer's plain update comes from ``optimizers/<kind>.py``, found
by the ``kind`` of the configuration's ``optimizer`` group. A parameter set
is a flat dict ``name -> array``; ``param_spec`` lists the names in forward
order.
"""

from __future__ import annotations

import statistics
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import family_of, named


# ----------------------------------------------------------------------
# the model: its family's file
def param_spec(arch: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, shape, role) of every parameter, in forward order."""
    return family_of(arch).param_spec(arch)


def forward(arch: dict, p: dict, x, dtype=None):
    """Logits of a batch. ``dtype`` lowers the operands of the family's
    matrix products (the control), nothing else."""
    return family_of(arch).forward(arch, p, x, dtype)


# ----------------------------------------------------------------------
# the job: keys, batches, optimizer, aggregation
def round_key(seed: int, t: int, r: int):
    """Key of round r of time step t: the experiment key, folded with the
    purpose (train = 0), the time step and the round."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    return jax.random.fold_in(jax.random.fold_in(k, t), r)


def pair_key(rkey, m: int, c: int, num_models: int, num_clients: int):
    return jax.random.split(rkey, num_models * num_clients).reshape(
        num_models, num_clients, 2)[m, c]


def batch_indices(key, time_w, n_per_step: int, batch: int, num_steps: int):
    """[num_steps, batch] indices into the client's flattened [T1 * N]
    samples: a time step drawn by weight, then one contiguous batch in it."""
    nb = n_per_step // batch
    logits = jnp.log(time_w + 1e-30)
    out = []
    for k in jax.random.split(key, num_steps):
        k1, k2 = jax.random.split(k)
        t_idx = jax.random.categorical(k1, logits)
        slot = jax.random.randint(k2, (), 0, nb)
        out.append(t_idx * n_per_step + slot * batch + jnp.arange(batch))
    return jnp.stack(out)


@partial(jax.jit, static_argnames=("arch_key", "optimizer", "fault", "dtype"))
def _local_sgd(p, opt, xb, yb, hyper, *, arch_key, optimizer, fault=None,
               dtype=None):
    """``xb`` [steps, B, ...]: the optimizer's update over the steps.
    Returns params, optimizer state, the steps' mean loss and the norms of
    the first step's gradient per parameter."""
    arch = _ARCHS[arch_key]
    family, update = family_of(arch), named("optimizers", optimizer).update

    def loss_fn(p, x, y):
        if fault == "half_batch":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        return family.nll(family.forward(arch, p, x, dtype), y).mean()

    def step(carry, xy):
        p, o = carry
        loss, g = jax.value_and_grad(loss_fn)(p, *xy)
        gnorm = {k: jnp.sqrt((v.astype(jnp.float32) ** 2).sum())
                 for k, v in g.items()}
        return update(p, g, o, hyper), (loss, gnorm)

    (p, opt), (losses, gnorms) = jax.lax.scan(step, (p, opt), (xb, yb))
    return p, opt, losses.mean(), {k: v[0] for k, v in gnorms.items()}


@partial(jax.jit, static_argnames=("arch_key", "dtype"))
def _eval(p, x, y, *, arch_key, dtype=None):
    """(correct count, summed loss) over the labels of one client's whole
    time step as one batch, as the program evaluates it."""
    arch = _ARCHS[arch_key]
    family = family_of(arch)
    logits = family.forward(arch, p, x, dtype)
    return family.hits(logits, y).sum(), family.nll(logits, y).sum()


def bf16_residue(a):
    """``a`` less ``a`` rounded to bfloat16 (to nearest, ties to even), by
    the bits: on a TPU the compiler is free to drop a float32 -> bfloat16 ->
    float32 pair of converts, which reads every residue as nought."""
    a = a.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    up = jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    kept = (bits + up) & jnp.uint32(0xFFFF0000)
    return a - jax.lax.bitcast_convert_type(kept, jnp.float32)


@jax.jit
def _residue_sq(a):
    """(what a bfloat16 cannot hold of ``a``, ``a``), as sums of squares.
    Their ratio's root is the share of an array's norm that is stored below
    bfloat16's last bit: about 2**-9 / sqrt(3) for float32 numbers, nought
    for numbers kept in bfloat16."""
    a = a.astype(jnp.float32)
    r = bf16_residue(a)
    return (r * r).sum(), (a * a).sum()


_ARCHS: dict[str, dict] = {}


def _arch_key(arch: dict) -> str:
    import json
    key = json.dumps(arch, sort_keys=True)
    _ARCHS[key] = arch
    return key


# ----------------------------------------------------------------------
# following the program's first time steps
class Reference:
    """Follows a federated job from benchmark-made weights.

    ``x`` [C, T1, N, ...] and ``y`` [C, T1, N, ...] (a label per sample, or
    whatever trailing axes the family's labels have) are host arrays of the
    time steps it will need; ``init`` is a list of M flat parameter dicts;
    ``hyper`` is the configuration's ``optimizer`` group with the program's
    ``lr`` and ``wd``. ``lower`` runs the whole of it one precision step
    down (bfloat16 parameters, moments, aggregation and convolution
    operands): the control where the program has no such path of its own.
    ``fault`` plants ``half_batch`` (half of each batch left out, the mean
    over the rest). ``compute_dtype`` lowers the convolutions' operands
    alone, as the configurations state their compute: the look at what
    bfloat16 compute by itself does to the numbers compared (PERF.md
    section 2).
    """

    def __init__(self, arch, hyper, init, x, y, seed, *, batch, local_steps,
                 lower=False, fault=None, compute_dtype=None):
        self.arch, self.akey = arch, _arch_key(arch)
        self.kind = hyper["kind"]
        self.optimizer = named("optimizers", self.kind)
        self.hyper = {k: float(hyper[k]) for k in self.optimizer.HYPER}
        self.dtype = "bfloat16" if lower else compute_dtype
        pd = jnp.bfloat16 if lower else jnp.float32
        self.params = [{k: jnp.asarray(v, pd) for k, v in p.items()}
                       for p in init]
        self.init = [dict(p) for p in self.params]
        self.x, self.y = x, y
        self.seed = seed
        self.batch, self.local_steps = batch, local_steps
        self.fault = fault
        self.M, self.C = len(init), x.shape[0]
        self.N = x.shape[2]
        self.labels = int(np.prod(y.shape[2:]))     # of a client's time step
        self.opt: dict[tuple[int, int], dict] = {}
        self.first_grad_norms: dict[str, float] | None = None

    def begin_time_step(self) -> None:
        """Optimizer states are fresh at every time-step boundary."""
        self.opt = {}

    def round(self, t: int, r: int, time_w: np.ndarray, c_pad: int) -> None:
        """One round under ``time_w`` [M, C, T1]. ``c_pad`` is the program's
        padded client axis, which sets how a round's key is split."""
        rkey = round_key(self.seed, t, r)
        B = min(self.batch, self.N)
        new = []
        for m in range(self.M):
            acc, wsum = None, 0.0
            members = [c for c in range(self.C) if time_w[m, c].sum() > 0]
            for c in members:
                w = jnp.asarray(time_w[m, c], jnp.float32)
                idx = np.asarray(batch_indices(
                    pair_key(rkey, m, c, self.M, c_pad), w, self.N, B,
                    self.local_steps))
                xf = self.x[c].reshape((-1,) + self.x.shape[3:])
                yf = self.y[c].reshape((-1,) + self.y.shape[3:])
                opt = self.opt.get((m, c))
                if opt is None:
                    opt = self.optimizer.new_state(self.params[m])
                p, opt, _loss, gn = _local_sgd(
                    self.params[m], opt, jnp.asarray(xf[idx]),
                    jnp.asarray(yf[idx]), self.hyper, arch_key=self.akey,
                    optimizer=self.kind, fault=self.fault,
                    dtype=self.dtype)
                self.opt[(m, c)] = opt
                if self.first_grad_norms is None:
                    self.first_grad_norms = {k: float(v)
                                             for k, v in gn.items()}
                n = float(time_w[m, c].sum()) * self.N
                acc = {k: n * v.astype(jnp.float32) for k, v in p.items()} \
                    if acc is None else \
                    {k: acc[k] + n * p[k].astype(jnp.float32) for k in acc}
                wsum += n
            if acc is None:
                new.append(self.params[m])
            else:
                dt = next(iter(self.params[m].values())).dtype
                new.append({k: (v / wsum).astype(dt) for k, v in acc.items()})
        self.params = new

    def moment_norms(self, which: str) -> dict[str, float]:
        """Per parameter, the norm of the optimizer's moment ``which`` over
        every pair that trained in this time step."""
        out: dict[str, float] = {}
        for o in self.opt.values():
            for k, v in o[which].items():
                out[k] = out.get(k, 0.0) + float(
                    (v.astype(jnp.float32) ** 2).sum())
        return {k: v ** 0.5 for k, v in out.items()}

    def moment_store_share(self, which: str) -> float:
        """The share of the norm stored below bfloat16's last bit, of the
        moment ``which`` of the pairs that trained."""
        res = tot = 0.0
        for o in self.opt.values():
            for v in o[which].values():
                r, n = _residue_sq(v)
                res, tot = res + float(r), tot + float(n)
        return (res / max(tot, 1e-300)) ** 0.5

    def param_store_share(self, models) -> float:
        res = tot = 0.0
        for m in models:
            for v in self.params[m].values():
                r, n = _residue_sq(v)
                res, tot = res + float(r), tot + float(n)
        return (res / max(tot, 1e-300)) ** 0.5

    def eval_matrix(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        """(correct [M, C], loss_sum [M, C]) on time step t's data."""
        corr = np.zeros((self.M, self.C))
        loss = np.zeros((self.M, self.C))
        for m in range(self.M):
            for c in range(self.C):
                k, l = _eval(self.params[m], jnp.asarray(self.x[c, t]),
                             jnp.asarray(self.y[c, t]), arch_key=self.akey,
                             dtype=self.dtype)
                corr[m, c], loss[m, c] = float(k), float(l)
        return corr, loss

    def losses(self, t: int, train_idx, test_idx) -> tuple[float, float]:
        """Train/Loss on t and Test/Loss on t + 1 as the runner logs them:
        each client under the model it trains (tests) with."""
        out = []
        for step, idx in ((t, train_idx), (t + 1, test_idx)):
            tot = 0.0
            for c in range(self.C):
                _, l = _eval(self.params[int(idx[c])],
                             jnp.asarray(self.x[c, step]),
                             jnp.asarray(self.y[c, step]),
                             arch_key=self.akey, dtype=self.dtype)
                tot += float(l)
            out.append(tot / (self.C * self.labels))
        return out[0], out[1]

    def change(self, models) -> dict[str, float]:
        """Per parameter, the norm of the change since the start over the
        models in ``models``."""
        out = {}
        for k in self.params[0]:
            out[k] = float(np.sqrt(sum(
                float(((self.params[m][k].astype(jnp.float32)
                        - self.init[m][k].astype(jnp.float32)) ** 2).sum())
                for m in models)))
        return out


# ----------------------------------------------------------------------
# the comparison
def worst_norm_gap(prog: dict[str, float], ref: dict[str, float],
                   skip=()) -> tuple[float, str]:
    """The widest gap between the program's norm and the reference's over
    the parameters, against the reference's norm of that parameter or of the
    median parameter, whichever is larger."""
    names = [k for k in ref if k not in skip]
    med = statistics.median(ref[k] for k in names)
    worst, at = 0.0, ""
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def median_norm_gap(prog: dict[str, float], ref: dict[str, float],
                    skip=()) -> float:
    """The median parameter's gap, by the measure of ``worst_norm_gap``:
    steadier from seed to seed than the worst one's."""
    names = [k for k in ref if k not in skip]
    med = statistics.median(ref[k] for k in names)
    return statistics.median(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
                             for k in names)


def flat_gradient_leaves(first_grad_norms: dict[str, float]) -> set[str]:
    """Parameters whose gradient is nought to rounding in the reference
    (under a thousandth of the median parameter's): they move by round-off
    alone and are left out of the change."""
    med = statistics.median(first_grad_norms.values())
    return {k for k, v in first_grad_norms.items() if v < 1e-3 * med}


def compare(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """``{name: {"value", "limit", "ok"}}`` for every limit; a number that
    is not finite, or that the comparison did not work out, is not ok."""
    out = {}
    for k, lim in limits.items():
        v = float(numbers.get(k, float("nan")))
        out[k] = {"value": v, "limit": lim,
                  "ok": bool(np.isfinite(v) and v <= lim)}
    return out
