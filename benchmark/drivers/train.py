"""Driver for ``"kind": "train"`` traffic: a federated drift job through the
program's normal path (``ExperimentConfig`` -> ``Experiment`` ->
``run_iteration``).

Set-up builds ONE ``Experiment``, gives its pool the benchmark's weights,
drives it through the warm-up time steps (recording what the comparison
needs) and hands the same object to the window. The window is a run of
whole time steps, closed with ``block_until_ready`` on the pool. After it:
the memory peak is read, the program's device state is freed, and the plain
reference follows the first time steps to decide ``correct``.
"""

from __future__ import annotations

import gc
import glob
import inspect
import json
import os
import shutil
import time

import numpy as np

from benchmark import family_of, flops, named, reference, weights, xplane
from benchmark.run import ROOT, load_json, log, read_per_layer


# ----------------------------------------------------------------------
# building the job from the two files
def change_point_literal(rows: list[list[int]], clients: int) -> str:
    """The traffic's change-point matrix, columns tiled to ``clients``, as
    the matrix literal the program's loader parses."""
    mat = np.asarray(rows, dtype=np.int64)
    reps = -(-clients // mat.shape[1])
    mat = np.tile(mat, (1, reps))[:, :clients]
    return ";".join(" ".join(str(int(v)) for v in row) for row in mat)


def experiment_config(config, traffic, sizes, seed, clients):
    """The program's own configuration: the three files' ``program`` groups,
    the cell's last. Every client takes part in every round unless one of
    them sets ``client_num_per_round``."""
    from feddrift_tpu.config import ExperimentConfig
    fields = {"client_num_per_round": clients, **config["program"],
              **traffic["program"], **sizes["program"]}
    fields.update(
        seed=seed, client_num_in_total=clients,
        change_points=change_point_literal(traffic["change_points"], clients),
        cost_model="off", trace_sync=False, checkpoint_every_iteration=False)
    return ExperimentConfig(**fields)


def assignment_rule(traffic: dict):
    """The job's assignment rule, ``assignment/<name>.py``, found by the
    name the traffic file gives under ``check``."""
    return named("assignment", traffic["check"]["assignment"])


def optimizer_of(config: dict):
    """The comparison's side of the client optimizer,
    ``optimizers/<kind>.py``, found by the ``kind`` of the configuration's
    ``optimizer`` group."""
    return named("optimizers", config["optimizer"]["kind"])


def numbers_of(config: dict, traffic: dict) -> list[str]:
    """The numbers ``check`` works out for a cell, in the order of the
    ``numbers`` line."""
    return _numbers(optimizer_of(config), assignment_rule(traffic))


def _numbers(opt, rule) -> list[str]:
    """Those of the optimizer's moments only where it has them."""
    return (["train_loss_gap", "test_loss_gap", rule.NUMBER]
            + (["moment_gap", "moment_gap_median"] if opt.RECENT else [])
            + (["first_grad_gap", "first_grad_gap_median"]
               if opt.FIRST_GRAD else [])
            + (["moment_store_gap"] if opt.RECENT else [])
            + ["change_gap", "change_gap_median", "param_store_gap"])


def check_cell(config: dict, traffic: dict, sizes: dict) -> None:
    """A cell's files are refused as they are loaded where the family or
    the optimizer has no file, or a limit is set on a number that the
    cell's optimizer cannot give."""
    family_of(config["arch"])
    there = numbers_of(config, traffic)
    stray = sorted(set(sizes["limits"]) - set(there))
    if stray:
        raise ValueError(
            f"cells/{sizes.get('name', '?')}.json sets a limit on {stray}, "
            f"which optimizer {config['optimizer']['kind']!r} under "
            f"assignment {traffic['check']['assignment']!r} cannot give; "
            f"the numbers there are: {there}")


# ----------------------------------------------------------------------
# what the warm-up records of the program, for the comparison
class Recorder:
    """Stands in front of the traffic's ``round_program`` (the entry point of
    the program's ``TrainStep`` that the job dispatches) while the warm-up
    time steps run: notes each dispatch's time weights, as the program
    masks them by the round's participants, and reduces the optimizer state
    it returns to per-parameter norms of the moments the optimizer has (its
    file names them and says where they sit). It changes no argument and no
    result, and is taken off before the window."""

    def __init__(self, exp, arch, optimizer, round_program):
        import jax
        import jax.numpy as jnp
        self.exp, self.arch, self.name = exp, arch, round_program
        self.optimizer = optimizer
        self.time_w: list[np.ndarray] = []
        self.moments = self.store_share = None

        def norms(tree):
            return jax.tree_util.tree_map(
                lambda l: jnp.sqrt((l.astype(jnp.float32) ** 2).sum()), tree)

        def reduce(moments):
            share = None
            if optimizer.RECENT:
                f32 = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                    lambda l: l.astype(jnp.float32),
                    moments[optimizer.RECENT]))
                res = sum((reference.bf16_residue(l) ** 2).sum() for l in f32)
                tot = sum((l * l).sum() for l in f32)
                share = jnp.sqrt(res / jnp.maximum(tot, 1e-30))
            return {k: norms(v) for k, v in moments.items()}, share
        self._norms = jax.jit(reduce)
        fn = getattr(type(exp.step), round_program)
        self._signature = inspect.signature(fn)
        setattr(exp.step, round_program, self._wrap(fn, exp.step))

    def _wrap(self, fn, step):
        def call(*a, **kw):
            out = fn(step, *a, **kw)
            args = self._signature.bind(step, *a, **kw).arguments
            self.time_w.extend(masked_time_weights(
                np.asarray(args["time_w"]),
                args.get("client_mask", args.get("client_masks"))))
            if self.optimizer.MOMENTS:
                self.moments, self.store_share = self._norms(
                    self.optimizer.moments_of(out[1]))
            return out
        return call

    def take(self):
        """(time weights of the rounds dispatched since the last take, or
        one for all the rounds of a fused dispatch; per moment of the
        optimizer its norms after the last of them; the store share of the
        recent-gradient moment, or None where the optimizer has none)."""
        tw, self.time_w = self.time_w, []
        flat = {name: {k: float(v) for k, v in
                       weights.from_program_tree(self.arch, tree).items()}
                for name, tree in (self.moments or {}).items()}
        share = self.store_share
        return tw, flat, None if share is None else float(share)

    def remove(self):
        delattr(self.exp.step, self.name)


def masked_time_weights(time_w: np.ndarray, mask) -> list[np.ndarray]:
    """The [M, C, T1] time weights of a dispatch as its rounds train on
    them: the program multiplies them by the round's 0/1 participation mask
    over the clients (``core/step.py``: ``time_w * client_mask[None, :,
    None]``). No mask: one tensor, for every round of the dispatch. A [C]
    mask: that round's. [R, C] masks of a fused dispatch: one per round."""
    if mask is None:
        return [time_w]
    mask = np.asarray(mask, np.float32)
    rows = mask[None] if mask.ndim == 1 else mask
    return [time_w * row[None, :, None] for row in rows]


def initial_models(flat: dict, traffic: dict) -> list[dict]:
    """The M flat parameter sets the job starts from, as host arrays: every
    slot holds model 0, as the program starts its own pool; where the job
    re-draws distinct models at the first time step (IFCA), slot m holds
    model m."""
    host = {k: np.asarray(v) for k, v in flat.items()}
    M = next(iter(host.values())).shape[0]
    distinct = bool(traffic.get("distinct_init"))
    return [{k: v[m if distinct else 0] for k, v in host.items()}
            for m in range(M)]


def install_weights(exp, arch, traffic, seed):
    """The pool starts from the benchmark's weights (``initial_models``,
    which it returns): model 0 in every slot and as the program's reinit
    target, at the pool's own types. Where the job re-draws distinct models
    at the first time step, the re-draw uploads slot m's start model from
    those host arrays when the program asks for it, so the harness keeps
    nothing on the device. The program's first pool goes before the weights
    are made and the weights' device copy before the new pool is: never
    more than a pool and one model beside the data."""
    import jax
    from feddrift_tpu.parallel.mesh import replicate
    pool = exp.pool
    like = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), pool.params)
    _delete((pool.params, pool.init_params))
    pool.params = pool.init_params = None
    flat = weights.make_weights(arch, seed, pool.num_models)
    host = {k: np.asarray(v) for k, v in flat.items()}
    _delete(flat)
    tree = weights.to_program_tree(arch, host)
    if jax.tree_util.tree_structure(tree) != jax.tree_util.tree_structure(like) \
            or [l.shape for l in jax.tree_util.tree_leaves(tree)] \
            != [l.shape for l in jax.tree_util.tree_leaves(like)]:
        raise ValueError("the configuration's arch does not describe the "
                         "program's model")
    # stored at the pool's own type (float32 as the configurations state it)
    pool.params = jax.tree_util.tree_map(
        lambda l, ref: replicate(exp.mesh, jax.numpy.broadcast_to(
            jax.numpy.asarray(l[:1], ref.dtype), ref.shape)), tree, like)
    pool.init_params = jax.tree_util.tree_map(lambda l: l[0], pool.params)
    init = initial_models(host, traffic)
    if traffic.get("distinct_init"):
        def redraw(m, seed=None):
            pool.set_slot(m, jax.tree_util.tree_map(
                lambda l, ref: np.asarray(l, ref.dtype),
                weights.to_program_tree(arch, init[m]), like))
            # set_slot builds its pool beside the one it reads: the line
            # says whether that or a round sets the run's peak
            jax.block_until_ready(pool.params)
            memory(f"re-draw of slot {m}")
        pool.distinct_reinit_slot = redraw
    return init


def _delete(tree) -> None:
    """Frees the device arrays of ``tree`` now, whoever else refers to
    them."""
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "delete"):
            leaf.delete()


def memory(stage: str) -> dict:
    """What each chip's allocator reports. ``peak_bytes_in_use`` is the
    memory peak the result carries. On a TPU it counts the arrays the
    process holds; ``peak_bytes_reserved``, printed and reported beside it
    under its own name, is the region the runtime keeps for the loaded
    programs' temporaries (PERF.md section 4, Sizing)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    out = {"stage": stage,
           "bytes_in_use": [s.get("bytes_in_use") for s in stats],
           "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
           "peak_bytes_reserved": [s.get("peak_bytes_reserved") for s in stats]}
    log("memory " + json.dumps(out))
    if stage == "after window":
        log("memory_stats " + json.dumps(stats[0]))
    return out


class CompileCounter:
    """Counts XLA programs built (compiled or fetched from the persistent
    cache) while it is armed, whatever jitted function they belong to."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.armed and (event.endswith("backend_compile_duration")
                           or event.endswith("cache_retrieval_time_sec")):
            self.count += 1


# ----------------------------------------------------------------------
def time_step_record(exp, t, wall, clients, cfg):
    """One time step of the window. Its active pairs are those of its first
    round's time weights, before any participation mask."""
    tw = np.asarray(exp.algo.round_inputs(t, 0)[0])[:, :clients]
    bd = exp.last_round_breakdown
    return {"t": t, "wall_s": wall, "rounds": cfg.comm_round,
            "active_pairs": int((tw.sum(-1) > 0).sum()),
            "segments": dict(bd["segments"])}


def run(*, manifest, cell, config, traffic, sizes, seed, seconds, trace,
        rehearse, device, t_start):
    import jax
    from feddrift_tpu.parallel.mesh import make_mesh
    from feddrift_tpu.simulation.runner import Experiment

    arch = config["arch"]
    optimizer = optimizer_of(config)
    chips = int(cell["chips"])
    clients = int(sizes["clients_per_chip"]) * chips
    cfg = experiment_config(config, traffic, sizes, seed, clients)
    compiles = CompileCounter()
    programs_before = _tracked_compiles()

    log(f"set-up: imports and the device after {time.time() - t_start:.1f} s")
    exp = Experiment(cfg, mesh=make_mesh(num_devices=chips))
    init = install_weights(exp, arch, traffic, seed)
    memory("built")
    log(f"set-up: data, experiment and weights after "
        f"{time.time() - t_start:.1f} s")

    follow = int(traffic["check"]["follow_time_steps"])
    warm = int(traffic["warmup_time_steps"])
    if follow > warm:
        raise ValueError("the comparison follows warm-up time steps only")
    rec = Recorder(exp, arch, optimizer, traffic["round_program"])
    seen = []
    for t in range(warm):
        exp.run_iteration(t)
        if t < follow:
            tw, moments, store_share = rec.take()
            seen.append({
                "t": t, "time_w": tw, "moments": moments,
                "moment_store_share": store_share,
                "c_pad": exp.C_pad,
                "assign": np.asarray(exp.algo.weights[t]).copy(),
                "train_idx": np.asarray(exp.algo.train_model_idx(t)),
                "test_idx": np.asarray(exp.algo.test_model_idx(t)),
                "train_loss": exp.logger.last("Train/Loss"),
                "test_loss": exp.logger.last("Test/Loss"),
                "params": weights.from_program_tree(
                    arch, jax.device_get(exp.pool.params))})
        if t + 1 == follow:
            rec.remove()
        memory(f"after time step {t}")
        log(f"set-up: warm-up time step {t} done after "
            f"{time.time() - t_start:.1f} s")
    if follow == 0:
        rec.remove()
    jax.block_until_ready(exp.pool.params)

    # ---- the window -------------------------------------------------
    last_t = cfg.train_iterations - 1
    traced_steps = int(traffic.get("traced_time_steps", 2)) if trace else 0
    steps, traced = [], []
    rollbacks0 = exp.divergence_guard.total_rollbacks \
        if exp.divergence_guard is not None else 0
    compiles.armed = True
    tracked0 = _tracked_compiles()
    setup_s = time.time() - t_start
    w0 = time.perf_counter()
    t = warm
    mean = 0.0
    while t <= last_t - traced_steps:
        elapsed = time.perf_counter() - w0
        if elapsed >= seconds or (trace and steps
                                  and elapsed + traced_steps * mean >= seconds):
            break
        s0 = time.perf_counter()
        exp.run_iteration(t)
        steps.append(time_step_record(exp, t, time.perf_counter() - s0,
                                      clients, cfg))
        mean = (time.perf_counter() - w0) / len(steps)
        t += 1
    jax.block_until_ready(exp.pool.params)
    window_s = time.perf_counter() - w0
    if t > last_t - traced_steps and window_s < seconds:
        log(f"the window reached the last time step of the data (T="
            f"{cfg.train_iterations}) after {window_s:.1f} s of {seconds}")
    records = {
        "window_s": window_s, "time_steps": steps, "chips": chips,
        "clients": clients, "batch": min(cfg.batch_size, cfg.sample_num),
        "participants": min(cfg.client_num_per_round, clients),
        "local_steps": cfg.epochs,
        "round_program": traffic["round_program"],
        "train_flops_per_example": flops.train_flops_per_example(arch),
        "compiles": compiles.count,
        "tracked_compiles": _tracked_compiles() - tracked0,
        "device_kind": device["kind"],
        "peaks": load_json("peaks.json"),
    }
    compiles.armed = False

    trace_red = None
    if trace:
        trace_red = _traced_steps(
            exp, t, traced_steps, traced, clients, cfg, rehearse,
            scopes=getattr(family_of(arch), "DEVICE_SCOPES", ()))
    stats = memory("after window")
    peak = max(u or 0 for u in stats["peak_bytes_in_use"])
    records["peak_bytes"] = peak
    records["peak_reserved_bytes"] = max(
        r or 0 for r in stats["peak_bytes_reserved"])
    rounds = sum(s["rounds"] for s in steps + traced)
    failed = ((exp.divergence_guard.total_rollbacks - rollbacks0)
              * cfg.comm_round if exp.divergence_guard is not None else 0)

    # ---- free the program's device state, then the reference ---------
    x_host, y_host = exp.ds.x[:, : follow + 1], exp.ds.y[:, : follow + 1]
    hyper = dict(config["optimizer"], lr=cfg.lr, wd=cfg.wd)
    job = {"seed": cfg.seed, "batch": cfg.batch_size, "local_steps": cfg.epochs}
    if steps:
        longest = max(steps, key=lambda s: s["wall_s"])
        log(f"longest time step of the window: t={longest['t']}, "
            f"{longest['wall_s']:.3f} s (median "
            f"{float(np.median([s['wall_s'] for s in steps])):.3f} s), "
            f"segments " + json.dumps(longest["segments"]))
    _free(exp)
    del exp, rec
    gc.collect()
    c0 = time.perf_counter()
    numbers = check(arch, hyper, init, x_host, y_host, job, traffic, seen)
    log(f"reference and comparison took {time.perf_counter() - c0:.1f} s")
    checks = reference.compare(numbers, sizes["limits"])
    correct = bool(checks) and all(c["ok"] for c in checks.values()) \
        and len(steps) > 0

    # ---- the result ---------------------------------------------------
    examples = sum(s["rounds"] for s in steps) * records["participants"] \
        * records["local_steps"] * records["batch"]
    if rehearse:
        metrics = {}      # a CPU run gives no number under a metric's name
    elif trace:
        metrics = read_per_layer(manifest, cell, records, trace_red)
    else:
        metrics = {
            "train_examples_per_s": {"value": examples / window_s,
                                     "unit": "examples/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    dev = dict(device, memory_peak_bytes=int(peak))
    result = {"correct": correct, "attempted": int(rounds),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if trace_red is not None:
        dev["busy_s"] = trace_red["busy_s"]
        dev["window_s"] = trace_red["window_s"]
        result["breakdown"] = trace_red["breakdown"]
    log(f"window {window_s:.3f} s, {len(steps)} time steps, set-up "
        f"{setup_s:.1f} s, programs built in the window {compiles.count}, "
        f"before it {_tracked_compiles() - programs_before} tracked")
    result["check"] = checks
    result["numbers"] = numbers     # every number worked out, limit or none
    return result


def _tracked_compiles() -> int:
    """The program's own count of jit_compile + jit_recompile events."""
    from feddrift_tpu import obs
    snap = obs.registry().snapshot()
    return sum(int(v) for k, v in snap.items()
               if str(k).startswith("jit_compiles"))


def _traced_steps(exp, t, n, traced, clients, cfg, rehearse, scopes=()):
    """Runs ``n`` more time steps under the profiler and reduces the trace.
    Each is wrapped in a ``bench_time_step`` annotation from here; the
    program's own spans give the host segment of each idle gap, the model
    family's ``scopes`` the layer of each device op."""
    import jax
    out_dir = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the annotations are enough of the host
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        sync_wall = time.time()
        with jax.profiler.TraceAnnotation("bench_sync"):
            pass
        for i in range(n):
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_time_step"):
                exp.run_iteration(t + i)
                jax.block_until_ready(exp.pool.params)
            traced.append(time_step_record(exp, t + i,
                                           time.perf_counter() - s0,
                                           clients, cfg))
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError("the profiler wrote no xplane file")
    r0 = time.perf_counter()
    raw = xplane.load(paths[0])
    if rehearse and not raw["devices"]:
        log("rehearsal: the CPU's trace has no device plane; nothing reduced")
        return None
    host_spans = [(s["name"], s["ts"] * 1e-6, s["dur"] * 1e-6)
                  for s in exp.spans.spans()]
    red = xplane.reduce(raw, sync_wall=sync_wall, host_spans=host_spans,
                        rounds=sum(s["rounds"] for s in traced),
                        scopes=scopes)
    log(f"trace of {n} time steps: {os.path.getsize(paths[0]) / 1e6:.1f} MB, "
        f"reduced in {time.perf_counter() - r0:.1f} s; device time by scope "
        f"{json.dumps(red['scope_s'])}")
    for scope, ops in (red["scope_ops"] or {}).items():
        log(f"device ops of scope {scope}: " + json.dumps(ops))
    shutil.rmtree(out_dir, ignore_errors=True)
    return red


def _free(exp) -> None:
    import jax
    _delete((exp.x, exp.y, exp.pool.params, exp.pool.init_params,
             getattr(exp.algo, "_tw", None),
             getattr(exp.algo, "_ones_sample_w", None),
             getattr(exp.algo, "_ones_feat_mask", None)))
    jax.clear_caches()


# ----------------------------------------------------------------------
def check(arch, hyper, init, x, y, job, traffic, seen, *, lower=False,
          fault=None) -> dict:
    """The reference follows the time steps in ``seen`` and every number of
    the comparison is worked out: see PERF.md section 2 for each."""
    ref = reference.Reference(arch, hyper, init, x, y, job["seed"],
                              batch=job["batch"],
                              local_steps=job["local_steps"], lower=lower,
                              fault=fault)
    rule, opt = assignment_rule(traffic), ref.optimizer
    C = x.shape[0]
    numbers = {"train_loss_gap": 0.0, "test_loss_gap": 0.0, rule.NUMBER: 0.0}
    used = set()
    for s in seen:
        t = s["t"]
        prog_assign = s["assign"].argmax(axis=0)[:C]
        ref.begin_time_step()
        for r, tw in enumerate(_round_weights(s["time_w"], traffic)):
            ref.round(t, r, tw[:, :C], s["c_pad"])
            used |= {m for m in range(ref.M) if tw[m, :C].sum() > 0}
        numbers[rule.NUMBER] = max(numbers[rule.NUMBER],
                                   rule.reading(ref, t, prog_assign))
        tr, te = ref.losses(t, s["train_idx"][:C], s["test_idx"][:C])
        log(f"check detail: time step {t} train loss {s['train_loss']:.5f} "
            f"(reference {tr:.5f}), test loss {s['test_loss']:.5f} "
            f"(reference {te:.5f})")
        for name, mine, theirs in (("train_loss_gap", tr, s["train_loss"]),
                                   ("test_loss_gap", te, s["test_loss"])):
            numbers[name] = max(numbers[name], abs(theirs - mine) / abs(mine))
        if t == seen[0]["t"]:
            for number, which in (("moment_gap", opt.RECENT),
                                  ("first_grad_gap", opt.FIRST_GRAD)):
                if which is None:
                    continue
                mine = ref.moment_norms(which)
                numbers[number], at = reference.worst_norm_gap(
                    s["moments"][which], mine)
                numbers[f"{number}_median"] = reference.median_norm_gap(
                    s["moments"][which], mine)
                log(f"check detail: {number} worst at {at}")
            if opt.RECENT:
                mine = ref.moment_store_share(opt.RECENT)
                numbers["moment_store_gap"] = \
                    abs(s["moment_store_share"] - mine) / max(mine, 1e-30)
    flat = reference.flat_gradient_leaves(ref.first_grad_norms)
    if flat:
        log(f"check detail: left out of the change (gradient nought to "
            f"rounding in the reference): {sorted(flat)}")
    models = sorted(used)
    last = seen[-1]["params"]
    prog_change = {k: float(np.sqrt(sum(
        ((np.asarray(last[k][m], np.float32) - init[m][k]) ** 2).sum()
        for m in models))) for k in last}
    numbers["change_gap"], at = reference.worst_norm_gap(
        prog_change, ref.change(models), skip=flat)
    numbers["change_gap_median"] = reference.median_norm_gap(
        prog_change, ref.change(models), skip=flat)
    log(f"check detail: change_gap worst at {at}; models in use {models}")
    mine = ref.param_store_share(models)
    theirs = _store_share([last[k][m] for k in last for m in models])
    numbers["param_store_gap"] = abs(theirs - mine) / max(mine, 1e-30)
    log(f"check detail: share of the norm stored below bfloat16's last bit: "
        f"parameters {theirs:.3e} (reference {mine:.3e})")
    return {k: numbers[k] for k in _numbers(opt, rule)}


def _store_share(arrays) -> float:
    """``reference._residue_sq`` summed over host arrays."""
    res = tot = 0.0
    for a in arrays:
        r, n = reference._residue_sq(np.asarray(a, np.float32))
        res, tot = res + float(r), tot + float(n)
    return (res / max(tot, 1e-300)) ** 0.5


def reference_as_program(arch, hyper, init, x, y, job, traffic, *,
                         lower=False, fault=None,
                         compute_dtype=None) -> list[dict]:
    """The reference put in the program's place: it runs the job's first
    time steps itself, making the job's own assignments by the traffic's
    rule, and returns what the warm-up would have recorded of the program.
    ``lower`` computes it one precision step down, ``compute_dtype`` lowers
    its convolutions' operands alone; ``fault`` plants ``half_batch``,
    ``state_unchanged`` (the rounds return their state as they got it) or
    ``assign_altered`` (the first client is sent to the next model)."""
    ref = reference.Reference(
        arch, hyper, init, x, y, job["seed"], batch=job["batch"],
        local_steps=job["local_steps"], lower=lower,
        compute_dtype=compute_dtype,
        fault=fault if fault == "half_batch" else None)
    rule = assignment_rule(traffic)
    M, C, T1 = len(init), x.shape[0], x.shape[1]
    hist = np.zeros((T1, M, C), np.float32)
    seen = []
    for t in range(int(traffic["check"]["follow_time_steps"])):
        ref.begin_time_step()
        assign = rule.choose(ref, t)
        tws = []
        for r in range(int(traffic["program"]["comm_round"])):
            hist[t] = 0.0
            hist[t, assign, np.arange(C)] = 1.0
            if traffic["check"]["train_on"] == "current_step":
                hist[:t] = 0.0
            tws.append(np.transpose(hist, (1, 2, 0)).copy())
            if fault != "state_unchanged":
                ref.round(t, r, tws[-1], C)
            if rule.EVERY_ROUND:
                assign = rule.choose(ref, t)
        if fault == "assign_altered":
            assign = np.array(assign)
            assign[0] = (assign[0] + 1) % M
        hist[t] = 0.0
        hist[t, assign, np.arange(C)] = 1.0
        tr, te = ref.losses(t, assign, assign)
        nought = {k: 0.0 for k in init[0]}
        recent = ref.optimizer.RECENT
        seen.append({
            "t": t, "time_w": tws if rule.EVERY_ROUND else tws[:1],
            "moments": {which: ref.moment_norms(which) or nought
                        for which in ref.optimizer.MOMENTS},
            "moment_store_share":
                ref.moment_store_share(recent) if recent else None,
            "c_pad": C, "assign": hist[t].copy(), "train_idx": assign,
            "test_idx": assign, "train_loss": tr, "test_loss": te,
            "params": {k: np.stack([np.asarray(p[k], np.float32)
                                    for p in ref.params])
                       for k in init[0]}})
    return seen


def _round_weights(time_w: list[np.ndarray], traffic) -> list[np.ndarray]:
    """One [M, C, T1] weight tensor per round of the time step: a fused
    dispatch carries one for all of its rounds."""
    rounds = int(traffic["program"]["comm_round"])
    if len(time_w) == 1:
        return time_w * rounds
    if len(time_w) != rounds:
        raise ValueError(f"{len(time_w)} dispatches recorded for {rounds} "
                         f"rounds")
    return time_w

