"""First-contact smoke: train and serve on the TPU through the normal entry
points, at full width, and check what comes out.

    python chip_smoke.py

The parent process never imports JAX. It runs the steps below as child
processes, one at a time, each owning the chip(s) for its lifetime and all
sharing one compile cache (``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache`` — feddrift_tpu/utils/cache.py). It uses every chip
JAX finds (the default mesh) and says how many.

  probe        what JAX sees; anything but a TPU ends the smoke at once
  train_fused  the README Quickstart command verbatim: canonical SEA-4
               softcluster, 10 x 200 rounds, through train_iteration_eval
  repeat       iterations 0-1 of the same config in a second process: must
               add no compile-cache entry; iteration 1 runs under
               utils/tracing.xla_trace and the xplane must hold a TPU plane
               with train_iteration_eval on it; on several chips the dataset
               must be sharded over all of them
  train_conv   tracked config 3 at its defined width, cut in length only:
               cifar10 / resnet20 / IFCA hard-r — the per-round path
               (train_round + acc_matrix) and the conv models in bf16
  serve        ``serve`` on the pool train_fused just checkpointed:
               2000 requests, 0 errors, 0 compiles after warm-up
  train_seq    shakespeare / transformer / win-1: on one chip the Pallas
               flash kernel under remat . vmap . vmap . grad inside the fused
               program; on several, GSPMD cannot partition a Mosaic kernel,
               "auto" resolves to blockwise and run_start must say so
  kernel       the flash kernel alone, compiled (never interpreted), against
               blockwise_attention at the docstring's shape and the model's

Every step asserts on results, not on an exit code alone. Any failed step
makes the smoke exit non-zero with the failing child's output tail and no
result line; on success the last line of stdout is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``. There is no CPU
mode: it proves the system starts on the chip, and nothing else.
"""

from __future__ import annotations

import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
CACHE_DIR = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(ROOT, ".jax_cache"))

# The whole smoke must end inside this many seconds, compilation included.
BUDGET_S = 1140.0

# README Quickstart, first command. A dict so the CLI child (flags) and the
# API child (ExperimentConfig) build the SAME programs and share cache keys.
CANONICAL = dict(
    dataset="sea", model="fnn", concept_drift_algo="softcluster",
    concept_drift_algo_arg="H_A_C_1_10_0", concept_num=4, change_points="A",
    client_num_in_total=10, train_iterations=10, comm_round=200, epochs=5,
    batch_size=500, lr=0.01)
# Final Test/Acc of that command on CPU (f32), committed in
# runs/sea-fnn-softcluster-H_A_C_1_10_0-s0/metrics.jsonl. On a TPU the
# default precision="auto" computes in bf16. Measured on v5e (PR 21): final
# 0.8628, and over the ten time steps the chip is never further than 0.0034
# from the CPU run — so the tolerance is 0.01, three times that worst gap
# and two standard errors of the 5000-example test set.
CANONICAL_CPU_ACC = 0.8626
CANONICAL_ACC_TOL = 0.01

# ROADMAP tracked config 3 at its defined width; 3 time steps of 10 rounds.
CONV = dict(
    dataset="cifar10", model="resnet",
    concept_drift_algo="softclusterwin-1", concept_drift_algo_arg="hard-r",
    concept_num=3, client_num_in_total=10, client_num_per_round=10,
    epochs=5, batch_size=64, sample_num=500, lr=0.05,
    train_iterations=3, comm_round=10)

SEQ = dict(
    dataset="shakespeare", model="transformer", concept_drift_algo="win-1",
    client_num_in_total=10, client_num_per_round=10, epochs=2,
    batch_size=50, sample_num=200, lr=0.01,
    train_iterations=2, comm_round=10)


KERNEL_TOL = 4e-2      # flash kernel vs blockwise_attention, max abs error


class SmokeFailure(Exception):
    """A step ran and its results are wrong (or it did not run to an end)."""


def need(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def flags(cfg: dict) -> list[str]:
    return [a for k, v in cfg.items() for a in (f"--{k}", str(v))]


def run_dir(step: str) -> str:
    return os.path.join(OUT, "runs", step)


def cache_entries() -> int:
    return len(glob.glob(os.path.join(CACHE_DIR, "*-cache")))


def read_jsonl(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>\n"


# ----------------------------------------------------------------------
# parent side: run one child, then check what it left behind
def run_child(name: str, argv: list[str], deadline: float) -> dict:
    """Run one child to its end (or kill its whole process group at the
    deadline). Returns wall time, new cache entries and its stdout."""
    out_path = os.path.join(OUT, f"{name}.out")
    err_path = os.path.join(OUT, f"{name}.err")
    before = cache_entries()
    t0 = time.monotonic()
    timeout = deadline - t0
    need(timeout > 5, f"{name}: no time left in the {BUDGET_S:.0f}s budget")
    with open(out_path, "w") as out, open(err_path, "w") as err:
        proc = subprocess.Popen(
            argv, cwd=ROOT, stdout=out, stderr=err, start_new_session=True,
            env={**os.environ, "PYTHONUNBUFFERED": "1"})
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            # the child's process group goes with it, whatever happened
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    wall = time.monotonic() - t0
    if rc != 0:
        why = (f"timed out after {wall:.0f}s" if rc is None
               else f"exited {rc}")
        raise SmokeFailure(
            f"{name}: child {why}\n--- stdout tail ---\n{tail(out_path)}"
            f"--- stderr tail ---\n{tail(err_path)}")
    with open(out_path) as f:
        stdout = f.read()
    return {"wall_s": round(wall, 1), "new_cache_entries":
            cache_entries() - before, "stdout": stdout}


def last_json_line(stdout: str) -> dict:
    for line in reversed(stdout.strip().splitlines()):
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict):
            return obj
    raise SmokeFailure(f"no JSON result in child output:\n{stdout[-2000:]}")


def check_run_dir(step: str, device: dict, chance: float, program: str,
                  warmup_iterations: int) -> dict:
    """What every training step must have left in its run directory."""
    events = read_jsonl(os.path.join(run_dir(step), "events.jsonl"))
    metrics = read_jsonl(os.path.join(run_dir(step), "metrics.jsonl"))
    with open(os.path.join(run_dir(step), "ckpt", "MANIFEST.json")) as f:
        cfg = json.load(f)["config"]       # the config as the run resolved it
    start = next((e for e in events if e["kind"] == "run_start"), None)
    need(start is not None, f"{step}: no run_start event")
    for key, want in (("backend", device["platform"]),
                      ("device_kind", device["device_kind"]),
                      ("device_count", device["count"])):
        need(start.get(key) == want,
             f"run_start.{key} = {start.get(key)!r}, expected {want!r}")
    need(start.get("mesh") == {"clients": device["count"]},
         f"default mesh {start.get('mesh')} does not take every chip")
    need(any(e["kind"] == "run_end" for e in events), "no run_end event")
    need(any(e["kind"] == "hbm_watermark" and e.get("peak_bytes")
             for e in events), "no hbm_watermark event (memory_stats)")
    compiles = [e for e in events
                if e["kind"] in ("jit_compile", "jit_recompile")]
    need(any(e["fn"] == program for e in compiles),
         f"{program} never compiled; this step ran "
         f"{sorted({e['fn'] for e in compiles})}")
    late = [e for e in compiles
            if e.get("iteration", 0) >= warmup_iterations]
    need(not late, f"compiles after the {warmup_iterations} warm-up "
                   f"iteration(s): {late[:3]}")
    need(metrics, "metrics.jsonl is empty")
    for row in metrics:
        for k in ("Train/Loss", "Test/Loss", "Train/Acc", "Test/Acc"):
            need(isinstance(row.get(k), (int, float))
                 and math.isfinite(row[k]),
                 f"non-finite {k} at round {row.get('round')}: {row.get(k)}")
    final = metrics[-1]
    # above chance by more than sampling noise: 4 standard errors of a
    # chance-level classifier on the examples one eval scores
    examples = cfg["client_num_in_total"] * cfg["sample_num"]
    floor = chance + 4 * math.sqrt(chance * (1 - chance) / examples)
    best = max(r["Train/Acc"] for r in metrics)
    need(best > floor, f"Train/Acc never left chance: best {best:.4f}, "
                       f"chance {chance:.4f}, floor {floor:.4f}")
    need(final["Train/Loss"] < metrics[0]["Train/Loss"],
         f"Train/Loss did not fall: {metrics[0]['Train/Loss']:.4f} -> "
         f"{final['Train/Loss']:.4f}")
    need(final["round"] + 1 == cfg["train_iterations"] * cfg["comm_round"],
         f"{step}: last eval at round {final['round']}")
    return {"start": start, "final": final, "events": events,
            "compiles": [f"{e['fn']}@t{e.get('iteration')}r{e.get('round')}"
                         for e in compiles]}


def step_probe(deadline: float) -> dict:
    res = run_child("probe", [sys.executable, __file__, "--child", "probe"],
                    deadline)
    dev = last_json_line(res["stdout"])
    print(f"[probe] jax={dev['jax']} jaxlib={dev['jaxlib']} "
          f"platform={dev['platform']} device_kind={dev['device_kind']!r} "
          f"count={dev['count']} ({res['wall_s']}s)", flush=True)
    need(dev["platform"] == "tpu",
         f"JAX found no TPU: platform={dev['platform']!r} "
         f"device_kind={dev['device_kind']!r} count={dev['count']}")
    return {k: dev[k] for k in ("platform", "device_kind", "count")}


def step_train(name: str, cfg: dict, device: dict, deadline: float,
               chance: float, program: str, warmup_iterations: int) -> dict:
    """One ``python -m feddrift_tpu run`` child and its run directory."""
    res = run_child(name, [sys.executable, "-m", "feddrift_tpu", "run",
                           *flags(cfg), "--out_dir", run_dir(name),
                           "--flat_out_dir"], deadline)
    need(last_json_line(res["stdout"]).get("platform") == device["platform"],
         f"{name}: the CLI's result line does not name the "
         f"{device['platform']}")
    info = check_run_dir(name, device, chance, program, warmup_iterations)
    start, final = info["start"], info["final"]
    print(f"[{name}] {res['wall_s']}s, {res['new_cache_entries']} new cache "
          f"entries; precision={start['precision']} "
          f"compute_dtype={start['compute_dtype']} "
          f"attention_impl={start['attention_impl']}; final "
          f"Train/Acc={final['Train/Acc']:.4f} "
          f"Test/Acc={final['Test/Acc']:.4f} "
          f"Train/Loss={final['Train/Loss']:.4f}; compiles "
          f"{info['compiles']}", flush=True)
    return info


def step_train_fused(device: dict, deadline: float) -> None:
    info = step_train("train_fused", CANONICAL, device, deadline, chance=0.5,
                      program="train_iteration_eval", warmup_iterations=2)
    acc = info["final"]["Test/Acc"]
    state = [e for e in info["events"] if e["kind"] == "cluster_state"][-1]
    print(f"[train_fused] final Test/Acc {acc:.4f} vs CPU f32 "
          f"{CANONICAL_CPU_ACC} (tol {CANONICAL_ACC_TOL}); spawns="
          f"{state.get('spawns')} merges={state.get('merges')} models="
          f"{state.get('num_models')}", flush=True)
    need(abs(acc - CANONICAL_CPU_ACC) <= CANONICAL_ACC_TOL,
         f"canonical final Test/Acc {acc:.4f} is not within "
         f"{CANONICAL_ACC_TOL} of the CPU run's {CANONICAL_CPU_ACC}")


def step_train_seq(device: dict, deadline: float) -> None:
    info = step_train("train_seq", SEQ, device, deadline, chance=1 / 90,
                      program="train_iteration_eval", warmup_iterations=1)
    want = "pallas" if device["count"] == 1 else "blockwise"
    got = info["start"]["attention_impl"]
    need(got == want, f"attention_impl resolved to {got!r} on "
                      f"{device['count']} chip(s), expected {want!r}")


def step_repeat(device: dict, deadline: float) -> None:
    res = run_child("repeat", [sys.executable, __file__, "--child", "repeat"],
                    deadline)
    info = last_json_line(res["stdout"])
    print(f"[repeat] {res['wall_s']}s, {res['new_cache_entries']} new cache "
          f"entries ({info['cache_hits']} hits, {info['cache_misses']} "
          f"misses); x sharded over {info['x_devices']} device(s); xplane "
          f"{info['xplane_bytes']} B, {info['program_events']} "
          f"train_iteration_eval events on {info['tpu_planes']}", flush=True)
    need(res["new_cache_entries"] == 0,
         f"a second process of the same config wrote "
         f"{res['new_cache_entries']} new compile-cache entries into "
         f"{CACHE_DIR}: it did not hit the cache")
    need(info["cache_hits"] > 0, f"repeat child saw no cache hit: {info}")
    need(info["x_devices"] == device["count"],
         f"exp.x spans {info['x_devices']} of {device['count']} devices")


def step_serve(device: dict, deadline: float) -> None:
    res = run_child("serve", [
        sys.executable, "-m", "feddrift_tpu", "serve",
        run_dir("train_fused"),
        "--requests", "2000", "--concurrency", "16"], deadline)
    stats = json.loads(res["stdout"])
    print(f"[serve] {res['wall_s']}s, {res['new_cache_entries']} new cache "
          f"entries; {stats['completed']} completed, {stats['errors']} "
          f"errors, {len(stats['warmup_compiles'])} warm-up compiles, steady "
          f"compiles {stats['steady_compiles']}", flush=True)
    need(stats.get("platform") == device["platform"],
         f"serve ran on {stats.get('platform')!r}")
    need(stats["errors"] == 0 and stats["completed"] == 2000,
         f"serve: {stats['errors']} errors, {stats['completed']} completed")
    need(stats["warmup_compiles"],
         "serve: warm-up compiled nothing (compile counters lost)")
    need(not stats["steady_compiles"],
         f"serve compiled after warm-up: {stats['steady_compiles']}")


def step_kernel(device: dict, deadline: float) -> None:
    res = run_child("kernel", [sys.executable, __file__, "--child", "kernel"],
                    deadline)
    print(f"[kernel] {res['wall_s']}s; max abs error vs blockwise_attention "
          f"(f32, highest precision), tol {KERNEL_TOL}: "
          f"{last_json_line(res['stdout'])['errors']}", flush=True)


def step_train_conv(device: dict, deadline: float) -> None:
    step_train("train_conv", CONV, device, deadline, chance=0.1,
               program="train_round", warmup_iterations=1)


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "feddrift_tpu")):
        print("chip_smoke.py: feddrift_tpu/ is not next to this file — "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    shutil.rmtree(OUT, ignore_errors=True)   # this smoke's own directory
    os.makedirs(OUT)
    t_start = time.monotonic()
    deadline = t_start + BUDGET_S
    try:
        device = step_probe(deadline)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    print(f"[cache] {CACHE_DIR} ({cache_entries()} entries; "
          f"JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})",
          flush=True)

    # every step is attempted; repeat and serve stand on train_fused's
    # compile cache and checkpoint
    failed = []
    for step, after in ((step_train_fused, None),
                        (step_repeat, step_train_fused),
                        (step_serve, step_train_fused),
                        (step_train_conv, None),
                        (step_train_seq, None),
                        (step_kernel, None)):
        name = step.__name__.removeprefix("step_")
        if after in failed:
            print(f"[{name}] SKIPPED — needs {after.__name__}", flush=True)
            failed.append(step)
            continue
        try:
            step(device, deadline)
        except SmokeFailure as e:
            failed.append(step)
            print(f"[{name}] FAILED — {e}", flush=True)

    print(f"[total] {time.monotonic() - t_start:.0f}s of {BUDGET_S:.0f}s; "
          f"{cache_entries()} cache entries", flush=True)
    if failed:
        print(f"chip_smoke: FAILED steps: "
              f"{[s.__name__.removeprefix('step_') for s in failed]}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["count"]}}))
    return 0


# ----------------------------------------------------------------------
# child side: the steps that need JAX in the process. Each is its own
# process (python chip_smoke.py --child <name>), started by main() above.
def _require_tpu():
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke child: platform is {platform!r}, "
                         f"not 'tpu'")
    return jax


def child_probe() -> None:
    import jax
    import jaxlib
    d = jax.devices()
    print(json.dumps({"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                      "platform": d[0].platform,
                      "device_kind": d[0].device_kind, "count": len(d)}))


def child_repeat() -> None:
    """Iterations 0-1 of the canonical config, again, in this new process:
    every program must come out of the compile cache. Iteration 1 runs
    under the device profiler."""
    sys.path.insert(0, ROOT)
    from feddrift_tpu.utils.cache import enable_compile_cache
    enable_compile_cache()
    jax = _require_tpu()
    from jax import monitoring

    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1
    monitoring.register_event_listener(on_event)

    from feddrift_tpu.config import ExperimentConfig
    from feddrift_tpu.simulation.runner import Experiment
    from feddrift_tpu.utils.tracing import xla_trace

    trace_dir = os.path.join(OUT, "trace")
    exp = Experiment(ExperimentConfig(**CANONICAL), out_dir=run_dir("repeat"))
    # the client axis of the dataset must be split over every chip
    shards = exp.x.addressable_shards
    x_devices = len({s.device for s in shards})
    rows = sorted({s.data.shape[0] for s in shards})
    if rows != [exp.C_pad // len(jax.devices())]:
        raise SystemExit(f"x is not split evenly over the chips: shard rows "
                         f"{rows}, C_pad {exp.C_pad}")
    exp.run_iteration(0)
    jax.block_until_ready(exp.pool.params)
    with xla_trace(trace_dir):
        exp.run_iteration(1)
        jax.block_until_ready(exp.pool.params)

    planes = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    if not planes:
        raise SystemExit(f"xla_trace left no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(planes[0])
    tpu_planes, hits, layout = [], 0, {}
    for plane in data.planes:
        on_tpu = plane.name.startswith("/device:TPU")
        lines = {}
        for line in plane.lines:
            names = [e.name for e in line.events] if on_tpu else ()
            lines[line.name] = len(names) if on_tpu else \
                sum(1 for _ in line.events)
            hits += sum("train_iteration_eval" in n for n in names)
        layout[plane.name] = lines
        if on_tpu:
            tpu_planes.append(plane.name)
    with open(os.path.join(OUT, "trace_layout.json"), "w") as f:
        json.dump(layout, f, indent=1)
    if not tpu_planes or not hits:
        raise SystemExit(f"no train_iteration_eval event on a TPU device "
                         f"plane; planes: {list(layout)}")
    print(json.dumps({"cache_hits": counts["hits"],
                      "cache_misses": counts["misses"],
                      "x_devices": x_devices, "shard_rows": rows,
                      "xplane_bytes": os.path.getsize(planes[0]),
                      "tpu_planes": tpu_planes, "program_events": hits}))


def child_kernel() -> None:
    """The flash kernel alone, compiled by Mosaic, against the repo's jnp
    reference (blockwise_attention on f32 inputs at highest precision)."""
    sys.path.insert(0, ROOT)
    from feddrift_tpu.utils.cache import enable_compile_cache
    enable_compile_cache()
    jax = _require_tpu()
    import jax.numpy as jnp
    import numpy as np

    from feddrift_tpu.parallel.pallas_attention import flash_attention
    from feddrift_tpu.parallel.ring_attention import blockwise_attention

    def reference(q, k, v):
        with jax.default_matmul_precision("highest"):
            return blockwise_attention(*(a.astype(jnp.float32)
                                         for a in (q, k, v)), causal=True)

    errors = {}
    # (the docstring's shape, block 512) and (the transformer's: L=80, D=32)
    for tag, (B, H, L, D) in (("L2048_D64", (4, 8, 2048, 64)),
                              ("L80_D32", (20, 4, 80, 32))):
        # Mosaic's default-precision dots round operands to bf16 (8
        # mantissa bits) whatever the input dtype: on outputs up to ~4 in
        # magnitude that is ~1.6e-2 per rounding. Measured on v5e (PR 21):
        # 0.008-0.012 in all four cases.
        for dtype in (jnp.float32, jnp.bfloat16):
            q, k, v = (jax.random.normal(key, (B, H, L, D), jnp.float32)
                       .astype(dtype)
                       for key in jax.random.split(jax.random.PRNGKey(0), 3))
            out = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))(
                q, k, v)
            ref = reference(q, k, v)
            out = np.asarray(out.astype(jnp.float32))
            case = f"{tag}/{jnp.dtype(dtype).name}"
            if out.shape != (B, H, L, D) or not np.isfinite(out).all():
                raise SystemExit(f"{case}: bad output")
            err = float(np.max(np.abs(out - np.asarray(ref))))
            errors[case] = round(err, 5)
            if err > KERNEL_TOL:
                raise SystemExit(f"{case}: max abs error {err:.4g} > "
                                 f"{KERNEL_TOL} vs blockwise_attention; "
                                 f"all: {errors}")
    # gradients flow through the custom_vjp at the model's shape
    q, k, v = (jax.random.normal(key, (4, 4, 80, 32), jnp.bfloat16)
               for key in jax.random.split(jax.random.PRNGKey(1), 3))
    grads = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    if not all(np.isfinite(np.asarray(g.astype(jnp.float32))).all()
               for g in grads):
        raise SystemExit("non-finite gradient through flash_attention")
    print(json.dumps({"errors": errors}))


CHILDREN = {"probe": child_probe, "repeat": child_repeat,
            "kernel": child_kernel}

if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        CHILDREN[sys.argv[2]]()
        sys.exit(0)
    sys.exit(main())
