"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

looks the cell up in ``BENCHMARK.json``, loads the configuration's, the
traffic mix's and the cell's own file by name, and hands them to the driver
module that the traffic file's ``kind`` names. The last line of standard output is the
result; everything else goes to standard error. Without the chips the cell
asks for it prints no result and exits with code 3.

``--rehearse`` drives the same control flow on the CPU at the tiny size the
files give under ``rehearse``. It prints no result line and no metric.
"""

from __future__ import annotations

import time

_T_START = time.time()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*rel: str) -> dict:
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in manifest['workloads']]}")


def driver_of(traffic: dict):
    """The driver ``drivers/<kind>.py`` that the traffic file's ``kind``
    names."""
    return importlib.import_module(f"benchmark.drivers.{traffic['kind']}")


def load_cell(manifest: dict, name: str, rehearse: bool = False):
    """(cell, config, traffic, sizes): the manifest's entry and the three
    files it names. ``sizes`` is ``cells/<cell>.json``: what belongs to the
    pair of configuration and traffic (clients per chip, time steps of data,
    the limits of ``correct``). A rehearsal lays each file's ``rehearse``
    group over it. The driver refuses files it could not run to an end
    (``check_cell``): a family or an optimizer with no file, a limit on a
    number that the cell cannot give."""
    cell = find_cell(manifest, name)
    files = [load_json("configs", f"{cell['config']}.json"),
             load_json("traffic", f"{cell['traffic']}.json"),
             load_json("cells", f"{cell['name']}.json")]
    if rehearse:
        files = [overlay(f, f.get("rehearse", {})) for f in files]
    driver_of(files[1]).check_cell(*files)
    return (cell, *files)


def metrics_of(manifest: dict, section: str, cell: str) -> list[dict]:
    """The metrics of ``section`` that the cell reports: those without a
    ``workloads`` key, and those that list it."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it, nested dicts merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def read_per_layer(manifest: dict, cell: dict, records: dict,
                   trace: dict | None) -> dict:
    """Each per-layer metric through its reader ``metrics/<name>.py``; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in metrics_of(manifest, "per_layer", cell["name"]):
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        value = reader.read(records, trace, cell)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def setup_jax(rehearse: bool) -> None:
    """Platform and compile cache, before the first backend use. The cache
    sits at a fixed path inside the checkout unless the environment places
    it; every program is cached, however quick to compile."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(chips: int) -> dict:
    """The device as JAX reports it; exits with 3 where it is no TPU or has
    fewer chips than the cell asks for."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator: {e}")
        raise SystemExit(3)
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"the cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    manifest = load_manifest()
    cell, config, traffic, sizes = load_cell(manifest, args.workload,
                                             args.rehearse)

    setup_jax(args.rehearse)
    if args.rehearse:
        device = {"platform": "cpu", "kind": "cpu", "count": 1}
    else:
        device = require_chips(cell["chips"])
    driver = driver_of(traffic)
    # the program may print; the result line alone goes to standard output
    stdout, sys.stdout = sys.stdout, sys.stderr
    result = driver.run(manifest=manifest, cell=cell, config=config,
                        traffic=traffic, sizes=sizes, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        rehearse=args.rehearse, device=device,
                        t_start=_T_START)
    sys.stdout = stdout
    checks = result.pop("check")
    log("numbers " + json.dumps(result.pop("numbers")))
    for name, c in checks.items():
        log(f"check {name}: {c['value']:.6g} limit {c['limit']:.6g} "
            f"{'ok' if c['ok'] else 'NOT OK'}")
    if args.rehearse:
        log(f"rehearsal done: correct={result['correct']} (no result line: "
            f"a CPU run gives no device number)")
        return 0 if result["correct"] else 1
    result["check"] = {k: [c["value"], c["limit"]] for k, c in checks.items()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
