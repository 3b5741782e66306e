"""The benchmark's manifest on the CPU: what the driver's check would refuse
before any run. (No file here holds more than seven tests: pytest-xdist hands
out files by their number of tests, largest first, and
``tests/test_device_selection.py::TestServeExitCode``, eight tests, passes
only on a worker that has compiled nothing yet; smaller files are handed out
after it and leave the order before it as it was. PERF.md section 7.)"""

import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [c["name"] for c in MANIFEST["workloads"]]
END_TO_END = [m["name"] for m in MANIFEST["end_to_end"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_paths_hold_the_command_and_nothing_points_outside():
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert not p.startswith("/") and ".." not in p.split("/")
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")


def test_every_cell_names_its_files_and_a_reason():
    assert CELLS
    for c in MANIFEST["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4)
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert c["config"] in [k["name"] for k in MANIFEST["configs"]]
        for sub, stem in (("configs", c["config"]), ("traffic", c["traffic"]),
                          ("cells", c["name"])):
            assert os.path.isfile(os.path.join(ROOT, "benchmark", sub,
                                               f"{stem}.json"))
        traffic = bench.load_json("traffic", f"{c['traffic']}.json")
        for sub, stem in (("drivers", traffic["kind"]),
                          ("assignment", traffic["check"]["assignment"])):
            assert os.path.isfile(os.path.join(ROOT, "benchmark", sub,
                                               f"{stem}.py"))
        config = bench.load_json("configs", f"{c['config']}.json")
        for sub, stem in (("families", config["arch"]["family"]),
                          ("optimizers", config["optimizer"]["kind"])):
            assert os.path.isfile(os.path.join(ROOT, "benchmark", sub,
                                               f"{stem}.py"))
        sizes = bench.load_json("cells", f"{c['name']}.json")
        # a rehearsal is held to the cell's own limits
        assert sizes["limits"] and "limits" not in sizes.get("rehearse", {})


def test_cells_pair_configuration_and_traffic_once_and_few_take_four_chips():
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(c["chips"] == 4 for c in MANIFEST["workloads"])
    assert four <= max(1, len(pairs) // 4)


def test_every_configuration_file_states_its_source_and_cuts():
    assert MANIFEST["configs"]
    for entry in MANIFEST["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["file"].startswith("benchmark/configs/")
        with open(os.path.join(ROOT, entry["file"])) as f:
            body = json.load(f)
        assert body["source"] == entry["source"]
        assert sorted(body["reduced"]) == sorted(entry["reduced"])
        assert body["assumed"]
        for key in entry["reduced"]:      # no width is ever cut
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|filters|hidden|width)$", key)
        assert any(c["config"] == entry["name"]
                   for c in MANIFEST["workloads"])


def test_end_to_end_metrics_are_bounded_and_taken_by_the_harness():
    assert "setup_s" in END_TO_END and len(END_TO_END) >= 2
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for cell in CELLS:
        assert len(bench.metrics_of(MANIFEST, "end_to_end", cell)) >= 2


def test_every_per_layer_metric_has_a_reader_and_moves_one_end_to_end_metric():
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
        assert m["moves"] in END_TO_END
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        assert callable(reader.read) and reader.__doc__
        moved = next(x for x in MANIFEST["end_to_end"]
                     if x["name"] == m["moves"])
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert "workloads" not in moved or cell in moved["workloads"]
    # the whole step's share of the peak is reported by every cell
    mfu = [m for m in MANIFEST["per_layer"] if "mfu" in m["name"].split("_")]
    assert mfu and all("workloads" not in m and m["unit"] == "%" for m in mfu)
    for cell in CELLS:
        assert bench.metrics_of(MANIFEST, "per_layer", cell)
