"""The door is open to the manifest too: a configuration, its cells and a
per-layer metric that arrive as files and APPENDED manifest entries
(``bench_driving.open_door``, with the fixture family as a file under
``families/``) leave the tests that read the manifest green, unedited. They
run in a process of their own from a copy that holds the benchmark's
``paths`` and that ``BENCHMARK.json``, with the copy first on ``sys.path``.
An accepted entry removed, renamed, reordered, altered or widened in that copy
fails the test that pins the accepted entries. No number from here is a
device number. (Seven tests a file at most: ``test_manifest.py`` says why.)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_driving as bd  # noqa: E402

PIN = ("test_kanana2_cell.py::"
       "test_the_cells_entries_come_after_the_ones_that_were_there")
MANIFEST_TESTS = [
    "test_manifest.py",
    "test_device_scopes.py::"
    "test_the_resnet_cell_reports_none_of_the_shares_and_the_decoder_all",
    PIN]
LAUNCH = """
import sys
sys.path[:0] = [{copy!r}, {root!r}]
from benchmark import run
assert run.ROOT == {copy!r}, run.ROOT
import pytest
sys.exit(pytest.main(sys.argv[1:]))
"""


def checkout(tmp: str, edit=None) -> dict:
    """``open_door``'s copy, with the fixture family as a file and the tests
    of ``tests/benchmark/`` beside it, as a checkout of the benchmark's
    ``paths`` holds them; ``edit`` changes the copy's manifest in place."""
    manifest = bd.open_door(tmp)
    shutil.copy(os.path.join(bd.HERE, f"{bd.FAMILY}.py"),
                os.path.join(tmp, "benchmark", "families"))
    shutil.copytree(bd.HERE, os.path.join(tmp, "tests", "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    if edit:
        edit(manifest)
        with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
            json.dump(manifest, f, indent=1)
    return manifest


def run_tests(copy: str, tests) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", LAUNCH.format(copy=copy, root=bd.ROOT),
         "-q", "-rA", "-p", "no:cacheprovider", "-p", "no:randomly",
         "--rootdir", copy,
         *(os.path.join("tests", "benchmark", t) for t in tests)],
        cwd=copy, env=dict(
            {k: v for k, v in os.environ.items()
             if not k.startswith("PYTEST_")},
            JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1"),
        capture_output=True, text=True, timeout=600)


def test_appended_entries_leave_the_manifest_tests_green(tmp_path):
    copy = str(tmp_path)
    manifest = checkout(copy)
    accepted = bd.MANIFEST
    for key in ("configs", "workloads", "per_layer"):
        n = len(accepted[key])
        assert manifest[key][:n] == accepted[key] and len(manifest[key]) > n
    assert manifest["per_layer"][-1]["workloads"] == [bd.door_cell("sgd")]
    p = run_tests(copy, MANIFEST_TESTS)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    passed = [l for l in p.stdout.splitlines() if l.startswith("PASSED ")]
    ran = {l.split("::")[0][len("PASSED tests/benchmark/"):] for l in passed}
    assert ran == {"test_manifest.py", "test_device_scopes.py",
                   "test_kanana2_cell.py"}
    assert "skipped" not in p.stdout.splitlines()[-1], p.stdout[-2000:]


def _swap(items, i, j):
    items[i], items[j] = items[j], items[i]


def _entry(manifest, key, name):
    return next(e for e in manifest[key] if e["name"] == name)


EDITS = {
    # the door's configuration put before the accepted ones, not after
    "inserted": lambda m: m["configs"].insert(0, m["configs"].pop(-1)),
    "reordered": lambda m: _swap(m["workloads"], 0, 1),
    "renamed": lambda m: _entry(m, "per_layer", "drift_decision_ms").update(
        name="drift_decision_s"),
    "removed": lambda m: m["per_layer"].remove(
        _entry(m, "per_layer", "eval_program_device_ms")),
    "altered": lambda m: _entry(m, "workloads", "kanana2.ifca_perround")
    .update(chips=4),
    # an accepted metric's cells widened to a new one
    "widened": lambda m: _entry(m, "per_layer", "pairs_run_per_round")
    ["workloads"].append(bd.door_cell("sgd")),
}


@pytest.mark.parametrize("edit", sorted(EDITS))
def test_an_accepted_entry_edited_in_the_copy_fails_the_pin(tmp_path, edit):
    copy = str(tmp_path)
    checkout(copy, EDITS[edit])
    p = run_tests(copy, [PIN])
    assert p.returncode == 1, p.stdout[-4000:] + p.stderr[-2000:]
    failed = [l for l in p.stdout.splitlines() if l.startswith("FAILED ")]
    assert len(failed) == 1 and PIN in failed[0], p.stdout[-4000:]
    assert "AssertionError" in p.stdout
