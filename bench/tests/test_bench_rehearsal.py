"""One CPU rehearsal per cell through the harness: the control flow and
the shape of the result line, never a time."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import manifest, run

CELLS = [w["name"] for w in manifest.load(run.ROOT)["workloads"]]


def _check_shape(res, cell, trace):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 1
    man = manifest.load(run.ROOT)
    want = (manifest.per_layer(man, cell) if trace
            else manifest.end_to_end(man, cell))
    units = {m["name"]: m["unit"] for m in want}
    assert set(res["metrics"]) <= set(units)
    for name, m in res["metrics"].items():
        assert m["unit"] == units[name]
        assert isinstance(m["value"], (int, float))
    for c in res["compared"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_well_formed(rehearse, cell):
    res = rehearse(cell)
    _check_shape(res, cell, trace=False)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    man = manifest.load(run.ROOT)
    assert set(res["metrics"]) == {m["name"] for m in
                                   manifest.end_to_end(man, cell)}
    assert all(c["value"] == 0 for c in res["compared"].values())


def test_traced_rehearsal_reports_host_metrics(rehearse):
    res = rehearse("stream-steady", "--trace", "1")
    _check_shape(res, "stream-steady", trace=True)
    assert res["correct"]
    assert set(res["metrics"]) == {"tick_ms_p50", "backlog_events_end"}


def test_no_chip_exits_2_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(run.BENCH, "run.py"),
                        "--workload", "stream-backlog", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_it_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "day-batch", "--seed", "1", "--seconds", "1",
                        "--trace", "0", "--rehearse"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_cell_added_by_files_alone_is_picked_up(tmp_path):
    """A new traffic file, metric reader and BENCHMARK.json entries, and no
    edit to any file of the harness: the harness runs the new cell and
    reads the new metric."""
    root = tmp_path
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(run.ROOT, "src"), root / "src")
    man = manifest.load(run.ROOT)
    (root / "bench/traffic/throwaway.json").write_text(
        json.dumps({"loop": "closed"}))
    (root / "bench/metrics/steps_in_window.py").write_text(
        "def read(ctx):\n    return len(ctx['window']['step_s'])\n")
    man["workloads"].append(dict(
        name="stream-throwaway", config="client-events-stream",
        traffic="throwaway", chips=1, why="a cell made of files alone"))
    man["end_to_end"].append(dict(
        name="steps_in_window", unit="steps", better="higher", bound=0.25,
        source="host_clock", workloads=["stream-throwaway"]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "stream-throwaway", "--seed", "5", "--seconds", "1",
                        "--trace", "0", "--rehearse"], cwd=root,
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"]
    assert res["metrics"]["steps_in_window"]["value"] >= 1
    assert set(res["metrics"]) == {"steps_in_window", "setup_s"}
