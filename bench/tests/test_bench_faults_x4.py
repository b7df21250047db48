"""A four-chip cell added by files alone, rehearsed on four CPU devices:
the drafted ``stream-backlog-x4`` (its configuration and its
``collective_exposed_share`` reader are kept in ``data/``) runs correct
through the harness, and the check fails its control and a run whose
repartition leaves out the exchange between chips."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import manifest, run

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "stream-backlog-x4"
CONFIG = "client-events-stream-x4"
NO_EXCHANGE = """
import jax
jax.lax.all_to_all = lambda v, axis, split_axis, concat_axis, **kw: v
"""
CHILD = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
{prelude}
from bench import run
sys.exit(run.main({argv!r}))
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose benchmark has the four-chip cell as new files and
    manifest entries only."""
    root = tmp_path_factory.mktemp("x4")
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(DATA, CONFIG + ".json"), root / "bench/configs")
    shutil.copy(os.path.join(DATA, "collective_exposed_share.py"),
                root / "bench/metrics")
    man = manifest.load(run.ROOT)
    man["configs"].append(dict(
        name=CONFIG, source="arXiv:1208.4171 over four chips",
        file=f"bench/configs/{CONFIG}.json",
        reduced=["events_per_day", "max_open"],
        why="four shards of the streaming sessionizer, one per chip"))
    man["workloads"].append(dict(
        name=CELL, config=CONFIG, traffic="backlog", chips=4,
        why="closed loop of full ticks repartitioned over four chips"))
    man["end_to_end"][0]["workloads"].append(CELL)
    man["per_layer"].append(dict(
        name="collective_exposed_share", unit="share", better="lower",
        source="device_trace", layer="dist/collectives",
        moves="events_per_s", workloads=[CELL]))
    assert manifest.problems(man) == []
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _rehearse(root, *extra, prelude=""):
    argv = ["--workload", CELL, "--seed", str(2 ** 31 + 5), "--seconds",
            "1.5", "--trace", "0", "--rehearse", *extra]
    code = CHILD.format(root=str(root), src=os.path.join(run.ROOT, "src"),
                        prelude=prelude, argv=argv)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _failed(res, *numbers):
    assert not res["correct"]
    bad = {k for k, c in res["compared"].items() if c["value"] > c["limit"]}
    assert bad >= set(numbers), res["compared"]


def test_four_chip_cell_runs_correct(root):
    res = _rehearse(root)
    assert res["correct"], res["compared"]
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"events_per_s", "setup_s"}


def test_control_dedup_off_fails(root):
    _failed(_rehearse(root, "--control"), "sessions_mismatched",
            "bigram_abs_diff")


def test_exchange_between_chips_left_out_fails(root):
    _failed(_rehearse(root, prelude=NO_EXCHANGE), "sessions_mismatched")
