"""CPU rehearsals through the harness, never a time: one per cell for the
control flow and the shape of the result line; the log cells' readings
pinned as the harness gave them before it took systems of every kind;
the served cell's warm-up and counts, and its check failing its control
and faults planted under the timed path (one chip: there is no exchange
between chips to leave out)."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from contextlib import nullcontext

import jax.numpy as jnp
import pytest

from bench import loops, manifest, model_flops, run
from bench.systems import serve
from repro.models import transformer
from repro.serve import cache, scheduler

CELLS = [w["name"] for w in manifest.load(run.ROOT)["workloads"]]
SERVE = "serve-sessions-backlog"
PIN_SEED = 2 ** 31 + 101
PIN_NAMES = ("sessions_mismatched", "bigram_abs_diff", "funnel_abs_diff",
             "events_dropped", "sessions_truncated", "window_compiles")
# cell, control, attempted, the numbers compared (all limits 0)
PINS = [
    ("stream-backlog", False, 1024, (0, 0, 0, 0, 0, 0)),
    ("stream-backlog", True, 1024, (50, 35, 0, 0, 0, 0)),
    ("day-batch", False, 8192, (0, 0, 0, 0, 0, 0)),
    ("day-batch", True, 8192, (304, 164, 0, 0, 0, 0)),
    ("stream-steady", False, 1, (0, 0, 0, 0, 0, 0)),
    ("stream-steady", True, 1, (32, 23, 0, 0, 0, 0)),
]


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
    # an exact number reads 0; a tolerance is read against its limit
    assert all(c["value"] == 0 if c["limit"] == 0 else
               0 <= c["value"] <= c["limit"]
               for c in res["compared"].values())


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


@pytest.mark.parametrize("cell,control,attempted,values", PINS)
def test_log_cell_reads_as_before(rehearse, cell, control, attempted,
                                  values):
    """A one-step rehearsal (``--seconds 0``) at a fixed seed gives the
    ``attempted``, ``failed`` and ``compared`` it gave before."""
    res = rehearse(cell, *(["--control"] if control else []), seconds=0,
                   seed=PIN_SEED)
    assert res["attempted"] == attempted
    assert res["failed"] == 0
    assert res["compared"] == {n: dict(value=v, limit=0)
                               for n, v in zip(PIN_NAMES, values)}


def _serve_cfg(**kw):
    cfg = run._read_json(os.path.join(
        run.ROOT, manifest.config_entry(manifest.load(run.ROOT),
                                        "behavior-lm-100m-sessions")["file"]))
    cfg.update(cfg["rehearse"], **kw)
    traffic = run._read_json(os.path.join(run.BENCH, "traffic",
                                          "sessions-backlog.json"))
    traffic.update(traffic["rehearse"])
    return cfg, traffic


def _one_device():
    import jax
    return jax.sharding.Mesh(jax.devices()[:1], ("data",))


def test_serve_warm_up_compiles_every_prefill_the_window_can_reach():
    cfg, traffic = _serve_cfg()
    system = serve.System.from_seed(cfg, traffic, 2 ** 31 + 23,
                                    _one_device())
    system.warm()
    shapes = serve.warm_shapes(tuple(cfg["buckets"]), cfg["block_size"],
                               traffic["prompt_cap"])
    counts = system.sched.trace_counts
    assert counts["prefill"] == len(shapes)
    assert counts["insert"] == len({k[0] for k in shapes})
    assert counts["decode"] == 1
    # the traffic's own prompts add no program
    for _ in range(30):
        system.step(system.unit)
    assert dict(system.sched.trace_counts) == dict(counts)


def test_serve_window_counts_and_check_in_float32():
    """In float32 the served tokens are the reference's greedy choices;
    the step counts are the tokens the requests gained."""
    cfg, traffic = _serve_cfg(d_model=64, num_heads=4, num_kv_heads=4,
                               head_dim=16, d_ff=128, dtype="float32")
    system = serve.System.from_seed(cfg, traffic, 2 ** 31 + 29,
                                    _one_device())
    system.warm()
    window = loops.closed(system, 1.0, traffic, lambda n: nullcontext())
    system.finish()
    ctx = system.ctx()
    assert ctx["window_tokens"] == window["events"] > 0
    assert ctx["window_flops"] > model_flops.per_token(system.m) * \
        window["events"]
    got = system.compared(0, False)
    assert got["token_logit_shortfall_max"]["value"] < 1e-3
    assert got["requests_unfinished"]["value"] == 0
    attempted, failed = system.tally(window)
    assert attempted > 0 and failed == 0
    assert system.compared(0, True)["token_logit_shortfall_max"][
        "value"] > 1e-2


def test_serve_traced_rehearsal_reads_the_prefix_counter(rehearse):
    """On the CPU the device metrics find no trace; the program's prefix
    counter is read all the same."""
    res = rehearse(SERVE, "--trace", "1")
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"prefix_skipped_share"}
    assert 0 <= res["metrics"]["prefix_skipped_share"]["value"] <= 100


def _failed(res, *numbers):
    assert not res["correct"]
    bad = {k for k, c in res["compared"].items() if c["value"] > c["limit"]}
    assert bad >= set(numbers), res["compared"]


def test_serve_control_in_float8_fails(rehearse):
    # every request runs its whole budget, so a window finishes few of
    # them; 5 s finish some 40 even on a loaded CPU, and float8's gap
    # shows over their several hundred tokens
    _failed(rehearse(SERVE, "--control", seconds=5),
            "token_logit_shortfall_max")


def test_serve_decode_that_leaves_the_cache_unchanged_fails(rehearse,
                                                            monkeypatch):
    monkeypatch.setattr(cache.PagedKVState, "commit",
                        lambda self, new_state: None)
    _failed(rehearse(SERVE), "token_logit_shortfall_max")


def test_serve_half_the_slots_left_out_fails(rehearse, monkeypatch):
    orig = transformer.decode_step

    def half(params, token, cache_, index, *a, **kw):
        logits, new = orig(params, token, cache_, index, *a, **kw)
        h = logits.shape[0] // 2       # the second half gets the first's
        return jnp.concatenate([logits[:h], logits[:len(logits) - h]]), new
    monkeypatch.setattr(transformer, "decode_step", half)
    _failed(rehearse(SERVE), "token_logit_shortfall_max")


def test_serve_token_altered_where_it_is_produced_fails(rehearse,
                                                        monkeypatch):
    orig = scheduler.ContinuousScheduler.step

    def altered(self):
        out = orig(self)
        for rid in out:           # every third request's name token moves
            if rid % 3 == 0 and self.outputs[rid][-1] > 3:
                self.outputs[rid][-1] = 4 + (self.outputs[rid][-1] - 3) % 112
        return out
    monkeypatch.setattr(scheduler.ContinuousScheduler, "step", altered)
    _failed(rehearse(SERVE), "token_logit_shortfall_max")
