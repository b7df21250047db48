"""One traced step of each log-tier benchmark cell, recorded on a TPU v5e
by ``scripts/record_log_tier_trace.py``: the device scopes on the ops,
the program's spans on the benchmark's clock inside its step span, and
``bench/trace_reduce.py`` putting the day's idle gaps down to them."""
import gzip
import json
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
CELLS = {"stream-backlog": ("v5e_stream_backlog.planes.json.gz", "tick"),
         "day-batch": ("v5e_day_batch.planes.json.gz", "day")}
SCOPES = ["repartition", "dedup", "sessionize", "sort", "segments", "grid",
          "ring", "rollup/ngram", "rollup/funnel", "dedup/sort",
          "sessionize/sort"]
NAMED = {"repartition", "dedup", "sessionize", "sort", "segments", "grid",
         "ring", "rollup", "ngram", "funnel"}
PROGRAM = ("streampipe.", "distpipe.", "store.")
DAY_SPANS = ("day", "day.pipeline", "day.store", "day.fold")


def _planes(cell):
    with gzip.open(os.path.join(DATA, CELLS[cell][0]), "rt") as f:
        return json.load(f)


def _scope(op_name):
    """The op's path of named scopes: ``jit(tick)/jit(_sessionize)/
    sessionize/sort/while/body/sort`` -> ``sessionize/sort``."""
    return "/".join(p for p in op_name.split("/")[:-1] if p in NAMED)


def _device_ops(planes):
    """``(scope, seconds)`` of every op but the containers, which span
    the ops of their bodies."""
    return [(_scope(name), d * 1e-9)
            for p in planes if p["name"].startswith(tr.DEVICE_PREFIX)
            for ln in p["lines"] if ln["name"] == tr.OPS_LINE
            for (op, _, d), name in zip(ln["events"], ln["op_names"])
            if tr.opcode(op) not in tr.CONTAINERS]


def _program_spans(planes):
    """``(name, start, end)`` of every program span on the host."""
    return [(n, s, s + d) for p in planes
            if not p["name"].startswith(tr.DEVICE_PREFIX)
            for ln in p["lines"] for n, s, d in ln["events"]
            if n.startswith(PROGRAM)]


@pytest.mark.parametrize("cell", CELLS)
def test_every_scope_is_on_the_device_ops(cell):
    paths = {s for s, _ in _device_ops(_planes(cell))}
    for scope in SCOPES:
        if scope == "ring" and cell == "day-batch":
            continue                         # the day job has no ring
        assert any(f"/{scope}/" in f"/{p}/" for p in paths), scope


@pytest.mark.parametrize("cell", CELLS)
def test_named_scopes_hold_nine_tenths_of_device_time(cell):
    ops = _device_ops(_planes(cell))
    total = sum(s for _, s in ops)
    named = sum(s for p, s in ops if p)
    assert total > 0 and named >= 0.9 * total, (named, total)


@pytest.mark.parametrize("cell", CELLS)
def test_program_spans_lie_inside_the_step_and_cover_it(cell):
    planes = _planes(cell)
    (step,) = tr.host_spans(planes, [CELLS[cell][1]])
    spans = _program_spans(planes)
    assert spans
    assert all(step[1] <= a <= b <= step[2] for _, a, b in spans)
    covered = sum(b - a for a, b in tr.union([(a, b) for _, a, b in spans]))
    assert covered >= 0.95 * (step[2] - step[1])


def test_day_idle_gaps_fall_under_the_store_and_gather():
    planes = _planes("day-batch")
    (window,) = tr.host_spans(planes, ["window"])
    names = {n for n, _, _ in _program_spans(planes)}
    by_program = tr.breakdown(tr.reduce(planes, window[1:], names))
    by_bench = dict(tr.breakdown(tr.reduce(planes, window[1:],
                                           DAY_SPANS))["idle_gaps"])
    idle = dict(by_program["idle_gaps"])
    store = sum(s for n, s in idle.items()
                if n.startswith("store.") or n == "distpipe.gather")
    assert idle.get(tr.NO_SPAN, 0.0) < 0.05 * sum(idle.values())
    assert store == pytest.approx(by_bench["day.store"], rel=0.1)
