"""The readers of the program's spans on a hand-made span buffer: warm
steps, the window's steps and a flush tick after the window."""
from __future__ import annotations

import os

import pytest

from bench import program_spans, run
from repro.core import spans

MS = 1_000_000


def _tick(t, flush=0, pull=2, store=3, events=100):
    """One streaming tick's records starting at ``t`` ms, in the order
    they close."""
    at = t * MS
    return [
        spans.Span("streampipe.pull", "streampipe.tick", at + MS,
                   at + (1 + pull) * MS, dict(bytes=64, sessions=5)),
        spans.Span("store.encode_payloads", "store.append_sessions",
                   at + 4 * MS, at + 5 * MS, {}),
        spans.Span("store.append_sessions", "streampipe.tick", at + 4 * MS,
                   at + (4 + store) * MS, dict(sessions=5, events=50)),
        spans.Span("streampipe.tick", None, at, at + 10 * MS,
                   dict(events=events, flush=flush)),
    ]


def _day(t, store=7, pull=2, gather=1):
    at = t * MS
    return [
        spans.Span("distpipe.pull", "distpipe.call", at + MS,
                   at + (1 + pull) * MS, dict(bytes=128)),
        spans.Span("distpipe.call", None, at, at + 5 * MS, dict(events=200)),
        spans.Span("distpipe.gather", None, at + 5 * MS,
                   at + (5 + gather) * MS, dict(sessions=9)),
        spans.Span("store.append_sessions", None, at + 6 * MS,
                   at + (6 + store) * MS, dict(sessions=9, events=190)),
    ]


@pytest.fixture
def buffer(monkeypatch):
    def use(records):
        monkeypatch.setattr(spans, "recent", lambda: list(records))
    return use


def _ctx(k, events, trace=True):
    return dict(window=dict(step_s=[1.0] * k, events=events),
                trace={} if trace else None)


def _reader(name):
    return run._load_module(os.path.join(run.BENCH, "metrics",
                                         name + ".py")).read


def test_window_is_the_last_k_steps_without_the_flush(buffer):
    # two warm ticks, three window ticks, then the flush
    recs = [r for t in (0, 20, 40, 60, 80) for r in _tick(t, pull=t // 20)]
    recs += _tick(100, flush=1, pull=50, store=50, events=0)
    buffer(recs)
    got = program_spans.window_spans(_ctx(3, 300))
    ticks = sorted(r.start_ns for r in got if r.name == "streampipe.tick")
    assert ticks == [40 * MS, 60 * MS, 80 * MS]
    assert len(got) == 3 * 4
    # pulls of 2, 3 and 4 ms; appends of 3 ms each, over 300 events
    assert _reader("pull_ns_per_event")(_ctx(3, 300)) == \
        pytest.approx(9 * MS / 300)
    assert _reader("store_ns_per_event")(_ctx(3, 300)) == \
        pytest.approx(9 * MS / 300)


def test_day_window_takes_the_store_after_its_last_call(buffer):
    buffer(_day(0, store=100) + _day(20) + _day(40, store=8, gather=2))
    got = program_spans.window_spans(_ctx(2, 400))
    assert {r.start_ns for r in got if r.name == "distpipe.call"} == \
        {20 * MS, 40 * MS}
    assert _reader("store_ns_per_event")(_ctx(2, 400)) == \
        pytest.approx(15 * MS / 400)
    # pulls 2 + 2 ms and gathers 1 + 2 ms
    assert _reader("pull_ns_per_event")(_ctx(2, 400)) == \
        pytest.approx(7 * MS / 400)


@pytest.mark.parametrize("name", ["store_ns_per_event", "pull_ns_per_event"])
def test_no_trace_or_too_few_steps_reads_nothing(buffer, name):
    buffer(_tick(0) + _tick(20))
    assert _reader(name)(_ctx(2, 200, trace=False)) is None
    assert _reader(name)(_ctx(3, 300)) is None
    assert _reader(name)(_ctx(2, 200)) is not None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert program_spans.window_spans(_ctx(1, 100)) is None
    assert _reader("store_ns_per_event")(_ctx(1, 100)) is None
