"""The comparison fails what it must, on the streaming cells: the control
(the reference with dedup off put in the program's place) and faults
planted under the timed path, each through a whole rehearsal run."""
from __future__ import annotations

import numpy as np
import pytest

from repro.data import store as store_mod
from repro.data import streampipe


def _failed(res, *numbers):
    assert not res["correct"]
    bad = {k for k, c in res["compared"].items() if c["value"] > c["limit"]}
    assert bad >= set(numbers), res["compared"]


def test_sound_run_is_correct(rehearse):
    assert rehearse("stream-backlog")["correct"]


def test_control_dedup_off_fails(rehearse):
    _failed(rehearse("stream-backlog", "--control"), "sessions_mismatched",
            "bigram_abs_diff")


def test_tick_that_returns_its_ring_unchanged_fails(rehearse, monkeypatch):
    orig = streampipe.StreamPipeline._device_tick

    def stale(self, ev, wm_prev, wm_new):
        ring = self._ring
        out = orig(self, ev, wm_prev, wm_new)
        self._ring = ring
        return out
    monkeypatch.setattr(streampipe.StreamPipeline, "_device_tick", stale)
    _failed(rehearse("stream-backlog"), "sessions_mismatched")


def test_half_the_tick_left_out_fails(rehearse, monkeypatch):
    orig = streampipe._StreamBase._pad_events

    def half(self, *a):
        ev = orig(self, *a)
        n = int(ev["valid"].sum())
        ev["valid"] = ev["valid"] & (np.arange(len(ev["valid"])) < n // 2)
        return ev
    monkeypatch.setattr(streampipe._StreamBase, "_pad_events", half)
    _failed(rehearse("stream-steady"), "sessions_mismatched")


@pytest.mark.parametrize("cell", ["stream-backlog", "stream-steady"])
def test_symbol_altered_where_stored_fails(rehearse, monkeypatch, cell):
    orig = store_mod.Store.append_sessions

    def altered(self, seqs):
        seqs.symbols = seqs.symbols.copy()
        seqs.symbols[0, 0] = seqs.symbols[0, 0] + 1
        return orig(self, seqs)
    monkeypatch.setattr(store_mod.Store, "append_sessions", altered)
    _failed(rehearse(cell), "sessions_mismatched")
