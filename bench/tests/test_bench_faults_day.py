"""The comparison fails what it must, on the daily job's cell: the
control and faults planted under the timed path."""
from __future__ import annotations

import numpy as np

from repro.data import distpipe
from repro.data import store as store_mod


def _failed(res, *numbers):
    assert not res["correct"]
    bad = {k for k, c in res["compared"].items() if c["value"] > c["limit"]}
    assert bad >= set(numbers), res["compared"]


def test_control_dedup_off_fails(rehearse):
    _failed(rehearse("day-batch", "--control"), "sessions_mismatched",
            "bigram_abs_diff")


def test_store_left_unchanged_fails(rehearse, monkeypatch):
    monkeypatch.setattr(store_mod.Store, "append_sessions",
                        lambda self, seqs: None)
    _failed(rehearse("day-batch"), "sessions_mismatched")


def test_half_the_day_left_out_fails(rehearse, monkeypatch):
    orig = distpipe.DistributedPipeline.__call__

    def half(self, user_id, *cols, **kw):
        n = len(user_id)
        return orig(self, user_id, *cols, valid=np.arange(n) < n // 2)
    monkeypatch.setattr(distpipe.DistributedPipeline, "__call__", half)
    _failed(rehearse("day-batch"), "sessions_mismatched", "bigram_abs_diff")


def test_symbol_altered_where_stored_fails(rehearse, monkeypatch):
    orig = store_mod.Store.append_sessions

    def altered(self, seqs):
        seqs.symbols = seqs.symbols.copy()
        seqs.symbols[0, 0] = seqs.symbols[0, 0] + 1
        return orig(self, seqs)
    monkeypatch.setattr(store_mod.Store, "append_sessions", altered)
    _failed(rehearse("day-batch"), "sessions_mismatched")
