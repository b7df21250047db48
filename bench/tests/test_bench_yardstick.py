"""The fixed parts of the yardstick: least bytes and model operations
against hand counts, the table of peaks, and the names and units of
BENCHMARK.json."""
from __future__ import annotations

import json

import pytest

from bench import least_bytes, manifest, model_flops, peaks, run


def test_stream_tick_least_bytes_by_hand():
    cfg = dict(max_open=4096, max_len=256, tick_capacity=65536)
    # ring: 4096 slots x (256 x (4 + 8 + 8) + 8 + 8 + 4 + 1) = 21,057,536 B,
    # read and written once a tick; 37 B in and 4 B out per event
    assert least_bytes.stream_tick(cfg) == 37 + 4 + 2 * 21_057_536 / 65536


def test_day_job_least_bytes_by_hand():
    cfg = dict(alphabet_size=112, ngram_n=2, funnel=[1, 2, 3, 4],
               events_per_day=1 << 20)
    # 112^2 bigram cells and 4 funnel cells of 8 B over 2^20 events
    assert least_bytes.day_job(cfg) == 41 + 8 * (12544 + 4) / (1 << 20)


def test_peaks_of_v5e_and_unknown_device_raises(tmp_path):
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")
    with open(peaks.PATH) as f:
        assert json.load(f)["source"]


def test_manifest_names_units_and_texts_are_allowed():
    man = manifest.load(run.ROOT)
    assert manifest.problems(man) == []
    bad = json.loads(json.dumps(man))
    bad["per_layer"][0]["unit"] = "events per second"
    bad["workloads"][0]["name"] = "stream backlog"
    bad["end_to_end"][0]["name"] = "μs_per_event"
    assert len(manifest.problems(bad)) == 3


def test_every_cell_has_setup_another_end_to_end_and_a_per_layer_metric():
    man = manifest.load(run.ROOT)
    for w in man["workloads"]:
        e2e = {m["name"] for m in manifest.end_to_end(man, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = manifest.per_layer(man, w["name"])
        assert layers and all(m["moves"] in e2e for m in layers)


def test_manifest_lookups_name_what_is_missing_and_take_any_metric():
    man = manifest.load(run.ROOT)
    with pytest.raises(KeyError, match="no configuration"):
        manifest.config_entry(man, "no-such-config")
    with pytest.raises(KeyError, match="no workload"):
        manifest.cell(man, "no-such-cell")
    # a per-layer metric without ``workloads`` goes to every cell that
    # reports the end-to-end metric it moves
    man = json.loads(json.dumps(man))
    man["per_layer"].append(dict(
        name="everywhere", unit="ms", better="lower", source="host_clock",
        layer="ingest", moves="setup_s"))
    for w in man["workloads"]:
        assert "everywhere" in {m["name"] for m in
                                manifest.per_layer(man, w["name"])}


def test_model_flops_by_hand():
    m = dict(num_layers=2, d_model=8, head_dim=2, num_heads=4,
             num_kv_heads=2, d_ff=16, vocab_size=10)
    # a layer: projections 2*8*2*(2*4 + 2*2) = 384, MLP 6*8*16 = 768;
    # head 2*8*10 = 160; attention 4*4*2 = 32 per layer and position
    assert model_flops.per_token(m) == 2 * (384 + 768) + 160
    assert model_flops.per_context(m) == 2 * 32
    # positions 3, 4, 5 of one sequence and 0 of another: contexts
    # 4 + 5 + 6 and 1
    assert model_flops.positions(m, [3, 0], [6, 1]) == \
        4 * 2464 + 64 * (4 + 5 + 6 + 1)
    assert model_flops.positions(m, [5], [5]) == 0
