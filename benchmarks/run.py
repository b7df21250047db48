"""Benchmark driver — one section per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows:
  compression     — §4.2 "about fifty times smaller" claim
  query_speed     — §4.2/§5 sequences-vs-raw query latency
  rollups         — §3.2 Oink five-schema aggregations
  ngram_table     — §5.4 temporal-signal table + collocations
  pipeline_tput   — substrate throughput (vectorized vs Pig-style oracle)
  serve_tput      — serving tokens/sec + p50/p99 request latency
                    (fixed single-batch vs continuous batching)

Roofline derivation lives in benchmarks/roofline.py (reads the dry-run
artifacts; see EXPERIMENTS.md).
"""
from __future__ import annotations

import argparse
import json
import os


def select_sections(picked, sections):
    """Resolve ``--only`` values against the section registry.

    Accepts space- and/or comma-separated names (``--only a,b c``),
    preserves first-mention order, drops repeats, and raises ``ValueError``
    naming any unknown section — an unknown ``--only`` must fail loudly,
    never silently produce no rows.
    """
    names = [n for arg in picked for n in arg.split(",") if n]
    unknown = [n for n in names if n not in sections]
    if unknown:
        raise ValueError(
            f"unknown benchmark section(s) {', '.join(sorted(set(unknown)))}"
            f"; available: {', '.join(sorted(sections))}")
    seen: dict[str, None] = {}
    for n in names:
        seen.setdefault(n)
    return list(seen)


def main() -> None:
    from . import compression, query_speed, rollups, ngram_table, \
        pipeline_tput, serve_tput
    sections = dict(compression=compression, query_speed=query_speed,
                    rollups=rollups, ngram_table=ngram_table,
                    pipeline_tput=pipeline_tput, serve_tput=serve_tput)
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="+", metavar="SECTION",
                    help="run only these sections, space- or comma-"
                         "separated (default: all); unknown names error")
    ap.add_argument("--json", action="store_true",
                    help="also write each section's machine-readable "
                         "payload (BENCH_<section>.json next to the CSV) "
                         "so the perf trajectory is recorded")
    args = ap.parse_args()
    try:
        picked = (select_sections(args.only, sections) if args.only
                  else list(sections))
    except ValueError as e:
        ap.error(str(e))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name in picked:
        mod = sections[name]
        for line in mod.run():
            print(line, flush=True)
        payload = getattr(mod, "LAST_JSON", None)
        if args.json and payload is not None:
            path = getattr(mod, "JSON_PATH", f"BENCH_{name}.json")
            # Sections share files (compression/query_speed/pipeline_tput
            # all land in BENCH_pipeline.json): merge top-level keys so a
            # partial --only run never clobbers the other sections.
            merged = {}
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        merged = json.load(f)
                except (json.JSONDecodeError, OSError):
                    merged = {}
            merged.update(payload)
            with open(path, "w") as f:
                json.dump(merged, f, indent=2, sort_keys=True)
                f.write("\n")


if __name__ == "__main__":
    main()
