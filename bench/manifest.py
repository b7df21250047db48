"""``BENCHMARK.json``: loading it, the cells' metrics, and the checks on
its names and units."""
from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
TEXT_KEYS = ("why", "layer", "source")


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json; it has "
                   f"{[c['name'] for c in manifest['configs']]}")


def end_to_end(manifest: dict, cell_name: str) -> list[dict]:
    return [m for m in manifest["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(manifest: dict, cell_name: str) -> list[dict]:
    moved = {m["name"] for m in end_to_end(manifest, cell_name)}
    return [m for m in manifest["per_layer"]
            if "workloads" in m and cell_name in m["workloads"]] + [
        m for m in manifest["per_layer"]
        if "workloads" not in m and m["moves"] in moved]


def problems(manifest: dict) -> list[str]:
    """Names, units and one-line texts outside the allowed characters."""
    out = []
    entries = (manifest["configs"] + manifest["workloads"]
               + manifest["end_to_end"] + manifest["per_layer"])
    for e in entries:
        names = [e["name"]] + [e[k] for k in ("config", "traffic")
                               if k in e] + list(e.get("reduced", []))
        out += [f"name {n!r}" for n in names if not NAME.fullmatch(n)]
        if "unit" in e and not UNIT.fullmatch(e["unit"]):
            out.append(f"unit {e['unit']!r} of {e['name']}")
        for k in TEXT_KEYS:
            v = e.get(k)
            if v is not None and not (1 <= len(v) <= 200 and "\n" not in v
                                      and "\t" not in v):
                out.append(f"{k} of {e['name']}")
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in manifest[kind]]
        if len(set(names)) != len(names):
            out.append(f"duplicate names in {kind}")
    metrics = [m["name"] for m in manifest["end_to_end"]
               + manifest["per_layer"]]
    if len(set(metrics)) != len(metrics):
        out.append("duplicate metric names")
    return out
