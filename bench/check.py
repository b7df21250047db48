"""The verdict on the numbers a system compares (``System.compared``):
``{name: {"value": v, "limit": l}}``, each passing at or under its
limit."""
from __future__ import annotations

import sys


def passed(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def report(compared: dict, stream=sys.stderr) -> None:
    """One line per number: its name, its value and its limit."""
    for k, c in compared.items():
        print(f"{k} {c['value']} limit {c['limit']}", file=stream)
