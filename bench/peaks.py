"""Published peaks of the chips the benchmark runs on, by ``device_kind``
(``peaks.json``, with its source)."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> dict[str, float]:
    """The peaks of one chip of ``device_kind``; a device the table does
    not hold is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}: add them with their source")
    return {k: float(v) for k, v in table[device_kind].items()}
