"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind`` (``peaks.json``, with its source).  A kind that is not
in the table is an error, never a default."""
from __future__ import annotations

import json
import pathlib
from typing import Dict

TABLE = pathlib.Path(__file__).with_name("peaks.json")


def lookup(device_kind: str) -> Dict[str, float]:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{TABLE.name}; known: {sorted(table)}")
    return table[device_kind]
