"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

_TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(_TABLE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
