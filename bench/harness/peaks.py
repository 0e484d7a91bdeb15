"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Roofline shares and utilizations divide by these.  A device that is not
in the table is an error, never a default."""
from __future__ import annotations

import json
import os

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]
