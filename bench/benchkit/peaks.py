"""Published peaks of each chip, keyed by JAX's ``device_kind``
(``bench/peaks.json``).  A kind that is not in the table is an error."""
from __future__ import annotations

import json
import pathlib

_TABLE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str, table: pathlib.Path = _TABLE) -> dict:
    with open(table, encoding="utf-8") as fh:
        known = json.load(fh)
    if device_kind not in known:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(known)}")
    return known[device_kind]
