"""The table of published peaks, keyed by `device_kind`."""

from __future__ import annotations

import json
import os


def lookup(kind: str) -> dict:
    """The published peaks of `kind`; an unknown device is an error, not a
    default."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(
            f"device kind {kind!r} is not in benchmarks/peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[kind]
