"""The table of peaks, keyed by the exact ``device_kind`` jax reports."""
from __future__ import annotations

import json
import os


class UnknownDevice(KeyError):
    """A device kind with no row in peaks.json: an error, never a default."""


def load(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks on record for device kind {device_kind!r}; "
            f"known: {sorted(table)}")
    return table[device_kind]
