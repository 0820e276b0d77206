"""Published peaks of the chips the benchmark knows, keyed by the
``device_kind`` JAX reports.  One table (``peaks.json``), each row with its
source; a device that is not in it is an error, never a default."""

from __future__ import annotations

import json
import os

__all__ = ["peaks_for", "UnknownDevice"]

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r} in "
            f"{_TABLE} (known: {sorted(table)}); add a row with its source")
    return table[device_kind]
