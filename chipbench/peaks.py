"""The table of device peaks (``peaks.json``), keyed by JAX's
``device_kind``.  A device that is not in the table is an error."""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peaks(device_kind: str, path: Path = _TABLE) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(table)}")
    return table[device_kind]
