"""Published per-chip peaks, keyed by ``device_kind`` (``bench/peaks.json``).

A device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parents[1] / "peaks.json"


def lookup(device_kind: str, table: Path = TABLE) -> dict:
    devices = json.loads(table.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"device_kind {device_kind!r} is not in {table.name}; "
                       f"known: {sorted(devices)}")
    return devices[device_kind]
