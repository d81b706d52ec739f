"""Device peaks from ``peaks.json``, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name}; known: {sorted(table)}"
        )
    return table[device_kind]
