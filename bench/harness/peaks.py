"""The chip's published peaks, from ``bench/peaks.json`` only."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(LookupError):
    """A device kind the peaks table does not list: never a default."""


def lookup(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
