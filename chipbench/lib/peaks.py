"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports."""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def lookup(device_kind: str, path=PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a kind the table lacks is an error,
    never a default."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
