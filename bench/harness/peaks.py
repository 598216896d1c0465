"""The chip's published peaks, keyed by the ``device_kind`` JAX reports."""
from __future__ import annotations

import json

from harness.cell import BENCH


def peaks_for(device_kind: str) -> dict:
    """Peaks of ``device_kind``; ``KeyError`` for a device not in
    ``bench/peaks.json`` (an unknown device is an error, not a default)."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
