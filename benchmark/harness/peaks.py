"""The table of peaks, by ``device_kind``. A device that is not in the table
is an error, never a default."""

from __future__ import annotations

import os

from benchmark.harness.manifest import load_json

_TABLE = load_json(os.path.join(os.path.dirname(__file__), "peaks.json"))


def peaks_for(device_kind: str) -> dict:
    row = _TABLE.get(device_kind)
    if not isinstance(row, dict):
        kinds = sorted(k for k in _TABLE if not k.startswith("_"))
        raise KeyError(f"device_kind {device_kind!r} is not in the peaks "
                       f"table {kinds}; add it to benchmark/harness/peaks.json "
                       f"with its source")
    return row
