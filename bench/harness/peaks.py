"""The chip peaks every roofline and utilization is divided by.

One table, ``bench/peaks.json``, keyed by JAX's ``device_kind``, with its
source. A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(LookupError):
    pass


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """``{"flops_per_s", "hbm_bytes_per_s", "hbm_bytes"}`` of one chip."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}")
    return table[device_kind]
