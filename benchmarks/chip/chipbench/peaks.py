"""The chip's published peaks, keyed by ``device_kind``. An unknown kind is
an error, never a default."""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def load_peaks() -> dict:
    with open(PEAKS_FILE) as fh:
        return json.load(fh)


def peaks_for(device_kind: str) -> dict:
    """``{"flops_per_s", "hbm_bytes_per_s", "hbm_bytes"}`` of one chip."""
    kinds = load_peaks()["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"device kind {device_kind!r} has no row in "
                       f"{PEAKS_FILE}; known: {sorted(kinds)}")
    return kinds[device_kind]
