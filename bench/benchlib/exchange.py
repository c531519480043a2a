"""The least a cluster shuffle has to move between chips, whatever
implements it.

Chip ``c`` holds block ``c`` of the catalog: rows ``[c*s, (c+1)*s)``, with
``s = ceil(rows / chips)``. A zone that owns rows is reduced on one chip,
over its bucket: its own rows and the border copies of the rows of the
zones on either side (with the zone height equal to the search radius every
row is copied into both neighbours). Every bucket row that is not already
on that chip has to cross, so the least is reached by reducing each zone
on the chip that holds most of its bucket. Each crossing row is 3
coordinates in the wire dtype.

Zones are read from the rows' declinations and the configuration's zone
height, not from the program.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from benchlib import catalog
from benchlib.roofline import COORDS, WIRE_BYTES

PEAKS = Path(__file__).resolve().parents[1] / "ici_peaks.json"
DEG = math.pi / 180.0
# names the device trace gives the exchange's collective (HLO instruction)
ALL_TO_ALL = ("all-to-all", "all_to_all")


def zone_blocks(cfg, xyz, chips: int) -> np.ndarray:
    """-> ``[zones of the tile, chips]``: rows of each zone in each block."""
    h = cfg["zone_height_deg"] * DEG
    k0, k1 = catalog.zone_range(cfg)
    dec = np.arcsin(np.clip(np.asarray(xyz, np.float64)[:, 2], -1.0, 1.0))
    zone = np.floor((dec + math.pi / 2) / h).astype(np.int64) - k0
    if len(zone) and (zone.min() < 0 or zone.max() >= k1 - k0):
        raise ValueError("a row lies outside the configured zones")
    block = np.arange(len(zone)) // max(-(-len(zone) // chips), 1)
    out = np.zeros((k1 - k0, chips), np.int64)
    np.add.at(out, (zone, block), 1)
    return out


def least_rows(counts) -> int:
    """``counts`` from ``zone_blocks`` -> bucket rows that must cross."""
    c = np.pad(np.asarray(counts, np.int64), ((1, 1), (0, 0)))
    bucket = c[:-2] + c[1:-1] + c[2:]            # each zone with its copies
    owns = c[1:-1].sum(axis=1) > 0
    return int((bucket.sum(axis=1) - bucket.max(axis=1))[owns].sum())


def least_bytes(cfg, xyz, chips: int) -> int:
    return (least_rows(zone_blocks(cfg, xyz, chips)) * COORDS
            * WIRE_BYTES[cfg["codec"]])


def ici_bytes_per_s(kind: str) -> float:
    """Published inter-chip bandwidth of one chip of ``kind``."""
    table = json.loads(PEAKS.read_text())
    if kind not in table["chips"]:
        raise KeyError(f"no published ICI peak for device kind {kind!r} in "
                       f"{PEAKS.name}")
    return float(table["chips"][kind]["ici_bytes_per_s"])
