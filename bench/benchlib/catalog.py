"""Sky catalogs drawn from a configuration file and a seed.

A configuration fixes a tile of declination zones (height ``zone_height_deg``,
zone ``k`` covering ``[k*h - 90deg, (k+1)*h - 90deg)``) and how many rows each
zone holds: proportional to the zone's area on the sphere (uniform surface
density), rounded by largest remainder to exactly ``rows``. The seed draws
only positions inside each zone, so every seed gives the same per-zone counts,
the same tier shapes in the shuffle, and the same compiled programs.

Inside a zone a share ``1 - clumped_fraction`` of rows is uniform over the
zone's area and RA range; the rest sits in Gaussian clumps of ``clump_rows``
rows with sigma ``clump_sigma_deg``, reflected at the zone's edges so that
every row stays in its zone. Rows keep ``edge_margin_rad`` from each zone edge:
far above float32 rounding of the program's zone assignment, and wide enough
that no pair of rows two zones apart can score inside the search radius.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

DEG = math.pi / 180.0


def load_config(path) -> dict:
    return json.loads(Path(path).read_text())


def zone_range(cfg) -> tuple[int, int]:
    """Global zone indices ``[k0, k1)`` of the configured tile."""
    h = cfg["zone_height_deg"]
    k0 = round((cfg["dec_min_deg"] + 90.0) / h)
    k1 = round((cfg["dec_max_deg"] + 90.0) / h)
    return k0, k1


def n_zones_total(cfg) -> int:
    """Zones of the whole sphere at this height (the program's partitions)."""
    return int(math.ceil(math.pi / (cfg["zone_height_deg"] * DEG)))


def zone_counts(cfg) -> np.ndarray:
    """Rows per tile zone, ``[k1 - k0]`` int64, summing to ``rows``."""
    h = cfg["zone_height_deg"]
    k0, k1 = zone_range(cfg)
    lo = (np.arange(k0, k1) * h - 90.0) * DEG
    area = np.sin(np.minimum(lo + h * DEG, math.pi / 2)) - np.sin(lo)
    want = cfg["rows"] * area / area.sum()
    counts = np.floor(want).astype(np.int64)
    short = cfg["rows"] - int(counts.sum())
    counts[np.argsort(-(want - counts), kind="stable")[:short]] += 1
    return counts


def partition_counts(cfg) -> np.ndarray:
    """Owned rows per program partition, ``[n_zones_total]``."""
    k0, _ = zone_range(cfg)
    out = np.zeros(n_zones_total(cfg), np.int64)
    c = zone_counts(cfg)
    out[k0:k0 + len(c)] = c
    return out


def _reflect(x, lo, hi):
    span = hi - lo
    y = np.mod(x - lo, 2.0 * span)
    return lo + np.where(y > span, 2.0 * span - y, y)


def make_catalog(cfg, seed: int) -> np.ndarray:
    """-> ``[rows, 3]`` float32 unit vectors, zone by zone."""
    rng = np.random.default_rng(seed)
    h = cfg["zone_height_deg"] * DEG
    m = cfg["edge_margin_rad"]
    ra0, ra1 = cfg["ra_min_deg"] * DEG, cfg["ra_max_deg"] * DEG
    full_ra = cfg["ra_max_deg"] - cfg["ra_min_deg"] >= 360.0
    sigma = cfg["clump_sigma_deg"] * DEG
    k0, _ = zone_range(cfg)
    decs, ras = [], []
    for i, n in enumerate(zone_counts(cfg)):
        lo = (k0 + i) * h - math.pi / 2 + m
        hi = min((k0 + i + 1) * h - math.pi / 2, math.pi / 2) - m
        n_clumped = int(round(cfg["clumped_fraction"] * n))
        n_clumps = -(-n_clumped // cfg["clump_rows"])
        # uniform rows and clump centres: uniform over the zone's area
        u = rng.uniform(np.sin(lo), np.sin(hi), n - n_clumped + n_clumps)
        dec = np.arcsin(u)
        ra = rng.uniform(ra0, ra1, len(u))
        cdec, cra = dec[n - n_clumped:], ra[n - n_clumped:]
        dec, ra = dec[:n - n_clumped], ra[:n - n_clumped]
        member = np.minimum(np.arange(n_clumped) // cfg["clump_rows"],
                            max(n_clumps - 1, 0))
        mdec = _reflect(cdec[member] + rng.normal(0.0, sigma, n_clumped),
                        lo, hi)
        mra = cra[member] + rng.normal(0.0, sigma, n_clumped) / np.maximum(
            np.cos(cdec[member]), 1e-3)
        mra = (np.mod(mra - ra0, 2 * math.pi) + ra0 if full_ra
               else _reflect(mra, ra0, ra1))
        decs += [dec, mdec]
        ras += [ra, mra]
    dec = np.concatenate(decs)
    ra = np.concatenate(ras)
    xyz = np.stack([np.cos(dec) * np.cos(ra), np.cos(dec) * np.sin(ra),
                    np.sin(dec)], axis=1)
    return xyz.astype(np.float32)


def shuffled_copy(xyz: np.ndarray, seed: int) -> np.ndarray:
    """The same rows in another order (same answers, another host array)."""
    return xyz[np.random.default_rng(seed).permutation(len(xyz))]
