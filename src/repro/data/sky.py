"""Synthetic astronomy catalogs (the paper's input data, generated).

Points uniform on the unit sphere; the Zones algorithm [Gray et al., MSR-TR-2006-52]
partitions by declination zones of height h (radians). Distances are angular:
theta(a, b) = arccos(a . b); neighbors: theta <= radius.
"""
from __future__ import annotations

import numpy as np

ARCSEC = np.pi / (180.0 * 3600.0)


def make_catalog(n: int, seed: int = 0) -> np.ndarray:
    """-> unit vectors [n, 3] float32, uniform on the sphere."""
    rng = np.random.default_rng(seed)
    z = rng.uniform(-1.0, 1.0, n)
    phi = rng.uniform(0.0, 2 * np.pi, n)
    r = np.sqrt(np.maximum(1.0 - z * z, 0.0))
    return np.stack([r * np.cos(phi), r * np.sin(phi), z],
                    axis=1).astype(np.float32)


def dec_of(xyz: np.ndarray) -> np.ndarray:
    return np.arcsin(np.clip(xyz[:, 2], -1.0, 1.0))


def zone_of(xyz: np.ndarray, zone_height: float) -> np.ndarray:
    """Zone index per point (declination bands of height `zone_height` rad)."""
    return np.floor((dec_of(xyz) + np.pi / 2) / zone_height).astype(np.int32)


def n_zones(zone_height: float) -> int:
    return int(np.ceil(np.pi / zone_height))


def brute_force_pairs(xyz: np.ndarray, radius_rad: float) -> int:
    """O(n^2) oracle: number of unordered pairs within radius.

    A pair is within radius when its float32 dot ``(x0*y0 + x1*y1) + x2*y2``
    (each product and sum rounded, no fused multiply-add) is >= the float32
    ``cos(radius)``: the scores of the Pallas kernel. A BLAS matmul fuses
    and reorders the sum, and XLA:CPU contracts products and sums into
    fused multiply-adds; either flips pairs sitting within an ulp of the
    threshold once catalogs reach ~1e4 rows. Blocks of 2048 rows keep
    memory at O(2048 * n)."""
    block = 2048
    x = np.asarray(xyz, np.float32)
    cmin = np.float32(np.cos(radius_rad))
    hits = 0
    for lo in range(0, len(x), block):
        a = x[lo:lo + block]
        dots = a[:, None, 0] * x[None, :, 0]
        for k in range(1, x.shape[1]):
            dots += a[:, None, k] * x[None, :, k]
        ok = dots >= cmin
        ok[np.arange(len(a)), lo + np.arange(len(a))] = False
        hits += int(np.count_nonzero(ok))
    return hits // 2


def brute_force_hist(xyz: np.ndarray, edges_rad: np.ndarray) -> np.ndarray:
    """Pair-distance histogram oracle (the Neighbor Statistics application)."""
    dots = np.clip(xyz @ xyz.T, -1.0, 1.0)
    iu = np.triu_indices(len(xyz), k=1)
    theta = np.arccos(dots[iu])
    h, _ = np.histogram(theta, bins=edges_rad)
    return h
