"""The plain reference: angular pair counts by brute force over declination
bands.

Semantics (the configuration's ``guarantee``): an unordered pair of distinct
rows ``(p, q)`` is within angle ``theta`` when its squared chord
``|p - q|^2`` is at most ``4 sin^2(theta / 2)``. A histogram over edges
``e_1 < ... < e_k`` answers the pairs per bin ``(0, e_1], (e_1, e_2], ...``.

In float32 the chord's error is relative to the chord itself (the
differences of nearby coordinates are exact), so these counts are the
angular truth to about 1e-7 of the pairs at any radius; the program's score,
a float32 dot product compared with ``cos theta``, is not (``PERF.md``).

Nothing here comes from the program: no zones, no border copies, no tiers,
no kernel. Rows are sorted by ``z`` (declination is monotone in it); each
block of ``BLOCK`` rows is compared with every row whose ``z`` lies within
the widest chord (plus slack) of the block's, and every ordered pair is
counted once.

``dtype=bfloat16`` is the control: the same computation with coordinates,
differences, squares, sums and thresholds in bfloat16, the precision below
the float32 the configuration states.
"""
from __future__ import annotations

import math

import numpy as np

BLOCK = 1024
WINDOW_QUANTUM = 4096
Z_SLACK = 1e-3


def chord2(radii_rad) -> np.ndarray:
    """Squared chord of each angle, float64."""
    return 4.0 * np.sin(np.asarray(radii_rad, np.float64) / 2.0) ** 2


def _windows(zs: np.ndarray, dz: float, block: int):
    n = len(zs)
    starts = np.arange(0, n, block)
    last = np.minimum(starts + block, n) - 1
    lo = np.searchsorted(zs, zs[starts] - dz, side="left")
    hi = np.searchsorted(zs, zs[last] + dz, side="right")
    widest = int((hi - lo).max())
    return starts, lo, -(-widest // WINDOW_QUANTUM) * WINDOW_QUANTUM


def _block_counts(dtype, n: int, n_radii: int, block: int, window: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def counts(x, c2, s, lo):
        a = jax.lax.dynamic_slice_in_dim(x, s, block).astype(dtype)
        b = jax.lax.dynamic_slice_in_dim(x, lo, window).astype(dtype)
        d = [a[:, None, k] - b[None, :, k] for k in range(3)]
        d2 = (d[0] * d[0] + d[1] * d[1]) + d[2] * d[2]
        ia = s + jnp.arange(block, dtype=jnp.int32)[:, None]
        ib = lo + jnp.arange(window, dtype=jnp.int32)[None, :]
        ok = (ia != ib) & (ia < n) & (ib < n)
        c2 = c2.astype(dtype)
        return jnp.stack([jnp.sum((d2 <= c2[k]) & ok, dtype=jnp.int32)
                          for k in range(n_radii)])

    return counts


def pair_counts(xyz: np.ndarray, radii_rad, *, dtype="float32",
                block: int = BLOCK) -> np.ndarray:
    """Unordered pairs of distinct rows within each radius. -> int64 ``[K]``."""
    import jax
    import jax.numpy as jnp
    c2 = chord2(radii_rad)
    xyz = np.asarray(xyz, np.float32)
    n = len(xyz)
    order = np.argsort(xyz[:, 2], kind="stable")
    xs = xyz[order]
    zs = xs[:, 2].astype(np.float64)
    # |z_p - z_q| <= |p - q|: a pair within the widest chord lies in the band
    dz = math.sqrt(float(c2.max())) + Z_SLACK
    starts, lo, window = _windows(zs, dz, block)
    lo = np.minimum(lo, max(n - window, 0))
    pad = np.zeros((max(block, window), 3), np.float32)   # masked out
    x = jnp.asarray(np.concatenate([xs, pad]))
    counts = _block_counts(jnp.dtype(dtype), n, len(c2), block, window)
    cd = jnp.asarray(c2, jnp.float32)
    c = [counts(x, cd, np.int32(s), np.int32(b)) for s, b in zip(starts, lo)]
    ordered = np.asarray(jax.device_get(jnp.stack(c)), np.int64).sum(axis=0)
    # a pair's chord is the same bits from either end, and in float32 both
    # rows lie in each other's band; the bfloat16 control may score in pairs
    # beyond the band, from one end only
    if dtype == "float32" and np.any(ordered % 2):
        raise AssertionError(f"ordered pair counts not even: {ordered}")
    return ordered // 2


def reference_answers(xyz, queries, *, dtype="float32") -> list:
    """The answer of every query in ``queries`` (see ``traffic.queries``):
    each histogram's pairs per bin."""
    radii = sorted({r for q in queries for r in q["radii_rad"]})
    cum = dict(zip(radii, pair_counts(xyz, radii, dtype=dtype).tolist()))
    return [np.diff(np.concatenate(
        [[0], np.array([cum[r] for r in q["radii_rad"]], np.int64)]))
        for q in queries]
