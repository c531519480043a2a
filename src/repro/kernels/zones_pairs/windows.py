"""Which tile pairs of a tier can hold a hit: the box test both engines skip by.

A tile is ``t`` consecutive rows of a partition. Its box is the per-coordinate
``[min, max]`` of its real rows (capacity padding masked out; a tile with no
real row has an empty box). If the boxes of an owned tile and a bucket tile
lie further apart than the widest edge's chord, no pair of their rows can
score ``>= cos_min``, and the tile pair is skipped.

The test is exact: ``box_keep`` never drops a tile pair that holds a pair the
kernels would count. For float32 rows with ``|x|^2 <= mn2`` and a score
``s`` rounded as ``ref._dots2d`` rounds it, a counted pair (``s >= c``)
satisfies ``|u - v|^2 <= 2 mn2 - 2 c`` up to about ``(6 d + 12) 2^-24 (mn2 +
|c|)`` of rounding in the score, the norms, the box gap and the threshold
itself; ``_slack`` allows four times that. Its effect on the windows is nil
at survey radii: 1.4e-5 against a squared chord of 5.3e-3 at 250 arcmin.

Tile boxes are tight only when rows near each other on the sky sit near each
other in the partition: the shuffle orders each zone by RA
(``ZonePartitioner.sort_key_device``), on one chip in
``_scatter_tiers_jit`` and on a mesh on the chip that owns the zone, after
the exchange (``_exchange_jit``). Where a partitioner has no sort key, rows
keep arrival order, every box spans the zone, every tile pair passes, and
the skip degrades to scoring all of them.

The Pallas kernel (``kernel.py``) needs its kept bucket tiles as runs it can
loop over: ``two_intervals`` covers each owned tile's kept tiles with two
intervals, the second for a footprint that crosses RA +-180 deg, where the
kept tiles sit at both ends of the RA order. The blocked engine
(``blocked.py``) gathers the kept tile pairs themselves.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_ROUNDING = 2.0 ** -24          # float32 unit roundoff


def tile_boxes(x, n_rows, t: int):
    """[P, C, d] rows, [P] real counts -> (lo, hi, mn2): ``lo`` and ``hi``
    [P, C // t, d] float32, the bounds of each tile's real rows (+inf /
    -inf where a tile holds none), and ``mn2`` the largest squared norm of
    any real row (0 if none)."""
    P, C, d = x.shape
    x = x.astype(jnp.float32)
    valid = (jnp.arange(C, dtype=jnp.int32)[None, :]
             < n_rows[:, None])[..., None]
    tiles = x.reshape(P, C // t, t, d)
    v = valid.reshape(P, C // t, t, 1)
    lo = jnp.min(jnp.where(v, tiles, jnp.inf), axis=2)
    hi = jnp.max(jnp.where(v, tiles, -jnp.inf), axis=2)
    mn2 = jnp.max(jnp.where(valid[..., 0], jnp.sum(x * x, axis=-1), 0.0))
    return lo, hi, mn2


def _slack(d: int, mn2, cos_min):
    return 4.0 * (6 * d + 12) * _ROUNDING * (mn2 + jnp.abs(cos_min))


def box_keep(a, b, n_a, n_b, cos_min, tm: int, tn: int):
    """[P, M, d] owned rows, [P, N, d] bucket rows and their real counts ->
    [P, M // tm, N // tn] bool: the (owned tile, bucket tile) pairs whose
    boxes lie within reach of ``cos_min``. Every tile pair that holds a pair
    scoring ``>= cos_min`` is True; an empty tile's pairs are False."""
    alo, ahi, amn2 = tile_boxes(a, n_a, tm)
    blo, bhi, bmn2 = tile_boxes(b, n_b, tn)
    mn2 = jnp.maximum(amn2, bmn2)
    cos_min = jnp.asarray(cos_min, jnp.float32)
    # per-coordinate gap between the boxes, 0 where they overlap; an empty
    # box has lo = +inf and hi = -inf, so its gaps are +inf and never nan
    gap = jnp.maximum(jnp.maximum(blo[:, None] - ahi[:, :, None],
                                  alo[:, :, None] - bhi[:, None]), 0.0)
    gap2 = jnp.sum(gap * gap, axis=-1)
    reach = 2.0 * mn2 - 2.0 * cos_min + _slack(a.shape[-1], mn2, cos_min)
    return ~(gap2 > reach)            # a nan row keeps its tile pairs


def two_intervals(keep):
    """[..., g] bool -> [..., 4] int32 ``(lo1, hi1, lo2, hi2)``: tile
    intervals ``[lo1, hi1)`` and ``[lo2, hi2)``, ``hi1 <= lo2``, that cover
    every True and as few Falses as two intervals can (the span of the Trues
    less its longest run of Falses). A row with no True gets ``(1, 1, 1,
    1)``; an unused second interval is empty at ``hi1``. So ``hi1 >= 1`` and
    ``hi2 >= 1`` always, which ``kernel.py``'s block index relies on."""
    g = keep.shape[-1]
    axis = keep.ndim - 1
    j = jnp.arange(g, dtype=jnp.int32)
    first = jnp.argmax(keep, axis=-1).astype(jnp.int32)
    last = (g - 1 - jnp.argmax(keep[..., ::-1], axis=-1)).astype(jnp.int32)
    seen = jax.lax.cummax(jnp.where(keep, j, -1), axis=axis)
    before = jnp.concatenate(                  # the last True left of j
        [jnp.full(keep.shape[:-1] + (1,), -1, jnp.int32), seen[..., :-1]],
        axis=-1)
    run = jnp.where(keep & (before >= 0), j - before - 1, 0)   # Falses
    cut = jnp.argmax(run, axis=-1).astype(jnp.int32)           # ending at j
    cut_len = jnp.max(run, axis=-1)
    split = cut_len > 0
    hi1 = jnp.where(split, cut - cut_len, last + 1)
    lo2 = jnp.where(split, cut, last + 1)
    out = jnp.stack([first, hi1, lo2, last + 1], axis=-1)
    return jnp.where(jnp.any(keep, axis=-1)[..., None], out, 1)


def interval_tiles(win):
    """[..., 4] intervals -> [...] int32: the tiles they cover."""
    return (win[..., 1] - win[..., 0]) + (win[..., 3] - win[..., 2])


def real_tiles(n_a, n_b, tm: int, tn: int):
    """[P] real counts -> [P] int32: tile pairs that hold a real row on both
    sides, ``ceil(n_a / tm) * ceil(n_b / tn)``."""
    return ((n_a + tm - 1) // tm) * ((n_b + tn - 1) // tn)
