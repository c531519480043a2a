"""Backend selection for the Zones pair reductions.

``pair_count``/``pair_hist`` are the host engine's per-partition oracle: the
plain jnp reference on every backend, so the reference never runs the code
under test.

``pair_count_masked``/``pair_hist_masked`` are the engine="device" batched
reduce: one call covers a whole size tier of partitions, padded rows masked
via n_a/n_b, never via pad-value tricks. On TPU: the Pallas kernels with a
leading partition grid axis. Elsewhere: the blocked reduce
(``blocked.py``) — same results, fixed-shape chunks so the XLA compile is
shared across codecs, radii, and job shapes. Both skip the tile pairs that
the box test (``windows.py``) proves hold no hit, and both return a
``PairTotals``: the counts, and the tile pairs scored beside those of real
rows. The blocked path runs eagerly (it plans its blocks on the
host), NOT under jit. The Pallas path builds one Pallas call per tier
shape and keeps it (``kernel._hist_call``), so it traces and lowers once
per shape and each later call of that shape dispatches a cached program.

Width: a pair count passes int32 at survey scale (a 250 arcmin
histogram of DES Y3's 865k lenses holds 1e10 ordered pairs), and JAX runs
without 64-bit integers. So the masked reductions return exact totals as
two int32 base-2^16 digits, ``[2, ...]`` (``wide_sum``): each partition's
count is split before partitions are summed, and the digits survive the
engine's sums over tiers and its ``psum`` over chips for up to 2^15
partitions. ``wide_value`` joins them on the host in int64. A single
partition's count (the kernel's accumulator) must still fit int32.

Traceability: the Pallas variants are pure traced jax and can run inside a
``shard_map`` region (the mesh-sharded device reduce; interpret mode
included), and both tolerate all-padding shards (every n_a/n_b zero — the
``pl.when`` guard / validity mask zero out every tile). The blocked path
CANNOT be traced (host-side block planning); ``masked_uses_pallas``
resolves which one a given ``use_pallas`` setting lands on, so the engine
knows whether the sharded reduce may trace the kernel or must slice shards
eagerly. Interpret mode is reachable only through an explicit
``use_pallas=True`` off the chip (tests, ``md_check.py``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.zones_pairs.kernel import (pair_count_masked_pallas,
                                              pair_hist_masked_pallas)
from repro.kernels.zones_pairs.ref import pair_count_ref, pair_hist_ref


class PairTotals(NamedTuple):
    """A pair reduction's total: ``counts`` as ``wide_sum`` digits, and
    ``tiles``, [2] int32, the tile pairs its kernel scored and those with a
    real row on both sides (their ratio is how much the box test left to
    score; the host engine scores no tiles and reports zeros). A pytree, so
    tiers, splits and chips add it up leaf by leaf."""

    counts: jax.Array
    tiles: jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


pair_count = jax.jit(pair_count_ref, static_argnames=("exclude_self",))
pair_hist = jax.jit(pair_hist_ref, static_argnames=("exclude_self",))


@jax.jit
def wide_sum(counts):
    """Non-negative int32 ``counts`` summed over axis 0, exactly, as base-2^16
    digits: ``[2, ...]`` int32 (high, low)."""
    return jnp.stack([jnp.sum(counts >> 16, axis=0, dtype=jnp.int32),
                      jnp.sum(counts & 0xFFFF, axis=0, dtype=jnp.int32)])


@jax.jit
def _pair_totals(counts, tiles):
    """Per-partition counts ``[P, ...]`` and tile pairs ``[P, 2]`` ->
    ``PairTotals`` of the tier, in one dispatch."""
    return PairTotals(wide_sum(counts), jnp.sum(tiles, axis=0,
                                                  dtype=jnp.int32))


def no_tiles(counts) -> PairTotals:
    """``PairTotals`` of a reduction that scores no tiles (the host
    engine's per-partition oracle)."""
    return PairTotals(counts, jnp.zeros((2,), jnp.int32))


def wide_value(digits) -> np.ndarray:
    """``wide_sum`` digits (summed any number of times) -> int64 values."""
    d = np.asarray(digits, np.int64)
    return d[0] * 65536 + d[1]


def masked_uses_pallas(use_pallas: bool | None = None) -> bool:
    """Resolve a ``use_pallas`` setting: True -> traceable Pallas masked
    kernels, False -> the eager-only blocked engine."""
    return _on_tpu() if use_pallas is None else use_pallas


def pair_count_masked(a, b, n_a, n_b, cos_min, *,
                      use_pallas: bool | None = None) -> PairTotals:
    """-> the tier's pair count: ``PairTotals`` with ``[2]`` digits."""
    if masked_uses_pallas(use_pallas):
        return _pair_totals(*pair_count_masked_pallas(
            a, b, n_a, n_b, cos_min, interpret=not _on_tpu()))
    from repro.kernels.zones_pairs.blocked import pair_count_blocked
    count, tiles = pair_count_blocked(a, b, n_a, n_b, cos_min)
    return _pair_totals(count[None], tiles[None])


def pair_hist_masked(a, b, n_a, n_b, cos_edges, *,
                     use_pallas: bool | None = None) -> PairTotals:
    """-> the tier's cumulative counts: ``PairTotals`` with ``[2, NB]``
    digits (the blocked engine's total, an int32 sum, is exact below 2^31:
    the sizes it runs at off the chip)."""
    if masked_uses_pallas(use_pallas):
        return _pair_totals(*pair_hist_masked_pallas(
            a, b, n_a, n_b, cos_edges, interpret=not _on_tpu()))
    from repro.kernels.zones_pairs.blocked import pair_hist_blocked
    hist, tiles = pair_hist_blocked(a, b, n_a, n_b, cos_edges)
    return _pair_totals(hist[None], tiles[None])
