"""Neighbor Searching (the paper's data-intensive app) as a MapReduce job.

Zones algorithm [Gray/Nieto-Santisteban/Szalay, MSR-TR-2006-52]: declination
bands with border replication make each zone bucket self-contained, so a
blockwise pair kernel reduces every zone independently. Every within-radius
unordered pair (p, q) is seen exactly twice across zones (once from each
endpoint's own zone), plus each owned point sees itself once; ``finalize``
corrects for both.

This module is now a thin definition on the composable Job API
(``mapreduce/job.py``): ``ZonePartitioner`` is the map-stage plugin (zone
assignment + border-replication policy), ``PairCountReducer`` the
reduce-stage plugin, and ``neighbor_search_job`` wires them together with any
registered shuffle codec. ``neighbor_search_count`` keeps the original
signature as a deprecated wrapper.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np

from repro.data import sky
from repro.kernels.zones_pairs.ops import (no_tiles, pair_count,
                                           pair_count_masked, wide_sum,
                                           wide_value)
from repro.mapreduce.job import (MapReduceJob, Partitioner, Reducer,
                                 ShuffledData, run_job)

# Border-replication margin: replicating a hair MORE than the radius is
# always safe (extra copies can only re-find pairs that are already counted
# from both endpoints' zones), while replicating a hair less silently drops
# a pair. The epsilon absorbs f32-vs-f64 rounding in the edge tests, so the
# host and device engines agree exactly even for points that sit within one
# ulp of radius-from-edge.
REPLICA_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class ZonePartitioner(Partitioner):
    """Declination bands of height ``zone_height`` (default: the radius —
    the paper's "always favor larger blocks" choice, so border copies come
    only from adjacent zones). Points within ``radius`` (+eps) of a band
    edge are replicated into the neighboring band's bucket."""

    radius: float
    zone_height: float = 0.0

    @property
    def height(self) -> float:
        return self.zone_height or max(self.radius, 1e-4)

    def n_partitions(self, items):
        return sky.n_zones(self.height)

    def assign(self, items):
        dec = sky.dec_of(items)
        Z = self.n_partitions(items)
        return np.clip(((dec + np.pi / 2) / self.height).astype(np.int32),
                       0, Z - 1)

    def replicas(self, items, keys, n_parts):
        h, margin = self.height, self.radius + REPLICA_EPS
        dec = sky.dec_of(items)
        kf = keys.astype(np.float32)        # f32 edge math, same as device
        lo_edge = (dec - (kf * h - np.pi / 2)) <= margin
        hi_edge = (((kf + 1) * h - np.pi / 2) - dec) <= margin
        for k in range(n_parts):
            if k > 0:
                yield k - 1, np.flatnonzero((keys == k) & lo_edge)
            if k + 1 < n_parts:
                yield k + 1, np.flatnonzero((keys == k) & hi_edge)

    # device map stage: zone assignment and border replication as jax ops —
    # the whole (owned, lower-border, upper-border) entry stream has the
    # static length 3n, bucketed by one argsort in the engine.

    def _dec_device(self, items):
        return jnp.arcsin(jnp.clip(items[:, 2], -1.0, 1.0))

    def assign_device(self, items):
        Z = self.n_partitions(items)
        dec = self._dec_device(items)
        return jnp.clip(((dec + np.pi / 2) / self.height).astype(jnp.int32),
                        0, Z - 1)

    def sort_key_device(self, items):
        # RA order within each zone: a zone is a band in declination, so
        # rows near each other in RA are near each other on the sky, and a
        # tile's box is short (order never changes results, only how many
        # tile pairs the reduce skips)
        return jnp.arctan2(items[:, 1], items[:, 0])

    def bucket_entries_device(self, items, keys, n_parts):
        h, margin = self.height, self.radius + REPLICA_EPS
        dec = self._dec_device(items)
        kf = keys.astype(jnp.float32)
        lo_edge = (dec - (kf * h - np.pi / 2)) <= margin
        hi_edge = (((kf + 1) * h - np.pi / 2) - dec) <= margin
        n = keys.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        dest = jnp.concatenate([keys, keys - 1, keys + 1])
        src = jnp.concatenate([idx, idx, idx])
        valid = jnp.concatenate([jnp.ones((n,), bool),
                                 lo_edge & (keys > 0),
                                 hi_edge & (keys + 1 < n_parts)])
        return dest, src, valid


@dataclasses.dataclass(frozen=True)
class PairCountReducer(Reducer):
    """Blockwise within-radius pair count per zone, as ``PairTotals``;
    finalize removes self pairs and the double-count."""

    radius: float
    use_pallas: bool | None = None

    def per_partition(self, owned_p, bucket_p):
        return no_tiles(wide_sum(pair_count(owned_p, bucket_p,
                                            float(np.cos(self.radius)))[None]))

    def reduce_partitions(self, owned, bucket, n_owned, n_bucket):
        return pair_count_masked(owned, bucket, n_owned, n_bucket,
                                 float(np.cos(self.radius)),
                                 use_pallas=self.use_pallas)

    def reduce_traceable(self):
        from repro.kernels.zones_pairs.ops import masked_uses_pallas
        return masked_uses_pallas(self.use_pallas)

    def tile_pairs(self, total):
        return total.tiles

    def finalize(self, total, sd: ShuffledData):
        return (int(wide_value(total.counts)) - int(sd.n_owned.sum())) // 2

    def flops(self, sd: ShuffledData):
        # per zone: C1*C2 dot products (2*3 FLOPs) + compares
        return sd.pair_cells * 8.0


def neighbor_search_job(radius_rad: float, *, zone_height: float = 0.0,
                        codec="identity", tile: int = 256,
                        use_pallas: bool | None = None,
                        partitioner: ZonePartitioner | None = None,
                        ) -> MapReduceJob:
    """The Neighbor Searching app as a composable job. Pass ``partitioner``
    explicitly to batch it with other jobs over one shuffle (``run_jobs``)."""
    part = partitioner or ZonePartitioner(radius_rad, zone_height)
    return MapReduceJob("neighbor_search", part,
                        PairCountReducer(radius_rad, use_pallas),
                        codec=codec, tile=tile)


def neighbor_search_count(xyz: np.ndarray, radius_rad: float, *, mesh=None,
                          compress_coords: bool = False,
                          use_pallas: bool | None = None,
                          tile: int = 256, zone_height: float = 0.0) -> int:
    """Deprecated wrapper (use ``neighbor_search_job`` + ``run_job``):
    total number of unordered neighbor pairs within radius."""
    warnings.warn("neighbor_search_count is deprecated; build a job with "
                  "neighbor_search_job() and execute it with run_job()",
                  DeprecationWarning, stacklevel=2)
    job = neighbor_search_job(radius_rad, zone_height=zone_height,
                              codec="int16" if compress_coords else "identity",
                              tile=tile, use_pallas=use_pallas)
    return run_job(job, xyz, mesh=mesh).output


def neighbor_pairs_dense(xyz: np.ndarray, radius_rad: float):
    """Small-N exact pair list (test oracle / example output)."""
    dots = xyz @ xyz.T
    np.fill_diagonal(dots, -2)
    i, j = np.where(dots >= np.cos(radius_rad))
    keep = i < j
    return np.stack([i[keep], j[keep]], axis=1)
