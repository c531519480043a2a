"""Neighbor Statistics (the paper's compute-intensive app) as a MapReduce job.

Same map/shuffle stages as Neighbor Searching (shared via ``ZonePartitioner``
— batch both apps over one shuffle with ``run_jobs``); the reducer emits
per-zone cumulative counts per angular edge, and ``finalize`` (the paper's
second, trivial MapReduce) removes self pairs, halves the double count, and
differentiates the cumulative counts into a histogram.

``neighbor_statistics`` keeps the original signature as a deprecated wrapper
over ``neighbor_statistics_job`` + ``run_job``.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np

from repro.data.sky import ARCSEC
from repro.kernels.zones_pairs.ops import (no_tiles, pair_hist,
                                           pair_hist_masked, wide_sum,
                                           wide_value)
from repro.mapreduce.job import MapReduceJob, Reducer, ShuffledData, run_job
from repro.mapreduce.zones import ZonePartitioner

DEFAULT_EDGES_ARCSEC = tuple(float(e) for e in range(1, 61))


@dataclasses.dataclass(frozen=True)
class PairHistReducer(Reducer):
    """Cumulative per-edge pair counts per zone, as ``PairTotals``;
    finalize differentiates."""

    edges_rad: tuple
    use_pallas: bool | None = None

    def _cos_edges(self):
        return jnp.asarray(np.cos(np.asarray(self.edges_rad)), jnp.float32)

    def per_partition(self, owned_p, bucket_p):
        return no_tiles(wide_sum(
            pair_hist(owned_p, bucket_p, self._cos_edges())[None]))

    def reduce_partitions(self, owned, bucket, n_owned, n_bucket):
        return pair_hist_masked(owned, bucket, n_owned, n_bucket,
                                self._cos_edges(),
                                use_pallas=self.use_pallas)

    def reduce_traceable(self):
        from repro.kernels.zones_pairs.ops import masked_uses_pallas
        return masked_uses_pallas(self.use_pallas)

    def tile_pairs(self, total):
        return total.tiles

    def finalize(self, total, sd: ShuffledData):
        cum = wide_value(total.counts)
        cum -= int(sd.n_owned.sum())   # self pairs (theta=0) hit every edge
        cum //= 2                      # each unordered pair seen twice
        return np.diff(np.concatenate([[0], cum]))

    def flops(self, sd: ShuffledData):
        return sd.pair_cells * (6.0 + len(self.edges_rad))


def neighbor_statistics_job(edges_arcsec=None, *, codec="identity",
                            tile: int = 256,
                            use_pallas: bool | None = None,
                            partitioner: ZonePartitioner | None = None,
                            ) -> MapReduceJob:
    """The Neighbor Statistics app as a composable job. The partition radius
    is the largest edge; pass a shared ``partitioner`` to batch with the
    search job over one shuffle."""
    if edges_arcsec is None:
        edges_arcsec = DEFAULT_EDGES_ARCSEC
    edges_rad = tuple(float(e) * ARCSEC for e in np.asarray(edges_arcsec))
    part = partitioner or ZonePartitioner(edges_rad[-1])
    return MapReduceJob("neighbor_statistics", part,
                        PairHistReducer(edges_rad, use_pallas),
                        codec=codec, tile=tile)


def neighbor_statistics(xyz: np.ndarray, *, edges_arcsec=None, mesh=None,
                        compress_coords: bool = False,
                        use_pallas: bool | None = None,
                        tile: int = 256) -> np.ndarray:
    """Deprecated wrapper (use ``neighbor_statistics_job`` + ``run_job``):
    histogram over (0, e1], (e1, e2], ... in arcsec (unordered pairs)."""
    warnings.warn("neighbor_statistics is deprecated; build a job with "
                  "neighbor_statistics_job() and execute it with run_job()",
                  DeprecationWarning, stacklevel=2)
    job = neighbor_statistics_job(
        edges_arcsec, tile=tile, use_pallas=use_pallas,
        codec="int16" if compress_coords else "identity")
    return run_job(job, xyz, mesh=mesh).output
