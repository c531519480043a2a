"""The least work a pair reduction has to do, whatever implements it.

For one reduction (one query) over a catalog:

- bytes: every real owned row and every real bucket row (the row itself and
  its border copies in the neighbouring zones) read once, 3 coordinates in
  the wire dtype, plus the answer (4 bytes per edge);
- operations: 8 per within-radius hit (3 products, 2 sums, a compare, the
  count and its mask), where the hits are the ordered pairs within the
  query's widest radius counted from both ends, plus one self pair per row.
  They come from the verified answer.

Neither depends on tile size, capacity padding, tier layout, pruning or the
order of the sum, so a kernel that skips work cannot read above 100%.
"""
from __future__ import annotations

import numpy as np

WIRE_BYTES = {"identity": 4}
OPS_PER_HIT = 8
COORDS = 3


def bucket_rows(partition_counts) -> int:
    """Owned rows plus their copies in the zones on either side (every row
    is copied into both neighbours when the zone height equals the radius;
    the first and last zone of the sphere have one neighbour)."""
    c = np.asarray(partition_counts, np.int64)
    return int(c.sum() + c[1:].sum() + c[:-1].sum())


def query_bytes(partition_counts, codec: str, n_edges: int) -> int:
    rows = int(np.sum(partition_counts)) + bucket_rows(partition_counts)
    return rows * COORDS * WIRE_BYTES[codec] + 4 * n_edges


def query_ops(answer, n_rows: int) -> int:
    """``answer``: a search's pair count, or a histogram's bins."""
    pairs = int(np.sum(answer))
    return OPS_PER_HIT * (2 * pairs + n_rows)


def least_seconds(bytes_, ops, peak: dict) -> tuple[float, str]:
    """-> (seconds, which bound) on a chip with ``peak``'s numbers."""
    tb = bytes_ / peak["hbm_bytes_per_s"]
    to = ops / peak["flops_per_s"]
    return (tb, "bytes") if tb >= to else (to, "ops")
