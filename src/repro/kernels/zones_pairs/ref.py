"""Pure-jnp oracle for the Zones pair kernel (plain and masked-batched)."""
from __future__ import annotations

import jax.numpy as jnp


def _dots2d(a, b):
    """[M,d] x [N,d] -> [M,N] scores as an unrolled broadcast sum. Every
    engine path (host lax.map, masked-batched, blocked) shares this
    formulation so scores agree bit-for-bit: XLA lowers a d=3 dot_general
    with FMA (no intermediate rounding), which differs in the last ulp from
    the rounded product sum and would flip pairs sitting exactly on a
    threshold."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return sum(a[:, None, k] * b[None, :, k] for k in range(a.shape[-1]))


def pair_count_ref(a, b, cos_min, *, exclude_self: bool = False):
    """a: [M,3], b: [N,3] unit vectors. Count of (i,j) with a_i . b_j >= cos_min.

    exclude_self: drop the diagonal (use when a and b are the same block).
    """
    ok = _dots2d(a, b) >= cos_min
    if exclude_self:
        M, N = ok.shape
        ok = ok & ~jnp.eye(M, N, dtype=bool)
    return jnp.sum(ok, dtype=jnp.int32)


def pair_hist_ref(a, b, cos_edges, *, exclude_self: bool = False):
    """Cumulative counts per edge: out[k] = #{(i,j): dot >= cos_edges[k]}.

    cos_edges descending in angle (i.e. cos ascending? NO: theta_k ascending =>
    cos_edges descending). The differential histogram for bin (theta_{k-1},theta_k]
    is out[k] - out[k-1].
    """
    dots = _dots2d(a, b)
    if exclude_self:
        M, N = dots.shape
        dots = jnp.where(jnp.eye(M, N, dtype=bool), -2.0, dots)
    return jnp.sum(dots[None, :, :] >= cos_edges[:, None, None],
                   axis=(1, 2), dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Masked-batched variants: leading partition axis + per-partition real counts
# (the engine="device" reduce — padded rows are *masked out*, not neutralized
# by pad-value tricks, so one skewed partition can't poison the others).
# ---------------------------------------------------------------------------

def _pair_mask(M, N, n_a, n_b):
    """[P, M, N] validity: row i of partition p is real iff i < n_a[p]."""
    mi = jnp.arange(M, dtype=jnp.int32)[None, :] < n_a[:, None]    # [P, M]
    mj = jnp.arange(N, dtype=jnp.int32)[None, :] < n_b[:, None]    # [P, N]
    return mi[:, :, None] & mj[:, None, :]


def _batched_dots(a, b):
    """[P,M,d] x [P,N,d] -> [P,M,N] dot scores; same unrolled broadcast
    formulation as ``_dots2d`` (bit-identical scores across engine paths; on
    CPU also ~5x faster to run and ~2x faster to compile than a d=3
    dot_general)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return sum(a[:, :, None, k] * b[:, None, :, k]
               for k in range(a.shape[-1]))


def pair_count_masked_ref(a, b, n_a, n_b, cos_min):
    """a: [P,M,3], b: [P,N,3], n_a/n_b: [P] real counts. Total count of
    valid (p,i,j) with a[p,i] . b[p,j] >= cos_min, summed over partitions."""
    dots = _batched_dots(a, b)
    ok = (dots >= cos_min) & _pair_mask(a.shape[1], b.shape[1], n_a, n_b)
    return jnp.sum(ok, dtype=jnp.int32)


def pair_hist_masked_ref(a, b, n_a, n_b, cos_edges):
    """Cumulative counts per edge over all partitions: out[k] = #{valid
    (p,i,j): dot >= cos_edges[k]} (edges descending in cos == ascending in
    angle, as in ``pair_hist_ref``).

    One binning pass (searchsorted + bincount) instead of an NB-fold
    broadcast, so the [P, M, N] score tensor is read once regardless of the
    number of edges."""
    dots = jnp.where(_pair_mask(a.shape[1], b.shape[1], n_a, n_b),
                     _batched_dots(a, b), -2.0)
    asc = cos_edges[::-1]                                  # ascending cos
    nb = asc.shape[0]
    # c = #edges <= dot; then #dots >= asc[j] == #dots with c > j
    c = jnp.searchsorted(asc, dots.ravel(), side="right")
    h = jnp.bincount(c, length=nb + 1)
    cum_from_top = jnp.cumsum(h[::-1])[::-1]               # [nb+1]
    return cum_from_top[1:][::-1].astype(jnp.int32)        # reorder to edges
