"""Z-banded blocked reduce: the CPU/XLA twin of the masked Pallas grid.

The masked-batched kernels cover every (owned-tile, bucket-tile) pair of a
partition. For the Zones algorithm that is wasteful: a within-radius pair
satisfies ``|z_i - z_j| <= |v_i - v_j| <= sqrt(2*max_norm^2 - 2*cos_min)``,
so tile pairs whose z-ranges are further apart than that bound *cannot*
contain a hit and can be skipped outright. This module:

1. chops every partition of a [P, C, 3] tier into fixed TM/TN-row tiles and
   computes per-tile z ranges on device (padding rows masked out),
2. prunes tile pairs with the (conservative, codec-error-aware) z-gap bound
   on the host — index metadata only, a [P, gm, gn] boolean,
3. gathers the surviving tile pairs into a block stream and reduces it in
   fixed-shape chunks ([B0, TM, 3] x [B0, TN, 3]) through ONE jitted masked
   kernel, so the expensive XLA compile happens once per process instead of
   once per job shape.

The pruning bound is exact: a skipped tile pair provably contains no dot
``>= cos_min`` even after f32 rounding (the slack term covers it), so
blocked results match the dense masked reference bit-for-bit — this is
property-checked in ``tests/test_kernels.py``.

Chunk geometry: TM=TN=64 rows (falls back to the largest divisor of the
capacity), B0=512 blocks per chunk — ~2M score cells per dispatch, enough
to amortize dispatch overhead while keeping the [B0, TM, TN] score tensor
inside the L2-ish working set. ``chunk_shape()`` resolves the shape per
run: ``set_chunk_shape`` override > ``REPRO_AUTO_CHUNK=1`` (the cost
model's calibrated replay-measured choice) > these constants. Results are
bit-identical for every shape.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.zones_pairs.kernel import _fit_tile
from repro.kernels.zones_pairs.ref import _batched_dots, _pair_mask

TM = 64           # tile rows (owned side)
TN = 64           # tile rows (bucket side)
B0 = 512          # blocks per kernel dispatch (fixed -> one compile)
_SLACK = 1e-3     # covers f32 rounding in dots/ranges/threshold

# chunk-shape resolution: hand-tuned module constants by default; an explicit
# override (tests / power users) wins; REPRO_AUTO_CHUNK=1 asks the cost
# model, which only deviates from the hand-tuned shape when its calibration
# replay measured a faster one. Any shape is exact — tiles are masked and
# ``_fit_tile`` handles every capacity — so this changes speed, never bits.
_CHUNK_OVERRIDE: "tuple[int, int, int] | None" = None


def set_chunk_shape(tm: int | None = None, tn: int | None = None,
                    b0: int | None = None) -> None:
    """Force a (TM, TN, B0) chunk shape; ``set_chunk_shape()`` resets to
    the default resolution order."""
    global _CHUNK_OVERRIDE
    _CHUNK_OVERRIDE = (None if tm is None
                       else (int(tm), int(tn or tm), int(b0 or B0)))


def chunk_shape() -> "tuple[int, int, int]":
    """The (TM, TN, B0) the blocked engine will use for the next run."""
    if _CHUNK_OVERRIDE is not None:
        return _CHUNK_OVERRIDE
    if os.environ.get("REPRO_AUTO_CHUNK") == "1":
        from repro.core.cost_model import get_cost_model
        return get_cost_model().choose_blocked_chunk(default=(TM, TN, B0))
    return (TM, TN, B0)


@jax.jit
def _count_chunk(a, b, na, nb, cos_min):
    """[B0,TM,3], [B0,TN,3], [B0], [B0] -> masked pair count (int32).
    Shares ``ref._batched_dots``/``ref._pair_mask`` so the scores are
    bit-identical to every other engine path (the parity contract)."""
    dots = _batched_dots(a, b)
    ok = (dots >= cos_min) & _pair_mask(a.shape[1], b.shape[1], na, nb)
    return jnp.sum(ok, dtype=jnp.int32)


@jax.jit
def _hist_chunk(a, b, na, nb, cos_edges):
    """Cumulative per-edge counts for one chunk (edges descending in cos).
    ``fori_loop`` over edges so the score tensor is hoisted out of the loop
    and materialized ONCE: a broadcast ``dots >= edges[:, None]`` fuses the
    dot computation into every edge row (NB-fold recompute, ~10x slower),
    and searchsorted lowers to a per-element binary-search gather on CPU
    (worse still)."""
    dots = jnp.where(_pair_mask(a.shape[1], b.shape[1], na, nb),
                     _batched_dots(a, b), -2.0)

    def body(k, acc):
        return acc.at[k].set(jnp.sum(dots >= cos_edges[k], dtype=jnp.int32))

    return jax.lax.fori_loop(0, cos_edges.shape[0], body,
                             jnp.zeros(cos_edges.shape, jnp.int32))


@functools.partial(jax.jit, static_argnames=("gm", "tm"))
def _tile_ranges(x, n_rows, *, gm, tm):
    """Per-tile z min/max + max squared norm, padding rows masked.
    x: [P, C, 3], n_rows: [P] -> (zmin [P,gm], zmax [P,gm], max_norm2)."""
    P = x.shape[0]
    z = x[..., 2].reshape(P, gm, tm)
    n2 = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1).reshape(P, gm, tm)
    row = jnp.arange(gm * tm, dtype=jnp.int32).reshape(gm, tm)
    valid = row[None] < n_rows[:, None, None]
    zmin = jnp.min(jnp.where(valid, z, jnp.inf), axis=-1)
    zmax = jnp.max(jnp.where(valid, z, -jnp.inf), axis=-1)
    mn2 = jnp.max(jnp.where(valid, n2, 0.0))
    return zmin, zmax, mn2


def _plan_blocks(a, b, n_a, n_b, cos_min, tm0=None, tn0=None):
    """-> (a_tile_idx, b_tile_idx, na_blk, nb_blk) numpy arrays of surviving
    tile pairs, plus (gm, tm, gn, tn). Empty tiles and z-gap-pruned tile
    pairs are dropped."""
    P, C1, _ = a.shape
    C2 = b.shape[1]
    tm = _fit_tile(C1, TM if tm0 is None else tm0)
    tn = _fit_tile(C2, TN if tn0 is None else tn0)
    gm, gn = C1 // tm, C2 // tn
    azmin, azmax, amn2, bzmin, bzmax, bmn2 = jax.device_get(
        _tile_ranges(a, n_a, gm=gm, tm=tm)
        + _tile_ranges(b, n_b, gm=gn, tm=tn))    # one host sync
    mn2 = float(max(amn2, bmn2))
    # |z_i - z_j| > sqrt(|v_i|^2 + |v_j|^2 - 2*cos_min)  =>  dot < cos_min
    thresh = float(np.sqrt(max(2.0 * mn2 - 2.0 * float(cos_min), 0.0))
                   ) + _SLACK
    gap = np.maximum(bzmin[:, None, :] - azmax[:, :, None],
                     azmin[:, :, None] - bzmax[:, None, :])   # [P, gm, gn]
    pi, ii, jj = np.nonzero(gap <= thresh)    # empty tiles: gap == +inf
    na_blk = np.clip(np.asarray(n_a)[pi] - ii * tm, 0, tm).astype(np.int32)
    nb_blk = np.clip(np.asarray(n_b)[pi] - jj * tn, 0, tn).astype(np.int32)
    return ((pi * gm + ii).astype(np.int32), (pi * gn + jj).astype(np.int32),
            na_blk, nb_blk, (gm, tm, gn, tn))


@jax.jit
def _pick_chunk(A, B, na, nb, k):
    """One dispatch for all four chunk slices (cheap slicing-only compile)."""
    f = lambda x: jax.lax.dynamic_index_in_dim(x, k, 0, keepdims=False)
    return f(A), f(B), f(na), f(nb)


@jax.jit
def _gather_chunk(fa, fb, ai, bi, na, nb, k):
    """Chunk ``k`` gathered straight from the flat device-resident tiles:
    one dispatch for both block gathers and the count slices."""
    f = lambda x: jax.lax.dynamic_index_in_dim(x, k, 0, keepdims=False)
    return fa[f(ai)], fb[f(bi)], f(na), f(nb)


def _run_blocked(a, b, n_a, n_b, cos_min, chunk_fn, chunk_arg, out0):
    tm0, tn0, b0 = chunk_shape()
    ai, bi, na_blk, nb_blk, (gm, tm, gn, tn) = _plan_blocks(
        a, b, n_a, n_b, cos_min, tm0, tn0)
    nblk = len(ai)
    if not nblk:              # everything pruned or empty
        return out0
    pad = (-nblk) % b0
    if pad:   # padded blocks point at tile 0 with zero-row masks
        z = np.zeros(pad, np.int32)
        ai, bi = np.concatenate([ai, z]), np.concatenate([bi, z])
        na_blk, nb_blk = (np.concatenate([na_blk, z]),
                          np.concatenate([nb_blk, z]))
    nchunks = (nblk + pad) // b0
    ai, bi, na_blk, nb_blk = (x.reshape(nchunks, b0)
                              for x in (ai, bi, na_blk, nb_blk))
    fa = a.reshape((-1, tm) + a.shape[2:])
    fb = b.reshape((-1, tn) + b.shape[2:])
    if jax.default_backend() == "cpu":
        # numpy fancy indexing (zero-copy view in) beats XLA's eager gather
        # by ~5x on CPU: gather the whole block stream once
        A = jnp.asarray(np.asarray(fa)[ai])
        B = jnp.asarray(np.asarray(fb)[bi])
        na_d, nb_d = jnp.asarray(na_blk), jnp.asarray(nb_blk)
        pick = lambda k: _pick_chunk(A, B, na_d, nb_d, k)
    else:
        # accelerators keep the tiles device-resident and gather one chunk
        # per dispatch: the whole block stream is O(score cells / tile) rows
        # and would not fit device memory at catalog scale
        idx = tuple(jnp.asarray(x) for x in (ai, bi, na_blk, nb_blk))
        pick = lambda k: _gather_chunk(fa, fb, *idx, k)
    out = out0
    for k in range(nchunks):   # dynamic index: one compiled slice per shape
        out = out + chunk_fn(*pick(jnp.int32(k)), chunk_arg)
    return out


def pair_count_blocked(a, b, n_a, n_b, cos_min):
    """Z-banded blocked twin of ``pair_count_masked_ref`` ([P,C1,3] x
    [P,C2,3] + real counts -> total int32). Exact same result; skips tile
    pairs that provably cannot contain a within-threshold pair."""
    if a.shape[-1] != 3:   # pruning bound assumes 3D unit-ish vectors
        from repro.kernels.zones_pairs.ref import pair_count_masked_ref
        return pair_count_masked_ref(a, b, n_a, n_b, cos_min)
    return _run_blocked(a, b, n_a, n_b, cos_min, _count_chunk,
                        jnp.float32(cos_min), jnp.int32(0))


def pair_hist_blocked(a, b, n_a, n_b, cos_edges):
    """Z-banded blocked twin of ``pair_hist_masked_ref`` (cumulative counts
    per cos edge, edges descending in cos). Pruning uses the loosest edge."""
    if a.shape[-1] != 3:
        from repro.kernels.zones_pairs.ref import pair_hist_masked_ref
        return pair_hist_masked_ref(a, b, n_a, n_b, cos_edges)
    edges = jnp.asarray(cos_edges, jnp.float32)
    cos_min = float(jnp.min(edges))
    return _run_blocked(a, b, n_a, n_b, cos_min, _hist_chunk, edges,
                        jnp.zeros(edges.shape, jnp.int32))
