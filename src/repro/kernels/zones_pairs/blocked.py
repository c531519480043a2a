"""Blocked reduce: the CPU/XLA twin of the masked Pallas grid.

The masked-batched kernels would cover every (owned-tile, bucket-tile) pair
of a partition. Both engines instead skip the tile pairs whose boxes lie
out of reach of the widest edge (``windows.box_keep``: per-tile bounds of
the real rows, an exact test, so results match the dense masked reference
bit-for-bit — property-checked in ``tests/test_kernels.py``). The shuffle
orders each zone by RA, so the boxes are short and most tile pairs go.
This module:

1. chops every partition of a [P, C, 3] tier into fixed TM/TN-row tiles and
   runs the box test on device — index metadata only, a [P, gm, gn]
   boolean brought to the host,
2. gathers the surviving tile pairs into a block stream and reduces it in
   fixed-shape chunks ([B0, TM, 3] x [B0, TN, 3]) through ONE jitted masked
   kernel, so the expensive XLA compile happens once per process instead of
   once per job shape.

Chunk geometry: TM=TN=64 rows (falls back to the largest divisor of the
capacity), B0=512 blocks per chunk — ~2M score cells per dispatch, enough
to amortize dispatch overhead while keeping the [B0, TM, TN] score tensor
inside the L2-ish working set. ``chunk_shape()`` resolves the shape per
run: the ``set_chunk_shape`` override, else these constants. Results are
bit-identical for every shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.zones_pairs import windows
from repro.kernels.zones_pairs.kernel import _fit_tile
from repro.kernels.zones_pairs.ref import _batched_dots, _pair_mask

TM = 64           # tile rows (owned side)
TN = 64           # tile rows (bucket side)
B0 = 512          # blocks per kernel dispatch (fixed -> one compile)

# chunk-shape resolution: hand-tuned module constants by default; an explicit
# override (tests / power users) wins. Any shape is exact — tiles are masked
# and ``_fit_tile`` handles every capacity — so this changes speed, never
# bits.
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
    return _CHUNK_OVERRIDE if _CHUNK_OVERRIDE is not None else (TM, TN, B0)


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


_box_keep = jax.jit(windows.box_keep, static_argnames=("tm", "tn"))


def _plan_blocks(a, b, n_a, n_b, cos_min, tm0=None, tn0=None):
    """-> (a_tile_idx, b_tile_idx, na_blk, nb_blk) numpy arrays of surviving
    tile pairs, plus (gm, tm, gn, tn). Empty tiles and tile pairs out of
    reach (``windows.box_keep``) are dropped."""
    P, C1, _ = a.shape
    C2 = b.shape[1]
    tm = _fit_tile(C1, TM if tm0 is None else tm0)
    tn = _fit_tile(C2, TN if tn0 is None else tn0)
    gm, gn = C1 // tm, C2 // tn
    keep = np.asarray(_box_keep(a, b, n_a, n_b, cos_min, tm=tm, tn=tn))
    pi, ii, jj = np.nonzero(keep)
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
    """-> (the summed chunk results, [2] int32 tile pairs: scored, and with
    a real row on both sides)."""
    tm0, tn0, b0 = chunk_shape()
    ai, bi, na_blk, nb_blk, (gm, tm, gn, tn) = _plan_blocks(
        a, b, n_a, n_b, cos_min, tm0, tn0)
    nblk = len(ai)
    real = int(np.sum(windows.real_tiles(np.asarray(n_a, np.int64),
                                         np.asarray(n_b, np.int64), tm, tn)))
    tiles = jnp.asarray([nblk, real], jnp.int32)
    if not nblk:              # everything pruned or empty
        return out0, tiles
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
    return out, tiles


def pair_count_blocked(a, b, n_a, n_b, cos_min):
    """Blocked twin of ``pair_count_masked_ref`` ([P,C1,d] x [P,C2,d] + real
    counts -> (total int32, [2] tile pairs as ``_run_blocked``)). Exact same
    result; skips tile pairs that provably cannot contain a within-threshold
    pair."""
    return _run_blocked(a, b, n_a, n_b, cos_min, _count_chunk,
                        jnp.float32(cos_min), jnp.int32(0))


def pair_hist_blocked(a, b, n_a, n_b, cos_edges):
    """Blocked twin of ``pair_hist_masked_ref`` (cumulative counts per cos
    edge, edges descending in cos; and the tile pairs). Skipping uses the
    loosest edge."""
    edges = jnp.asarray(cos_edges, jnp.float32)
    cos_min = float(jnp.min(edges))
    return _run_blocked(a, b, n_a, n_b, cos_min, _hist_chunk, edges,
                        jnp.zeros(edges.shape, jnp.int32))
