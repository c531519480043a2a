"""Pallas TPU kernel: masked-batched pair search for the Zones algorithm.

The compute hot spot of the paper's Neighbor Searching / Neighbor Statistics
apps. A tier holds ``P`` partitions: owned rows ``[P, M, d]`` and bucket rows
``[P, N, d]``, cut into tiles of ``tm`` and ``tn`` rows. Per-partition real
counts ``n_a``/``n_b`` mask rows beyond the real count in-kernel, so
capacity padding contributes zero regardless of the pad fill. The [tm, tn]
score tile lives only in VMEM — the analogue of the paper's insight that the
reducer should never write O(n^2) intermediates.

Windows: the kernel scores, for each owned tile, only the bucket tiles in
its window: at most two intervals ``[lo1, hi1)``, ``[lo2, hi2)`` that cover
every bucket tile whose box lies within reach of the widest edge
(``windows.box_keep``; exact, so skipping changes no count). The shuffle
orders each zone by RA, so a window is the short run of tiles within the
widest radius in RA, or two runs where the footprint crosses RA +-180 deg;
over unordered rows the window covers every tile of real rows. A small jit
(``_prepare``, one dispatch a tier) computes the windows and lays the bucket
out as ``[P, N/tn (padded), d, tn]``; the windows reach the kernel through
scalar prefetch (SMEM).

Grid ``(P, M/tm, N/(NB tn))``: partition, owned tile, then a block of ``NB``
bucket tiles that one DMA brings into VMEM. Each step loops
(``lax.fori_loop``) over the tiles of its block inside the owned tile's
intervals. A block with none keeps the block index of the step before (the
bucket ``index_map`` clamps it into the intervals), so it is not fetched: a
skipped tile pair costs neither a DMA nor a grid step of its own, and VMEM
holds two blocks whatever the tier's capacity.

Scores are the rounded product sum ``(a0*b0 + a1*b1) + a2*b2`` on the VPU,
the ``ref._dots2d`` formulation every engine shares, so counts are
bit-identical to the references. An MXU ``dot_general`` would not be: its
default f32 precision is one bf16 pass (about 4e-3 error on a unit-vector
dot against a ``1 - cos r`` margin of 2e-4 at r = 0.02 rad), and K=3 gains
nothing on the MXU anyway.

Layout: owned rows stay ``[P, M, d]``; a bucket tile is ``[d, tn]``, so a
bucket coordinate is a lane-dense row that broadcasts against an owned
column. Each partition accumulates its cumulative per-edge counts in ONE
resident lane-dense ``(R, 128)`` int32 block across its steps (edge ``k``
at row ``k // 128``, lane ``k % 128``), so the output is ``P`` small tiles.

Dispatch: the ``pl.pallas_call`` of a tier shape is built once
(``_hist_call``) and Pallas jits it, so the first call of a shape traces,
lowers and compiles the kernel and every later call dispatches the cached
executable through jit's C++ cache. ``_prepare`` is jitted on its own. The
kernel stays outside any ``jax.jit`` of this module: XLA names a custom call
after its innermost enclosing jit (Pallas's own excepted), and a profile
finds this kernel by its target name, ``tpu_custom_call``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.zones_pairs import windows

TM = 256
TN = 256
NB = 64           # bucket tiles per VMEM block (512 KiB of f32 tiles)
_LANES = 128


def _fit_tile(C: int, t: int) -> int:
    """Largest divisor of C that is <= t — keeps VMEM blocks bounded even
    when a tier capacity isn't a multiple of the default tile (a whole-axis
    fallback would materialize an [C, C] score tile)."""
    t = min(t, C)
    while C % t:
        t -= 1
    return t


@functools.partial(jax.jit, static_argnames=("tm", "tn", "nb"))
def _prepare(a, b, n_a, n_b, cos_edges, *, tm, tn, nb):
    """-> (windows, flat ``[P * M/tm * 4]`` int32; bucket tiles
    ``[P, G, d, tn]`` with ``G`` the bucket's tiles padded to a multiple of
    ``nb``; ``[P, 2]`` int32 tile pairs per partition, scored and real)."""
    P, N, d = b.shape
    g = N // tn
    win = windows.two_intervals(windows.box_keep(
        a, b, n_a, n_b, jnp.min(cos_edges), tm, tn))
    pad = (-g) % nb * tn
    bt = jnp.pad(b, ((0, 0), (0, pad), (0, 0))).reshape(
        P, (N + pad) // tn, tn, d).transpose(0, 1, 3, 2)
    tiles = jnp.stack([jnp.sum(windows.interval_tiles(win), axis=1),
                       windows.real_tiles(n_a, n_b, tm, tn)], axis=1)
    return win.reshape(-1), bt, tiles


def _bucket_block(p, i, jb, win, *, gm, nb):
    """The bucket block a step reads: ``jb`` where it meets one of owned
    tile ``i``'s intervals, else the nearest block of the interval before
    it (or of the first interval), which a step before has fetched."""
    w = 4 * (p * gm + i)
    lo1, hi1, lo2, hi2 = win[w], win[w + 1], win[w + 2], win[w + 3]
    blk = lambda t: jax.lax.div(t, nb)
    return jnp.where(jb < blk(lo2),
                     jnp.clip(jb, blk(lo1), blk(hi1 - 1)),
                     jnp.clip(jb, blk(lo2), blk(hi2 - 1)))


def _hist_windowed_kernel(win_ref, na_ref, nb_ref, edges_ref, a_ref, bt_ref,
                          o_ref):
    p, i, jb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    gm = pl.num_programs(1)
    tm, nb, tn = a_ref.shape[1], bt_ref.shape[1], bt_ref.shape[3]
    na, nbr = na_ref[p], nb_ref[p]

    @pl.when((i == 0) & (jb == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

    w = 4 * (p * gm + i)
    j0 = jb * nb
    runs = [(jnp.maximum(win_ref[w + 2 * r], j0),
             jnp.minimum(win_ref[w + 2 * r + 1], j0 + nb)) for r in (0, 1)]

    @pl.when((runs[0][1] > runs[0][0]) | (runs[1][1] > runs[1][0]))
    def _():
        a = a_ref[0].astype(jnp.float32)                    # [tm, d]
        ri = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + i * tm
        rows = o_ref.shape[1]
        cell = (jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
                * _LANES
                + jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1))

        def tile(j, acc):
            bt = bt_ref[0, j - j0].astype(jnp.float32)      # [d, tn]
            dots = a[:, 0:1] * bt[0:1, :]
            for k in range(1, a.shape[1]):                  # _dots2d's order
                dots = dots + a[:, k:k + 1] * bt[k:k + 1, :]
            rj = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 1) + j * tn
            dots = jnp.where((ri < na) & (rj < nbr), dots, -2.0)
            for k in range(edges_ref.shape[0]):             # static: few edges
                hits = jnp.sum((dots >= edges_ref[k]).astype(jnp.int32))
                acc = acc + jnp.where(cell == k, hits, 0)
            return acc

        acc = o_ref[0]
        for lo, hi in runs:
            acc = jax.lax.fori_loop(lo, hi, tile, acc)
        o_ref[0] = acc


@functools.lru_cache(maxsize=None)
def _hist_call(P: int, M: int, G: int, d: int, nbins: int, tm: int, tn: int,
               nb: int, interpret: bool):
    """The kernel's Pallas call for one tier shape (``G`` bucket tiles, a
    multiple of ``nb``); the same object, and so the same compiled program,
    serves every later call of that shape."""
    rows = 8 * max(1, -(-nbins // (8 * _LANES)))
    gm = M // tm
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    bucket = functools.partial(_bucket_block, gm=gm, nb=nb)
    return pl.pallas_call(
        _hist_windowed_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(P, gm, G // nb),
            in_specs=[
                smem, smem, smem,
                pl.BlockSpec((1, tm, d), lambda p, i, jb, win: (p, i, 0)),
                pl.BlockSpec((1, nb, d, tn), lambda p, i, jb, win: (
                    p, bucket(p, i, jb, win), 0, 0))],
            out_specs=pl.BlockSpec((1, rows, _LANES),
                                   lambda p, i, jb, win: (p, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((P, rows, _LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )


def pair_hist_masked_pallas(a, b, n_a, n_b, cos_edges, *, tm: int = TM,
                            tn: int = TN, nb: int = NB,
                            interpret: bool = False):
    """a: [P,M,d], b: [P,N,d] (any float dtype), n_a/n_b: [P] int32 real
    counts, cos_edges: [NB]. -> ([P, NB] int32 cumulative counts of each
    partition, ``#{valid (i,j): a[p,i] . b[p,j] >= cos_edges[k]}``; [P, 2]
    int32 tile pairs of each partition, those inside the windows (scored)
    and those with a real row on both sides). The caller sums partitions
    (``ops.wide_sum``): a tier's total can pass int32 where no partition's
    count does."""
    P, M, d = a.shape
    N = b.shape[1]
    nbins = cos_edges.shape[0]
    tm, tn = _fit_tile(M, tm), _fit_tile(N, tn)
    nb = min(nb, N // tn)
    n_a = jnp.asarray(n_a, jnp.int32)
    n_b = jnp.asarray(n_b, jnp.int32)
    edges = jnp.asarray(cos_edges, jnp.float32)
    win, bt, tiles = _prepare(a, b, n_a, n_b, edges, tm=tm, tn=tn, nb=nb)
    call = _hist_call(P, M, bt.shape[1], d, nbins, tm, tn, nb, interpret)
    out = call(win, n_a, n_b, edges, a, bt)
    return out.reshape(P, -1)[:, :nbins], tiles


def pair_count_masked_pallas(a, b, n_a, n_b, cos_min, *, tm: int = TM,
                             tn: int = TN, nb: int = NB,
                             interpret: bool = False):
    """Masked pair count: the one-edge histogram. -> ([P] int32, [P, 2]
    int32 tile pairs as in ``pair_hist_masked_pallas``)."""
    edges = jnp.full((1,), cos_min, jnp.float32)
    counts, tiles = pair_hist_masked_pallas(a, b, n_a, n_b, edges, tm=tm,
                                            tn=tn, nb=nb,
                                            interpret=interpret)
    return counts[:, 0], tiles
