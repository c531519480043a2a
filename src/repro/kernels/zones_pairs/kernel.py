"""Pallas TPU kernel: masked-batched pair search for the Zones algorithm.

The compute hot spot of the paper's Neighbor Searching / Neighbor Statistics
apps. Grid ``(P, M/TM, N/TN)``: a leading *partition* axis over a whole
capacity tier, then (owned-tile, bucket-tile) pairs. Per-partition real
counts ``n_a``/``n_b`` mask rows/cols beyond the real count in-kernel, so
capacity padding contributes zero regardless of the pad fill, and tiles that
are all padding are skipped. The [TM, TN] score tile lives only in VMEM —
the analogue of the paper's insight that the reducer should never write
O(n^2) intermediates.

Scores are the rounded product sum ``(a0*b0 + a1*b1) + a2*b2`` on the VPU,
the ``ref._dots2d`` formulation every engine shares, so counts are
bit-identical to the references. An MXU ``dot_general`` would not be: its
default f32 precision is one bf16 pass (about 4e-3 error on a unit-vector
dot against a ``1 - cos r`` margin of 2e-4 at r = 0.02 rad), and K=3 gains
nothing on the MXU anyway.

Layout: owned rows stay ``[P, M, d]``; bucket rows are passed transposed,
``[P, d, N]``, so a bucket coordinate is a lane-dense row that broadcasts
against an owned column. Scalars (the per-partition counts and the cos
edges) live in SMEM. Each partition accumulates its cumulative per-edge
counts in ONE resident lane-dense ``(R, 128)`` int32 block across its
``(i, j)`` steps (edge ``k`` at row ``k // 128``, lane ``k % 128``), so the
output is ``P`` small tiles, not one per grid step.

Dispatch: the ``pl.pallas_call`` of a tier shape ``(P, M, N, d, nbins)``
is built once (``_hist_call``) and Pallas jits it, so the first call of a
shape traces, lowers and compiles the kernel and every later call
dispatches the cached executable through jit's C++ cache. The operand
casts, the transpose of ``b`` and the closing sum are eager jnp calls,
cached the same way. They stay outside any ``jax.jit`` of this module:
XLA names a custom call after its innermost enclosing jit (Pallas's own
excepted), and a profile finds this kernel by its target name,
``tpu_custom_call``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TM = 256
TN = 256
_LANES = 128


def _fit_tile(C: int, t: int) -> int:
    """Largest divisor of C that is <= t — keeps VMEM blocks bounded even
    when a tier capacity isn't a multiple of the default tile (a whole-axis
    fallback would materialize an [C, C] score tile)."""
    t = min(t, C)
    while C % t:
        t -= 1
    return t


def _hist_masked_kernel(na_ref, nb_ref, edges_ref, a_ref, bt_ref, o_ref):
    p, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tm, tn = a_ref.shape[1], bt_ref.shape[2]
    na, nb = na_ref[p], nb_ref[p]

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

    @pl.when((i * tm < na) & (j * tn < nb))          # skip all-padding tiles
    def _():
        a = a_ref[0].astype(jnp.float32)             # [tm, d]
        bt = bt_ref[0].astype(jnp.float32)           # [d, tn]
        dots = a[:, 0:1] * bt[0:1, :]
        for k in range(1, a.shape[1]):               # _dots2d's sum order
            dots = dots + a[:, k:k + 1] * bt[k:k + 1, :]
        ri = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + i * tm
        rj = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 1) + j * tn
        dots = jnp.where((ri < na) & (rj < nb), dots, -2.0)
        rows = o_ref.shape[1]
        cell = (jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
                * _LANES
                + jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1))
        acc = o_ref[0]
        for k in range(edges_ref.shape[0]):          # static: few edges
            hits = jnp.sum((dots >= edges_ref[k]).astype(jnp.int32))
            acc = acc + jnp.where(cell == k, hits, 0)
        o_ref[0] = acc


@functools.lru_cache(maxsize=None)
def _hist_call(P: int, M: int, N: int, d: int, nbins: int, tm: int, tn: int,
               interpret: bool):
    """The kernel's Pallas call for one tier shape; the same object, and so
    the same compiled program, serves every later call of that shape."""
    rows = 8 * max(1, -(-nbins // (8 * _LANES)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _hist_masked_kernel,
        grid=(P, M // tm, N // tn),
        in_specs=[smem, smem, smem,
                  pl.BlockSpec((1, tm, d), lambda p, i, j: (p, i, 0)),
                  pl.BlockSpec((1, d, tn), lambda p, i, j: (p, 0, j))],
        out_specs=pl.BlockSpec((1, rows, _LANES), lambda p, i, j: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((P, rows, _LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )


def pair_hist_masked_pallas(a, b, n_a, n_b, cos_edges, *, tm: int = TM,
                            tn: int = TN, interpret: bool = False):
    """a: [P,M,d], b: [P,N,d] (any float dtype), n_a/n_b: [P] int32 real
    counts, cos_edges: [NB]. -> [NB] int32 cumulative counts
    ``#{valid (p,i,j): a[p,i] . b[p,j] >= cos_edges[k]}``."""
    P, M, d = a.shape
    N = b.shape[1]
    nbins = cos_edges.shape[0]
    call = _hist_call(P, M, N, d, nbins, _fit_tile(M, tm), _fit_tile(N, tn),
                      interpret)
    out = call(jnp.asarray(n_a, jnp.int32), jnp.asarray(n_b, jnp.int32),
               jnp.asarray(cos_edges, jnp.float32), a, jnp.swapaxes(b, 1, 2))
    return jnp.sum(out, axis=0, dtype=jnp.int32).reshape(-1)[:nbins]


def pair_count_masked_pallas(a, b, n_a, n_b, cos_min, *, tm: int = TM,
                             tn: int = TN, interpret: bool = False):
    """Masked pair count: the one-edge histogram. -> scalar int32."""
    edges = jnp.full((1,), cos_min, jnp.float32)
    return pair_hist_masked_pallas(a, b, n_a, n_b, edges, tm=tm, tn=tn,
                                   interpret=interpret)[0]
