"""Pallas kernel sweeps: interpret-mode kernel vs pure-jnp oracle over
shapes x dtypes (per assignment: every kernel gets an allclose sweep)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import sky
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.quantize.kernel import dequantize_pallas, quantize_pallas
from repro.kernels.quantize.ref import dequantize_ref, quantize_ref
from repro.kernels.zones_pairs.kernel import (pair_count_masked_pallas,
                                              pair_hist_masked_pallas)
from repro.kernels.zones_pairs.ref import pair_count_ref, pair_hist_ref


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,cols", [(8, 256), (16, 1024), (8, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_sweep(rng, rows, cols, dtype):
    x = (jax.random.normal(rng, (rows, cols), jnp.float32) * 3).astype(dtype)
    q1, s1 = quantize_pallas(x, interpret=True)
    q2, s2 = quantize_ref(x)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    else:
        # bf16 inputs: division order at exact .5 boundaries may differ by 1 LSB
        d = np.abs(np.asarray(q1, np.int32) - np.asarray(q2, np.int32))
        assert d.max() <= 1 and (d > 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    # dequant: kernel vs ref on identical (q, s) must agree exactly; and the
    # roundtrip error stays within the per-block quantization bound
    d1 = dequantize_pallas(q1, s1, interpret=True)
    d2 = dequantize_ref(q1, s1)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)
    err = np.abs(np.asarray(d1) - np.asarray(x, np.float32))
    bound = np.repeat(np.asarray(s1), 256, axis=-1) * 0.51 + 1e-6
    assert np.all(err <= bound + np.asarray(s1).max())


# ---------------------------------------------------------------------------
# zones_pairs
# ---------------------------------------------------------------------------

# The masked kernels at P=1 with full counts: one dense (a, b) block pair.

def _full(x):
    return x[None], jnp.asarray([x.shape[0]], jnp.int32)


@pytest.mark.parametrize("m,n,tm,tn", [(256, 256, 256, 256),
                                       (512, 256, 256, 256),
                                       (512, 512, 128, 256)])
@pytest.mark.parametrize("radius", [0.02, 0.1])
def test_pair_count_sweep(m, n, tm, tn, radius):
    a, na = _full(jnp.asarray(sky.make_catalog(m, 1)))
    b, nb = _full(jnp.asarray(sky.make_catalog(n, 2)))
    cm = float(np.cos(radius))
    got = pair_count_masked_pallas(a, b, na, nb, cm, tm=tm, tn=tn,
                                   interpret=True)
    want = pair_count_ref(a[0], b[0], cm)
    assert got.shape == (1,) and int(got[0]) == int(want)


def test_pair_count_exclude_self():
    """Self pairs: every unit vector scores ~1 against itself, so the masked
    count of (a, a) minus the diagonal is the exclude-self reference."""
    a, na = _full(jnp.asarray(sky.make_catalog(256, 3)))
    cm = float(np.cos(0.05))
    got = pair_count_masked_pallas(a, a, na, na, cm, tm=128, tn=128,
                                   interpret=True)
    want = pair_count_ref(a[0], a[0], cm, exclude_self=True)
    assert int(got[0]) - 256 == int(want)


@pytest.mark.parametrize("nbins", [4, 16, 60])
def test_pair_hist_sweep(nbins):
    a, na = _full(jnp.asarray(sky.make_catalog(256, 4)))
    b, nb = _full(jnp.asarray(sky.make_catalog(512, 5)))
    edges = jnp.asarray(np.cos(np.linspace(0.01, 0.2, nbins)), jnp.float32)
    got = pair_hist_masked_pallas(a, b, na, nb, edges, tm=256, tn=256,
                                  interpret=True)
    want = pair_hist_ref(a[0], b[0], edges)
    np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want))


# ---------------------------------------------------------------------------
# zones_pairs: masked-batched variants (leading partition axis + n_a/n_b
# masking) — Pallas interpret-mode and the z-banded blocked reduce, both vs
# a per-partition loop over the 2D reference on the *real* (unpadded) rows.
# ---------------------------------------------------------------------------

# ragged per-partition real counts, including zero-size partitions, a
# full-capacity partition, a single-partition "tier", and an ALL-padding
# batch (every count zero — what a phantom-only mesh shard hands the
# kernels; they must return exactly zero, not NaN or garbage)
MASKED_CASES = [
    # (P, C1, C2, n_owned, n_bucket)
    (4, 128, 256, (0, 128, 64, 1), (0, 256, 100, 3)),
    (3, 64, 64, (64, 64, 64), (64, 64, 64)),          # single size class
    (1, 256, 128, (200,), (90,)),                      # single partition
    (5, 64, 128, (0, 0, 10, 64, 33), (0, 5, 0, 128, 77)),
    (3, 64, 64, (0, 0, 0), (0, 0, 0)),                 # all-padding shard
]


def _masked_case(P, C1, C2, n_o, n_b, seed=0):
    a = jnp.asarray(np.stack([sky.make_catalog(C1, seed + p)
                              for p in range(P)]))
    b = jnp.asarray(np.stack([sky.make_catalog(C2, 100 + seed + p)
                              for p in range(P)]))
    return a, b, jnp.asarray(n_o, jnp.int32), jnp.asarray(n_b, jnp.int32)


def _loop_count(a, b, n_o, n_b, cmin):
    return sum(int(pair_count_ref(a[p, :n_o[p]], b[p, :n_b[p]], cmin))
               for p in range(a.shape[0]))


def _loop_hist(a, b, n_o, n_b, edges):
    out = np.zeros(edges.shape[0], np.int64)
    for p in range(a.shape[0]):
        out += np.asarray(pair_hist_ref(a[p, :n_o[p]], b[p, :n_b[p]], edges),
                          np.int64)
    return out


@pytest.mark.parametrize("P,C1,C2,n_o,n_b", MASKED_CASES)
@pytest.mark.parametrize("radius", [0.05, 0.3])
def test_pair_count_masked_ragged(P, C1, C2, n_o, n_b, radius):
    from repro.kernels.zones_pairs.blocked import pair_count_blocked
    from repro.kernels.zones_pairs.ref import pair_count_masked_ref
    a, b, no, nb = _masked_case(P, C1, C2, n_o, n_b)
    cmin = float(np.cos(radius))
    want = _loop_count(a, b, list(n_o), list(n_b), cmin)
    got_pl = pair_count_masked_pallas(a, b, no, nb, cmin, tm=64, tn=64,
                                      interpret=True)
    got_ref = pair_count_masked_ref(a, b, no, nb, cmin)
    got_blk = pair_count_blocked(a, b, no, nb, cmin)
    assert [int(g) for g in got_pl] == [
        _loop_count(a[p:p + 1], b[p:p + 1], n_o[p:p + 1], n_b[p:p + 1], cmin)
        for p in range(P)], got_pl
    assert int(np.sum(got_pl)) == want and int(got_ref) == want, (got_pl,
                                                                   want)
    assert int(got_blk) == want, (got_blk, want)


@pytest.mark.parametrize("P,C1,C2,n_o,n_b", MASKED_CASES)
@pytest.mark.parametrize("nbins", [3, 17])
def test_pair_hist_masked_ragged(P, C1, C2, n_o, n_b, nbins):
    from repro.kernels.zones_pairs.blocked import pair_hist_blocked
    from repro.kernels.zones_pairs.ref import pair_hist_masked_ref
    a, b, no, nb = _masked_case(P, C1, C2, n_o, n_b, seed=7)
    edges = jnp.asarray(np.cos(np.linspace(0.02, 0.4, nbins)), jnp.float32)
    want = _loop_hist(a, b, list(n_o), list(n_b), edges)
    got_pl = pair_hist_masked_pallas(a, b, no, nb, edges, tm=64, tn=64,
                                     interpret=True)
    got_ref = pair_hist_masked_ref(a, b, no, nb, edges)
    got_blk = pair_hist_blocked(a, b, no, nb, edges)
    np.testing.assert_array_equal(np.asarray(got_pl, np.int64).sum(axis=0),
                                  want)
    np.testing.assert_array_equal(np.asarray(got_ref, np.int64), want)
    np.testing.assert_array_equal(np.asarray(got_blk, np.int64), want)


def _masked_pallas_and_ref(kind, a, b, no, nb):
    from repro.kernels.zones_pairs.ref import (pair_count_masked_ref,
                                               pair_hist_masked_ref)
    if kind == "count":
        cmin = float(np.cos(0.3))
        return (lambda: pair_count_masked_pallas(a, b, no, nb, cmin, tm=32,
                                                 tn=32, interpret=True).sum(),
                pair_count_masked_ref(a, b, no, nb, cmin))
    edges = jnp.asarray(np.cos(np.linspace(0.05, 0.4, 5)), jnp.float32)
    return (lambda: pair_hist_masked_pallas(a, b, no, nb, edges, tm=32,
                                            tn=32, interpret=True).sum(0),
            pair_hist_masked_ref(a, b, no, nb, edges))


@pytest.mark.parametrize("kind", ["hist", "count"])
@pytest.mark.parametrize("new_shape", [
    (5, 96, 160, (1, 96, 0, 40, 7), (3, 160, 0, 99, 160)),    # a new P
    (3, 96, 224, (96, 0, 17), (224, 9, 0)),                   # a new N
])
def test_masked_pallas_traces_once_per_shape(kind, new_shape):
    """The masked kernel wrappers trace and lower a tier shape once: a
    second call of that shape (other rows, other counts) runs no trace and
    no lowering, and a new partition count or capacity traces anew. Every
    call stays bit-identical to the masked reference."""
    from repro.obs import Tracer
    calls = [(3, 96, 160, (96, 5, 0), (160, 1, 77), 0),
             (3, 96, 160, (20, 96, 3), (0, 160, 42), 11),
             (*new_shape, 23)]
    tr = Tracer()
    for i, (P, C1, C2, n_o, n_b, seed) in enumerate(calls):
        run, want = _masked_pallas_and_ref(
            kind, *_masked_case(P, C1, C2, n_o, n_b, seed=seed))
        with tr.span(f"call{i}"):
            got = run()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    counts = {e["name"]: e["args"] for e in tr.events}
    assert counts["call1"].get("jax_traces", 0) == 0, counts["call1"]
    assert counts["call1"].get("jax_lowerings", 0) == 0, counts["call1"]
    assert counts["call2"]["jax_traces"] > 0, counts["call2"]
    assert counts["call2"]["jax_lowerings"] > 0, counts["call2"]


def test_blocked_prunes_but_counts_exactly():
    """The z-banded blocked reduce must skip tile pairs (on a z-sorted
    catalog spanning the sphere) yet return exactly the dense masked
    count."""
    from repro.kernels.zones_pairs import blocked
    from repro.kernels.zones_pairs.ref import pair_count_masked_ref
    xyz = sky.make_catalog(2048, 3)
    xyz = xyz[np.argsort(xyz[:, 2])]        # z-sorted -> tight tile ranges
    a = jnp.asarray(xyz[None])               # one big partition
    no = jnp.asarray([2048], jnp.int32)
    cmin = float(np.cos(0.05))
    planned = blocked._plan_blocks(a, a, no, no, cmin)
    n_tiles = (2048 // blocked.TM)
    assert len(planned[0]) < n_tiles * n_tiles          # pruning happened
    got = blocked.pair_count_blocked(a, a, no, no, cmin)
    want = pair_count_masked_ref(a, a, no, no, cmin)
    assert int(got) == int(want)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,H,Kv,dh,window,cap", [
    (256, 4, 4, 64, 0, 0.0),
    (256, 4, 2, 64, 0, 0.0),         # GQA
    (256, 4, 1, 32, 64, 0.0),        # MQA + window
    (128, 8, 4, 64, 0, 50.0),        # softcap (gemma2)
    (192, 2, 2, 64, 0, 0.0),         # non-multiple of block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(rng, S, H, Kv, dh, window, cap, dtype):
    k1, k2, k3 = jax.random.split(rng, 3)
    q = (jax.random.normal(k1, (2, S, H, dh)) * 0.5).astype(dtype)
    k = (jax.random.normal(k2, (2, S, Kv, dh)) * 0.5).astype(dtype)
    v = (jax.random.normal(k3, (2, S, Kv, dh)) * 0.5).astype(dtype)
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 softcap=cap, bq=64, bk=64, interpret=True)
    want = attention_ref(q, k, v, causal=True, window=window, softcap=cap)
    atol = 2e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_custom_vjp_backward(rng):
    from repro.kernels.flash_attention.ops import flash_attention
    q = jax.random.normal(rng, (1, 64, 2, 16))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 64, 2, 16))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (1, 64, 2, 16))
    g1 = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, True, 0, 0.0,
                                                    None, False)))(q)
    g2 = jax.grad(lambda q: jnp.sum(attention_ref(q, k, v, causal=True)))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_wide_sum_is_exact_past_int32():
    """Pair counts summed over partitions, tiers and chips pass int32 at
    survey scale: the base-2^16 digits stay exact through further sums."""
    from repro.kernels.zones_pairs.ops import wide_sum, wide_value
    c = jnp.full((8, 3), 2**31 - 1, jnp.int32).at[:, 1].set(123_456_789)
    d = wide_sum(c)
    assert d.dtype == jnp.int32 and d.shape == (2, 3)
    twice = d + d                    # a second tier, or a psum of two chips
    np.testing.assert_array_equal(
        wide_value(twice), 16 * np.array([2**31 - 1, 123_456_789, 2**31 - 1],
                                         np.int64))
