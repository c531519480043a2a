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
    got, _ = pair_count_masked_pallas(a, b, na, nb, cm, tm=tm, tn=tn,
                                      interpret=True)
    want = pair_count_ref(a[0], b[0], cm)
    assert got.shape == (1,) and int(got[0]) == int(want)


def test_pair_count_exclude_self():
    """Self pairs: every unit vector scores ~1 against itself, so the masked
    count of (a, a) minus the diagonal is the exclude-self reference."""
    a, na = _full(jnp.asarray(sky.make_catalog(256, 3)))
    cm = float(np.cos(0.05))
    got, _ = pair_count_masked_pallas(a, a, na, na, cm, tm=128, tn=128,
                                      interpret=True)
    want = pair_count_ref(a[0], a[0], cm, exclude_self=True)
    assert int(got[0]) - 256 == int(want)


@pytest.mark.parametrize("nbins", [4, 16, 60])
def test_pair_hist_sweep(nbins):
    a, na = _full(jnp.asarray(sky.make_catalog(256, 4)))
    b, nb = _full(jnp.asarray(sky.make_catalog(512, 5)))
    edges = jnp.asarray(np.cos(np.linspace(0.01, 0.2, nbins)), jnp.float32)
    got, _ = pair_hist_masked_pallas(a, b, na, nb, edges, tm=256, tn=256,
                                     interpret=True)
    want = pair_hist_ref(a[0], b[0], edges)
    np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want))


# ---------------------------------------------------------------------------
# zones_pairs: masked-batched variants (leading partition axis + n_a/n_b
# masking) — Pallas interpret-mode and the blocked reduce, both vs
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
    got_pl, _ = pair_count_masked_pallas(a, b, no, nb, cmin, tm=64, tn=64,
                                         interpret=True)
    got_ref = pair_count_masked_ref(a, b, no, nb, cmin)
    got_blk, _ = pair_count_blocked(a, b, no, nb, cmin)
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
    got_pl, _ = pair_hist_masked_pallas(a, b, no, nb, edges, tm=64, tn=64,
                                        interpret=True)
    got_ref = pair_hist_masked_ref(a, b, no, nb, edges)
    got_blk, _ = pair_hist_blocked(a, b, no, nb, edges)
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
                                                 tn=32, interpret=True)[0].sum(),
                pair_count_masked_ref(a, b, no, nb, cmin))
    edges = jnp.asarray(np.cos(np.linspace(0.05, 0.4, 5)), jnp.float32)
    return (lambda: pair_hist_masked_pallas(a, b, no, nb, edges, tm=32,
                                            tn=32, interpret=True)[0].sum(0),
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


def _band(n, dec_deg, ra_deg, seed, order="ra", grid=False):
    """[n, 3] float32 unit vectors uniform over a box of declination and RA
    (degrees; RA may run past 180), in RA order (``atan2``, as the shuffle
    orders a zone), or in random order for ``order="random"``. ``grid``
    rounds each coordinate to a multiple of 2^-11: every product and sum of
    a score is then exact in float32, so a count cannot depend on the order
    of evaluation (the CPU interpreter contracts the kernel's products and
    sums into fused multiply-adds; the chip and the reference do not)."""
    rng = np.random.default_rng(seed)
    lo, hi = np.sin(np.deg2rad(dec_deg))
    z = rng.uniform(lo, hi, n)
    ra = np.deg2rad(rng.uniform(*ra_deg, n))
    r = np.sqrt(1.0 - z * z)
    x = np.stack([r * np.cos(ra), r * np.sin(ra), z], 1)
    x = (np.round(x * 2048) / 2048 if grid else x).astype(np.float32)
    if order == "ra":
        return x[np.argsort(np.arctan2(x[:, 1], x[:, 0]), kind="stable")]
    return x[rng.permutation(n)]


ZONE = 250 / 60                     # zone height = widest edge, degrees

# (dec band, RA range, n_owned per partition, n_bucket, order): owned rows
# are the bucket's first rows' band, every partition in RA order unless
# "random"; the sizes are cut to tm = tn = 32 and 8-tile bucket blocks, so
# a bucket spans several blocks
WINDOW_CASES = {
    "ra_sorted": ((-60.8, -60.8 + ZONE), (0, 100), (700, 500), (1500, 1200),
                  "ra"),
    "ra_wraps_180": ((-40, -40 + ZONE), (150, 240), (600, 640), (1400, 1500),
                     "ra"),
    "polar": ((90 - ZONE, 90), (0, 360), (400, 300), (900, 1000), "ra"),
    "smaller_than_a_tile": ((-50, -50 + ZONE), (0, 100), (5, 31), (20, 2),
                            "ra"),
    "all_padding": ((-50, -50 + ZONE), (0, 100), (0, 0), (0, 0), "ra"),
    "unsorted": ((-60.8, -60.8 + ZONE), (0, 100), (700, 500), (1500, 1200),
                 "random"),
}


def _window_case(name):
    dec, ra, n_o, n_b, order = WINDOW_CASES[name]
    C1, C2 = 768, 1536
    a = np.zeros((2, C1, 3), np.float32)
    b = np.full((2, C2, 3), 7.0, np.float32)      # padding far from all
    for p in range(2):
        lo = dec[0] - ZONE if p else dec[0]
        b[p, :n_b[p]] = _band(n_b[p], (lo, dec[1]), ra, 10 * p + 1, order,
                              grid=True)
        a[p, :n_o[p]] = _band(n_o[p], dec, ra, 10 * p + 2, order, grid=True)
    return (jnp.asarray(a), jnp.asarray(b), jnp.asarray(n_o, jnp.int32),
            jnp.asarray(n_b, jnp.int32))


@pytest.mark.parametrize("kind", ["count", "hist"])
@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_windowed_kernel_is_exact(case, kind):
    """The windowed kernel scores only the bucket tiles in each owned tile's
    window, and its counts equal the dense masked reference bit for bit:
    on RA-ordered zones, across RA +-180 deg, at a pole, in partitions
    smaller than one tile, on all padding, and over unordered rows."""
    from repro.kernels.zones_pairs.ref import (pair_count_masked_ref,
                                               pair_hist_masked_ref)
    a, b, no, nb = _window_case(case)
    edges = jnp.asarray(np.cos(np.deg2rad(np.geomspace(0.2, ZONE, 6))),
                        jnp.float32)
    kw = dict(tm=32, tn=32, nb=8, interpret=True)
    if kind == "count":
        got, tiles = pair_count_masked_pallas(a, b, no, nb, edges[-1], **kw)
        want = pair_count_masked_ref(a, b, no, nb, edges[-1])
        assert int(np.sum(got)) == int(want)
    else:
        got, tiles = pair_hist_masked_pallas(a, b, no, nb, edges, **kw)
        want = pair_hist_masked_ref(a, b, no, nb, edges)
        np.testing.assert_array_equal(np.asarray(got).sum(0),
                                      np.asarray(want))
    scored, real = np.asarray(tiles).sum(0)
    assert real == sum(-(-int(x) // 32) * -(-int(y) // 32)
                       for x, y in zip(no, nb))
    if case in ("ra_sorted", "ra_wraps_180"):
        assert scored < 0.6 * real, (scored, real)
    elif case == "polar":           # the cap is mostly within reach
        assert 0.9 * real < scored <= real, (scored, real)
    elif case == "unsorted":
        assert scored == real
    else:
        assert scored <= real


@pytest.mark.parametrize("order", ["ra", "random"])
@pytest.mark.parametrize("ra", [(0, 100), (150, 240), (0, 360)])
def test_box_test_keeps_every_tile_pair_with_a_hit(ra, order):
    """Numpy property of the window: every tile pair holding a pair that
    could score over the widest edge under any float32 rounding (its float64
    score within 1e-6 of the edge) passes the box test, and every tile pair
    that passes lies inside its owned tile's two intervals, which start and
    end on passing tiles."""
    from repro.kernels.zones_pairs import windows
    tm = tn = 16
    for seed in range(4):
        a = _band(160, (-52, -52 + ZONE), ra, seed, order)
        b = _band(480, (-52 - ZONE, -52 + 2 * ZONE), ra, seed + 50, order)
        n_a, n_b = 160 - 3 * seed, 480 - 7 * seed
        cmin = np.float32(np.cos(np.deg2rad(ZONE)))
        dots = a.astype(np.float64) @ b.astype(np.float64).T
        hit = (dots >= cmin - 1e-6)[:n_a, :n_b]
        A = np.zeros((1, 160, 3), np.float32)
        A[0, :n_a] = a[:n_a]
        B = np.zeros((1, 480, 3), np.float32)
        B[0, :n_b] = b[:n_b]
        keep = np.asarray(windows.box_keep(
            jnp.asarray(A), jnp.asarray(B), jnp.asarray([n_a]),
            jnp.asarray([n_b]), cmin, tm, tn))[0]
        pad = np.zeros((160, 480), bool)
        pad[:n_a, :n_b] = hit
        has_hit = pad.reshape(10, tm, 30, tn).any(axis=(1, 3))
        assert not (has_hit & ~keep).any()
        win = np.asarray(windows.two_intervals(jnp.asarray(keep)))
        j = np.arange(30)
        inside = (((j >= win[:, :1]) & (j < win[:, 1:2]))
                  | ((j >= win[:, 2:3]) & (j < win[:, 3:4])))
        assert not (keep & ~inside).any()
        assert (win[:, 1] <= win[:, 2]).all() and (win[:, [1, 3]] >= 1).all()
        for i in np.flatnonzero(keep.any(axis=1)):
            lo1, hi1, lo2, hi2 = win[i]
            ends = [lo1, hi1 - 1] + ([lo2, hi2 - 1] if hi2 > lo2 else [])
            assert keep[i, ends].all(), (i, win[i])
        if order == "random":
            assert keep[:-(-n_a // tm), :-(-n_b // tn)].all()


def test_blocked_prunes_but_counts_exactly():
    """The blocked reduce must skip tile pairs (by the box test it shares
    with the Pallas kernel, on an RA-ordered zone) yet return exactly the
    dense masked count, and report the tile pairs it scored."""
    from repro.kernels.zones_pairs import blocked
    from repro.kernels.zones_pairs.ref import pair_count_masked_ref
    xyz = _band(2048, (-60, -60 + ZONE), (0, 100), 3)
    a = jnp.asarray(xyz[None])               # one big partition
    no = jnp.asarray([2048], jnp.int32)
    cmin = float(np.cos(np.deg2rad(ZONE)))
    planned = blocked._plan_blocks(a, a, no, no, cmin)
    n_tiles = (2048 // blocked.TM)
    assert len(planned[0]) < 0.3 * n_tiles * n_tiles      # pruning happened
    got, tiles = blocked.pair_count_blocked(a, a, no, no, cmin)
    want = pair_count_masked_ref(a, a, no, no, cmin)
    assert int(got) == int(want)
    assert np.asarray(tiles).tolist() == [len(planned[0]), n_tiles ** 2]


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
