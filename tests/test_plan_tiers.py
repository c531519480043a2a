"""Tier planner: the vectorized ``plan_tiers`` is behavior-identical to the
original exhaustive ``itertools.combinations`` search (copied verbatim
below as the oracle), tie-breaks included, and stays fast at pathological
unique-capacity counts. Plus the blocked engine's chunk-shape override,
which may change speed but never results."""
import itertools
import time

import numpy as np
import pytest

from repro.data import sky
from repro.mapreduce import neighbor_search_job, plan_tiers, run_job
from repro.mapreduce.job import _round_up


def _plan_tiers_oracle(n_owned, n_bucket, tile, max_tiers=3,
                       pad_partitions_to=1):
    """The original O(U choose k) search, verbatim."""
    n_owned = np.asarray(n_owned, np.int64)
    n_bucket = np.asarray(n_bucket, np.int64)
    caps = np.array([_round_up(int(c), tile) for c in n_bucket], np.int64)
    uniq = np.unique(caps)

    def cost_and_tiers(thresholds):
        cost, tiers, lo = 0.0, [], -1
        for th in thresholds:
            sel = np.flatnonzero((caps > lo) & (caps <= th))
            lo = th
            if not len(sel):
                continue
            C1 = _round_up(int(n_owned[sel].max()), tile)
            cost += float(_round_up(len(sel), pad_partitions_to)) * C1 * th
            tiers.append((sel, C1, int(th)))
        return cost, tiers

    best = cost_and_tiers([int(uniq[-1])])
    for k in range(2, min(max_tiers, len(uniq)) + 1):
        for cut in itertools.combinations(range(len(uniq) - 1), k - 1):
            cand = cost_and_tiers([int(uniq[i]) for i in cut]
                                  + [int(uniq[-1])])
            if cand[0] < best[0]:
                best = cand
    return best[1]


@pytest.mark.parametrize("seed", range(40))
def test_plan_tiers_matches_exhaustive_oracle(seed):
    rng = np.random.default_rng(seed)
    P = int(rng.integers(1, 40))
    tile = int(rng.choice([1, 8, 64, 256]))
    pad = int(rng.choice([1, 2, 4]))
    kmax = int(rng.choice([1, 2, 3, 4]))
    n_bucket = rng.integers(0, 2000, P)
    n_owned = rng.integers(0, 2000, P)
    got = plan_tiers(n_owned, n_bucket, tile, max_tiers=kmax,
                     pad_partitions_to=pad)
    want = _plan_tiers_oracle(n_owned, n_bucket, tile, max_tiers=kmax,
                              pad_partitions_to=pad)
    assert len(got) == len(want)
    for (gi, gc1, gc2), (wi, wc1, wc2) in zip(got, want):
        np.testing.assert_array_equal(gi, wi)
        assert (gc1, gc2) == (wc1, wc2)


def test_plan_tiers_500_unique_capacities_under_1s():
    # tile=1 keeps every capacity distinct: U=500 was minutes with the old
    # O(U^2) combinations search; the vectorized table + early exit must
    # plan it in well under a second.
    rng = np.random.default_rng(7)
    n_bucket = rng.permutation(np.arange(1, 501))
    n_owned = rng.integers(1, 500, 500)
    t0 = time.perf_counter()
    plan = plan_tiers(n_owned, n_bucket, 1, max_tiers=3)
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"500-unique plan took {dt:.2f}s"
    ids = np.sort(np.concatenate([t[0] for t in plan]))
    np.testing.assert_array_equal(ids, np.arange(500))


def test_blocked_chunk_override_is_exact():
    from repro.kernels.zones_pairs import blocked
    xyz = sky.make_catalog(4000, 1)
    job = neighbor_search_job(0.03)
    want = run_job(job, xyz, engine="device").output
    blocked.set_chunk_shape(32, 32, 128)
    try:
        assert blocked.chunk_shape() == (32, 32, 128)
        assert run_job(job, xyz, engine="device").output == want
    finally:
        blocked.set_chunk_shape()
    assert blocked.chunk_shape() == (blocked.TM, blocked.TN, blocked.B0)
