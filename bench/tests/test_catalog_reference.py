"""The catalog generator, the mix, the plain reference and the roofline
counts."""
import math

import numpy as np
import pytest

from benchlib import catalog, checks, reference, roofline, traffic

CONFIGS = ("des-y1-redmagic-z3", "des-y1-redmagic-z5")
BENCH = catalog.Path(__file__).resolve().parents[1]
MIX = traffic.load(BENCH / "traffic" / "wtheta.json")["mix"]


def small(name, rows=20000):
    cfg = catalog.load_config(BENCH / "configs" / f"{name}.json")
    return {**cfg, "rows": rows} if rows else cfg


def angular_truth(xyz, radii_rad):
    """Pairs within each angle, from float64 unit vectors and arccos-free
    float64 angles (atan2 of the cross and dot products)."""
    x = np.asarray(xyz, np.float64)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    out = np.zeros(len(radii_rad), np.int64)
    for lo in range(0, len(x), 2048):
        a = x[lo:lo + 2048]
        dot = a @ x.T
        cross = np.linalg.norm(np.cross(a[:, None, :], x[None, :, :]), axis=2)
        theta = np.arctan2(cross, dot)
        theta[np.arange(len(a)), lo + np.arange(len(a))] = np.inf
        out += [int(np.count_nonzero(theta <= r)) for r in radii_rad]
    return out // 2


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 99])
def test_zone_counts_are_the_configured_profile(name, seed):
    """Through the program's own device zone assignment."""
    import jax.numpy as jnp
    cfg = small(name)
    xyz = catalog.make_catalog(cfg, seed)
    _, part = traffic.jobs(cfg, traffic.queries(cfg, MIX))
    keys = np.asarray(part.assign_device(jnp.asarray(xyz)))
    got = np.bincount(keys, minlength=part.n_partitions(xyz))
    np.testing.assert_array_equal(got, catalog.partition_counts(cfg))
    assert got.sum() == cfg["rows"]


def test_seeds_move_rows_not_counts():
    cfg = small("des-y1-redmagic-z5")
    a, b = catalog.make_catalog(cfg, 1), catalog.make_catalog(cfg, 2)
    assert a.shape == b.shape and not np.array_equal(a, b)
    np.testing.assert_allclose(np.linalg.norm(a, axis=1), 1.0, atol=1e-6)


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_rows_and_edges_follow_the_source(name):
    cfg = small(name, rows=None)
    assert cfg["rows"] == round(cfg["n_gal_per_arcmin2"] * cfg["area_deg2"]
                                * 3600)
    k0, k1 = catalog.zone_range(cfg)
    band = (math.sin(math.radians(cfg["dec_max_deg"]))
            - math.sin(math.radians(cfg["dec_min_deg"])))
    area = band * math.radians(cfg["ra_max_deg"] - cfg["ra_min_deg"])
    assert area * (180 / math.pi) ** 2 == pytest.approx(cfg["area_deg2"],
                                                        rel=1e-4)
    assert (k1 - k0) * cfg["zone_height_deg"] == pytest.approx(
        cfg["dec_max_deg"] - cfg["dec_min_deg"])
    (q,) = traffic.queries(cfg, MIX)
    source = np.geomspace(2.5, 250.0, 21) * 60.0
    np.testing.assert_allclose(q["edges_arcsec"], source[5:])
    assert source[4] / 60.0 < cfg["theta_min_arcmin"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_is_the_angular_truth(name):
    cfg = small(name, rows=6000)
    xyz = catalog.make_catalog(cfg, 5)
    radii = traffic.theta_edges_arcmin({**cfg, "theta_min_arcmin": 0}) \
        * traffic.ARCMIN
    got = reference.pair_counts(xyz, radii)
    want = angular_truth(xyz, radii)
    assert np.all(np.abs(got - want) <= 1e-6 * want + 1), (got, want)


def test_program_float32_score_misses_the_cut_edges_only():
    """The program's float32 cosine score (its own brute force) agrees with
    the angular truth within the limit at the counted edges, and misses it
    below the cut: the reason for ``theta_min_arcmin``."""
    from repro.data import sky
    cfg = small("des-y1-redmagic-z3")
    xyz = catalog.make_catalog(cfg, 9)
    limit = checks.LIMITS["rel_gap"]
    for arcmin, inside in ((7.90569415, True), (15.77393361, True),
                           (250.0, True), (3.14731353, False),
                           (4.98815579, False), (6.27971608, False)):
        r = arcmin * traffic.ARCMIN
        want = reference.pair_counts(xyz, [r])[0]
        gap = abs(sky.brute_force_pairs(xyz, r) - want) / want
        assert (gap <= limit) == inside, (arcmin, gap)


def test_histogram_answers_are_bins_of_the_cumulative_counts():
    cfg = small("des-y1-redmagic-z5")
    xyz = catalog.make_catalog(cfg, 3)
    (q,) = traffic.queries(cfg, MIX)
    (h,) = reference.reference_answers(xyz, [q])
    np.testing.assert_array_equal(np.cumsum(h),
                                  reference.pair_counts(xyz, q["radii_rad"]))


def test_control_fails():
    from control import control_numbers
    cfg = small("des-y1-redmagic-z5")
    numbers = control_numbers(cfg, {"kind": "batch", "mix": MIX}, 11)
    assert not checks.passed(numbers)
    assert numbers["rel_gap"]["value"] > checks.LIMITS["rel_gap"]


@pytest.mark.parametrize("tile", [128, 512])
def test_roofline_counts_do_not_depend_on_the_tile(tile):
    """The program's padded work changes with the tile; the roofline's
    bytes and operations do not."""
    from repro.mapreduce import run_jobs
    cfg = small("des-y1-redmagic-z3", rows=6000)
    xyz = catalog.make_catalog(cfg, 4)
    qs = traffic.queries(cfg, MIX)
    counts = catalog.partition_counts(cfg)
    seen = {}
    for t in (256, tile):
        jobs, _ = traffic.jobs({**cfg, "tile": t}, qs)
        res = run_jobs(jobs, xyz, engine="device")
        seen[t] = (roofline.query_bytes(counts, cfg["codec"], 13),
                   roofline.query_ops(res[0].output, len(xyz)),
                   res[0].stats.reduce_flops)
    assert seen[256][:2] == seen[tile][:2]
    assert seen[256][2] != seen[tile][2]


def test_least_time_names_its_bound():
    peak = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    assert roofline.least_seconds(819e9, 1.0, peak) == (1.0, "bytes")
    assert roofline.least_seconds(1.0, 197e12, peak) == (1.0, "ops")
