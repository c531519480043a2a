"""Job API properties: codec contracts, engine parity, wrapper/oracle
agreement, multi-job batching, and StageStats -> Amdahl accounting."""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.data import sky
from repro.mapreduce import (HashPartitioner, MapReduceJob, ZonePartitioner,
                             available_codecs, get_codec,
                             neighbor_pairs_dense, neighbor_search_count,
                             neighbor_search_job, neighbor_statistics,
                             neighbor_statistics_job, run_job, run_jobs,
                             token_histogram, token_histogram_job)
from repro.mapreduce.codecs import Int16Codec


# ---------------------------------------------------------------------------
# ShuffleCodec contracts (property-style sweep over the registry)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(available_codecs()))
@pytest.mark.parametrize("n,d,seed", [(1, 1, 0), (7, 3, 1), (256, 3, 2),
                                      (1000, 3, 3), (513, 2, 4)])
def test_codec_roundtrip_within_tolerance(name, n, d, seed):
    codec = get_codec(name)
    rng = np.random.default_rng(seed)
    x = rng.uniform(-1.0, 1.0, (n, d)).astype(np.float32)
    back = codec.roundtrip(x)
    assert back.shape == x.shape
    err = np.max(np.abs(back - x))
    assert err <= codec.error_bound(x) + 1e-7, (name, err)


@pytest.mark.parametrize("name", sorted(available_codecs()))
def test_codec_wire_bytes_accounting(name):
    """encode() payload bytes == the static nbytes() formula the engine uses."""
    codec = get_codec(name)
    for n in (1, 255, 256, 257, 4096):
        x = np.linspace(-1, 1, n, dtype=np.float32)
        enc = codec.encode(x)
        assert enc.wire_bytes == codec.nbytes(n), (name, n)
        assert sum(a.nbytes for a in enc.arrays) == enc.wire_bytes, (name, n)


def test_codec_relative_sizes():
    """identity : int16 : int8 wire bytes ~= 4 : 2 : 1 (+ scale overhead)."""
    n = 3 * 4096
    idn = get_codec("identity").nbytes(n)
    i16 = get_codec("int16").nbytes(n)
    i8 = get_codec("int8").nbytes(n)
    assert idn == 4 * n and idn == 2 * i16
    assert i8 < i16 < idn
    assert i8 == n + 4 * (n // 256)        # int8 codes + one fp32 scale/block


def test_codec_registry_rejects_unknown():
    with pytest.raises(KeyError):
        get_codec("lzo")


def test_int8_codec_custom_block_roundtrips():
    from repro.mapreduce.codecs import Int8BlockCodec
    rng = np.random.default_rng(7)
    x = rng.normal(size=(300,)).astype(np.float32)   # not a block multiple
    for block in (64, 128, 512):
        codec = Int8BlockCodec(block=block)
        back = codec.roundtrip(x)
        assert np.max(np.abs(back - x)) <= codec.error_bound(x) + 1e-7
        assert codec.encode(x).wire_bytes == codec.nbytes(x.size)


# ---------------------------------------------------------------------------
# Engine: jobs vs oracles, batching, codecs interchangeable
# ---------------------------------------------------------------------------

def test_search_job_matches_oracle_all_codecs():
    """Codecs are interchangeable; count error tracks each codec's error
    bound (identity exact; int16 ~1/32767/coord; int8 ~1/127/coord, so it
    needs a radius well above its quantization step)."""
    xyz = sky.make_catalog(700, 11)
    for codec, radius, rel_tol in [("identity", 0.06, 0.0),
                                   ("int16", 0.06, 0.02),
                                   ("int8", 0.2, 0.05)]:
        want = sky.brute_force_pairs(xyz, radius)
        got = run_job(neighbor_search_job(radius, codec=codec, tile=64),
                      xyz).output
        assert abs(got - want) <= max(3 * bool(rel_tol), rel_tol * want), (
            codec, got, want)


def test_batched_jobs_share_one_shuffle():
    xyz = sky.make_catalog(600, 2)
    edges = np.linspace(0.02, 0.1, 5)
    part = ZonePartitioner(float(edges[-1]))
    jobs = [neighbor_search_job(float(edges[-1]), partitioner=part, tile=64),
            neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                    tile=64)]
    rs = run_jobs(jobs, xyz)
    assert rs[0].output == sky.brute_force_pairs(xyz, float(edges[-1]))
    np.testing.assert_array_equal(
        rs[1].output, sky.brute_force_hist(xyz, np.concatenate([[0], edges])))
    assert rs[0].stats is rs[1].stats          # one shuffle, shared stats
    assert rs[0].stats.job == "neighbor_search+neighbor_statistics"


def test_batched_jobs_reject_mismatched_stages():
    with pytest.raises(ValueError):
        run_jobs([neighbor_search_job(0.1, tile=64),
                  neighbor_search_job(0.1, tile=128)],
                 sky.make_catalog(50, 0))


def test_wordcount_matches_bincount_and_compresses():
    toks = np.random.default_rng(3).integers(0, 700, 6000)
    want = np.bincount(toks, minlength=700)
    r_id = token_histogram(toks, 700, tile=64)
    r_16 = token_histogram(toks, 700, codec="int16", tile=64)
    np.testing.assert_array_equal(r_id.output, want)
    np.testing.assert_array_equal(r_16.output, want)   # lossless: vocab < 32767
    assert r_16.stats.shuffle_wire_bytes * 2 == r_id.stats.shuffle_wire_bytes


def test_custom_job_composition():
    """A from-scratch job (hash partitioner + custom reducer) runs on the
    same engine: partition-sum of squares == global sum of squares."""
    import jax.numpy as jnp
    from repro.mapreduce import Reducer

    class SumSquares(Reducer):
        def per_partition(self, owned_p, bucket_p):
            return jnp.sum(owned_p[:, 0] ** 2)

    vals = np.arange(1, 501, dtype=np.float32)
    job = MapReduceJob("sumsq", HashPartitioner(4), SumSquares(), tile=32)
    got = float(run_job(job, vals).output)
    assert np.isclose(got, float(np.sum(vals ** 2)), rtol=1e-6)


# ---------------------------------------------------------------------------
# Engine parity: device (wire-dtype shuffle + tiered masked reduce) == host
# ---------------------------------------------------------------------------

def test_engine_parity_search_stats_wordcount():
    """engine="device" must match engine="host" EXACTLY for all three jobs
    with the exact (identity) and int16 codecs."""
    xyz = sky.make_catalog(1500, 9)
    radius = 0.07
    edges = np.linspace(0.02, radius, 6)
    toks = np.random.default_rng(5).integers(0, 900, 5000)
    for codec in ("identity", "int16"):
        sjob = neighbor_search_job(radius, codec=codec, tile=64)
        hjob = neighbor_statistics_job(edges / sky.ARCSEC, codec=codec,
                                       tile=64)
        assert (run_job(sjob, xyz, engine="device").output
                == run_job(sjob, xyz, engine="host").output)
        np.testing.assert_array_equal(
            run_job(hjob, xyz, engine="device").output,
            run_job(hjob, xyz, engine="host").output)
        np.testing.assert_array_equal(
            token_histogram(toks, 900, codec=codec, tile=64,
                            engine="device").output,
            token_histogram(toks, 900, codec=codec, tile=64,
                            engine="host").output)


def test_engine_parity_batched_and_skewed():
    """Batched jobs over one shuffle, with a skewed catalog (one crowded
    zone) so the tier planner actually splits size classes."""
    from repro.mapreduce import plan_tiers
    rng = np.random.default_rng(11)
    xyz = sky.make_catalog(900, 1)
    xyz = np.concatenate([xyz, sky.make_catalog(600, 2) * 0 + xyz[:1]])
    xyz[900:, 2] = np.clip(xyz[900:, 2] + rng.normal(0, 1e-3, 600), -1, 1)
    n = np.linalg.norm(xyz, axis=1, keepdims=True)
    xyz = (xyz / n).astype(np.float32)
    radius = 0.08
    part = ZonePartitioner(radius)
    edges = np.linspace(0.02, radius, 4)
    jobs = [neighbor_search_job(radius, partitioner=part, tile=64),
            neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                    tile=64)]
    rd = run_jobs(jobs, xyz, engine="device")
    rh = run_jobs(jobs, xyz, engine="host")
    assert rd[0].output == rh[0].output
    np.testing.assert_array_equal(rd[1].output, rh[1].output)
    assert rd[0].stats.engine == "device" and rh[0].stats.engine == "host"
    # the skewed zone must land in its own capacity tier
    keys = part.assign(xyz)
    n_owned = np.bincount(keys, minlength=part.n_partitions(xyz))
    tiers = plan_tiers(n_owned, n_owned * 2, 64)
    assert len(tiers) >= 2


def _tile_job(kind: str, tile: int):
    """(job, items) for one of the three paper jobs on a small seeded input:
    800 sky rows (5 histogram edges) or 5,000 tokens over 8 partitions."""
    if kind == "wordcount":
        toks = np.random.default_rng(5).integers(0, 900, 5000)
        return token_histogram_job(900, n_partitions=8, tile=tile), toks
    xyz = sky.make_catalog(800, 21)
    if kind == "search":
        return neighbor_search_job(0.07, tile=tile), xyz
    edges = np.linspace(0.02, 0.07, 5)
    return neighbor_statistics_job(edges / sky.ARCSEC, tile=tile), xyz


@pytest.mark.parametrize("tile", [8, 64, 256, 1024])
@pytest.mark.parametrize("kind", ["search", "statistics", "wordcount"])
def test_tile_quantum_never_changes_answers(kind, tile):
    """The capacity quantum reshapes the tiers (and the padding), never the
    answer: the device engine at any tile equals the host engine at 256."""
    job, items = _tile_job(kind, tile)
    want, _ = _tile_job(kind, 256)
    got = run_job(job, items, engine="device")
    np.testing.assert_array_equal(
        np.asarray(got.output),
        np.asarray(run_job(want, items, engine="host").output))
    assert got.stats.engine == "device"


def _refused(setting: str):
    xyz = sky.make_catalog(50, 0)
    if setting == "codec":
        run_job(neighbor_search_job(0.1, codec="auto"), xyz)
    elif setting == "tile":
        run_job(neighbor_search_job(0.1, tile="auto"), xyz)
    elif setting == "split_rows":
        run_job(neighbor_search_job(0.1), xyz, split_rows="auto")
    else:
        from repro.data import ArraySplits
        from repro.mapreduce import SpillConfig, run_job_streaming
        run_job_streaming(neighbor_search_job(0.1),
                          ArraySplits(xyz, n_splits=2),
                          spill=SpillConfig(budget_bytes=0, n_ranges="auto"))


@pytest.mark.parametrize("setting,error,concrete", [
    ("codec", KeyError, "identity"),
    ("tile", ValueError, "tile=256"),
    ("split_rows", ValueError, "split_rows=4096"),
    ("n_ranges", ValueError, "n_ranges=8"),
])
def test_removed_auto_settings_are_refused(setting, error, concrete):
    """``"auto"`` is no longer a value of these settings: each is refused
    before any work runs (no span opens), naming the concrete form."""
    from repro.obs import Tracer, use_tracer
    with use_tracer(Tracer()) as tr:
        with pytest.raises(error, match=concrete):
            _refused(setting)
    assert not tr.events


def test_engine_parity_jnp_index_path():
    """The pure-jnp argsort/scatter path (used on accelerator backends) must
    match the numpy index path used on CPU — same results AND the same
    shuffle metadata, with the resolved choice recorded in StageStats so an
    "auto" run is never ambiguous about which path built its tiers."""
    from repro.mapreduce import job as job_mod
    xyz = sky.make_catalog(700, 3)
    sjob = neighbor_search_job(0.09, codec="int16", tile=64)
    want = run_job(sjob, xyz, engine="device")
    assert want.stats.shuffle_index_impl == "host"    # CPU backend default
    old = job_mod.SHUFFLE_INDEX_IMPL
    job_mod.SHUFFLE_INDEX_IMPL = "jnp"
    try:
        got = run_job(sjob, xyz, engine="device")
    finally:
        job_mod.SHUFFLE_INDEX_IMPL = old
    assert got.output == want.output
    assert got.stats.shuffle_index_impl == "jnp"
    for f in ("shuffle_wire_bytes", "shuffle_raw_bytes", "n_partitions",
              "reduce_padded_ratio", "shard_padded_ratio", "reduce_bytes"):
        assert getattr(got.stats, f) == getattr(want.stats, f), f


ZONE_RAD = np.deg2rad(250 / 60)      # a Y1 zone: 250 arcmin


def _y1_like(n, seed, order="random"):
    """A DES Y1-like tile: ``n`` rows uniform over dec -60.83..-40 deg (5
    zones of 250 arcmin) and RA 0..100 deg, in arrival order ``order``
    ("random", or "ra")."""
    rng = np.random.default_rng(seed)
    z = rng.uniform(np.sin(np.deg2rad(-60.83)), np.sin(np.deg2rad(-40.0)), n)
    ra = np.deg2rad(rng.uniform(0.0, 100.0, n))
    r = np.sqrt(1.0 - z * z)
    xyz = np.stack([r * np.cos(ra), r * np.sin(ra), z], 1).astype(np.float32)
    return xyz[np.argsort(ra)] if order == "ra" else xyz


def _partition_rows(cat):
    """-> per real partition of a ``ResidentCatalog``: (owned, bucket) rows
    in the order the shuffle left them."""
    out = []
    for t in cat.sd.tiers:
        own = np.asarray(cat.codec.decode_device(*t.owned_wire))
        bkt = np.asarray(cat.codec.decode_device(*t.bucket_wire))
        no, nb = np.asarray(t.n_owned), np.asarray(t.n_bucket)
        out += [(own[p, :no[p]], bkt[p, :nb[p]]) for p in range(len(t.part_ids))]
    return out


def test_shuffle_orders_each_zone_by_ra():
    """Both index paths lay every partition's owned rows and bucket entries
    out in RA order (the jnp path to within one quantum of its 25-bit key),
    with the same tier shapes, and both give the same answers."""
    from repro.mapreduce import job as job_mod
    from repro.mapreduce.job import shuffle_once
    xyz = _y1_like(3000, 4)
    part = ZonePartitioner(ZONE_RAD, ZONE_RAD)
    hjob = neighbor_statistics_job(np.geomspace(10, 250, 5) * 60, tile=64,
                                   partitioner=part)
    ra = np.arctan2(xyz[:, 1], xyz[:, 0])
    quantum = 2 * (ra.max() - ra.min()) / 2 ** 25
    got = {}
    old = job_mod.SHUFFLE_INDEX_IMPL
    try:
        for impl in ("host", "jnp"):
            job_mod.SHUFFLE_INDEX_IMPL = impl
            cat = shuffle_once(part, xyz, tile=64)
            for own, bkt in _partition_rows(cat):
                for rows in (own, bkt):
                    step = np.diff(np.arctan2(rows[:, 1], rows[:, 0]))
                    assert (step >= (-quantum if impl == "jnp" else 0)).all()
            got[impl] = ([(t.Pt, t.C1, t.C2) for t in cat.sd.tiers],
                         cat.run(hjob)[0].output)
    finally:
        job_mod.SHUFFLE_INDEX_IMPL = old
    assert got["host"][0] == got["jnp"][0]
    np.testing.assert_array_equal(got["host"][1], got["jnp"][1])


@pytest.mark.parametrize("seed", [0, 1])
def test_exchange_orders_each_segment_on_the_owner(seed):
    """The owner's re-placement after the cluster exchange
    (``job._order_arrivals``), against a numpy reference: each slot's owned
    or bucket rows, arriving in any order from several senders, take the
    slot's first rows in order of their RA quanta (ties in arrival order);
    unused receive slots keep distinct rows past the tier buffer."""
    import jax.numpy as jnp

    from repro.mapreduce.job import (_order_arrivals, _ra_quanta,
                                     _segment_starts)
    rng = np.random.default_rng(seed)
    layout = ((2, 8, 16, 0, 16), (1, 32, 64, 48, 80))  # (q, C1, C2, o0, b0)
    starts = _segment_starts(layout)
    L = int(starts[-1])
    assert list(starts) == [0, 8, 16, 32, 48, 80, 144]
    n_seg = [5, 8, 0, 11, 20, 64]                 # arrivals per segment
    pos = np.concatenate([s + rng.permutation(n) for s, n in
                          zip(starts[:-1], n_seg)] + [L + np.arange(30)])
    pos = pos[rng.permutation(len(pos))].astype(np.int32)
    bits = 31 - (len(starts) - 1).bit_length()
    ra = rng.uniform(-np.pi, np.pi, len(pos)).astype(np.float32)
    ra[:12] = ra[12]                              # ties keep arrival order
    quanta = np.asarray(_ra_quanta(jnp.asarray(ra), bits))
    got = np.asarray(_order_arrivals(jnp.asarray(pos), jnp.asarray(quanta),
                                     jnp.asarray(starts), bits))
    want = pos.copy()
    seg = np.searchsorted(starts, pos, side="right") - 1
    for s in range(len(starts) - 1):
        idx = np.flatnonzero(seg == s)
        want[idx[np.argsort(quanta[idx], kind="stable")]] = (
            starts[s] + np.arange(len(idx)))
    used = pos < L
    np.testing.assert_array_equal(got[used], want[used])
    assert (got[~used] >= L).all() and len(set(got[~used])) == (~used).sum()
    assert len(np.unique(quanta)) > len(quanta) // 2     # not vacuous


def test_pair_tile_counter_reads_the_window_share():
    """While a tracer records, the reduce reports the tile pairs its kernel
    scored against those of real rows, on ``StageStats`` and on the
    ``reduce`` span: on an RA-ordered Y1-like tile the windows keep at most
    three in ten; on rows left in arrival order (a partitioner with no sort
    key, rows shuffled) every tile pair is scored. With nothing recording,
    nothing is read."""
    import dataclasses

    from repro.obs import Tracer, use_tracer

    @dataclasses.dataclass(frozen=True)
    class ArrivalOrder(ZonePartitioner):
        def sort_key_device(self, items):
            return None

    xyz = _y1_like(12000, 5)
    edges = np.geomspace(10, 250, 4) * 60
    ratio = {}
    for name, part in (("ra", ZonePartitioner(ZONE_RAD, ZONE_RAD)),
                       ("arrival", ArrivalOrder(ZONE_RAD, ZONE_RAD))):
        job = neighbor_statistics_job(edges, tile=256, partitioner=part)
        with use_tracer(Tracer()) as tr:
            st = run_job(job, xyz, engine="device").stats
        span = next(e["args"] for e in tr.events if e["name"] == "reduce")
        assert span["pair_tiles_scored"] == st.pair_tiles_scored
        assert span["pair_tiles_real"] == st.pair_tiles_real > 0
        ratio[name] = st.pair_tiles_scored / st.pair_tiles_real
        quiet = run_job(job, xyz, engine="device").stats
        assert quiet.pair_tiles_scored == quiet.pair_tiles_real == 0
    assert ratio["ra"] <= 0.3, ratio
    assert ratio["arrival"] == 1.0, ratio


def test_device_engine_stats_and_wire_accounting():
    xyz = sky.make_catalog(800, 6)
    res = run_job(neighbor_search_job(0.06, codec="int16", tile=64), xyz,
                  engine="device")
    st = res.stats
    assert st.engine == "device"
    assert st.compression_ratio == pytest.approx(2.0)   # int16 wire dtype
    assert st.reduce_padded_ratio >= 1.0
    assert st.reduce_bytes > 0 and st.reduce_flops > 0
    assert "reduce_padded_ratio" in st.to_dict()


def test_device_engine_accepts_any_mesh():
    """Device is the default engine everywhere now — ``engine="auto"`` picks
    it even when a mesh is present (the data-axis fallback to host is gone;
    multi-shard parity runs in md_check's ``mapreduce-device`` mode)."""
    from repro.core.compat import make_mesh
    xyz = sky.make_catalog(100, 0)
    job = neighbor_search_job(0.1, tile=64)
    want = run_job(job, xyz, engine="host").output
    for mesh in (make_mesh((1,), ("model",)), make_mesh((1, 1),
                                                        ("data", "model"))):
        res = run_job(job, xyz, mesh=mesh)              # engine="auto"
        assert res.stats.engine == "device"
        assert res.output == want
    with pytest.raises(ValueError):
        run_jobs([job], xyz, engine="nonsense")


def test_plan_tiers_pad_partitions_constraint():
    """``pad_partitions_to`` charges phantom rows in the cost search and the
    engine pads every tier to a multiple of it; a partition-count floor that
    would split wastefully under a wide mesh collapses to fewer tiers."""
    from repro.mapreduce import plan_tiers
    n_owned = np.array([10, 12, 9, 300, 11, 8, 290, 13])
    n_bucket = n_owned * 2
    plan1 = plan_tiers(n_owned, n_bucket, 64)
    for pad in (1, 4, 8):
        plan = plan_tiers(n_owned, n_bucket, 64, pad_partitions_to=pad)
        # every partition appears exactly once across tiers
        all_ids = np.sort(np.concatenate([ids for ids, _, _ in plan]))
        np.testing.assert_array_equal(all_ids, np.arange(len(n_owned)))
        # no empty tiers ever (the "zero-partition tier" cannot occur)
        assert all(len(ids) > 0 for ids, _, _ in plan)
        # padded cost never better than the unpadded plan's padded cost
        def padded_cells(p):
            return sum(-(-len(ids) // pad) * pad * C1 * C2
                       for ids, C1, C2 in p)
        assert padded_cells(plan) <= padded_cells(plan1)


def test_device_engine_phantom_partition_accounting():
    """Tier Pt padding (phantom partitions) shows up in the per-shard stats:
    n_shards and a shard_padded_ratio per shard, present even off-mesh."""
    xyz = sky.make_catalog(500, 2)
    res = run_job(neighbor_search_job(0.08, tile=64), xyz, engine="device")
    st = res.stats
    assert st.n_shards == 1
    assert len(st.shard_padded_ratio) == 1
    assert st.shard_padded_ratio[0] == pytest.approx(st.reduce_padded_ratio)
    host = run_job(neighbor_search_job(0.08, tile=64), xyz, engine="host")
    assert host.stats.n_shards == 1
    assert len(host.stats.shard_padded_ratio) == 1


@pytest.mark.slow
def test_ragged_shards_match_host_mesh_oracle():
    """Tier counts not divisible by the data axis, a tier landing entirely
    on one shard, and zero-entry partitions / the empty catalog — all must
    match the host mesh oracle exactly (8 host devices, subprocess)."""
    script = os.path.join(os.path.dirname(__file__), "md_check.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, script, "mapreduce-ragged"],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, (
        f"mapreduce-ragged failed:\n{r.stdout}\n{r.stderr}")
    assert "OK" in r.stdout


def test_device_engine_empty_catalog():
    """n=0 items: every stage must run clean and produce empty results."""
    xyz = np.zeros((0, 3), np.float32)
    job = neighbor_search_job(0.05, tile=64)
    assert run_job(job, xyz, engine="device").output == 0
    assert run_job(job, xyz, engine="host").output == 0
    hjob = neighbor_statistics_job([10.0, 20.0], tile=64)
    np.testing.assert_array_equal(
        run_job(hjob, xyz, engine="device").output, [0, 0])


# ---------------------------------------------------------------------------
# Split-streaming executor (the monolithic path is its one-split case)
# ---------------------------------------------------------------------------

def test_streaming_executor_stats_and_records():
    """Per-split records, fetch/overlap decomposition, and the aggregate
    stats contract of a streaming run (accumulate mode: pair job)."""
    from repro.data import ArraySplits
    from repro.mapreduce import run_job_streaming
    xyz = sky.make_catalog(1200, 8)
    job = neighbor_search_job(0.07, codec="int16", tile=64)
    mono = run_job(job, xyz)
    res = run_job_streaming(job, ArraySplits(xyz, 4), prefetch=2)
    assert res.output == mono.output
    st = res.stats
    assert st.n_splits == 4 and len(st.splits) == 4
    assert st.combiner == ""                    # pair kernels can't combine
    assert [r["split"] for r in st.splits] == [0, 1, 2, 3]
    assert sum(r["n_items"] for r in st.splits) == 1200
    assert st.n_items == 1200
    # streaming moves the same wire bytes as the monolithic shuffle
    assert st.shuffle_wire_bytes == mono.stats.shuffle_wire_bytes
    assert st.fetch_wall_s >= 0 and st.overlap_hidden_s >= 0
    assert 0.0 <= st.overlap_fraction <= 1.0
    d = st.to_dict()
    assert d["n_splits"] == 4 and "overlap_fraction" in d


def test_streaming_prefetch_off_matches_on():
    from repro.data import ArraySplits
    from repro.mapreduce import run_job_streaming
    xyz = sky.make_catalog(600, 3)
    job = neighbor_search_job(0.09, tile=64)
    a = run_job_streaming(job, ArraySplits(xyz, 3), prefetch=0)
    b = run_job_streaming(job, ArraySplits(xyz, 3), prefetch=2)
    assert a.output == b.output == run_job(job, xyz).output


def test_streaming_host_engine_matches_device():
    from repro.data import ArraySplits
    from repro.mapreduce import run_job_streaming, token_histogram_job
    xyz = sky.make_catalog(500, 6)
    job = neighbor_search_job(0.1, tile=64)
    dev = run_job_streaming(job, ArraySplits(xyz, 3), engine="device")
    host = run_job_streaming(job, ArraySplits(xyz, 3), engine="host")
    assert dev.output == host.output
    assert host.stats.engine == "host"
    toks = np.random.default_rng(9).integers(0, 50, 2000)
    items = toks.astype(np.float32).reshape(-1, 1)
    wjob = token_histogram_job(50, tile=64)
    for combiner in (None, "auto"):
        hd = run_job_streaming(wjob, ArraySplits(items, 5), engine="device",
                               combiner=combiner)
        hh = run_job_streaming(wjob, ArraySplits(items, 5), engine="host",
                               combiner=combiner)
        np.testing.assert_array_equal(hd.output, hh.output)
        np.testing.assert_array_equal(hd.output,
                                      np.bincount(toks, minlength=50))


def test_streaming_combiner_shrinks_wordcount_wire_bytes():
    """Map-side combine pre-aggregates each split to (token, count) rows, so
    for vocab << split size the wire carries ~vocab weighted entries instead
    of every occurrence — the paper's shrink-bytes-before-the-boundary move
    (the gate is >=2x; here the duplication factor is ~8x)."""
    from repro.data import ArraySplits
    from repro.mapreduce import run_job_streaming, token_histogram_job
    rng = np.random.default_rng(0)
    vocab, n = 64, 4096
    toks = rng.integers(0, vocab, n)
    items = toks.astype(np.float32).reshape(-1, 1)
    job = token_histogram_job(vocab, n_partitions=8, tile=64)
    on = run_job_streaming(job, ArraySplits(items, 4))
    off = run_job_streaming(job, ArraySplits(items, 4), combiner=None)
    np.testing.assert_array_equal(on.output, off.output)
    np.testing.assert_array_equal(on.output,
                                  np.bincount(toks, minlength=vocab))
    assert on.stats.combiner == "token_count" and off.stats.combiner == ""
    assert off.stats.shuffle_wire_bytes >= 2 * on.stats.shuffle_wire_bytes, (
        on.stats.shuffle_wire_bytes, off.stats.shuffle_wire_bytes)
    # n_items/map_bytes mean the RAW catalog even though the combiner
    # rewrote each split to (token, count) rows before the map
    assert on.stats.n_items == n == off.stats.n_items
    assert on.stats.map_bytes == items.nbytes
    assert sum(r["n_items"] for r in on.stats.splits) == n


def test_streaming_out_of_core_memmap_source(tmp_path):
    """A memmap-backed catalog 6x the split size streams split-by-split
    (nothing ever materializes the whole file) and matches the in-memory
    monolithic run bit-for-bit."""
    from repro.data import MemmapCatalogSplits
    from repro.mapreduce import run_job_streaming
    xyz = sky.make_catalog(1800, 12)
    path = str(tmp_path / "catalog.f32")
    MemmapCatalogSplits.write(path, xyz)
    src = MemmapCatalogSplits(path, d=3, rows_per_split=300)
    assert src.n_splits() == 6
    job = neighbor_search_job(0.06, codec="int16", tile=64)
    res = run_job_streaming(job, src)
    assert res.output == run_job(job, xyz).output
    assert res.stats.n_splits == 6
    assert max(r["n_items"] for r in res.stats.splits) == 300


def test_streaming_feeds_straggler_monitor():
    from repro.data import ArraySplits
    from repro.ft import StragglerMonitor
    from repro.mapreduce import run_job_streaming
    xyz = sky.make_catalog(400, 1)
    mon = StragglerMonitor(list(range(4)))
    run_job_streaming(neighbor_search_job(0.1, tile=64),
                      ArraySplits(xyz, 4), straggler_monitor=mon)
    assert sorted(mon.ema) == [0, 1, 2, 3]
    assert all(t >= 0 for t in mon.ema.values())


def test_streaming_rejects_bad_combiner():
    from repro.data import ArraySplits
    from repro.mapreduce import run_job_streaming
    with pytest.raises(ValueError):
        run_job_streaming(neighbor_search_job(0.1, tile=64),
                          ArraySplits(sky.make_catalog(50, 0), 2),
                          combiner="bogus")


def test_streaming_auto_combiner_requires_exact_codec():
    """int16 quantizes the combiner's count column into a different wire
    domain, so "auto" must NOT derive a combiner for lossy codecs."""
    from repro.data import ArraySplits
    from repro.mapreduce import run_job_streaming, token_histogram_job
    toks = np.random.default_rng(4).integers(0, 100, 3000)
    items = toks.astype(np.float32).reshape(-1, 1)
    res = run_job_streaming(token_histogram_job(100, codec="int16", tile=64),
                            ArraySplits(items, 3))
    assert res.stats.combiner == ""
    np.testing.assert_array_equal(res.output,
                                  np.bincount(toks, minlength=100))


@pytest.mark.slow
def test_streaming_matches_monolithic_on_mesh():
    """Streaming over 2/5/n-of-1 splits == monolithic on an 8-device data
    mesh, incl. wordcount with the combiner on/off (subprocess)."""
    script = os.path.join(os.path.dirname(__file__), "md_check.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, script, "mapreduce-streaming"],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, (
        f"mapreduce-streaming failed:\n{r.stdout}\n{r.stderr}")
    assert "OK" in r.stdout


def test_codec_exact_flags():
    assert get_codec("identity").exact
    assert not get_codec("int16").exact and not get_codec("int8").exact


def test_codec_device_transforms_roundtrip():
    """decode_device(encode_device(x)) matches the host roundtrip exactly
    for identity/int16 (bit-exact wire contract), within error_bound for
    the per-row int8 device layout."""
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    x = rng.uniform(-1, 1, (257, 3)).astype(np.float32)
    for name in ("identity", "int16"):
        codec = get_codec(name)
        dev = np.asarray(codec.decode_device(*codec.encode_device(
            jnp.asarray(x))))
        np.testing.assert_array_equal(dev, codec.roundtrip(x))
    codec = get_codec("int8")
    dev = np.asarray(codec.decode_device(*codec.encode_device(
        jnp.asarray(x))))
    assert np.max(np.abs(dev - x)) <= codec.error_bound(x) + 1e-7
    assert codec.device_bytes_per_item(3) == 3 + 4      # int8 codes + scale


# ---------------------------------------------------------------------------
# StageStats -> RooflineTerms
# ---------------------------------------------------------------------------

def test_stage_stats_feed_roofline():
    xyz = sky.make_catalog(500, 4)
    res = run_job(neighbor_search_job(0.08, codec="int16", tile=64), xyz)
    st = res.stats
    assert st.n_items == 500 and st.codec == "int16"
    assert st.shuffle_wire_bytes > 0
    assert st.compression_ratio == pytest.approx(2.0)
    assert st.reduce_flops > 0 and st.reduce_bytes > 0
    assert st.dominant_stage in ("map", "shuffle", "reduce")
    terms = st.roofline(chips=1)
    d = terms.to_dict()                        # the paper's Table-4 columns
    for key in ("AD", "ADN", "dominant", "chips_to_balance"):
        assert key in d
    full = st.to_dict()
    assert full["amdahl"]["flops"] == st.reduce_flops


# ---------------------------------------------------------------------------
# Deprecated wrappers: old signatures still work and match the dense oracle
# ---------------------------------------------------------------------------

def test_deprecated_wrappers_match_dense_oracle():
    for seed, n, radius in [(0, 300, 0.05), (1, 500, 0.1), (2, 200, 0.2)]:
        xyz = sky.make_catalog(n, seed)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            got = neighbor_search_count(xyz, radius, tile=64)
        assert got == len(neighbor_pairs_dense(xyz, radius))

    xyz = sky.make_catalog(400, 5)
    edges_rad = np.linspace(0.02, 0.12, 6)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        h = neighbor_statistics(xyz, edges_arcsec=edges_rad / sky.ARCSEC,
                                tile=64)
    np.testing.assert_array_equal(
        h, sky.brute_force_hist(xyz, np.concatenate([[0], edges_rad])))


def test_wrappers_warn_deprecation():
    xyz = sky.make_catalog(60, 0)
    with pytest.warns(DeprecationWarning):
        neighbor_search_count(xyz, 0.1, tile=64)
    with pytest.warns(DeprecationWarning):
        neighbor_statistics(xyz, edges_arcsec=[10.0, 20.0], tile=64)


# ---------------------------------------------------------------------------
# Mesh parity (8 host devices, via subprocess like test_multidevice.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_mesh_matches_single_device():
    script = os.path.join(os.path.dirname(__file__), "md_check.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, script, "mapreduce"],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"mapreduce check failed:\n{r.stdout}\n{r.stderr}"
    assert "OK" in r.stdout
