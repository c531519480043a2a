"""Multi-device semantics checks (run as a subprocess with 8 host devices;
``mapreduce-cluster`` with 4).

Usage: python tests/md_check.py <check-name>
Checks exit 0 on success; any assertion failure is a non-zero exit.
"""
import os
import sys

N_DEVICES = {"mapreduce-cluster": 4}.get(sys.argv[1:2][0] if sys.argv[1:]
                                         else "", 8)
os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                           f"{N_DEVICES}")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


from repro.core.compat import make_mesh, shard_map  # noqa: E402


def mesh3():
    return make_mesh((2, 2, 2), ("pod", "data", "model"))


def check_hierarchical_psum():
    from repro.core.collectives import hierarchical_psum_1d
    mesh = mesh3()
    x = jnp.arange(4 * 64, dtype=jnp.float32)      # [4 dp shards x 64] flattened

    def flat(v):
        return jax.lax.psum(v, ("pod", "data"))

    def hier(v):
        return hierarchical_psum_1d(v, "data", "pod")

    kw = dict(mesh=mesh, in_specs=P(("pod", "data")),
              out_specs=P(("pod", "data")),
              axis_names=frozenset({"pod", "data"}))
    o1 = jax.jit(shard_map(flat, **kw))(x)
    o2 = jax.jit(shard_map(hier, **kw))(x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)
    print("hierarchical == flat psum OK")


def check_compressed_psum():
    from repro.core.compression import compressed_psum_1d
    mesh = mesh3()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=4 * 512), jnp.float32)

    def comp(v):
        return compressed_psum_1d(v, "data")

    def flat(v):
        return jax.lax.psum(v, "data")

    spec = P(("pod", "data"))
    kw = dict(mesh=mesh, in_specs=spec, out_specs=spec,
              axis_names=frozenset({"pod", "data"}))
    o1 = jax.jit(shard_map(flat, **kw))(x)
    o2 = jax.jit(shard_map(comp, **kw))(x)
    err = np.abs(np.asarray(o1) - np.asarray(o2)).max()
    scale = np.abs(np.asarray(o1)).max()
    assert err <= scale * 0.03, (err, scale)
    print(f"compressed psum relerr={err/scale:.4f} OK")


def check_moe_multidevice():
    """Reduced granite MoE: 8-device EP result == 1-device result."""
    from repro.configs import get_arch
    import dataclasses
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import init_params, make_rules, use_mesh
    cfg = get_arch("granite-moe-3b-a800m").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0,
                                     chunk_tokens=64))
    mesh1 = make_mesh((1, 1), ("data", "model"))
    mesh8 = make_mesh((4, 2), ("data", "model"))
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (4, 16, cfg.d_model), jnp.float32) * 0.5
    bias = jnp.zeros((cfg.moe.n_experts_padded,), jnp.float32)
    with use_mesh(mesh1):
        p = init_params(moe_mod.moe_schema(cfg), rng, dtype_override="float32")
        y1, _ = jax.jit(lambda p, x: moe_mod.moe_apply(cfg, p, x, bias))(p, x)
    with use_mesh(mesh8, make_rules(mesh8)):
        y8, _ = jax.jit(lambda p, x: moe_mod.moe_apply(cfg, p, x, bias))(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y8),
                               atol=2e-4, rtol=2e-4)
    print("MoE 8-device == 1-device OK")


def check_train_step_sharded():
    """Reduced tinyllama: 2 train steps on a (2,2,2) mesh run + loss finite,
    and the explicit replicated+compressed path matches the sharded path's loss."""
    from repro.configs import RunConfig, get_arch
    from repro.parallel.sharding import make_rules, use_mesh
    from repro.training.state import init_state
    from repro.training.step import make_train_step
    cfg = get_arch("tinyllama-1.1b").reduced()
    mesh = mesh3()
    losses = {}
    for name, rc in {
        "sharded": RunConfig(remat="none", pod_param_mode="sharded"),
        "explicit": RunConfig(remat="none", pod_param_mode="replicated",
                              compress_grads=True, hierarchical_sync=True,
                              bucketed_updates=True),
    }.items():
        step_fn, _, _, rules = make_train_step(cfg, rc, mesh)
        with use_mesh(mesh, rules):
            state = init_state(cfg, rc, jax.random.PRNGKey(0), mesh)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
        batch = {"tokens": toks}
        for _ in range(2):
            state, mets = step_fn(state, batch)
        losses[name] = float(mets["loss"])
        assert np.isfinite(losses[name])
    # both modes train on identical data from identical init: losses close
    assert abs(losses["sharded"] - losses["explicit"]) < 0.15, losses
    print(f"train modes OK: {losses}")


def check_mapreduce_device_sharded():
    """Sharded DEVICE engine: on an 8-device data mesh, engine="device"
    (tier arrays sharded over ``data``, psum tier combine) must match the
    host-engine mesh oracle BIT-EXACTLY for exact codecs, across the ragged
    shard shapes that stress the phantom-partition padding:

    - both shuffle index paths ("jnp" and "host") -> identical metadata,
    - the traceable in-shard_map path (pure-jnp wordcount reducer, and the
      pair kernels forced through Pallas interpret mode).

    The ragged shard shapes (non-divisible tier counts, single-shard tiers,
    zero-entry partitions, empty catalog) live in ``mapreduce-ragged``.
    """
    from repro.core.compat import make_mesh as mk
    from repro.data import sky
    from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                                 neighbor_statistics_job, run_job, run_jobs,
                                 token_histogram)
    from repro.mapreduce import job as job_mod

    mesh = mk((8,), ("data",))

    # batched paper apps, identity + int16
    for codec in ("identity", "int16"):
        xyz = sky.make_catalog(1200, 7)
        radius = 0.1
        part = ZonePartitioner(radius)
        edges = np.linspace(0.02, radius, 5)
        jobs = [neighbor_search_job(radius, partitioner=part, tile=64,
                                    codec=codec),
                neighbor_statistics_job(edges / sky.ARCSEC, codec=codec,
                                        partitioner=part, tile=64)]
        rd = run_jobs(jobs, xyz, mesh=mesh, engine="device")
        rh = run_jobs(jobs, xyz, mesh=mesh, engine="host")
        r1 = run_jobs(jobs, xyz, engine="device")
        assert rd[0].output == rh[0].output == r1[0].output, (
            codec, rd[0].output, rh[0].output, r1[0].output)
        np.testing.assert_array_equal(rd[1].output, rh[1].output)
        np.testing.assert_array_equal(rd[1].output, r1[1].output)
        if codec == "identity":
            assert rd[0].output == sky.brute_force_pairs(xyz, radius)
        st = rd[0].stats
        assert st.engine == "device" and st.n_shards == 8
        assert len(st.shard_padded_ratio) == 8

    # engine="auto" now picks device on a data mesh
    ra = run_job(neighbor_search_job(0.1, tile=64), xyz, mesh=mesh)
    assert ra.stats.engine == "device"
    assert ra.output == sky.brute_force_pairs(xyz, 0.1)

    # wordcount on the mesh: the traceable pure-jnp in-shard_map path
    toks = np.random.default_rng(1).integers(0, 500, 4000)
    hd = token_histogram(toks, 500, n_partitions=8, tile=64, mesh=mesh,
                         engine="device").output
    hh = token_histogram(toks, 500, n_partitions=8, tile=64, mesh=mesh,
                         engine="host").output
    np.testing.assert_array_equal(hd, hh)
    np.testing.assert_array_equal(hd, np.bincount(toks, minlength=500))

    # both shuffle index impls produce identical results AND metadata; on
    # a data mesh the cluster exchange computes the index metadata on the
    # chips whichever impl is asked for
    xyz = sky.make_catalog(700, 3)
    j = neighbor_search_job(0.09, codec="int16", tile=64)
    want = run_job(j, xyz, mesh=mesh, engine="device")
    one_host = run_job(j, xyz, engine="device")
    old = job_mod.SHUFFLE_INDEX_IMPL
    job_mod.SHUFFLE_INDEX_IMPL = "jnp"
    try:
        got = run_job(j, xyz, mesh=mesh, engine="device")
        one_jnp = run_job(j, xyz, engine="device")
    finally:
        job_mod.SHUFFLE_INDEX_IMPL = old
    assert got.output == want.output == one_host.output == one_jnp.output
    assert one_host.stats.shuffle_index_impl == "host"  # CPU backend default
    assert one_jnp.stats.shuffle_index_impl == "jnp"
    assert want.stats.shuffle_index_impl == "jnp"
    assert got.stats.shuffle_index_impl == "jnp"
    for f in ("shuffle_wire_bytes", "n_partitions", "reduce_padded_ratio",
              "shard_padded_ratio", "reduce_bytes"):
        assert getattr(got.stats, f) == getattr(want.stats, f), f
    for f in ("shuffle_wire_bytes", "n_partitions", "reduce_padded_ratio",
              "reduce_bytes"):
        assert getattr(one_jnp.stats, f) == getattr(one_host.stats, f), f

    # traceable in-shard_map path: pair kernels through Pallas interpret,
    # single job AND batched (two reducers fused in one shard_map region)
    xyz = sky.make_catalog(400, 7)
    part = ZonePartitioner(0.1)
    edges = np.linspace(0.03, 0.1, 4)
    jobs_pl = [neighbor_search_job(0.1, partitioner=part, tile=64,
                                   use_pallas=True),
               neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                       tile=64, use_pallas=True)]
    jobs_bk = [neighbor_search_job(0.1, partitioner=part, tile=64),
               neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                       tile=64)]
    rp = run_jobs(jobs_pl, xyz, mesh=mesh, engine="device")
    rb = run_jobs(jobs_bk, xyz, mesh=mesh, engine="device")
    assert rp[0].output == rb[0].output
    np.testing.assert_array_equal(rp[1].output, rb[1].output)
    print("mapreduce sharded-device == host mesh oracle OK")


def check_mapreduce_ragged_shards():
    """Ragged shard shapes on an 8-device data mesh, sharded device engine
    vs the host mesh oracle (bit-exact):

    - tier partition counts not divisible by the data axis size (a 0.25-rad
      zone layout gives ~13 zones over 8 shards),
    - a skewed catalog whose crowded tier has fewer real partitions than
      shards (the tier lands entirely on one shard; the rest are phantoms),
    - zero-entry partitions (wordcount vocab < n_partitions, so five
      partitions own nothing) and the zero-partition/empty-catalog case.
    """
    from repro.core.compat import make_mesh as mk
    from repro.data import sky
    from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                                 neighbor_statistics_job, plan_tiers,
                                 run_job, token_histogram)

    mesh = mk((8,), ("data",))

    # tier counts not divisible by 8
    for codec in ("identity", "int16"):
        for n, seed, radius in [(700, 3, 0.09), (150, 1, 0.25)]:
            xyz = sky.make_catalog(n, seed)
            j = neighbor_search_job(radius, codec=codec, tile=64)
            d = run_job(j, xyz, mesh=mesh, engine="device").output
            h = run_job(j, xyz, mesh=mesh, engine="host").output
            assert d == h, (codec, n, d, h)

    # skewed catalog: crowded tier has fewer real partitions than shards
    rng = np.random.default_rng(11)
    sk = sky.make_catalog(900, 1)
    extra = sk[:1] + rng.normal(0, 1e-3, (600, 3))
    sk = np.concatenate([sk, extra])
    sk = (sk / np.linalg.norm(sk, axis=1, keepdims=True)).astype(np.float32)
    j = neighbor_search_job(0.08, tile=64)
    assert (run_job(j, sk, mesh=mesh, engine="device").output
            == run_job(j, sk, mesh=mesh, engine="host").output)
    part = ZonePartitioner(0.08)
    keys = part.assign(sk)
    no = np.bincount(keys, minlength=part.n_partitions(sk))
    plan = plan_tiers(no, no * 2, 64, pad_partitions_to=8)
    assert any(len(ids) < 8 for ids, _, _ in plan), (
        "skew did not produce a sub-shard tier")

    # zero-entry partitions + empty catalog
    toks = np.random.default_rng(0).integers(0, 3, 1000)
    hd = token_histogram(toks, 3, n_partitions=8, tile=64, mesh=mesh,
                         engine="device").output
    hh = token_histogram(toks, 3, n_partitions=8, tile=64, mesh=mesh,
                         engine="host").output
    np.testing.assert_array_equal(hd, hh)
    np.testing.assert_array_equal(hd, np.bincount(toks, minlength=3))
    xyz0 = np.zeros((0, 3), np.float32)
    assert run_job(neighbor_search_job(0.05, tile=64), xyz0, mesh=mesh,
                   engine="device").output == 0
    np.testing.assert_array_equal(
        run_job(neighbor_statistics_job([10.0, 20.0], tile=64), xyz0,
                mesh=mesh, engine="device").output, [0, 0])
    print("mapreduce ragged shards == host mesh oracle OK")


def check_mapreduce_streaming_sharded():
    """Split-streaming executor on an 8-device data mesh: streaming over
    2 and 5 splits (and n-splits-of-1 for a small catalog) is bit-identical
    to the monolithic mesh run for the batched paper apps (identity and
    int16 codecs — no combiner exists for pair kernels, so the accumulated
    wire streams cross one sharded reduce) and for wordcount with the
    map-side combiner on, off, and auto (per-split psum-sharded reduce,
    cross-split combine on the replicated partial)."""
    from repro.core.compat import make_mesh as mk
    from repro.data import ArraySplits, sky
    from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                                 neighbor_statistics_job, run_job_streaming,
                                 run_jobs, run_jobs_streaming,
                                 token_histogram_job)

    mesh = mk((8,), ("data",))
    radius = 0.09
    edges = np.linspace(0.03, radius, 4)
    for codec in ("identity", "int16"):
        part = ZonePartitioner(radius)
        jobs = [neighbor_search_job(radius, partitioner=part, codec=codec,
                                    tile=64),
                neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                        codec=codec, tile=64)]
        xyz = sky.make_catalog(900, 5)
        mono = run_jobs(jobs, xyz, mesh=mesh)
        for n_splits in (2, 5):
            srun = run_jobs_streaming(jobs, ArraySplits(xyz, n_splits),
                                      mesh=mesh)
            assert srun[0].stats.n_splits == n_splits
            assert srun[0].output == mono[0].output, (codec, n_splits)
            np.testing.assert_array_equal(srun[1].output, mono[1].output)
        small = xyz[:40]
        mono_s = run_jobs(jobs, small, mesh=mesh)
        ones = run_jobs_streaming(jobs, ArraySplits(small, 40), mesh=mesh)
        assert ones[0].output == mono_s[0].output, codec
        np.testing.assert_array_equal(ones[1].output, mono_s[1].output)

    toks = np.random.default_rng(2).integers(0, 300, 6000)
    items = toks.astype(np.float32).reshape(-1, 1)
    job = token_histogram_job(300, n_partitions=16, tile=64)
    want = np.bincount(toks, minlength=300)
    for combiner in (None, "auto", job.reducer.combiner()):
        res = run_job_streaming(job, ArraySplits(items, 4), mesh=mesh,
                                combiner=combiner)
        np.testing.assert_array_equal(res.output, want)
        if combiner is not None:
            assert res.stats.combiner == "token_count"
    print("mapreduce streaming == monolithic on 8-shard mesh OK")


def check_mapreduce_sharded():
    """Job engine: sharded-mesh results == mesh=None results, for both paper
    apps (batched over one shuffle) and the wordcount job."""
    from repro.data import sky
    from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                                 neighbor_statistics_job, run_jobs,
                                 token_histogram)
    mesh = make_mesh((4, 2), ("data", "model"))
    xyz = sky.make_catalog(1200, 7)
    radius = 0.1
    part = ZonePartitioner(radius)
    edges = np.linspace(0.02, radius, 5)
    jobs = [neighbor_search_job(radius, partitioner=part, tile=64),
            neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                    tile=64)]
    r1 = run_jobs(jobs, xyz, mesh=None)
    r8 = run_jobs(jobs, xyz, mesh=mesh)
    assert r1[0].output == r8[0].output, (r1[0].output, r8[0].output)
    np.testing.assert_array_equal(r1[1].output, r8[1].output)
    assert r8[0].output == sky.brute_force_pairs(xyz, radius)

    toks = np.random.default_rng(1).integers(0, 500, 4000)
    h1 = token_histogram(toks, 500, n_partitions=8, tile=64).output
    h8 = token_histogram(toks, 500, n_partitions=8, tile=64,
                         mesh=mesh).output
    np.testing.assert_array_equal(h1, h8)
    np.testing.assert_array_equal(h1, np.bincount(toks, minlength=500))
    print("mapreduce sharded == single-device OK")


def check_mapreduce_service_sharded():
    """MR query service on an 8-device data mesh: queries served from the
    resident psum-sharded catalog (micro-batched, duplicates coalesced) are
    bit-identical to a fresh per-query mesh run AND to the host-engine
    oracle; catalog reuse across batches never reshuffles."""
    from repro.core.compat import make_mesh as mk
    from repro.data import sky
    from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                                 neighbor_statistics_job, run_job)
    from repro.serving.mr_service import MRQueryService

    mesh = mk((8,), ("data",))
    xyz = sky.make_catalog(900, 5)
    radius = 0.09
    part = ZonePartitioner(radius)
    edges = np.linspace(0.03, radius, 4)
    jobs = [neighbor_search_job(radius, partitioner=part, codec="int16",
                                tile=64),
            neighbor_search_job(radius / 2, partitioner=part, codec="int16",
                                tile=64),
            neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                    codec="int16", tile=64)]
    svc = MRQueryService(mesh=mesh, max_batch=4)
    cat = svc.load_catalog("sky", xyz, part, codec="int16", tile=64)
    assert cat.run(jobs[0])[0].stats.n_shards == 8
    reqs = [svc.submit(j, catalog="sky") for j in jobs + jobs]
    svc.run_pending()                  # batches of 4: [j0 j1 j2 j0] [j1 j2]
    assert [b["size"] for b in svc.batches] == [4, 2]
    assert svc.batches[0]["n_unique"] == 3       # duplicate j0 coalesced
    for r, j in zip(reqs, jobs + jobs):
        dev = run_job(j, xyz, mesh=mesh).output
        host = run_job(j, xyz, mesh=mesh, engine="host").output
        np.testing.assert_array_equal(r.output, dev)
        np.testing.assert_array_equal(np.asarray(dev), np.asarray(host))
    svc.close()
    print("mapreduce service on 8-shard mesh == per-query mesh/host OK")


def check_mapreduce_lanes_sharded():
    """Concurrent split lanes across 8 host devices: with no mesh, each
    lane pins its worker to devices[lane % n_devices] so independent splits
    map/shuffle/reduce on different devices concurrently — results must be
    bit-identical to the monolithic single-device run, with injected chaos
    (seeded delays + transient faults + speculation) and without."""
    from repro.data import ArraySplits, sky
    from repro.ft import FaultySplitSource, SpeculativeConfig
    from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                                 run_job, run_job_streaming,
                                 token_histogram_job)

    assert len(jax.devices()) == 8
    radius = 0.09
    part = ZonePartitioner(radius)
    job = neighbor_search_job(radius, partitioner=part, codec="int16",
                              tile=64)
    xyz = sky.make_catalog(900, 5)
    want = run_job(job, xyz).output

    # plain per-device lanes, one lane per host device
    res = run_job_streaming(job, ArraySplits(xyz, 8), n_lanes=8)
    assert res.output == want
    assert res.stats.n_lanes == 8 and len(res.stats.lane_walls) == 8

    # chaos on top: seeded delays + transient faults + speculation
    src = FaultySplitSource(ArraySplits(xyz, 8), seed=0, delay_p=0.4,
                            fault_p=0.4, delay_s=0.05, max_faults=2)
    res2 = run_job_streaming(
        job, src, n_lanes=8, max_retries=2, retry_backoff_s=0.01,
        speculate=SpeculativeConfig(slowdown=2.0, min_finished=2))
    assert res2.output == want

    # wordcount combine mode across lanes (order-free monoid merge)
    toks = np.random.default_rng(2).integers(0, 300, 6000)
    items = toks.astype(np.float32).reshape(-1, 1)
    wjob = token_histogram_job(300, n_partitions=16, tile=64)
    wres = run_job_streaming(wjob, ArraySplits(items, 8), n_lanes=8)
    np.testing.assert_array_equal(wres.output,
                                  np.bincount(toks, minlength=300))
    print("mapreduce lanes across 8 devices == monolithic OK")


def check_mapreduce_cluster():
    """The cluster shuffle on a four-chip ``data`` mesh (4 host devices):
    each chip maps its own block of rows and one all-to-all moves every
    entry to its partition's owner. On a seeded clustered catalog over 32
    zones, with a row count the chips do not divide:

    (a) ``run_jobs`` on the mesh equals the one-device device path bit for
        bit (identity and int16) and the host engine (identity);
    (b) each chip's slice of every resident tier holds exactly the rows the
        one-device layout holds for the partitions it owns (rows within a
        partition in any order), padding and phantom partitions zero;
    (c) the ``shuffle.exchange`` span's byte counter equals the owned rows
        and bucket entries whose owner is not their block's chip, times the
        wire bytes of a row.
    """
    import jax.numpy as jnp
    from repro.data import sky
    from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                                 neighbor_statistics_job, run_jobs,
                                 shuffle_once)
    from repro.mapreduce.codecs import get_codec
    from repro.obs.trace import Tracer, use_tracer

    D = 4
    mesh = make_mesh((D,), ("data",))
    rng = np.random.default_rng(15)
    xyz = sky.make_catalog(2403, 15)
    centres = xyz[rng.integers(0, len(xyz), 12)]
    clumps = np.repeat(centres, 50, axis=0) + rng.normal(0, 0.01, (600, 3))
    xyz = np.concatenate([xyz, clumps])
    xyz = (xyz / np.linalg.norm(xyz, axis=1, keepdims=True)).astype(
        np.float32)[rng.permutation(len(xyz))]
    radius, tile = 0.1, 64
    part = ZonePartitioner(radius)
    assert part.n_partitions(xyz) >= 8
    edges = np.linspace(0.02, radius, 5)

    # (a)
    for codec in ("identity", "int16"):
        jobs = [neighbor_search_job(radius, partitioner=part, tile=tile,
                                    codec=codec),
                neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                        tile=tile, codec=codec)]
        r4 = run_jobs(jobs, xyz, mesh=mesh, engine="device")
        r1 = run_jobs(jobs, xyz, engine="device")
        assert r4[0].output == r1[0].output, (codec, r4[0].output,
                                              r1[0].output)
        np.testing.assert_array_equal(r4[1].output, r1[1].output)
        assert r4[0].stats.n_shards == D and r4[0].stats.exchange_rows > 0
        if codec == "identity":
            rh = run_jobs(jobs, xyz, engine="host")
            assert r4[0].output == rh[0].output == sky.brute_force_pairs(
                xyz, radius)
            np.testing.assert_array_equal(r4[1].output, rh[1].output)

    # (b), (c)
    codec = "identity"
    tracer = Tracer()
    with use_tracer(tracer):
        cat = shuffle_once(part, xyz, codec=codec, tile=tile, mesh=mesh)
    one = shuffle_once(part, xyz, codec=codec, tile=tile)
    where = {}
    for t in one.sd.tiers:
        for slot, p in enumerate(t.part_ids):
            where[int(p)] = (t, slot)

    def canon(rows):
        return rows[np.lexsort(rows.T[::-1])]

    owner = np.full(cat.P + 1, D)
    for t in cat.sd.tiers:
        q = t.Pt // D
        owner[t.part_ids] = np.arange(len(t.part_ids)) // q
        for name, wire, C, counts in (
                ("owned", t.owned_wire[0], t.C1, cat.sd.n_owned),
                ("bucket", t.bucket_wire[0], t.C2, cat.sd.n_bucket)):
            chips = set()
            for shard in wire.addressable_shards:
                chips.add(shard.device)
                lo = shard.index[0].start or 0
                assert shard.data.shape == (q, C, 3), shard.data.shape
                local = np.asarray(shard.data)
                for j in range(q):
                    slot = lo + j
                    if slot >= len(t.part_ids):     # phantom partition
                        assert not local[j].any(), (name, slot)
                        continue
                    p = int(t.part_ids[slot])
                    n = int(counts[p])
                    t1, s1 = where[p]
                    ref = np.asarray((t1.owned_wire if name == "owned"
                                      else t1.bucket_wire)[0][s1])
                    np.testing.assert_array_equal(canon(local[j, :n]),
                                                  canon(ref[:n]))
                    assert not local[j, n:].any(), (name, p)
            assert len(chips) == D, (name, chips)

    # independent count of the entries that leave their home chip
    keys = np.asarray(part.assign_device(jnp.asarray(xyz)))
    dest, src, valid = (np.asarray(a) for a in part.bucket_entries_device(
        jnp.asarray(xyz), jnp.asarray(keys), cat.P))
    step = -(-len(xyz) // D)
    home = np.arange(len(xyz)) // step
    crossing = (int(np.sum(owner[keys] != home))
                + int(np.sum(valid & (owner[np.where(valid, dest, cat.P)]
                                      != home[src]))))
    row_bytes = get_codec(codec).device_bytes_per_item(3)
    spans = [e for e in tracer.events if e["name"] == "shuffle.exchange"]
    assert len(spans) == 1, [e["name"] for e in tracer.events]
    args = spans[0]["args"]
    assert args["exchange_rows"] == crossing, (args, crossing)
    assert args["exchange_bytes"] == crossing * row_bytes, (args, crossing)
    assert cat.load_stats.exchange_bytes == crossing * row_bytes
    n_entries = len(xyz) + int(valid.sum())
    assert 0.6 < crossing / n_entries < 0.9, crossing / n_entries
    print(f"mapreduce cluster shuffle on 4 chips OK ({crossing} of "
          f"{n_entries} entries crossed)")


if __name__ == "__main__":
    checks = {
        "hier": check_hierarchical_psum,
        "compressed": check_compressed_psum,
        "moe": check_moe_multidevice,
        "train": check_train_step_sharded,
        "mapreduce": check_mapreduce_sharded,
        "mapreduce-device": check_mapreduce_device_sharded,
        "mapreduce-ragged": check_mapreduce_ragged_shards,
        "mapreduce-streaming": check_mapreduce_streaming_sharded,
        "mapreduce-service": check_mapreduce_service_sharded,
        "mapreduce-lanes": check_mapreduce_lanes_sharded,
        "mapreduce-cluster": check_mapreduce_cluster,
    }
    checks[sys.argv[1]]()
