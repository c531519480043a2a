"""One benchmark per paper exhibit. Each emits ``name,us_per_call,derived`` CSV rows.

Paper exhibit -> TPU-framework analogue:
  Figure 1 (direct I/O vs page cache)   -> fig1: donated vs copied state update
  Table 2  (network I/O is CPU-heavy)   -> table2: wire bytes flat/hier/int8 sync
  Figure 2 (HDFS throughput vs mappers) -> fig2: pipeline throughput vs hosts
  Figure 3 (buffering/LZO/direct I/O)   -> fig3: zones app with batching/compression
  Table 3  (app runtimes vs theta)      -> table3: neighbor search/stats vs radius
  Table 4  (Amdahl numbers per task)    -> table4: balance table from dry-run artifacts
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _t(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6, out


def _subprocess_bench_json(script: str, name: str):
    """Run a multi-device bench snippet in a subprocess on forced host CPU
    devices (they need their own process, and the parent may hold the
    chip) and parse its last stdout line as JSON. A failed child raises."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"{name} child failed (rc={r.returncode}): "
                           f"{r.stderr.strip()[-400:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def fig1_direct_io():
    """Donation (direct I/O analogue): in-place update vs copy on a 64MB state."""
    rows = []
    x = jnp.zeros((16 << 20,), jnp.float32)              # 64 MB
    g = jnp.ones_like(x) * 1e-3

    upd = lambda s, g: s * 0.999 + g
    f_copy = jax.jit(upd)
    f_donate = jax.jit(upd, donate_argnums=(0,))

    us_copy, _ = _t(lambda: f_copy(x, g), reps=10)
    state = x
    def donate_step():
        nonlocal state
        state = f_donate(state, g)
        return state
    us_don, _ = _t(donate_step, reps=10)
    rows.append(("fig1_update_copy", us_copy, f"bytes_moved={x.nbytes*2}"))
    rows.append(("fig1_update_donated", us_don,
                 f"bytes_moved={x.nbytes}_alias_in_place"))
    return rows


def table2_network():
    """Collective wire bytes for flat vs hierarchical vs int8 sync of a 64MB
    gradient on a 2x2x2 mesh (analyzed from SPMD HLO in a subprocess)."""
    script = r"""
import os
os.environ["XLA_FLAGS"]="--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, %r)
import json, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.collectives import hierarchical_psum_1d
from repro.core.compat import make_mesh, shard_map
from repro.core.compression import compressed_psum_1d
from repro.core.hlo_analysis import analyze_hlo
mesh = make_mesh((2,2,2), ("pod","data","model"))
n = 16 << 20
x = jax.ShapeDtypeStruct((n,), jnp.float32)
out = {}
for name, body in {
  "flat": lambda v: jax.lax.psum(v, ("pod","data")),
  "hier": lambda v: hierarchical_psum_1d(v, "data", "pod"),
  "hier_int8": lambda v: hierarchical_psum_1d(v, "data", "pod", codec="int8"),
}.items():
    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          axis_names=frozenset({"pod","data"})))
    hlo = f.lower(x).compile().as_text()
    a = analyze_hlo(hlo, pod_size=4)
    out[name] = {"intra": a.coll_wire_intra, "cross": a.coll_wire_cross}
print(json.dumps(out))
""" % (os.path.join(ROOT, "src"),)
    data = _subprocess_bench_json(script, "table2")
    return [(f"table2_sync_{name}", 0.0,
             f"wire_intra={d['intra']:.3g}_cross={d['cross']:.3g}")
            for name, d in data.items()]


def fig2_pipeline():
    """Data pipeline throughput vs number of reader hosts (HDFS mappers)."""
    from repro.data import Pipeline, PipelineConfig, SyntheticTokens, MemmapTokens
    rows = []
    B, S = 48, 1024            # divisible by 1..3 hosts
    for n_hosts in (1, 2, 3):
        src = SyntheticTokens(50000, 0)
        pipes = [Pipeline(src, PipelineConfig(B, S, host_id=h, n_hosts=n_hosts))
                 for h in range(n_hosts)]
        t0 = time.perf_counter()
        steps = 20
        for s in range(steps):
            for p in pipes:
                p.batch_at(s)
        dt = time.perf_counter() - t0
        mbs = steps * B * S * 4 / dt / 1e6
        rows.append((f"fig2_synthetic_{n_hosts}hosts", dt / steps * 1e6,
                     f"{mbs:.0f}MBps"))
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tok.bin")
        MemmapTokens.write(path, np.random.randint(0, 1000, (256, S)))
        src = MemmapTokens(path, S)
        pipe = Pipeline(src, PipelineConfig(B, S))
        t0 = time.perf_counter()
        for s in range(20):
            pipe.batch_at(s)
        dt = time.perf_counter() - t0
        rows.append(("fig2_memmap_1host", dt / 20 * 1e6,
                     f"{20*B*S*4/dt/1e6:.0f}MBps"))
    return rows


def fig3_improvements():
    """Neighbor Searching with the paper's improvements applied stepwise —
    each variant is the SAME job with a stage swapped (block size via tile /
    zone_height, shuffle codec via the registry), through the Job API's
    device engine. Each variant reports the best of 5 timed runs after one
    warmup (the warmup/rep convention ``_t`` applies to every other bench);
    lossy codecs are labeled ``exact=False`` with their pair-count delta vs
    the identity-codec row (int8's silent ~3x overcount in PR1 is now
    visible in the row itself)."""
    from repro.data import sky
    from repro.mapreduce import get_codec, neighbor_search_job, run_job
    xyz = sky.make_catalog(20000, 0)
    radius = 0.02
    rows = []
    variants = {
        # buffering analogue = the paper's block-size tuning ("always favor larger
        # blocks"): 4x-taller zones -> fewer, fuller buckets, less border copying
        "baseline": dict(tile=64, codec="identity"),
        "bigger_blocks": dict(tile=256, zone_height=4 * radius),
        "compressed_int16": dict(tile=64, codec="int16"),    # LZO analogue
        "compressed_int8": dict(tile=64, codec="int8"),      # heavier codec
        "blocks+int16": dict(tile=256, zone_height=4 * radius, codec="int16"),
    }
    base_pairs = None
    for name, kw in variants.items():
        job = neighbor_search_job(radius, **kw)
        run_job(job, xyz)                       # warmup (compile caches)
        res = min((run_job(job, xyz) for _ in range(5)),
                  key=lambda r: r.stats.wall_s)
        st = res.stats
        if base_pairs is None:
            base_pairs = int(res.output)
        codec = get_codec(job.codec)
        lossy = ("" if codec.exact else
                 f"_exact=False_dpairs={int(res.output) - base_pairs:+d}")
        rows.append((f"fig3_{name}", st.wall_s * 1e6,
                     f"pairs={res.output}_shuffleB={st.shuffle_wire_bytes}"
                     f"_ratio={st.compression_ratio:.1f}"
                     f"_domstage={st.dominant_stage}"
                     f"_padratio={st.reduce_padded_ratio:.2f}{lossy}"))
    rows += _fig3_sharded()
    return rows


def _fig3_sharded():
    """Sharded-mesh rows for fig3: the SAME search job on an 8-shard data
    mesh through both engines (subprocess, 8 forced host devices), so the
    device-vs-host crossover under sharding — the paper's "spread the
    reduce across more cores" claim — is measurable next to the
    single-device rows. Same warmup + best-of-5 convention."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, %r)
import json
import numpy as np
from repro.core.compat import make_mesh
from repro.data import sky
from repro.mapreduce import neighbor_search_job, run_job

mesh = make_mesh((8,), ("data",))
xyz = sky.make_catalog(20000, 0)
job = neighbor_search_job(0.02, tile=64, codec="int16")
out = {}
for engine in ("device", "host"):
    run_job(job, xyz, mesh=mesh, engine=engine)            # warmup
    res = min((run_job(job, xyz, mesh=mesh, engine=engine)
               for _ in range(5)), key=lambda r: r.stats.wall_s)
    st = res.stats
    out[engine] = {"us": st.wall_s * 1e6, "pairs": int(res.output),
                   "n_shards": st.n_shards,
                   "maxshardpad": max(st.shard_padded_ratio)}
print(json.dumps(out))
""" % (os.path.join(ROOT, "src"),)
    data = _subprocess_bench_json(script, "fig3_sharded")
    return [(f"fig3_sharded_{engine}_8shard", d["us"],
             f"pairs={d['pairs']}_nshards={d['n_shards']}"
             f"_maxshardpad={d['maxshardpad']:.2f}")
            for engine, d in data.items()]


def fig4_streaming():
    """Split-streaming executor rows (the Hadoop behaviors themselves, not a
    single paper exhibit): an out-of-core catalog 8x the per-split size
    streamed from a memmap file, map-side combine on vs off for wordcount
    (shuffle-byte and wall deltas), and the transfer/compute overlap
    fraction. Same warmup + best-of-3 convention as fig3."""
    import tempfile
    from repro.data import (ArraySplits, MemmapCatalogSplits, MemmapTokens,
                            TokenBlockSplits, sky)
    from repro.mapreduce import (neighbor_search_job, run_job,
                                 run_job_streaming, token_histogram_job)

    def best(fn, reps=3):
        fn()                                    # warmup (compile caches)
        return min((fn() for _ in range(reps)), key=lambda r: r.stats.wall_s)

    rows = []
    with tempfile.TemporaryDirectory() as d:
        # out-of-core neighbor search: a memmap catalog 8x the split size
        # streams split-by-split; the raw catalog is never whole on device
        # (only the accumulated int16 wire stream persists, at half size)
        xyz = sky.make_catalog(40000, 0)
        cat = os.path.join(d, "catalog.f32")
        MemmapCatalogSplits.write(cat, xyz)
        src = MemmapCatalogSplits(cat, d=3, rows_per_split=5000)
        job = neighbor_search_job(0.02, codec="int16", tile=256)
        res = best(lambda: run_job_streaming(job, src))
        st = res.stats
        mono = best(lambda: run_job(job, xyz))
        rows.append(("fig4_stream_outofcore_search_8x", st.wall_s * 1e6,
                     f"pairs={res.output}_nsplits={st.n_splits}"
                     f"_splitrows={src.rows_per_split}_totalrows={src.n_rows}"
                     f"_overlapfrac={st.overlap_fraction:.2f}"
                     f"_monolithic_us={mono.stats.wall_s * 1e6:.0f}"))
        assert res.output == mono.output, (res.output, mono.output)

        # out-of-core wordcount with map-side combine: only the combined
        # [vocab] accumulator persists across splits (O(vocab) device memory)
        vocab, seq, rows_per, n_splits = 2048, 1024, 16, 8
        tok = os.path.join(d, "tokens.bin")
        rng = np.random.default_rng(0)
        MemmapTokens.write(tok, rng.integers(0, vocab,
                                             (rows_per * n_splits, seq)))
        tsrc = TokenBlockSplits(MemmapTokens(tok, seq), seq_len=seq,
                                rows_per_split=rows_per, n_splits=n_splits)
        wjob = token_histogram_job(vocab, n_partitions=16, tile=256)
        on = best(lambda: run_job_streaming(wjob, tsrc))
        rows.append(("fig4_stream_outofcore_wordcount_8x",
                     on.stats.wall_s * 1e6,
                     f"tokens={rows_per * n_splits * seq}"
                     f"_nsplits={on.stats.n_splits}"
                     f"_combiner={on.stats.combiner}"
                     f"_overlapfrac={on.stats.overlap_fraction:.2f}"))

        # combiner on vs off: same source, wire bytes and wall side by side
        off = best(lambda: run_job_streaming(wjob, tsrc, combiner=None))
        ratio = off.stats.shuffle_wire_bytes / on.stats.shuffle_wire_bytes
        np.testing.assert_array_equal(on.output, off.output)
        rows.append(("fig4_stream_combiner_on", on.stats.wall_s * 1e6,
                     f"shuffleB={on.stats.shuffle_wire_bytes}"
                     f"_vs_off_ratio={ratio:.1f}"))
        rows.append(("fig4_stream_combiner_off", off.stats.wall_s * 1e6,
                     f"shuffleB={off.stats.shuffle_wire_bytes}"))
        assert ratio >= 2.0, f"combiner wire reduction below gate: {ratio}"

    # in-memory split streaming vs monolithic (executor overhead + overlap)
    xyz = sky.make_catalog(20000, 0)
    job = neighbor_search_job(0.02, codec="int16", tile=256)
    srun = best(lambda: run_job_streaming(job, ArraySplits(xyz, 4)))
    st = srun.stats
    exposed = st.fetch_wall_s
    rows.append(("fig4_stream_search_4split", st.wall_s * 1e6,
                 f"pairs={srun.output}_nsplits=4"
                 f"_overlapfrac={st.overlap_fraction:.2f}"
                 f"_exposedfetch_us={exposed * 1e6:.0f}"
                 f"_hidden_us={st.overlap_hidden_s * 1e6:.0f}"))
    return rows


def fig5_service():
    """MapReduce-as-a-service (the workload-consolidation argument): a
    resident sharded catalog answers a stream of small neighbor-search /
    statistics queries through the submit queue + admission window. Rows:
    the sequential run_job-per-query baseline (every query pays its own
    map+shuffle+reduce), the closed-loop batched service (gated >= 3x that
    baseline), and paced offered loads with p50/p99 latency — all steady
    state (warmup pass first, the ``_t`` convention)."""
    from repro.data import sky
    from repro.mapreduce import (ZonePartitioner, latency_summary,
                                 neighbor_search_job,
                                 neighbor_statistics_job, run_job)
    from repro.serving.mr_service import MRQueryService

    xyz = sky.make_catalog(20000, 0)
    R = 0.02
    part = ZonePartitioner(R)
    edges = np.linspace(R / 4, R, 4)
    distinct = [neighbor_search_job(r, partitioner=part, codec="int16",
                                    tile=256) for r in (R, R / 2, R / 4)]
    distinct.append(neighbor_statistics_job(edges / sky.ARCSEC,
                                            partitioner=part, codec="int16",
                                            tile=256))
    n_req = 32
    mix = [distinct[i % len(distinct)] for i in range(n_req)]

    # sequential baseline: one full map+shuffle+reduce per query
    for j in distinct:
        run_job(j, xyz)                        # warmup (compile caches)
    t0 = time.perf_counter()
    seq_out = [run_job(j, xyz).output for j in mix]
    seq_s = time.perf_counter() - t0
    rows = [("fig5_service_sequential", seq_s / n_req * 1e6,
             f"nreq={n_req}_ndistinct={len(distinct)}"
             f"_qps={n_req / seq_s:.1f}")]

    svc = MRQueryService(max_batch=16, max_wait_s=0.002)
    t0 = time.perf_counter()
    svc.load_catalog("sky", xyz, part, codec="int16", tile=256)
    load_s = time.perf_counter() - t0

    def burst():
        reqs = [svc.submit(j, catalog="sky") for j in mix]
        svc.run_pending()
        return [r.output for r in reqs]

    outs = burst()                             # warmup
    for got, want in zip(outs, seq_out):       # service == per-query runs
        np.testing.assert_array_equal(got, want)
    svc.request_stats.clear()
    svc.batches.clear()
    t0 = time.perf_counter()
    burst()
    svc_s = time.perf_counter() - t0
    s = latency_summary(svc.request_stats)
    speedup = seq_s / svc_s
    rows.append(("fig5_service_batched", svc_s / n_req * 1e6,
                 f"qps={n_req / svc_s:.0f}_speedup={speedup:.1f}x"
                 f"_p50ms={s['p50_ms']:.1f}_p99ms={s['p99_ms']:.1f}"
                 f"_meanbatch={s['mean_batch']:.1f}"
                 f"_shuffleonce_s={load_s:.2f}"))
    assert speedup >= 3.0, \
        f"batched service below 3x-vs-sequential gate: {speedup:.2f}x"

    # offered-load sweep: pace arrivals at fractions of burst capacity
    # through the background admission thread; latency vs throughput
    cap_qps = n_req / svc_s
    svc.start()
    for label, frac in (("0.5x", 0.5), ("1x", 1.0), ("2x", 2.0)):
        svc.request_stats.clear()
        offered = cap_qps * frac
        gap = 1.0 / offered
        t0 = time.perf_counter()
        reqs = []
        for i, j in enumerate(mix):
            target = t0 + i * gap
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            reqs.append(svc.submit(j, catalog="sky"))
        for r in reqs:
            r.result(timeout=300)
        wall = time.perf_counter() - t0
        s = latency_summary(svc.request_stats)
        rows.append((f"fig5_service_load{label}", wall / n_req * 1e6,
                     f"offered_qps={offered:.0f}"
                     f"_achieved_qps={s['qps']:.0f}"
                     f"_p50ms={s['p50_ms']:.1f}_p99ms={s['p99_ms']:.1f}"
                     f"_waitp99ms={s['wait_p99_ms']:.1f}"
                     f"_meanbatch={s['mean_batch']:.1f}"))
    svc.close()
    return rows


def fig6_speculation():
    """Speculative re-execution recovering an injected straggler (Hadoop's
    speculative-task claim, measured end to end on the lane scheduler).
    Three runs of the same 8-split neighbor search on 4 concurrent lanes:
    clean (no fault), a straggler split whose first fetch stalls ~3x the
    clean wall with speculation OFF (the stall is served out), and the same
    straggler with speculation ON (the slow attempt is cloned onto a free
    lane, the clone's fast re-fetch wins, the stalled original is cancelled
    mid-sleep). Gates: without speculation the straggler costs >= 2x the
    clean wall; with it the wall lands within 1.3x clean, recovering >= 70%%
    of the injected slowdown — and all three runs are bit-identical."""
    from repro.data import ArraySplits, sky
    from repro.ft import FaultySplitSource, SpeculativeConfig
    from repro.mapreduce import neighbor_search_job, run_job_streaming

    xyz = sky.make_catalog(20000, 0)
    job = neighbor_search_job(0.02, codec="int16", tile=256)
    n_splits, n_lanes = 8, 4
    spec_cfg = SpeculativeConfig(slowdown=1.5, min_finished=2, max_clones=1)

    def lanes_run(src, speculate=None):
        return run_job_streaming(job, src, n_lanes=n_lanes,
                                 speculate=speculate)

    def clean_src():
        return ArraySplits(xyz, n_splits)

    lanes_run(clean_src())                      # warmup (compile caches)
    clean = min((lanes_run(clean_src()) for _ in range(2)),
                key=lambda r: r.stats.elapsed_s)
    t_clean = clean.stats.elapsed_s
    rows = [("fig6_spec_nostraggler", t_clean * 1e6,
             f"pairs={clean.output}_nsplits={n_splits}_nlanes={n_lanes}")]

    delay = 3.0 * t_clean                       # the injected straggler

    def straggler_src():
        return FaultySplitSource(clean_src(), delays={0: delay})

    # speculation OFF: the stalled fetch is served out in full
    nospec = lanes_run(straggler_src())
    t_nospec = nospec.stats.elapsed_s
    rows.append(("fig6_spec_straggler_nospec", t_nospec * 1e6,
                 f"delay_s={delay:.2f}_slowdown={t_nospec / t_clean:.1f}x"))

    # speculation ON: clone wins, stalled original cancelled mid-sleep
    spec = min((lanes_run(straggler_src(), speculate=spec_cfg)
                for _ in range(2)), key=lambda r: r.stats.elapsed_s)
    t_spec = spec.stats.elapsed_s
    recovered = (t_nospec - t_spec) / (t_nospec - t_clean)
    rows.append(("fig6_spec_straggler_spec", t_spec * 1e6,
                 f"speculated={spec.stats.speculated}"
                 f"_clonewins={spec.stats.clone_wins}"
                 f"_vs_clean={t_spec / t_clean:.2f}x"
                 f"_recovered={recovered:.2f}"))

    assert clean.output == nospec.output == spec.output   # bit parity
    assert spec.stats.speculated >= 1 and spec.stats.clone_wins >= 1
    assert t_nospec >= 2.0 * t_clean, \
        f"injected straggler too cheap: {t_nospec / t_clean:.2f}x clean"
    assert t_spec <= 1.3 * t_clean, \
        f"speculation failed to recover: {t_spec / t_clean:.2f}x clean"
    assert recovered >= 0.7, \
        f"recovered only {recovered:.0%} of the injected slowdown"
    return rows


def table3_apps():
    """App runtimes vs radius (the paper's theta sweep) through the Job API,
    with the per-job Amdahl numbers the paper's Table 4 derives per task —
    plus the batched search+stats pass and the wordcount job. Steady state:
    each row runs once for warmup (compile caches) and reports the second
    run, the ``_t`` convention."""
    from repro.data import sky
    from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                                 neighbor_statistics_job, run_job, run_jobs,
                                 token_histogram)
    xyz = sky.make_catalog(20000, 1)
    rows = []

    def steady(fn):
        fn()
        return fn()

    for radius, label in [(0.01, "15as_scaled"), (0.02, "30as_scaled"),
                          (0.04, "60as_scaled")]:
        res = steady(lambda: run_job(neighbor_search_job(radius, tile=256),
                                     xyz))
        am = res.stats.roofline().amdahl_numbers()
        rows.append((f"table3_search_{label}", res.stats.wall_s * 1e6,
                     f"pairs={res.output}_AD={am['AD']:.2g}"))
    edges = np.linspace(0.005, 0.04, 8)
    res = steady(lambda: run_job(neighbor_statistics_job(
        edges / sky.ARCSEC, tile=256), xyz))
    rows.append(("table3_stats", res.stats.wall_s * 1e6,
                 f"pairs_total={int(res.output.sum())}"))
    # both apps batched over ONE shuffle (the Job API's multi-job batching)
    part = ZonePartitioner(float(edges[-1]))
    batched = steady(lambda: run_jobs(
        [neighbor_search_job(float(edges[-1]), partitioner=part, tile=256),
         neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                 tile=256)], xyz))
    rows.append(("table3_search+stats_batched", batched[0].stats.wall_s * 1e6,
                 f"pairs={batched[0].output}"))
    # non-astronomy workload on the same engine (Hadoop's wordcount)
    from repro.data import SyntheticTokens
    toks = SyntheticTokens(50000, 0).block(0, 64, 1024)
    res = steady(lambda: token_histogram(toks, 50000, n_partitions=16))
    rows.append(("table3_wordcount_64x1024", res.stats.wall_s * 1e6,
                 f"tokens={toks.size}_top={int(res.output.max())}"
                 f"_domstage={res.stats.dominant_stage}"))
    rows += _table3_sharded()
    return rows


def _table3_sharded():
    """The batched search+stats pass on an 8-shard data mesh through the
    sharded device engine (subprocess, 8 forced host devices) — the
    multi-node analogue of the paper's per-app runtime rows."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, %r)
import json
import numpy as np
from repro.core.compat import make_mesh
from repro.data import sky
from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                             neighbor_statistics_job, run_jobs)

mesh = make_mesh((8,), ("data",))
xyz = sky.make_catalog(20000, 1)
edges = np.linspace(0.005, 0.04, 8)
part = ZonePartitioner(float(edges[-1]))
jobs = [neighbor_search_job(float(edges[-1]), partitioner=part, tile=256),
        neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                tile=256)]
run_jobs(jobs, xyz, mesh=mesh, engine="device")            # warmup
rs = run_jobs(jobs, xyz, mesh=mesh, engine="device")
print(json.dumps({"us": rs[0].stats.wall_s * 1e6,
                  "pairs": int(rs[0].output),
                  "n_shards": rs[0].stats.n_shards}))
""" % (os.path.join(ROOT, "src"),)
    d = _subprocess_bench_json(script, "table3_sharded")
    return [("table3_search+stats_sharded_8shard", d["us"],
             f"pairs={d['pairs']}_nshards={d['n_shards']}_engine=device")]


def table4_amdahl():
    """Balance (Amdahl) table: per-JOB rows from MapReduce ``StageStats``
    (always available — the paper derives Amdahl numbers per Hadoop task)
    side by side with per-ARCH rows from the dry-run artifacts when
    ``repro.launch.dryrun`` has produced them."""
    rows = []
    # per-job Amdahl numbers straight from StageStats.roofline()
    from repro.data import sky
    from repro.mapreduce import (neighbor_search_job, neighbor_statistics_job,
                                 run_job, token_histogram)
    xyz = sky.make_catalog(8000, 0)
    jobs = {
        "search": lambda: run_job(neighbor_search_job(0.02, codec="int16"),
                                  xyz),
        "stats": lambda: run_job(neighbor_statistics_job(
            np.linspace(0.005, 0.02, 8) / sky.ARCSEC), xyz),
        "wordcount": lambda: token_histogram(
            np.random.default_rng(0).integers(0, 30000, 1 << 15), 30000),
    }
    for name, fn in jobs.items():
        fn()                                   # warmup (compile caches)
        st = fn().stats
        am = st.roofline().amdahl_numbers()
        rows.append((f"table4_job_{name}", st.wall_s * 1e6,
                     f"AD={am['AD']:.2g}_ADN={am['ADN']:.2g}"
                     f"_dom={st.dominant_stage}_engine={st.engine}"))
    # per-arch rows from dry-run artifacts (when they exist)
    art = os.path.join(ROOT, "artifacts", "dryrun")
    if not os.path.isdir(art):
        rows.append(("table4_archs_missing", 0.0,
                     "run repro.launch.dryrun for per-arch rows"))
        return rows
    for fn in sorted(os.listdir(art)):
        if not fn.endswith("__16x16__baseline.json") or "train_4k" not in fn:
            continue
        rec = json.load(open(os.path.join(art, fn)))
        if rec.get("status") != "ok":
            continue
        t = rec["terms"]
        rows.append((f"table4_{rec['arch']}", t["step_time_s"] * 1e6,
                     f"AD={t['AD']:.2f}_ADN={t['ADN']:.2f}"
                     f"_dom={t['dominant']}"
                     f"_useful={t['useful_flop_ratio']:.2f}"
                     f"_chips_bal={t['chips_to_balance']:.0f}"))
    return rows


def fig7_spill():
    """External shuffle spill tier (Hadoop's map-side spill-to-disk, the
    paper's memory-for-disk trade on low-power nodes): a pair job whose
    accumulated wire streams exceed a spill budget set to 1/4 of the
    spill-off accumulation, so the job can only complete out of core.
    Rows: spill OFF (today's accumulate path), spill ON at budget/4, and
    spill-everything (budget=0, the fully synchronous floor). Gates
    (asserted here, not just reported): all runs bit-identical to the
    monolithic oracle, and measured peak resident wire bytes <= budget +
    one spill chunk."""
    import tempfile
    from repro.data import MemmapCatalogSplits, sky
    from repro.mapreduce import (SpillConfig, neighbor_search_job, run_job,
                                 run_job_streaming)

    def best(fn, reps=3):
        fn()                                    # warmup (compile caches)
        return min((fn() for _ in range(reps)), key=lambda r: r.stats.wall_s)

    rows = []
    with tempfile.TemporaryDirectory() as d:
        xyz = sky.make_catalog(48000, 0)
        cat = os.path.join(d, "catalog.f32")
        MemmapCatalogSplits.write(cat, xyz)
        src = MemmapCatalogSplits(cat, d=3, rows_per_split=6000)
        job = neighbor_search_job(0.02, codec="int16", tile=256)

        mono = run_job(job, xyz)
        off = best(lambda: run_job_streaming(job, src))
        assert off.output == mono.output, (off.output, mono.output)
        rows.append(("fig7_spill_off", off.stats.wall_s * 1e6,
                     f"pairs={off.output}_nsplits={off.stats.n_splits}"
                     f"_wireB={off.stats.shuffle_wire_bytes}"))

        # budget = 1/4 of the spill-off wire accumulation: the run CANNOT
        # hold its streams resident — completing at all is the claim
        budget = off.stats.shuffle_wire_bytes // 4
        on = best(lambda: run_job_streaming(
            job, src, spill=SpillConfig(budget_bytes=budget,
                                        dir=os.path.join(d, "sp"))))
        st = on.stats
        assert on.output == mono.output, (on.output, mono.output)
        assert st.spilled_splits == st.n_splits, st.spilled_splits
        assert st.spill_peak_bytes <= budget + st.spill_chunk_bytes, \
            (st.spill_peak_bytes, budget, st.spill_chunk_bytes)
        rows.append(("fig7_spill_on_quarter", st.wall_s * 1e6,
                     f"pairs={on.output}_budgetB={budget}"
                     f"_spillB={st.spill_bytes}"
                     f"_peakB={st.spill_peak_bytes}"
                     f"_chunkB={st.spill_chunk_bytes}"
                     f"_ranges={st.spill_ranges}"
                     f"_spilled={st.spilled_splits}"
                     f"_spillwall_us={st.spill_wall_s * 1e6:.0f}"))

        # budget=0: every split spills synchronously — the out-of-core floor
        zero = best(lambda: run_job_streaming(
            job, src, spill=SpillConfig(budget_bytes=0,
                                        dir=os.path.join(d, "sp0"))), reps=2)
        zst = zero.stats
        assert zero.output == mono.output, (zero.output, mono.output)
        assert zst.spill_peak_bytes <= zst.spill_chunk_bytes, \
            (zst.spill_peak_bytes, zst.spill_chunk_bytes)
        rows.append(("fig7_spill_everything", zst.wall_s * 1e6,
                     f"pairs={zero.output}_spillB={zst.spill_bytes}"
                     f"_peakB={zst.spill_peak_bytes}"
                     f"_ranges={zst.spill_ranges}"
                     f"_vs_off_wall={zst.wall_s / off.stats.wall_s:.2f}x"))
    return rows


def fig8_autoplan():
    """Cost-model auto planning (``tile="auto"``/``codec="auto"``) vs the
    hand-tuned fig3/table3 configurations, paired rows per workload. Bit
    identity auto == hand is asserted internally (auto only moves shapes,
    never arithmetic); the auto >= hand TIMING gate lives in
    ``scripts/bench_diff.py --auto-gate`` over these rows, with the
    skewed-catalog pair required to be strictly faster — the workload where
    hand-tuned ``tile=256`` pays every small zone's padding and the
    predicted-wall planner does not. ``prederr`` in the derived field is
    ``StageStats.prediction_error`` (worst predicted-vs-actual stage-wall
    ratio; analytic-defaults backends are expected to be loose — the <=2x
    bound is a calibrated-backend property)."""
    from repro.data import SyntheticTokens, sky
    from repro.mapreduce import (neighbor_search_job, neighbor_statistics_job,
                                 run_job, token_histogram_job)
    rows = []

    def bench_pair(suffix, hand_job, auto_job, items, eq):
        res = {}
        for kind, job in (("hand", hand_job), ("auto", auto_job)):
            run_job(job, items)                  # warmup (compile caches)
            r = min((run_job(job, items) for _ in range(5)),
                    key=lambda r: r.stats.wall_s)
            res[kind] = r
            st = r.stats
            rows.append((f"fig8_{kind}_{suffix}", st.wall_s * 1e6,
                         f"tile={st.auto_tile or job.tile}"
                         f"_codec={st.codec}"
                         f"_padratio={st.reduce_padded_ratio:.2f}"
                         f"_prederr={st.prediction_error:.2f}"))
        assert eq(res["auto"].output, res["hand"].output), (
            suffix, res["auto"].output, res["hand"].output)
        return res

    # fig3-equivalent rows: the hand configs are fig3/table3's tuned picks
    xyz = sky.make_catalog(20000, 0)
    radius = 0.02
    bench_pair("search",
               neighbor_search_job(radius, tile=64),
               neighbor_search_job(radius, tile="auto", codec="auto"),
               xyz, lambda a, b: int(a) == int(b))
    edges = np.linspace(0.005, 0.04, 8)
    bench_pair("stats",
               neighbor_statistics_job(edges / sky.ARCSEC, tile=256),
               neighbor_statistics_job(edges / sky.ARCSEC, tile="auto",
                                       codec="auto"),
               xyz, lambda a, b: np.array_equal(a, b))
    toks = SyntheticTokens(50000, 0).block(0, 64, 1024)
    bench_pair("wordcount",
               token_histogram_job(50000, n_partitions=16, tile=256),
               token_histogram_job(50000, n_partitions=16, tile="auto",
                                   codec="auto"),
               toks.reshape(-1), lambda a, b: np.array_equal(a, b))

    # skewed catalog: 60% of the tokens come from a 50-token hot set that
    # hashes into a handful of giant partitions; the rest spread uniformly.
    # tile=256 (the fig3 "bigger blocks" hand pick) pads every small
    # partition toward the giants' quantum, and wordcount's bincount reduce
    # pays that padding DIRECTLY (no z-gap pruning rescues it like the
    # blocked pair engine does) — the rows-basis predicted-wall planner
    # must win outright here, not just tie.
    srng = np.random.default_rng(5)
    nskew = 120_000
    hot = srng.integers(0, 50, int(nskew * 0.6))
    cold = srng.integers(0, 50000, nskew - len(hot))
    skew_toks = srng.permutation(np.concatenate([hot, cold]))
    pair = bench_pair("skew",
                      token_histogram_job(50000, n_partitions=16, tile=256),
                      token_histogram_job(50000, n_partitions=16,
                                          tile="auto", codec="auto"),
                      skew_toks, lambda a, b: np.array_equal(a, b))
    assert pair["auto"].stats.wall_s < pair["hand"].stats.wall_s, (
        "auto planning must beat hand tile=256 on the skewed catalog",
        pair["auto"].stats.wall_s, pair["hand"].stats.wall_s)
    return rows


def fig9_energy():
    """The paper's headline (Fig. 9): energy-efficiency ratios of the
    low-power node vs the blade, split by workload class. The paper gets
    7.7x for data-intensive jobs but only 3.4x for compute-intensive ones
    — efficiency gains concentrate where the CPU mostly waits on I/O. We
    recast host-engine (numpy oracle, Atom-class profile: the CPU pays
    for every byte moved) vs device-engine (wire-dtype tiered shuffle,
    blade-class profile: I/O is cheap, compute draws the power) under the
    ``ModeledMeter``: per-stage-class watts x measured stage walls. The
    ORDERING is the reproduced claim (data-intensive ratio > compute-
    intensive ratio > 1), not the paper's absolute magnitudes — those
    depend on 2009-era Atom vs Xeon silicon we are not modeling. The
    balance-point row prices ``chips_to_balance`` in watts via the
    power-aware roofline term (the paper's 'four Atom cores' answer,
    asked as a wattage)."""
    from repro.data import sky
    from repro.mapreduce import (neighbor_search_job, neighbor_statistics_job,
                                 run_job)
    from repro.obs.energy import BLADE_DEVICE, ModeledMeter, use_meter

    xyz = sky.make_catalog(20000, 0)
    edges = np.linspace(0.005, 0.04, 8)
    workloads = [
        # search: one scalar per pair-block — shuffle/wire dominated
        ("search", neighbor_search_job(0.02, codec="int16", tile=256)),
        # stats: 8-bin histogram per block — reduce/compute dominated
        ("stats", neighbor_statistics_job(edges / sky.ARCSEC, codec="int16",
                                          tile=256)),
    ]
    rows, eff = [], {}
    with use_meter(ModeledMeter()):
        for wname, job in workloads:
            for engine in ("host", "device"):
                run_job(job, xyz, engine=engine)     # warmup (compile caches)
                r = min((run_job(job, xyz, engine=engine) for _ in range(3)),
                        key=lambda r: r.stats.wall_s)
                st = r.stats
                assert st.energy_j > 0.0, (wname, engine, st.energy_j)
                eff[(wname, engine)] = st
                rows.append((f"fig9_energy_{wname}_{engine}",
                             st.wall_s * 1e6,
                             f"energyJ={st.energy_j:.3f}"
                             f"_rowsperJ={st.rows_per_joule:.0f}"
                             f"_source={st.energy_source}"
                             f"_dominant={st.dominant_stage}"))

    def ratio(wname):
        return (eff[(wname, "device")].rows_per_joule
                / eff[(wname, "host")].rows_per_joule)

    r_data, r_comp = ratio("search"), ratio("stats")
    # the reproduced ordering: data-intensive efficiency gain exceeds the
    # compute-intensive one, both > 1 (paper: 7.7x vs 3.4x)
    assert r_data > r_comp > 1.0, (r_data, r_comp)
    st = eff[("search", "device")]
    terms = st.roofline(chip_w=BLADE_DEVICE.compute_w)
    rows.append(("fig9_energy_ratios", 0.0,
                 f"data_ratio={r_data:.2f}x_compute_ratio={r_comp:.2f}x"
                 f"_paper=7.7x/3.4x"
                 f"_balance_chips={terms.chips_to_balance():.3f}"
                 f"_balance_w={terms.balance_watts():.1f}"))
    return rows


ALL = [fig1_direct_io, table2_network, fig2_pipeline, fig3_improvements,
       fig4_streaming, fig5_service, fig6_speculation, fig7_spill,
       fig8_autoplan, fig9_energy, table3_apps, table4_amdahl]
