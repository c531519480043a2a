"""The paper's applications end-to-end on a synthetic sky catalog, through
the composable Job API.

Neighbor Searching (data-intensive) + Neighbor Statistics (compute-intensive)
are built from pluggable stages — ``ZonePartitioner`` (map), a registered
``ShuffleCodec`` (shuffle), and pair-kernel reducers — and run by one engine,
which also batches both apps over a single shuffle. Every run prints its
``StageStats`` and the per-job Amdahl numbers (the paper's Table-4 analysis).

The streaming section runs the same job out-of-core: the catalog lives in a
memmap file and crosses the engine split-by-split (HDFS-block analogues)
with the next split's read + transfer double-buffered under the current
split's compute — same answer, bounded memory, and the exposed-vs-hidden
I/O split printed from ``StageStats``.

The speculation section injects a straggler (one split's fetch stalls 3x
the clean wall) and shows the lane scheduler recover it: the slow attempt
is cloned onto a free lane, the clone wins, the stalled original is
cancelled — same answer, a fraction of the stall paid.

The last section flips the execution model from batch to SERVICE: the
catalog is shuffled once into a device-resident ``ResidentCatalog`` and a
stream of small queries goes through ``MRQueryService``'s submit queue —
micro-batched, coalesced, each answered by a pure fused reduce — with
qps / p50 / p99 from the per-request ``RequestStats``.

    PYTHONPATH=src python examples/neighbor_search.py [--n 50000]
"""
import argparse
import os
import tempfile

import numpy as np

from repro.core.compile_cache import use_compile_cache
from repro.data import MemmapCatalogSplits, sky
from repro.mapreduce import (ZonePartitioner, available_codecs,
                             neighbor_search_job, neighbor_statistics_job,
                             run_job, run_job_streaming, run_jobs)


def show(res, label):
    st = res.stats
    am = st.roofline().amdahl_numbers()
    print(f"  {label}: {st.wall_s:.2f}s "
          f"(map {st.map_wall_s:.2f} / shuffle {st.shuffle_wall_s:.2f} "
          f"/ reduce {st.reduce_wall_s:.2f}; dominant={st.dominant_stage}) "
          f"shuffle={st.shuffle_wire_bytes / 1e6:.1f}MB "
          f"x{st.compression_ratio:.1f} AD={am['AD']:.2g}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50000)
    ap.add_argument("--radius", type=float, default=0.02)
    args = ap.parse_args()
    use_compile_cache()

    print(f"== synthetic catalog: {args.n} objects ==")
    xyz = sky.make_catalog(args.n, seed=0)

    print("-- Neighbor Searching (radius sweep, cf. paper Table 3) --")
    for radius in (args.radius / 2, args.radius, args.radius * 2):
        res = run_job(neighbor_search_job(radius, tile=256), xyz)
        print(f"  radius={radius:.3f} rad: {res.output} pairs in "
              f"{res.stats.wall_s:.2f}s")

    print(f"-- stage swaps (cf. Figure 3; codecs: {available_codecs()}) --")
    for label, kw in {
        "baseline": dict(tile=64),
        "batched (buffering analogue)": dict(tile=512),
        "int16 shuffle (LZO analogue)": dict(tile=512, codec="int16"),
        # int8's ~1/127 coordinate step is coarse for radii this small: max
        # compression, visible count error — the LZO trade taken too far
        "int8 shuffle (block-quantized)": dict(tile=512, codec="int8"),
    }.items():
        res = run_job(neighbor_search_job(args.radius, **kw), xyz)
        show(res, f"{label}: pairs={res.output}")

    print("-- both apps batched over ONE shuffle (cf. paper section 2.2) --")
    edges = np.linspace(args.radius / 8, args.radius, 8)
    part = ZonePartitioner(args.radius)
    search, stats = run_jobs(
        [neighbor_search_job(args.radius, partitioner=part, tile=256),
         neighbor_statistics_job(edges / sky.ARCSEC, partitioner=part,
                                 tile=256)], xyz)
    print(f"  pairs={search.output}, histogram={stats.output.tolist()}")
    show(search, "batched search+stats")

    print("-- out-of-core: the same job streamed from a memmap catalog --")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "catalog.f32")
        MemmapCatalogSplits.write(path, xyz)        # stand-in for a big file
        src = MemmapCatalogSplits(path, d=3,
                                  rows_per_split=max(args.n // 8, 1))
        res = run_job_streaming(
            neighbor_search_job(args.radius, codec="int16", tile=256), src)
        st = res.stats
        print(f"  pairs={res.output} over {st.n_splits} splits "
              f"(per-split rows<={src.rows_per_split}); split I/O: "
              f"{st.overlap_hidden_s:.3f}s hidden under compute, "
              f"{st.fetch_wall_s:.3f}s exposed "
              f"(overlap={st.overlap_fraction:.0%})")

    print("-- speculative re-execution: an injected straggler recovered --")
    from repro.ft import FaultySplitSource, SpeculativeConfig
    from repro.data import ArraySplits
    clean = run_job_streaming(
        neighbor_search_job(args.radius, codec="int16", tile=256),
        ArraySplits(xyz, 8), n_lanes=4)
    t_clean = clean.stats.elapsed_s
    # split 0's first fetch stalls 3x the clean wall (a dying-disk analogue);
    # the policy clones it onto a free lane, the clone's fast re-fetch wins,
    # and the stalled original is cancelled mid-sleep
    slow = FaultySplitSource(ArraySplits(xyz, 8), delays={0: 3.0 * t_clean})
    spec = run_job_streaming(
        neighbor_search_job(args.radius, codec="int16", tile=256), slow,
        n_lanes=4, speculate=SpeculativeConfig(slowdown=1.5, min_finished=2))
    st = spec.stats
    print(f"  clean: {t_clean:.2f}s on {clean.stats.n_lanes} lanes; "
          f"straggler(+{3.0 * t_clean:.2f}s) with speculation: "
          f"{st.elapsed_s:.2f}s ({st.elapsed_s / t_clean:.2f}x clean; "
          f"speculated={st.speculated}, clone_wins={st.clone_wins})")
    assert spec.output == clean.output        # recovery is bit-identical

    print("-- service mode: resident catalog, micro-batched queries --")
    from repro.serving import MRQueryService
    svc = MRQueryService(max_batch=8, max_wait_s=0.002)
    cat = svc.load_catalog("sky", xyz, part, codec="int16", tile=256)
    print(f"  shuffled once: {cat.nbytes / 1e6:.1f}MB resident wire bytes, "
          f"{cat.P} partitions")
    with svc:                    # background admission/serving thread
        reqs = [svc.submit(neighbor_search_job(r, partitioner=part,
                                               codec="int16", tile=256),
                           catalog="sky")
                for r in (args.radius, args.radius / 2) * 4]
        outs = [r.result(timeout=600) for r in reqs]
    s = svc.latency_summary()
    print(f"  {s['n']} queries at {s['qps']:.0f} qps "
          f"(p50 {s['p50_ms']:.1f}ms / p99 {s['p99_ms']:.1f}ms, "
          f"mean batch {s['mean_batch']:.1f}); "
          f"pairs@radius={outs[0]}, pairs@radius/2={outs[1]}")


if __name__ == "__main__":
    main()
