"""MapReduce query-service driver: resident catalog + online query stream.

Loads a sky catalog once into the service (one shuffle, device-resident
tiers), then offers a paced stream of small neighbor-search / statistics
queries through the admission window and prints the qps / p50 / p99 rows.
``--qps 0`` runs a closed-loop burst (capacity); a positive value paces
arrivals at that offered load (latency under load).

    python -m repro.launch.serve_mr --n 20000 --requests 64 --qps 100
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.compile_cache import use_compile_cache
from repro.data import sky
from repro.mapreduce import (ZonePartitioner, neighbor_search_job,
                             neighbor_statistics_job)
from repro.serving.mr_service import MRQueryService


def query_mix(radius: float, partitioner, codec: str, tile: int):
    """The service's standing query menu: three search radii + one stats
    histogram, all ≤ the catalog partitioner's radius so every query is
    answerable from the one resident shuffle."""
    edges = np.linspace(radius / 4, radius, 4)
    return [
        neighbor_search_job(radius, partitioner=partitioner, codec=codec,
                            tile=tile),
        neighbor_search_job(radius / 2, partitioner=partitioner, codec=codec,
                            tile=tile),
        neighbor_search_job(radius / 4, partitioner=partitioner, codec=codec,
                            tile=tile),
        neighbor_statistics_job(edges / sky.ARCSEC, partitioner=partitioner,
                                codec=codec, tile=tile),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000, help="catalog rows")
    ap.add_argument("--radius", type=float, default=0.02)
    ap.add_argument("--codec", default="int16")
    ap.add_argument("--tile", type=int, default=256)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="offered load; 0 = closed-loop burst")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    args = ap.parse_args()
    use_compile_cache()

    xyz = sky.make_catalog(args.n, 0)
    part = ZonePartitioner(args.radius)
    svc = MRQueryService(max_batch=args.max_batch,
                         max_wait_s=args.max_wait_ms * 1e-3)
    t0 = time.perf_counter()
    cat = svc.load_catalog("sky", xyz, part, codec=args.codec,
                           tile=args.tile)
    print(f"[serve_mr] catalog: {args.n} rows -> {cat.P} partitions, "
          f"{cat.nbytes / 1e6:.1f} MB resident wire bytes, shuffled once in "
          f"{time.perf_counter() - t0:.2f}s")

    mix = query_mix(args.radius, part, args.codec, args.tile)
    # warm the jit caches so the measured stream reflects steady state
    for j in mix:
        svc.submit(j, catalog="sky")
    svc.run_pending()
    svc.request_stats.clear()
    svc.batches.clear()

    gap = 1.0 / args.qps if args.qps > 0 else 0.0
    with svc:
        t0 = time.perf_counter()
        reqs = []
        for i in range(args.requests):
            if gap:
                target = t0 + i * gap
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
            reqs.append(svc.submit(mix[i % len(mix)], catalog="sky"))
        outs = [r.result(timeout=600) for r in reqs]
    assert len(outs) == args.requests

    s = svc.latency_summary()
    load = f"{args.qps:.0f} qps offered" if args.qps > 0 else "closed loop"
    print(f"[serve_mr] {s['n']} queries ({load}): {s['qps']:.1f} qps served, "
          f"p50 {s['p50_ms']:.1f} ms, p99 {s['p99_ms']:.1f} ms, "
          f"queue-wait p99 {s['wait_p99_ms']:.1f} ms, "
          f"mean batch {s['mean_batch']:.1f} "
          f"({len(svc.batches)} micro-batches)")


if __name__ == "__main__":
    main()
