"""Closed-loop batch jobs: one ``run_jobs`` call of the whole mix at a time.

Each call maps, shuffles and reduces the whole catalog from host memory (the
HDFS-block analogue), as a batch user's job does. The window ends at the end
of the first job that finishes after ``seconds``, so every job counted ran
whole inside it.
"""
from __future__ import annotations

import contextlib
import sys
import time

import numpy as np

from benchlib import catalog, traffic
from benchlib.outcome import Outcome, check_partition_counts, check_paths

WARM_MAX = 8


def run(cfg, spec, seed, seconds, *, clock, window) -> Outcome:
    from repro.mapreduce import run_jobs

    xyz = catalog.make_catalog(cfg, seed)
    copies = [xyz] + [catalog.shuffled_copy(xyz, seed + i)
                      for i in range(1, spec["host_copies"])]
    qs = traffic.queries(cfg, spec["mix"])
    jobs, part = traffic.jobs(cfg, qs)
    check_partition_counts(part, xyz, cfg)

    # Warm-up and window share one loop and one call of ``run_jobs``: the
    # Pallas kernel's compile-cache key holds the Python call stack (the
    # source locations inside the kernel), so a call from another line
    # misses the cache and compiles.
    got, stats, retrace, job_s = [], [], [], []
    measuring, clean, i = False, 0, 0
    try:
        while True:
            m, t_job = clock.mark(), time.perf_counter()
            span = (window.span("bench:run_jobs") if measuring
                    else contextlib.nullcontext())
            with span:
                res = run_jobs(jobs, copies[i % len(copies)], engine="device")
            i += 1
            if measuring:
                job_s.append(time.perf_counter() - t_job)
                got.append([r.output for r in res])
                stats.append(res[0].stats)
                retrace.append(clock.since(m))
                if time.perf_counter() - t0 >= seconds:
                    break
                continue
            # warm-up: every input, then until two jobs in a row compile
            # nothing
            print(f"warm-up job {i - 1}: compiled {clock.compiled[m[1]:]}",
                  file=sys.stderr, flush=True)
            clean = 0 if clock.since(m)[1] else clean + 1
            if i >= WARM_MAX or (i >= len(copies) and clean >= 2):
                check_paths(res[0].stats)
                start = clock.mark()
                window.__enter__()
                measuring, i = True, 0
                t0 = time.perf_counter()
        elapsed = time.perf_counter() - t0
    finally:
        if measuring:
            window.__exit__(None, None, None)
    compiled = [(k, clock.compiled[start[1]:][:5]) for k, (_, c)
                in enumerate(retrace) if c]
    if compiled:
        raise RuntimeError(f"compiled inside the measured window (window "
                           f"job, programs): {compiled}")
    n_jobs = len(got)
    print(f"window: {n_jobs} jobs in {elapsed:.3f} s; job seconds min "
          f"{min(job_s):.3f} median {np.median(job_s):.3f} max "
          f"{max(job_s):.3f}; host compile-event seconds median "
          f"{np.median([s for s, _ in retrace]):.3f}", file=sys.stderr,
          flush=True)
    return Outcome(
        catalog=xyz, queries=qs * n_jobs,
        got=[a for job in got for a in job], missing=0,
        attempted=n_jobs * len(qs), failed=0, window_start=t0,
        window_s=elapsed,
        end_to_end={"batch_rows_per_s": n_jobs * len(xyz) / elapsed},
        layer={"jobs": n_jobs,
               "shuffle_s": [s.shuffle_wall_s for s in stats],
               "retrace_s": [s for s, _ in retrace],
               "mix": qs, "rows": len(xyz),
               "partition_counts": np.asarray(catalog.partition_counts(cfg))})
