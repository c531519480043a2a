"""Closed-loop batch jobs on a cluster of chips: one ``run_jobs`` call of the
whole mix at a time over a ``data`` mesh of the configuration's ``nodes``.

Each call hands the program the whole catalog from host memory; the program
cuts it into one contiguous block per chip (a node's HDFS block), as a
cluster's job does. Every host copy is the catalog in a random row order,
so every block holds rows of every zone, as in a file not sorted by zone.
Warm-up, the compile-free window and its end follow ``batch.py``: the
window ends at the end of the first job that finishes after ``seconds``.
"""
from __future__ import annotations

import contextlib
import sys
import time

import numpy as np

from benchlib import catalog, device, exchange, traffic
from benchlib.outcome import Outcome, check_partition_counts, check_paths

WARM_MAX = 8


def mesh_for(cfg):
    """The configuration's cluster as a ``data`` mesh; fewer chips than
    ``nodes`` are refused before any work."""
    import jax
    from repro.core.compat import make_mesh
    nodes = cfg["nodes"]
    have = len(jax.devices())
    if have < nodes:
        raise device.NoChip(f"the configuration asks for {nodes} chips, JAX "
                            f"sees {have}")
    if have == nodes:
        return make_mesh((nodes,), ("data",))
    return jax.sharding.Mesh(np.array(jax.devices()[:nodes]), ("data",))


def run(cfg, spec, seed, seconds, *, clock, window) -> Outcome:
    from repro.mapreduce import run_jobs

    mesh = mesh_for(cfg)
    xyz = catalog.make_catalog(cfg, seed)
    copies = [catalog.shuffled_copy(xyz, seed + i)
              for i in range(1, spec["host_copies"] + 1)]
    qs = traffic.queries(cfg, spec["mix"])
    jobs, part = traffic.jobs(cfg, qs)
    check_partition_counts(part, xyz, cfg)
    least = [exchange.least_bytes(cfg, c, cfg["nodes"]) for c in copies]

    # one loop and one call of ``run_jobs`` for warm-up and window (see
    # ``batch.py``: the call's line is part of the kernel's cache key)
    got, stats, retrace, job_s, moved = [], [], [], [], []
    measuring, clean, i = False, 0, 0
    try:
        while True:
            m, t_job = clock.mark(), time.perf_counter()
            span = (window.span("bench:run_jobs") if measuring
                    else contextlib.nullcontext())
            with span:
                res = run_jobs(jobs, copies[i % len(copies)], mesh=mesh,
                               engine="device")
            if measuring:
                job_s.append(time.perf_counter() - t_job)
                got.append([r.output for r in res])
                stats.append(res[0].stats)
                retrace.append(clock.since(m))
                moved.append(least[i % len(copies)])
                i += 1
                if time.perf_counter() - t0 >= seconds:
                    break
                continue
            i += 1
            print(f"warm-up job {i - 1}: compiled {clock.compiled[m[1]:]}",
                  file=sys.stderr, flush=True)
            clean = 0 if clock.since(m)[1] else clean + 1
            if i >= WARM_MAX or (i >= len(copies) and clean >= 2):
                check_paths(res[0].stats)
                if res[0].stats.n_shards != cfg["nodes"]:
                    raise AssertionError(f"the job ran on "
                                         f"{res[0].stats.n_shards} shards")
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
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in mesh.devices.ravel()]
    print(f"window: {n_jobs} jobs in {elapsed:.3f} s; job seconds min "
          f"{min(job_s):.3f} median {np.median(job_s):.3f} max "
          f"{max(job_s):.3f}; exchange rows a job "
          f"{getattr(stats[0], 'exchange_rows', None)}, least bytes "
          f"{least}; peak bytes by "
          f"chip {peaks}", file=sys.stderr, flush=True)
    return Outcome(
        catalog=xyz, queries=qs * n_jobs,
        got=[a for job in got for a in job], missing=0,
        attempted=n_jobs * len(qs), failed=0, window_start=t0,
        window_s=elapsed,
        end_to_end={"batch_rows_per_s": n_jobs * len(xyz) / elapsed},
        layer={"jobs": n_jobs, "chips": cfg["nodes"],
               "device_kind": mesh.devices.ravel()[0].device_kind,
               "shuffle_s": [s.shuffle_wall_s for s in stats],
               "retrace_s": [s for s, _ in retrace],
               "exchange_least_bytes": moved,
               "mix": qs, "rows": len(xyz),
               "partition_counts": np.asarray(catalog.partition_counts(cfg))})
