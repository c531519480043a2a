#!/usr/bin/env python3
"""Run the MapReduce device path once on a TPU at chip scale, and check it.

    python chip_smoke.py                # one chip, N = 2**21 catalog rows
    python chip_smoke.py --n 20000      # a smaller catalog
    python chip_smoke.py --chips 4      # only the data-axis mesh phase

One process, seeded by ``--seed``; the catalog is ``sky.make_catalog(n)``.
Zones radius 0.02 rad (158 zones), ``tile=256``, codecs identity and int16,
and the query service's standing mix (``launch/serve_mr.query_mix``: three
search radii and one four-edge histogram). Phases on one chip:

(a) batch: ``run_jobs`` over the mix, one shuffle per codec, device engine;
(b) streaming: ``run_jobs_streaming`` over ``ArraySplits(xyz, 8)`` — equal
    to (a) bit for bit;
(c) service: ``MRQueryService.load_catalog`` then 16 requests of the mix —
    every answer equal to (a) for the same job;
(d) correctness: at 20,000 rows the search count equals
    ``sky.brute_force_pairs`` (numpy); at full N every answer of (a) equals
    the z-banded blocked engine (``use_pallas=False``), which shares no code
    with the Pallas kernels.

``--chips 4`` runs (a) and (c) under ``make_mesh((4,), ("data",))`` and
compares them with the same jobs at ``mesh=None`` on device 0.

Every phase must take the Pallas reduce (``masked_uses_pallas()``) and the
jnp shuffle index path. Without a v5 TPU, with the wrong chip count, or on
any mismatch the script exits non-zero and prints no result line. The last
line of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

# a libtpu init failure must be an error, not a silent CPU run
os.environ.setdefault("JAX_PLATFORMS", "tpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

RADIUS = 0.02
TILE = 256
CODECS = ("identity", "int16")
N_SPLITS = 8
N_REQUESTS = 16
N_SMALL = 20_000


class SmokeFailure(RuntimeError):
    pass


MISMATCHES: list[str] = []


def check(cond, msg):
    """A precondition of the run (device, path taken): stop at once."""
    if not cond:
        raise SmokeFailure(msg)


def expect(cond, msg):
    """A result comparison: record a mismatch and go on, so one run reports
    every mismatch; any recorded mismatch fails the run at its end."""
    if not cond:
        MISMATCHES.append(msg)
        log(f"  MISMATCH: {msg}")


def log(msg):
    print(msg, flush=True)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no TPU found: jax.devices()[0].platform == {d0.platform!r}")
    check("v5" in d0.device_kind.lower(),
          f"not a v5 chip: device_kind == {d0.device_kind!r}")
    check(len(devs) == chips,
          f"asked for {chips} chip(s), JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its monitoring
    events), so each phase reports compile time apart from its wall."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def mem_gb(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use', 0) / 1e9:.3f}GB "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use', 0) / 1e9:.3f}GB "
            f"of {st.get('bytes_limit', 0) / 1e9:.3f}GB")


@contextlib.contextmanager
def phase(name, clock, dev):
    c0, t0 = clock.total, time.perf_counter()
    log(f"[{name}] start")
    yield
    wall = time.perf_counter() - t0
    log(f"[{name}] wall_s={wall:.3f} compile_s={clock.total - c0:.3f} "
        f"{mem_gb(dev)}")


def same(a, b) -> bool:
    import numpy as np
    return np.array_equal(np.asarray(a), np.asarray(b))


def compare(what, got, want):
    """Expect every answer in ``got`` to equal its twin in ``want``."""
    check(len(got) == len(want), f"{what}: {len(got)} answers for "
                                 f"{len(want)} references")
    ok = True
    for i, (g, w) in enumerate(zip(got, want)):
        ok &= same(g, w)
        expect(same(g, w), f"{what} answer {i}: {g} != {w}")
    if ok:
        log(f"  {what}: all {len(got)} answers equal")


def mix_jobs(codec, use_pallas=None):
    from repro.launch.serve_mr import query_mix
    from repro.mapreduce import ZonePartitioner
    import dataclasses
    jobs = query_mix(RADIUS, ZonePartitioner(RADIUS), codec, TILE)
    if use_pallas is None:
        return jobs
    return [dataclasses.replace(
        j, reducer=dataclasses.replace(j.reducer, use_pallas=use_pallas))
        for j in jobs]


def describe(results) -> str:
    import numpy as np
    return "; ".join(f"job{i}={np.asarray(r.output).tolist()}"
                     for i, r in enumerate(results))


def check_device_path(stats, what):
    from repro.kernels.zones_pairs.ops import masked_uses_pallas
    check(masked_uses_pallas(), f"{what}: the Pallas reduce was not taken")
    check(stats.shuffle_index_impl == "jnp",
          f"{what}: shuffle index path {stats.shuffle_index_impl!r}, "
          f"expected 'jnp'")
    check(stats.engine == "device", f"{what}: engine {stats.engine!r}")


def outputs(results):
    return [r.output for r in results]


def run_batch(xyz, codec, mesh=None):
    from repro.mapreduce import run_jobs
    res = run_jobs(mix_jobs(codec), xyz, mesh=mesh, engine="device")
    st = res[0].stats
    check_device_path(st, f"run_jobs[{codec}]")
    log(f"  {codec}: outputs {describe(res)}")
    log(f"  {codec}: map_s={st.map_wall_s:.3f} shuffle_s="
        f"{st.shuffle_wall_s:.3f} reduce_s={st.reduce_wall_s:.3f} "
        f"wire_bytes={st.shuffle_wire_bytes} "
        f"resident_tier_bytes={st.reduce_bytes} "
        f"padded_ratio={st.reduce_padded_ratio:.3f} "
        f"shuffle_index_impl={st.shuffle_index_impl} shards={st.n_shards}")
    return outputs(res)


def run_service(xyz, codec, want, clock, mesh=None):
    from repro.mapreduce import ZonePartitioner
    from repro.serving.mr_service import MRQueryService
    jobs = mix_jobs(codec)
    svc = MRQueryService(mesh=mesh, max_batch=N_REQUESTS)
    c0, t0 = clock.total, time.perf_counter()
    cat = svc.load_catalog("sky", xyz, ZonePartitioner(RADIUS), codec=codec,
                           tile=TILE)
    log(f"  {codec}: load_catalog set-up wall_s="
        f"{time.perf_counter() - t0:.3f} compile_s={clock.total - c0:.3f} "
        f"partitions={cat.P} tiers={len(cat.sd.tiers)} "
        f"resident_wire_bytes={cat.nbytes}")
    check(cat.load_stats.shuffle_index_impl == "jnp",
          f"service[{codec}]: shuffle index path "
          f"{cat.load_stats.shuffle_index_impl!r}")
    c0, t0 = clock.total, time.perf_counter()
    with svc:
        reqs = [svc.submit(jobs[i % len(jobs)], catalog="sky")
                for i in range(N_REQUESTS)]
        got = [r.result(timeout=600) for r in reqs]
    s = svc.latency_summary()
    log(f"  {codec}: {N_REQUESTS} requests wall_s="
        f"{time.perf_counter() - t0:.3f} compile_s={clock.total - c0:.3f} "
        f"batches={len(svc.batches)} p50_ms={s['p50_ms']:.3f} "
        f"p99_ms={s['p99_ms']:.3f}")
    compare(f"service[{codec}] vs run_jobs", got,
            [want[i % len(jobs)] for i in range(N_REQUESTS)])
    del svc, cat


def smoke_one_chip(args, clock, dev):
    import numpy as np
    from repro.data import ArraySplits, sky
    from repro.kernels.zones_pairs.blocked import set_chunk_shape
    from repro.mapreduce import run_jobs, run_jobs_streaming

    t0 = time.perf_counter()
    xyz = sky.make_catalog(args.n, args.seed)
    log(f"[setup] catalog n={args.n} seed={args.seed} "
        f"wall_s={time.perf_counter() - t0:.3f}")

    batch = {}
    with phase("a:batch", clock, dev):
        for codec in CODECS:
            batch[codec] = run_batch(xyz, codec)

    with phase("b:streaming", clock, dev):
        for codec in CODECS:
            res = run_jobs_streaming(mix_jobs(codec),
                                     ArraySplits(xyz, N_SPLITS))
            check_device_path(res[0].stats, f"streaming[{codec}]")
            check(res[0].stats.n_splits == N_SPLITS, "streaming: split count")
            log(f"  {codec}: reduce_s={res[0].stats.reduce_wall_s:.3f}")
            compare(f"streaming[{codec}] {N_SPLITS} splits vs batch",
                    outputs(res), batch[codec])

    with phase("c:service", clock, dev):
        for codec in CODECS:
            run_service(xyz, codec, batch[codec], clock)

    with phase("d:correctness", clock, dev):
        small = sky.make_catalog(N_SMALL, args.seed + 1)
        t0 = time.perf_counter()
        want = sky.brute_force_pairs(small, RADIUS)
        ref_s = time.perf_counter() - t0
        res = run_jobs(mix_jobs("identity")[:1], small, engine="device")
        check_device_path(res[0].stats, "small search")
        log(f"  n={N_SMALL}: chip search={res[0].output} "
            f"brute_force_pairs={want} (numpy {ref_s:.3f}s)")
        expect(res[0].output == want,
              f"n={N_SMALL}: chip {res[0].output} != brute force {want}")
        # the blocked engine's default chunk (64x64 tiles, 512 per dispatch)
        # is sized for a CPU; at 1e11 score cells it would spend minutes in
        # dispatches, so take 256x256 tiles here (any shape is exact)
        set_chunk_shape(TILE, TILE, 512)
        for codec in CODECS:
            t0 = time.perf_counter()
            ref = run_jobs(mix_jobs(codec, use_pallas=False), xyz,
                           engine="device")
            log(f"  {codec}: blocked engine wall_s="
                f"{time.perf_counter() - t0:.3f} outputs {describe(ref)}")
            compare(f"n={args.n} {codec} Pallas vs blocked engine",
                    batch[codec], outputs(ref))
        set_chunk_shape()
        expect(np.asarray(batch["identity"][0]) > 0, "no pairs found")


def smoke_four_chips(args, clock, dev):
    import jax
    from repro.core.compat import make_mesh
    from repro.data import sky

    t0 = time.perf_counter()
    xyz = sky.make_catalog(args.n, args.seed)
    log(f"[setup] catalog n={args.n} seed={args.seed} "
        f"wall_s={time.perf_counter() - t0:.3f}")
    mesh = make_mesh((args.chips,), ("data",))
    for codec in CODECS:
        with phase(f"ref:mesh=None[{codec}]", clock, dev):
            want = run_batch(xyz, codec)
        with phase(f"a:batch mesh=data:{args.chips}[{codec}]", clock, dev):
            got = run_batch(xyz, codec, mesh=mesh)
            compare(f"mesh[{codec}] vs one device", got, want)
        with phase(f"c:service mesh=data:{args.chips}[{codec}]", clock,
                   dev):
            run_service(xyz, codec, want, clock, mesh=mesh)
        for d in jax.devices():
            log(f"  device {d.id}: {mem_gb(d)}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=2 ** 21, help="catalog rows")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    import jax
    info = device_info(args.chips)
    from repro.core.compile_cache import use_compile_cache
    log(f"[device] {info} jax={jax.__version__} "
        f"compile_cache={use_compile_cache()}")
    clock = CompileClock()
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    if args.chips == 1:
        smoke_one_chip(args, clock, dev)
    else:
        smoke_four_chips(args, clock, dev)
    log(f"[total] wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.total:.3f} {mem_gb(dev)}")
    check(not MISMATCHES, f"{len(MISMATCHES)} result mismatch(es): "
                          + " | ".join(MISMATCHES))
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
