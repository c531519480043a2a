"""Observability layer: tracing, energy metering, metrics.

Three contracts, tested in isolation and threaded through the runtime:

- ``obs.trace``: spans nest, inherit ambient ids, export as valid Chrome
  trace-event JSON, and — the load-bearing invariant — every opened span
  CLOSES even when the traced code dies mid-stage (a chaos-killed lane),
  so ``open_spans == 0`` after a crashy run and the export still parses.
- ``obs.energy``: the modeled meter fills the ``StageStats`` energy
  fields deterministically (host profile != device profile), measured
  meters (RAPL) unwrap counter wraparound and degrade to unavailable
  instead of raising, and ``merge_from`` accumulates joules like any
  other per-stage cost.
- ``obs.metrics``: counters / gauges / histograms aggregate and export,
  and the MR query service feeds them live.

Plus the ``latency_summary`` degenerate-span edges (a single request
must not report ~1e9 qps) fixed alongside this layer.
"""
import glob
import importlib.util
import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.data import sky
from repro.data.pipeline import ArraySplits
from repro.ft import LaneChaos
from repro.mapreduce import (RequestStats, ZonePartitioner, latency_summary,
                             neighbor_search_job, neighbor_statistics_job,
                             run_job, run_job_streaming, run_jobs)
from repro.mapreduce.instrumentation import StageStats
from repro.obs import (ATOM_HOST, BLADE_DEVICE, MetricsRegistry, ModeledMeter,
                       NullTracer, NvmlMeter, RaplMeter, Tracer, get_meter,
                       get_tracer, pick_meter, use_meter, use_tracer)
from repro.obs import trace as trace_mod
from repro.serving import MRQueryService

RADIUS = 0.02
ROOT = Path(__file__).resolve().parents[1]


def _catalog(n=3000, seed=0):
    return sky.make_catalog(n, seed=seed)


# ---------------------------------------------------------------------------
# latency_summary edges (the degenerate-span qps fix)
# ---------------------------------------------------------------------------

def test_latency_summary_empty_stream():
    s = latency_summary([])
    assert s["n"] == 0 and s["qps"] == 0.0 and s["span_s"] == 0.0
    assert s["p50_ms"] == 0.0 and s["mean_batch"] == 0.0


def test_latency_summary_single_request_reports_span_not_blowup():
    r = RequestStats(rid=0, t_submit_s=10.0, latency_s=0.25, batch_size=1)
    s = latency_summary([r])
    assert s["n"] == 1
    assert s["span_s"] == pytest.approx(0.25)
    assert s["qps"] == pytest.approx(1 / 0.25)


def test_latency_summary_identical_zero_latency_submits_clamps_qps():
    # all requests at the same instant with zero latency: span carries no
    # throughput information — qps must clamp to 0, not divide by a floor
    reqs = [RequestStats(rid=i, t_submit_s=5.0, latency_s=0.0, batch_size=2)
            for i in range(4)]
    s = latency_summary(reqs)
    assert s["n"] == 4 and s["span_s"] == 0.0
    assert s["qps"] == 0.0
    assert s["mean_batch"] == 2.0


def test_latency_summary_normal_stream():
    reqs = [RequestStats(rid=i, t_submit_s=float(i), latency_s=0.5,
                         queue_wait_s=0.1, batch_size=3) for i in range(5)]
    s = latency_summary(reqs)
    assert s["span_s"] == pytest.approx(4.5)
    assert s["qps"] == pytest.approx(5 / 4.5)
    assert s["p50_ms"] == pytest.approx(500.0)
    assert s["wait_p50_ms"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# StageStats energy accumulation
# ---------------------------------------------------------------------------

def test_merge_from_sums_energy_fields():
    a = StageStats(job="x", engine="host", energy_source="modeled:atom-host",
                   energy_j=3.0, map_energy_j=1.0, shuffle_energy_j=0.5,
                   reduce_energy_j=1.5, n_items=100)
    b = StageStats(job="x", engine="host", energy_source="modeled:atom-host",
                   energy_j=2.0, map_energy_j=0.5, shuffle_energy_j=0.5,
                   reduce_energy_j=0.25, fetch_energy_j=0.25,
                   combine_energy_j=0.25, spill_energy_j=0.25, n_items=100)
    a.merge_from(b)
    assert a.energy_j == pytest.approx(5.0)
    assert a.map_energy_j == pytest.approx(1.5)
    assert a.shuffle_energy_j == pytest.approx(1.0)
    assert a.reduce_energy_j == pytest.approx(1.75)
    assert a.fetch_energy_j == pytest.approx(0.25)
    assert a.combine_energy_j == pytest.approx(0.25)
    assert a.spill_energy_j == pytest.approx(0.25)
    assert a.energy_source == "modeled:atom-host"
    assert a.rows_per_joule == pytest.approx(200 / 5.0)


def test_rows_per_joule_zero_when_unmetered():
    assert StageStats(n_items=100).rows_per_joule == 0.0


# ---------------------------------------------------------------------------
# Tracer unit behaviour
# ---------------------------------------------------------------------------

def test_tracer_nesting_ids_and_export_shape():
    tr = Tracer()
    with tr.ids(lane=2, split=7):
        with tr.span("outer", cat="stage"):
            with tr.span("inner", cat="io", attempt=1):
                pass
    tr.instant("mark", split=7)
    assert tr.open_spans == 0
    doc = json.loads(tr.export_json())
    assert doc["displayTimeUnit"] == "ms"
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert set(evs) == {"outer", "inner", "mark"}
    inner = evs["inner"]
    # complete event shape + ambient ids inherited, per-span ids merged
    assert inner["ph"] == "X" and inner["dur"] >= 0.0
    assert {"ts", "pid", "tid", "args"} <= set(inner)
    assert inner["args"] == {"lane": 2, "split": 7, "attempt": 1}
    assert evs["mark"]["ph"] == "i" and evs["mark"]["s"] == "t"
    # inner closed first: events append at close time
    assert doc["traceEvents"].index(inner) < \
        doc["traceEvents"].index(evs["outer"])


def test_tracer_span_closes_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("doomed"):
            raise RuntimeError("mid-stage death")
    assert tr.open_spans == 0
    assert tr.events[0]["name"] == "doomed"


def test_tracer_record_retroactive_and_summary():
    tr = Tracer()
    t0 = tr.now()
    tr.record("fetch-wait", t0, t0 + 0.001, cat="io", split=3)
    assert tr.events[0]["dur"] == pytest.approx(1000.0, rel=0.01)
    # negative interval clamps to zero duration, never a negative one
    tr.record("clock-skew", t0 + 1.0, t0)
    assert tr.events[1]["dur"] == 0.0
    text = tr.summary()
    assert "fetch-wait" in text and "count" in text


def test_tracer_threads_keep_separate_ambient_ids():
    tr = Tracer()
    errs = []

    def worker(lane):
        try:
            with tr.ids(lane=lane):
                for _ in range(50):
                    with tr.span("w"):
                        pass
        except Exception as e:          # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not errs and tr.open_spans == 0
    assert len(tr.events) == 200
    for ev in tr.events:
        # ambient ids must come from the recording thread's own stack
        assert ev["args"]["lane"] in range(4)


def test_null_tracer_is_reentrant_noop():
    tr = NullTracer()
    with tr.span("a"), tr.ids(x=1), tr.span("b"):
        tr.instant("c")
        tr.record("d", 0.0, 1.0)
    assert tr.events == () and tr.open_spans == 0 and not tr.enabled
    assert isinstance(get_tracer(), NullTracer)  # module default stays null


# ---------------------------------------------------------------------------
# Profiler sink and compile counters
# ---------------------------------------------------------------------------

DEVICE_SPANS = {"mr:job", "mr:map", "mr:shuffle", "mr:shuffle.wait",
                "mr:shuffle.plan", "mr:shuffle.scatter", "mr:shuffle.tiers",
                "mr:reduce", "mr:reduce.dispatch", "mr:reduce.wait"}


def _profiled_spans(trace_dir):
    """``{name: [(start_ns, end_ns, stats), ...]}`` of the ``mr:*`` host
    events of the one ``.xplane.pb`` under ``trace_dir``."""
    import jax
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace_mod.PREFIX):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


def test_profiler_session_records_device_job_spans(tmp_path):
    """Under a ``jax.profiler`` session and the default ``NullTracer``, a
    device-engine job writes every seam's span into the ``.xplane.pb``,
    nested inside ``mr:job`` and carrying the compile counters."""
    import jax
    xyz = _catalog(2345, seed=4)     # a catalog size of its own: it compiles
    job = neighbor_search_job(RADIUS, tile=128)
    with jax.profiler.trace(str(tmp_path)):
        res = run_jobs([job], xyz, engine="device")
    assert res[0].output == run_job(job, xyz, engine="host").output
    spans = _profiled_spans(str(tmp_path))
    assert set(spans) == DEVICE_SPANS
    ((j0, j1, job_stats),) = spans["mr:job"]
    for name, evs in spans.items():
        for s, e, stats in evs:
            assert j0 <= s <= e <= j1, name
            assert set(stats) == set(trace_mod.COUNTERS), name
            assert stats["jax_compile_s"] <= job_stats["jax_compile_s"]
    for parent in ("mr:shuffle", "mr:reduce"):
        ((p0, p1, _),) = spans[parent]
        for name in DEVICE_SPANS:
            if name.startswith(parent + "."):
                assert all(p0 <= s <= e <= p1 for s, e, _ in spans[name])
    # the new shapes were traced, lowered and compiled inside the job
    assert job_stats["jax_traces"] > 0 and job_stats["jax_lowerings"] > 0
    assert job_stats["jax_compiles"] + job_stats["jax_cache_hits"] > 0
    assert job_stats["jax_compile_s"] > 0


def test_span_counters_reach_chrome_args_and_profiler_stats(tmp_path):
    """A span's own counters (the cluster shuffle's exchange bytes and
    rows) land in the Chrome event's args and, under a profiler session,
    in the ``mr:*`` event's stats, from either tracer."""
    import jax
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("shuffle.exchange",
                     counters={"exchange_bytes": 120, "exchange_rows": 10}):
            pass
        with trace_mod.NullTracer().span("quiet",
                                         counters={"exchange_rows": 3}):
            pass
    (ev,) = [e for e in tr.events if e["name"] == "shuffle.exchange"]
    assert ev["args"]["exchange_bytes"] == 120
    assert ev["args"]["exchange_rows"] == 10
    spans = _profiled_spans(str(tmp_path))
    ((_, _, stats),) = spans["mr:shuffle.exchange"]
    assert (stats["exchange_bytes"], stats["exchange_rows"]) == (120, 10)
    ((_, _, stats),) = spans["mr:quiet"]
    assert stats["exchange_rows"] == 3


def test_compile_counters_nest_and_tell_cache_hits_from_compiles():
    import jax
    trace_dur = "/jax/core/compile/jaxpr_trace_duration"
    lower_dur = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    compile_dur = "/jax/core/compile/backend_compile_duration"
    tr = Tracer()
    other = []
    with tr.span("outer"):
        jax.monitoring.record_event_duration_secs(trace_dur, 0.5)
        with tr.span("inner"):
            jax.monitoring.record_event_duration_secs(lower_dur, 0.25)
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
            jax.monitoring.record_event_duration_secs(compile_dur, 0.125)
            jax.monitoring.record_event_duration_secs(compile_dur, 1.0)
        # another thread's events count in that thread's spans only
        t = threading.Thread(target=lambda: other.append(
            jax.monitoring.record_event_duration_secs(trace_dur, 8.0)))
        t.start()
        t.join()
    with tr.span("quiet"):
        pass
    evs = {e["name"]: e["args"] for e in tr.events}
    assert evs["inner"] == {"jax_traces": 0, "jax_lowerings": 1,
                            "jax_cache_hits": 1, "jax_compiles": 1,
                            "jax_compile_s": 1.375}
    assert evs["outer"] == {"jax_traces": 1, "jax_lowerings": 1,
                            "jax_cache_hits": 1, "jax_compiles": 1,
                            "jax_compile_s": 1.875}
    assert evs["quiet"] == {}            # no compile event, args as before


def test_no_profiler_and_null_tracer_register_no_listener():
    """A run with neither a profiler session nor an enabled ``Tracer``
    records nothing and never registers a ``jax.monitoring`` listener."""
    code = (
        "import jax\n"
        "from jax._src import monitoring\n"
        "from repro.data import sky\n"
        "from repro.mapreduce import neighbor_search_job, run_jobs\n"
        "from repro.obs import get_tracer, trace\n"
        "xyz = sky.make_catalog(1500, seed=1)\n"
        "run_jobs([neighbor_search_job(0.02, tile=128)], xyz,\n"
        "         engine='device')\n"
        "cbs = (monitoring.get_event_duration_listeners()\n"
        "       + monitoring.get_event_listeners())\n"
        "print(trace._COUNTS.registered,\n"
        "      any(getattr(c, '__self__', None) is trace._COUNTS\n"
        "          for c in cbs),\n"
        "      get_tracer().events)\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")])}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["False", "False", "()"]


def test_second_pallas_job_dispatches_cached_reduce_programs():
    """Two jobs over one catalog: the first traces and lowers the Pallas
    reduce once per tier shape, the second's ``reduce.dispatch`` span runs
    no trace and no lowering, and both histograms equal the host engine's."""
    xyz = _catalog(2718, seed=6)     # a catalog size of its own: it compiles
    edges = np.linspace(0.01, 0.05, 4) / sky.ARCSEC
    job = neighbor_statistics_job(edges, tile=64, use_pallas=True)
    want = run_job(neighbor_statistics_job(edges, tile=64), xyz,
                   engine="host").output
    with use_tracer(Tracer()) as tr:
        outs = [run_jobs([job], xyz, engine="device")[0].output
                for _ in range(2)]
    for out in outs:
        np.testing.assert_array_equal(out, want)
    first, second = [e["args"] for e in tr.events
                     if e["name"] == "reduce.dispatch"]
    assert first["jax_traces"] > 0 and first["jax_lowerings"] > 0
    assert second.get("jax_traces", 0) == 0, second
    assert second.get("jax_lowerings", 0) == 0, second


def test_tracer_chrome_export_keeps_stage_spans_beside_profiler(tmp_path):
    """An enabled ``Tracer`` still exports the stage spans as Chrome JSON,
    and under a profiler session its spans reach the ``.xplane.pb`` too."""
    import jax
    xyz = _catalog(2222, seed=5)
    with use_tracer(Tracer()) as tr, jax.profiler.trace(str(tmp_path)):
        run_jobs([neighbor_search_job(RADIUS, tile=128)], xyz,
                 engine="device")
    assert tr.open_spans == 0
    doc = json.loads(tr.export_json())
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert {n[len(trace_mod.PREFIX):] for n in DEVICE_SPANS} <= set(evs)
    assert evs["job"]["args"]["mode"] == "stream"
    assert evs["job"]["args"]["jax_traces"] > 0
    assert set(_profiled_spans(str(tmp_path))) == DEVICE_SPANS


def test_export_trace_script_passes(tmp_path, capsys):
    spec = importlib.util.spec_from_file_location(
        "export_trace", ROOT / "scripts" / "export_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = tmp_path / "trace.json"
    assert mod.main(str(out)) == 0
    names = {e["name"] for e in json.loads(out.read_text())["traceEvents"]}
    assert mod.REQUIRED_SPANS <= names
    assert "reduce.dispatch" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Tracing threaded through the runtime — and under chaos
# ---------------------------------------------------------------------------

def test_streaming_run_traces_stages_and_exports_valid_json():
    xyz = _catalog()
    job = neighbor_search_job(RADIUS, tile=128)
    want = run_job(job, xyz).output
    with use_tracer(Tracer()) as tr:
        res = run_job_streaming(job, ArraySplits(xyz, n_splits=6), n_lanes=3,
                                prefetch=2)
    assert res.output == want
    assert tr.open_spans == 0
    doc = json.loads(tr.export_json())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"map", "shuffle", "reduce", "fetch-wait", "lane-exec",
            "job"} <= names
    lane_ev = next(e for e in doc["traceEvents"] if e["name"] == "lane-exec")
    assert "lane" in lane_ev["args"] and "split" in lane_ev["args"]


def test_chaos_killed_lane_leaves_no_open_spans():
    """A lane killed mid-split must not leak spans: the span context
    closes in ``finally``, the retry/lane accounting still records, and
    the export stays valid Chrome trace JSON."""
    xyz = _catalog()
    job = neighbor_search_job(RADIUS, tile=128)
    want = run_job(job, xyz).output
    chaos = LaneChaos(kills=[(0, 1)])
    with use_tracer(Tracer()) as tr:
        res = run_job_streaming(job, ArraySplits(xyz, n_splits=6), n_lanes=3,
                                chaos=chaos)
    assert res.output == want and len(chaos.deaths) == 1
    assert tr.open_spans == 0
    doc = json.loads(tr.export_json())
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"map", "shuffle", "reduce", "lane-exec"} <= names


# ---------------------------------------------------------------------------
# Energy meters
# ---------------------------------------------------------------------------

def test_modeled_meter_fills_energy_fields_by_engine():
    xyz = _catalog()
    job = neighbor_search_job(RADIUS, tile=128)
    outs = {}
    with use_meter(ModeledMeter()):
        for engine in ("host", "device"):
            r = run_job(job, xyz, engine=engine)
            outs[engine] = r
            st = r.stats
            assert st.energy_j > 0.0
            assert st.map_energy_j > 0.0 and st.reduce_energy_j > 0.0
            assert st.rows_per_joule > 0.0
            # per-stage charges sum to the total
            parts = (st.map_energy_j + st.shuffle_energy_j
                     + st.reduce_energy_j + st.fetch_energy_j
                     + st.combine_energy_j + st.spill_energy_j)
            assert st.energy_j == pytest.approx(parts)
    assert outs["host"].stats.energy_source == "modeled:atom-host"
    assert outs["device"].stats.energy_source == "modeled:amdahl-blade"
    assert outs["host"].output == outs["device"].output  # metering is free


def test_modeled_meter_charges_class_watts():
    st = StageStats(engine="device", map_wall_s=1.0, shuffle_wall_s=2.0)
    ModeledMeter().attribute(None, st)
    assert st.map_energy_j == pytest.approx(1.0 * BLADE_DEVICE.compute_w)
    assert st.shuffle_energy_j == pytest.approx(2.0 * BLADE_DEVICE.io_w)
    host = StageStats(engine="host", shuffle_wall_s=1.0)
    ModeledMeter().attribute(None, host)
    assert host.shuffle_energy_j == pytest.approx(ATOM_HOST.io_w)
    assert ATOM_HOST.io_w > ATOM_HOST.compute_w      # CPU pays for I/O
    assert BLADE_DEVICE.io_w < BLADE_DEVICE.compute_w


def _fake_rapl(root, uj, max_uj=1000_000.0):
    d = root / "intel-rapl:0"
    d.mkdir(parents=True, exist_ok=True)
    (d / "energy_uj").write_text(f"{uj:.0f}\n")
    (d / "max_energy_range_uj").write_text(f"{max_uj:.0f}\n")
    return d


def test_rapl_meter_reads_delta_and_unwraps(tmp_path):
    d = _fake_rapl(tmp_path, 500_000.0)
    # a subdomain must NOT be summed (double count)
    sub = tmp_path / "intel-rapl:0:0"
    sub.mkdir()
    (sub / "energy_uj").write_text("999\n")
    (sub / "max_energy_range_uj").write_text("1000000\n")
    m = RaplMeter(root=str(tmp_path))
    assert m.available and len(m._domains) == 1
    tok = m.begin()
    (d / "energy_uj").write_text("800000\n")
    assert m.read_joules(tok) == pytest.approx(0.3)      # 300k uJ
    # wraparound: counter restarts below the start value
    tok = m.begin()
    (d / "energy_uj").write_text("100000\n")             # wrapped past 1e6
    assert m.read_joules(tok) == pytest.approx(0.3)      # (1e6-8e5)+1e5
    st = StageStats(engine="host", map_wall_s=0.75, shuffle_wall_s=0.25)
    tok = m.begin()
    (d / "energy_uj").write_text("200000\n")
    m.attribute(tok, st)
    assert st.energy_j == pytest.approx(0.1)
    assert st.map_energy_j == pytest.approx(0.075)       # wall-share split
    assert st.energy_source == "rapl"


def test_rapl_meter_unavailable_degrades(tmp_path):
    m = RaplMeter(root=str(tmp_path / "nope"))
    assert not m.available and m.begin() is None
    st = StageStats(map_wall_s=1.0)
    m.attribute(None, st)                                # no-op, no raise
    assert st.energy_j == 0.0 and st.energy_source == ""


def test_nvml_meter_unavailable_degrades():
    m = NvmlMeter(index=0)
    if m.available:                     # pragma: no cover - GPU runners
        pytest.skip("machine exposes an NVML energy counter")
    assert m.begin() is None
    st = StageStats(map_wall_s=1.0)
    m.attribute(None, st)
    assert st.energy_j == 0.0


def test_pick_meter_resolution():
    assert pick_meter("null").name == "null"
    assert pick_meter("modeled").name == "modeled"
    assert pick_meter("auto").name in ("rapl", "nvml", "modeled")
    assert get_meter().name == "null"   # module default stays null


def test_roofline_balance_watts():
    st = StageStats(job="s", engine="device", reduce_flops=1e9,
                    map_bytes=1e6, reduce_bytes=1e6, shuffle_wire_bytes=1e6)
    terms = st.roofline(chip_w=BLADE_DEVICE.compute_w)
    assert terms.chip_w == BLADE_DEVICE.compute_w
    assert terms.balance_watts() == pytest.approx(
        terms.chips_to_balance() * BLADE_DEVICE.compute_w)
    d = terms.to_dict()
    assert d["chip_w"] == BLADE_DEVICE.compute_w and "balance_watts" in d
    assert st.roofline().balance_watts() == 0.0          # no watts supplied


# ---------------------------------------------------------------------------
# Metrics registry + service feed
# ---------------------------------------------------------------------------

def test_metrics_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("reqs").inc()
    reg.counter("reqs").inc(4)
    reg.gauge("depth").set(3.0)
    reg.gauge("depth").add(-1.0)
    h = reg.histogram("lat_ms")
    for v in range(1, 101):
        h.observe(float(v))
    assert reg.counter("reqs").value == 5
    assert reg.gauge("depth").value == 2.0
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["p50"] == pytest.approx(50.0, abs=1.0)
    assert snap["p99"] == pytest.approx(99.0, abs=1.0)
    d = json.loads(reg.to_json())
    assert d["counters"]["reqs"] == 5
    text = reg.render_text()
    assert "reqs_total 5" in text and 'quantile="p99"' in text


def test_histogram_window_drops_oldest():
    from repro.obs.metrics import Histogram
    h = Histogram("w", max_samples=10)
    for v in range(100):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100                 # total observations
    assert snap["min"] == 90.0                  # window keeps the newest
    assert Histogram("empty").snapshot()["count"] == 0


def test_service_feeds_metrics():
    xyz = sky.make_catalog(600, 3)
    part = ZonePartitioner(0.1)
    job = neighbor_search_job(0.1, partitioner=part, codec="int16", tile=64)
    svc = MRQueryService(max_batch=8)
    svc.load_catalog("sky", xyz, part, codec="int16", tile=64)
    reqs = [svc.submit(job, catalog="sky") for _ in range(5)]
    assert svc.metrics.counter("mr_requests").value == 5
    assert svc.metrics.gauge("mr_queue_depth").value == 5.0
    svc.run_pending()
    want = run_job(job, xyz).output
    assert all(r.output == want for r in reqs)
    assert svc.metrics.counter("mr_requests_served").value == 5
    assert svc.metrics.counter("mr_batches").value >= 1
    assert svc.metrics.histogram("mr_latency_ms").count == 5
    assert svc.metrics.gauge("mr_queue_depth").value == 0.0
    assert "mr_latency_ms" in svc.metrics.render_text()


def test_service_batch_spans_under_tracer():
    xyz = sky.make_catalog(600, 3)
    part = ZonePartitioner(0.1)
    job = neighbor_search_job(0.1, partitioner=part, codec="int16", tile=64)
    svc = MRQueryService(max_batch=4, max_wait_s=0.001)
    svc.load_catalog("sky", xyz, part, codec="int16", tile=64)
    with use_tracer(Tracer()) as tr, svc:
        reqs = [svc.submit(job, catalog="sky") for _ in range(6)]
        [r.result(timeout=120) for r in reqs]
    batches = [e for e in tr.events if e["name"] == "service-batch"]
    assert batches and sum(b["args"]["size"] for b in batches) == 6
    assert all("batch" in b["args"] and "rids" in b["args"] for b in batches)
    assert tr.open_spans == 0
    json.loads(tr.export_json())                # parses
