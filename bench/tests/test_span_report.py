"""The program's spans read from a traced window: host time and compile
counters by span, and the chip's idle time put down to the innermost span."""
import json
import math
from pathlib import Path

import pytest

import span_report
from benchlib import trace_reduce

RECORDED = Path(__file__).resolve().parent / "data" / "trace_sdss_batch.json"

EVENTS = {"device": [["0", "opA", "jit_a", 100, 100],
                     ["0", "opB", "jit_b", 600, 100]],
          "host": [["bench:window", 0, 1000]]}
SPANS = [["mr:job", 50, 900, {"jax_lowerings": 3, "jax_compile_s": 0.5}],
         ["mr:map", 60, 90, {"jax_lowerings": 0}],
         ["mr:reduce", 300, 600, {"jax_lowerings": 3}],
         ["mr:reduce.dispatch", 300, 200, {"jax_lowerings": 3}],
         ["mr:reduce.wait", 500, 400, {"jax_lowerings": 0}],
         ["mr:job", 1200, 100, {"jax_lowerings": 3}]]    # after the window


def test_span_times_counters_and_idle_by_innermost_span():
    r = span_report.reduce_spans(EVENTS, SPANS)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["span_n"] == {"mr:job": 1, "mr:map": 1, "mr:reduce": 1,
                           "mr:reduce.dispatch": 1, "mr:reduce.wait": 1}
    assert r["span_s"]["mr:job"] == pytest.approx(900e-9)
    assert r["span_stats"]["mr:job"] == {"jax_lowerings": 3,
                                         "jax_compile_s": 0.5}
    idle = {k: v * 1e9 for k, v in r["idle_by_span"].items()}
    assert idle == pytest.approx({"(unspanned)": 100, "mr:job": 160,
                                  "mr:map": 40, "mr:reduce.dispatch": 200,
                                  "mr:reduce.wait": 300})
    busy = trace_reduce.reduce(EVENTS)["busy_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - busy)


def test_layer_metrics_per_job_and_absent_spans_left_out():
    r = span_report.reduce_spans(EVENTS, SPANS)
    m = span_report.layer_metrics(r, jobs=2)
    assert set(m) == {"map_dispatch_ms.batch", "reduce_dispatch_ms.batch",
                      "reduce_lowerings.batch", "idle_host_bound_pct.batch"}
    assert m["reduce_dispatch_ms.batch"] == pytest.approx(1e3 * 200e-9 / 2)
    assert m["reduce_lowerings.batch"] == 1.5
    assert m["idle_host_bound_pct.batch"] == pytest.approx(24.0)
    assert span_report.per_job(SPANS, "mr:reduce.dispatch",
                               "jax_lowerings") == [3, 0]


def test_recorded_trace_without_program_spans():
    """A trace from before the program had spans: no span readings, and the
    whole idle time stays unspanned."""
    events = json.loads(RECORDED.read_text())
    r = span_report.reduce_spans(events, [])
    assert r["span_s"] == r["span_n"] == r["span_stats"] == {}
    assert span_report.layer_metrics(r, jobs=1) == {}
    red = trace_reduce.reduce(events)
    assert r["idle_by_span"]["(unspanned)"] == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_traced_cpu_run_reads_every_layer_metric():
    import run
    with span_report.keeping_spans({}) as seen:
        line = run.run_cell("des-y1-redmagic-z5.wtheta", 2**31 + 7, 1.0,
                            True, require_chip=False,
                            cfg_override={"rows": 3000})
    assert line["correct"]
    r = span_report.readings(line, seen)
    assert set(r["metrics"]) == set(span_report.METRICS)
    assert all(math.isfinite(v) for v in r["metrics"].values())
    assert r["span_n"]["mr:job"] == r["span_n"]["mr:reduce.dispatch"]
    assert len(r["checks"]["reduce_lowerings_per_job"]) == 1
    assert r["checks"]["shuffle_spans_over_shuffle"] == pytest.approx(
        1.0, abs=0.2)
