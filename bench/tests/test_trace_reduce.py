"""The reduction from trace events to busy time, op times and idle gaps."""
import json
from pathlib import Path

import pytest

from benchlib import layers, trace_reduce

RECORDED = Path(__file__).resolve().parent / "data" / "trace_sdss_batch.json"


def test_busy_union_op_times_and_gaps():
    ev = {"device": [["0", "opA", "jit_a", 100, 50],
                     ["0", "opB", "jit_b", 120, 60],
                     ["0", "opA", "jit_a", 300, 100],
                     ["0", "opC", "jit_c", 440, 40]],     # cut at the window
          "host": [["bench:window", 50, 400], ["bench:run_jobs", 60, 380],
                   ["compile", 200, 90]]}
    r = trace_reduce.reduce(ev)
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx((80 + 100 + 10) * 1e-9)
    assert r["op_s"]["jit_a/opA"] == pytest.approx(150e-9)
    assert r["module_s"]["jit_c"] == pytest.approx(10e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["compile"] == pytest.approx(120e-9)     # innermost span
    assert gaps["bench:run_jobs"] == pytest.approx((50 + 40) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert layers.idle_pct(r) == pytest.approx(100 * (1 - 190 / 400))


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"device": [], "host": [["x", 0, 1]]})


def test_recorded_chip_trace():
    """A short window of ``sdss-dr7-mgs.batch`` traced on a TPU v5e."""
    r = trace_reduce.reduce(json.loads(RECORDED.read_text()))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert layers.device_seconds(r, layers.PAIR_KERNEL) > 0
    gaps = sum(s for _, s in r["idle_gaps"])
    assert gaps <= r["window_s"] - r["busy_s"] + 1e-9
