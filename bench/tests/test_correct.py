"""A whole run of a cell on the CPU at a small size, the chip check
skipped: the unbroken program comes out correct, and each fault planted
under the timed path comes out not correct."""
import pytest

import run

SMALL = {"des-y1-redmagic-z3.wtheta": {"rows": 6000},
         "des-y1-redmagic-z5.wtheta": {"rows": 3000}}


def run_small(cell, trace=False):
    return run.run_cell(cell, 2**31 + 5, 1.0, trace, require_chip=False,
                        cfg_override=SMALL[cell])


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_unbroken_run_is_correct(cell):
    line = run_small(cell)
    assert line["correct"], line["checks"]
    assert line["checks"]["answers_checked"]["value"] == line["attempted"]
    assert list(line)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics():
    line = run_small("des-y1-redmagic-z5.wtheta", trace=True)
    assert line["correct"]
    assert "device_idle_pct.batch" in line["metrics"]
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]


def _pairs_counted_twice(monkeypatch):
    """An answer altered where it is produced: ``finalize`` counts each
    pair once from either end."""
    from repro.mapreduce.stats import PairHistReducer
    finalize = PairHistReducer.finalize
    monkeypatch.setattr(PairHistReducer, "finalize",
                        lambda self, total, sd: 2 * finalize(self, total, sd))


def _half_rows_left_out(monkeypatch):
    from repro.mapreduce import executor, job
    real = job.map_split_device

    def half(partitioner, codec, items, P):
        return real(partitioner, codec, items[: len(items) // 2], P)
    monkeypatch.setattr(executor, "map_split_device", half)
    monkeypatch.setattr(job, "map_split_device", half)


FAULTS = [(c, f) for c in sorted(SMALL)
          for f in (_pairs_counted_twice, _half_rows_left_out)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    line = run_small(cell)
    assert not line["correct"], line["checks"]
