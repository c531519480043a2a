"""The four-chip cell: its traffic loop and least bytes, on the CPU."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchlib import device, exchange, mesh_batch, traffic

BENCH = Path(__file__).resolve().parents[1]
CFG = {"zone_height_deg": 30.0, "dec_min_deg": -60.0, "dec_max_deg": 30.0,
       "codec": "identity"}


def _rows_at(decs_deg):
    dec = np.deg2rad(np.asarray(decs_deg, np.float64))
    ra = np.linspace(0.1, 1.0, len(dec))
    return np.stack([np.cos(dec) * np.cos(ra), np.cos(dec) * np.sin(ra),
                     np.sin(dec)], axis=1).astype(np.float32)


def test_least_bytes_of_a_known_block_layout():
    # three zones of 30 deg; two chips of four rows each
    z0, z1, z2 = -45.0, -15.0, 15.0
    xyz = _rows_at([z0, z0, z1, z2, z1, z1, z2, z0])
    counts = exchange.zone_blocks(CFG, xyz, 2)
    np.testing.assert_array_equal(counts, [[2, 1], [1, 2], [1, 1]])
    # buckets (own rows and both neighbours' copies) per chip:
    # zone 0 [3, 3], zone 1 [4, 4], zone 2 [2, 3] -> 3 + 4 + 2 cross
    assert exchange.least_rows(counts) == 9
    assert exchange.least_bytes(CFG, xyz, 2) == 9 * 3 * 4


def test_a_zone_that_owns_no_rows_moves_nothing():
    assert exchange.least_rows([[1, 0], [0, 0], [0, 1]]) == 0
    # each zone's bucket is [3, 2]: two rows cross for either zone
    assert exchange.least_rows([[3, 0], [0, 2]]) == 4


def test_rows_outside_the_configured_zones_are_refused():
    with pytest.raises(ValueError):
        exchange.zone_blocks(CFG, _rows_at([45.0]), 2)


def test_ici_peak_of_the_chip():
    assert exchange.ici_bytes_per_s("TPU v5 lite") == 1600e9 / 8
    with pytest.raises(KeyError):
        exchange.ici_bytes_per_s("cpu")


def test_mesh_batch_refuses_fewer_devices_than_nodes():
    cfg = json.loads((BENCH / "configs" / "des-y3-redmagic-z3.json")
                     .read_text())
    mix = traffic.load(BENCH / "traffic" / "mesh4.json")
    import jax
    assert len(jax.devices()) < cfg["nodes"]
    with pytest.raises(device.NoChip):
        mesh_batch.run({**cfg, "rows": 1000}, mix, 1, 1.0, clock=None,
                       window=None)


CELL = """
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import run
line = run.run_cell("des-y3-redmagic-z3.mesh4", 2**31 + 9, 1.0, {trace},
                    require_chip=False, cfg_override={{"rows": 6000}})
print(json.dumps(line))
"""


@pytest.mark.parametrize("trace", [False, True])
def test_mesh_cell_runs_correct_on_four_host_devices(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CELL.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                       trace=trace)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    names = set(line["metrics"])
    if trace:
        assert {"exchange_ms.mesh4", "shuffle_ms.mesh4"} <= names, names
        assert math.isfinite(line["metrics"]["exchange_ms.mesh4"]["value"])
    else:
        assert {"batch_rows_per_s", "setup_s"} <= names, names
