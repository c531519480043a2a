"""One generator for every traffic mix: the queries of a job, from data.

A mix file (``traffic/<name>.json``) of kind ``batch`` is a closed loop of
``run_jobs`` calls, each over the whole ``mix``. A mix entry ``{"op":
"hist", "edges": "theta"}`` counts pairs into the configuration's angular
bins: ``theta_edges_arcmin`` (``space`` ``log`` or ``linear``, ``from``,
``to``, ``bins``), from ``theta_min_arcmin`` up.

Every seed runs the same jobs; the seed only draws the catalog's positions.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

DEG = math.pi / 180.0
ARCMIN = DEG / 60.0


def load(path) -> dict:
    return json.loads(Path(path).read_text())


def theta_edges_arcmin(cfg) -> np.ndarray:
    """The configuration's bin edges that the mix counts, in arcmin."""
    spec = cfg["theta_edges_arcmin"]
    space = np.geomspace if spec["space"] == "log" else np.linspace
    edges = space(spec["from"], spec["to"], spec["bins"] + 1)
    return edges[edges >= cfg["theta_min_arcmin"]]


def queries(cfg, entries) -> list[dict]:
    """Mix entries -> queries: the program's edges (arcsec) and the radii
    whose cumulative pair counts answer them (rad)."""
    out = []
    for e in entries:
        if e != {"op": "hist", "edges": "theta"}:
            raise ValueError(f"unknown mix entry {e}")
        edges = theta_edges_arcmin(cfg)
        if edges[-1] > cfg["zone_height_deg"] * 60.0 * (1 + 1e-9):
            raise ValueError("a bin edge exceeds the zone height")
        out.append({"op": "hist",
                    "edges_arcsec": [float(a) * 60.0 for a in edges],
                    "radii_rad": [float(a) * ARCMIN for a in edges]})
    return out


def jobs(cfg, qs):
    """The program's jobs for ``qs``, all over one shared zone partitioner
    (so they batch over one shuffle and reduce against one resident
    catalog)."""
    from repro.mapreduce import ZonePartitioner, neighbor_statistics_job
    part = ZonePartitioner(cfg["radius_deg"] * DEG,
                           cfg["zone_height_deg"] * DEG)
    kw = dict(partitioner=part, codec=cfg["codec"], tile=cfg["tile"])
    return [neighbor_statistics_job(q["edges_arcsec"], **kw) for q in qs], part
