#!/usr/bin/env python3
"""The program's own spans in a traced window of a cell: host time, JAX's
compile events and the chip's idle time, by program layer.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does, from a Python stack of the same
depth, and before the window's trace is deleted reads from it the ``mr:*``
host events that the program's spans (``repro.obs.trace``) put there, each
with its compile counters. Prints run.py's result line, then one JSON line
of readings:

- ``span_ms`` (host milliseconds per job by span name), ``span_n`` and
  ``span_stats`` (the ``jax_*`` counters summed per job by span name);
- ``idle_by_span_s``: the first device's idle seconds in the window, each
  moment put down to the innermost ``mr:*`` span open then, or to
  ``(unspanned)``;
- ``metrics``: the per-layer metrics these readings give (``METRICS``);
- ``checks``: the spans against the benchmark's own host readings.

The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import sys
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import trace_reduce  # noqa: E402

PREFIX = "mr:"
UNSPANNED = "(unspanned)"
# the spans in which the host, not the chip, holds a job up
HOST_BOUND = ("mr:map", "mr:shuffle.plan", "mr:reduce.dispatch")
# metric name -> (span it reads, what of the span)
METRICS = {
    "map_dispatch_ms.batch": ("mr:map", "ms"),
    "shuffle_wait_ms.batch": ("mr:shuffle.wait", "ms"),
    "shuffle_plan_ms.batch": ("mr:shuffle.plan", "ms"),
    "reduce_dispatch_ms.batch": ("mr:reduce.dispatch", "ms"),
    "reduce_lowerings.batch": ("mr:reduce.dispatch", "jax_lowerings"),
    "idle_host_bound_pct.batch": (HOST_BOUND, "idle_pct"),
}


def extract_spans(path: str) -> list:
    """``[name, start_ns, duration_ns, stats]`` of every ``mr:*`` host
    event in one ``.xplane.pb``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    return [[ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats)]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def extract_spans_dir(trace_dir: str) -> list:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return extract_spans(max(paths, key=os.path.getmtime))


def _idle_intervals(events: dict, ws: float, we: float) -> list:
    """Idle ``(start, end)`` of the first device inside the window, as
    ``trace_reduce.reduce`` cuts them."""
    per_dev = defaultdict(list)
    for dev, _, _, s, d in events["device"]:
        s, e = max(s, ws), min(s + d, we)
        if e > s:
            per_dev[dev].append((s, e))
    first = trace_reduce._union(per_dev[min(per_dev)]) if per_dev else []
    edges = [ws] + [x for iv in first for x in iv] + [we]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def reduce_spans(events: dict, spans: list) -> dict:
    """-> ``window_s``, ``span_s``, ``span_n`` and ``span_stats`` (by span
    name, the spans clipped to the window) and ``idle_by_span`` (seconds)."""
    win = [h for h in events["host"] if h[0] == trace_reduce.WINDOW]
    if not win:
        raise ValueError(f"no {trace_reduce.WINDOW!r} span in the trace")
    ws = min(h[1] for h in win)
    we = max(h[1] + h[2] for h in win)
    span_s, span_n = defaultdict(float), defaultdict(int)
    span_stats = defaultdict(lambda: defaultdict(float))
    inside = []
    for name, s, d, stats in spans:
        s0, e0 = max(s, ws), min(s + d, we)
        if e0 <= s0:
            continue
        inside.append((s0, e0, name))
        span_s[name] += (e0 - s0) * 1e-9
        span_n[name] += 1
        for k, v in stats.items():
            span_stats[name][k] += v
    # sweep the window: at each moment, the innermost open span (the one
    # opened last; of two opened together, the one that ends first) takes
    # the idle time
    points = [(s, 1, i) for i, (s, _, _) in enumerate(inside)]
    points += [(e, -1, i) for i, (_, e, _) in enumerate(inside)]
    for s, e in _idle_intervals(events, ws, we):
        points += [(s, 2, -1), (e, -2, -1)]
    points.sort()
    idle = defaultdict(float)
    open_, in_gap, prev = {}, False, ws
    for t, kind, i in points:
        if in_gap and t > prev:
            name = (inside[max(open_, key=lambda j: (inside[j][0],
                                                     -inside[j][1]))][2]
                    if open_ else UNSPANNED)
            idle[name] += (t - prev) * 1e-9
        prev = t
        if kind == 1:
            open_[i] = True
        elif kind == -1:
            open_.pop(i, None)
        else:
            in_gap = kind == 2
    return {"window_s": (we - ws) * 1e-9, "span_s": dict(span_s),
            "span_n": dict(span_n),
            "span_stats": {k: dict(v) for k, v in span_stats.items()},
            "idle_by_span": dict(idle)}


def layer_metrics(red: dict, jobs: int) -> dict:
    """The per-layer metrics of ``METRICS``, per job; a metric whose span
    the trace does not hold is left out."""
    out = {}
    for metric, (span, what) in METRICS.items():
        names = span if isinstance(span, tuple) else (span,)
        if not any(n in red["span_n"] for n in names):
            continue
        if what == "ms":
            out[metric] = 1e3 * red["span_s"][span] / jobs
        elif what == "idle_pct":
            out[metric] = 100.0 * sum(red["idle_by_span"].get(n, 0.0)
                                      for n in names) / red["window_s"]
        else:
            out[metric] = red["span_stats"][span].get(what, 0) / jobs
    return out


def per_job(spans: list, name: str, key: str) -> list:
    """``key`` (a stat, or ``"ms"``) summed over the ``name`` spans inside
    each ``mr:job`` span, in order."""
    jobs = sorted((s, s + d) for n, s, d, _ in spans if n == "mr:job")
    out = []
    for s0, e0 in jobs:
        inner = [(d, st) for n, s, d, st in spans
                 if n == name and s0 <= s and s + d <= e0]
        out.append(sum(d * 1e-6 if key == "ms" else st.get(key, 0)
                       for d, st in inner))
    return out


@contextlib.contextmanager
def keeping_spans(seen: dict):
    """While open, a traced window's ``trace_events`` also leaves the
    window's events and its ``mr:*`` spans in ``seen``."""
    from benchlib.outcome import Window
    trace_events = Window.trace_events

    def keep(self):
        if self.trace and self.dir is not None:
            seen["spans"] = extract_spans_dir(self.dir)
        seen["events"] = trace_events(self)
        return seen["events"]

    Window.trace_events = keep
    try:
        yield seen
    finally:
        Window.trace_events = trace_events


def _ratio(a: float, b: float) -> float | None:
    return a / b if b else None


def readings(line: dict, seen: dict) -> dict:
    """The readings of one traced run: its result line, and what
    ``keeping_spans`` kept of it."""
    spans = seen["spans"]
    red = reduce_spans(seen["events"], spans)
    jobs = red["span_n"]["mr:job"]
    metric = {k: v["value"] for k, v in line["metrics"].items()}
    idle_in_job = sum(v for k, v in red["idle_by_span"].items()
                      if k != UNSPANNED)
    shuffle_parts_ms = 1e3 * sum(v for k, v in red["span_s"].items()
                                 if k.startswith("mr:shuffle.")) / jobs
    job_compile_ms = 1e3 * sum(per_job(spans, "mr:job", "jax_compile_s")) \
        / jobs
    return {
        "jobs": jobs, "window_s": red["window_s"],
        "span_ms": {k: 1e3 * v / jobs for k, v in red["span_s"].items()},
        "span_n": red["span_n"],
        "span_stats": {k: {s: v / jobs for s, v in st.items()}
                       for k, st in red["span_stats"].items()},
        "idle_by_span_s": red["idle_by_span"],
        "metrics": layer_metrics(red, jobs),
        "checks": {
            "job_compile_over_retrace":
                _ratio(job_compile_ms, metric["retrace_ms.batch"]),
            "unspanned_over_idle_in_job":
                _ratio(red["idle_by_span"].get(UNSPANNED, 0.0), idle_in_job),
            "shuffle_spans_over_shuffle":
                _ratio(shuffle_parts_ms, metric["shuffle_ms.batch"]),
            "reduce_lowerings_per_job":
                sorted(set(per_job(spans, "mr:reduce.dispatch",
                                   "jax_lowerings"))),
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run
    from benchlib import device
    # run_cell is called from here, as run.py's main calls it: the host cost
    # of the program's Pallas re-trace depends on the Python stack above it
    try:
        with keeping_spans({}) as seen:
            line = run.run_cell(args.workload, args.seed, args.seconds, True)
    except device.NoChip as e:
        print(f"span_report: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **readings(line, seen)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
