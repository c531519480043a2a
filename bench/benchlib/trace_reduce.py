"""From a profiler trace of the window to device busy time, op times and the
idle gaps, each gap named after what the host was doing in it.

``extract`` reads the ``.xplane.pb`` JAX wrote into a compact dict:

- ``device``: ``[device, op name, module name, start_ns, duration_ns]`` for
  every operation that ran on a device (the ``XLA Ops`` line of each TPU
  plane, the op named by its HLO instruction, ``%tpu_custom_call.1``, and
  its module by the ``XLA Modules`` span it starts in; on a CPU, host
  events that carry an ``hlo_op``);
- ``host``: ``[name, start_ns, duration_ns]`` for every other host event
  with a duration: the benchmark's ``bench:*`` annotations and the
  runtime's own (dispatch, compile, transfers).

``reduce`` works on that dict alone, so it is tested on a small recorded one.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
from collections import defaultdict

WINDOW = "bench:window"


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def extract(path: str) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = plane.name.split(":")[-1]
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name.split("(")[0])
                          for ev in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            for ev in lines.get("XLA Ops", []):
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                module = mods[i][2] if i >= 0 and mods[i][1] >= ev.start_ns \
                    else ""
                device.append([dev, ev.name.split(" = ")[0], module,
                               ev.start_ns, ev.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    op = _stat(ev, "hlo_op")
                    if op is not None:      # CPU backend: ops run on host
                        device.append(["0", str(op),
                                       str(_stat(ev, "hlo_module") or ""),
                                       ev.start_ns, ev.duration_ns])
                    elif ev.duration_ns > 0:
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"device": device, "host": host}


def extract_dir(trace_dir: str) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return extract(max(paths, key=os.path.getmtime))


def _union(intervals):
    """Merged, sorted ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """-> ``window_s``, ``busy_s`` (mean over the devices that ran
    anything), ``op_s`` and ``module_s`` (device seconds by ``module/op`` and
    by module, all devices), ``op_n`` (calls by ``module/op``),
    ``device_ops`` and ``idle_gaps`` (the ``top`` largest, as ``[name,
    seconds]``)."""
    win = [h for h in events["host"] if h[0] == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    ws = min(h[1] for h in win)
    we = max(h[1] + h[2] for h in win)
    per_dev = defaultdict(list)
    op_s, module_s, op_n = defaultdict(float), defaultdict(float), \
        defaultdict(int)
    for dev, name, module, s, d in events["device"]:
        s, e = max(s, ws), min(s + d, we)
        if e <= s:
            continue
        per_dev[dev].append((s, e))
        op_s[f"{module}/{name}" if module else name] += (e - s) * 1e-9
        module_s[module] += (e - s) * 1e-9
        op_n[f"{module}/{name}" if module else name] += 1
    busy = {dev: _union(iv) for dev, iv in per_dev.items()}
    busy_s = (sum(sum(e - s for s, e in u) for u in busy.values())
              / len(busy) * 1e-9) if busy else 0.0
    # idle gaps of the first device, each named after the innermost host
    # event that covers its middle
    gaps = defaultdict(float)
    first = busy[min(busy)] if busy else []
    edges = [ws] + [x for iv in first for x in iv] + [we]
    spans = sorted((h[1], h[2], h[0]) for h in events["host"]
                   if h[0] != WINDOW)
    active, i = [], 0              # heap of (duration, end, name) covering
    for s, e in zip(edges[0::2], edges[1::2]):     # gaps in time order
        if e <= s:
            continue
        mid = (s + e) / 2
        while i < len(spans) and spans[i][0] <= mid:
            heapq.heappush(active, (spans[i][1], spans[i][0] + spans[i][1],
                                    spans[i][2]))
            i += 1
        while active and active[0][1] < mid:
            heapq.heappop(active)
        gaps[active[0][2] if active else "(no host span)"] += (e - s) * 1e-9
    ranked = lambda d: [[k, v] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (we - ws) * 1e-9, "busy_s": busy_s,
            "op_s": dict(op_s), "module_s": dict(module_s),
            "op_n": dict(op_n), "device_ops": ranked(op_s),
            "idle_gaps": ranked(gaps)}
