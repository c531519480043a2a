#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``bench/configs/<config>.json``: the catalog and its zones)
and a traffic mix (``bench/traffic/<traffic>.json``, run by the driver
``benchlib/<kind>.py`` that its ``kind`` names). Set-up draws the catalog
from ``--seed``, warms every program the window will call and counts as
``setup_s``. The window
then runs for ``--seconds``; no program may compile inside it. Afterwards
every answer is recounted by the plain reference (``benchlib/reference.py``)
and compared (``benchlib/checks.py``). ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces the window and reports its
per-layer metrics, each read by ``bench/metrics/<name>.py``.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` come last in it (``checks``) and as the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks for,
the run exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import sys           # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import checks, device, reference  # noqa: E402
from benchlib import traffic as traffic_mod  # noqa: E402
from benchlib.outcome import Window  # noqa: E402

def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """-> (BENCHMARK.json, cell, configuration, traffic mix), by name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = traffic_mod.load(BENCH / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, cfg, mix


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: dict, trace: bool) -> list[dict]:
    """The metrics this cell reports in this mode."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in spec[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def use_cache():
    """JAX's persistent compilation cache, where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), for every
    program however fast it compiled: the program re-traces each Pallas
    call, and only a cached executable keeps that from compiling again."""
    import jax
    from repro.core.compile_cache import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True,
             cfg_override: dict | None = None,
             keep_trace: str | None = None) -> dict:
    """Set up, measure, free, recount and compare one cell. -> the result
    line as a dict."""
    spec, cell, cfg, mix = load_cell(name)
    if cfg_override:
        cfg = {**cfg, **cfg_override}
    import jax
    info = (device.device_info(cell["chips"]) if require_chip else
            {"platform": jax.devices()[0].platform,
             "kind": jax.devices()[0].device_kind, "count": cell["chips"]})
    use_cache()
    clock = device.CompileClock()
    driver = importlib.import_module(f"benchlib.{mix['kind']}")
    window = Window(trace)
    out = driver.run(cfg, mix, seed, seconds, clock=clock, window=window)
    setup_s = out.window_start - T_START
    devs = jax.devices()[:cell["chips"]]
    info["memory_peak_bytes"] = device.memory_peak_bytes(devs)
    gc.collect()

    events = window.trace_events()
    from benchlib import trace_reduce
    if events and keep_trace:
        Path(keep_trace).write_text(json.dumps(events))
    out.trace = trace_reduce.reduce(events) if events else None
    if out.trace:
        top = sorted(out.trace["module_s"].items(), key=lambda kv: -kv[1])
        print("device seconds by module:", json.dumps(top[:15]),
              file=sys.stderr, flush=True)

    answered = [(g, q) for g, q in zip(out.got, out.queries) if g is not None]
    distinct = list({id(q): q for _, q in answered}.values())
    want = dict(zip(map(id, distinct),
                    reference.reference_answers(out.catalog, distinct)))
    numbers = checks.compare([g for g, _ in answered],
                             [want[id(q)] for _, q in answered],
                             missing=out.missing)
    correct = checks.passed(numbers)

    metrics = {}
    ctx = {"outcome": out, "trace": out.trace, "cfg": cfg,
           "peak": device.peaks(info["kind"]) if require_chip else None,
           "want": want, "correct": correct}
    for m in cell_metrics(spec, cell, trace):
        if m["name"] == "setup_s":
            value = setup_s
        elif not trace:
            value = out.end_to_end.get(m["name"])
        else:
            value = metric_reader(m["name"])(ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": info}
    if trace and out.trace:
        info["busy_s"] = out.trace["busy_s"]
        info["window_s"] = out.trace["window_s"]
        line["breakdown"] = {"device_ops": out.trace["device_ops"],
                             "idle_gaps": out.trace["idle_gaps"]}
        bound = ctx.get("roofline_bound")
        if bound:
            line["roofline_bound"] = bound
    line["checks"] = numbers
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the window's trace events (JSON) here")
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), keep_trace=args.keep_trace)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for k, v in line["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
