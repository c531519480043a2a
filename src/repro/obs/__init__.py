"""Observability: structured tracing, energy metering, and service metrics.

Three small, dependency-light layers threaded through the MapReduce runtime
(the executor, the per-split engines, the spill tier, and the query
service):

- ``obs.trace``: a thread-safe ``Tracer`` with nestable spans (job / map /
  combine / shuffle / reduce / fetch-wait / spill-write / lane-exec /
  retry / clone-race / service-batch) on a monotonic clock, exportable as
  Chrome trace-event JSON (load it in Perfetto / chrome://tracing) plus a
  text summary. Disabled by default via a no-op ``NullTracer``. Under a
  ``jax.profiler`` session the spans of either tracer also land in the
  profiler's trace as ``mr:<span>`` host events beside the device ops —
  for a device job ``mr:job``, ``mr:map``, ``mr:shuffle`` with
  ``.wait`` / ``.plan`` / ``.scatter`` / ``.tiers`` and ``mr:reduce`` with
  ``.dispatch`` / ``.wait`` — each carrying its thread's JAX compile-event
  counts (``jax_traces``, ``jax_lowerings``, ``jax_cache_hits``,
  ``jax_compiles``, ``jax_compile_s``).
- ``obs.energy``: an ``EnergyMeter`` protocol — ``RaplMeter`` (powercap
  sysfs counter deltas, wraparound-safe), optional ``NvmlMeter``, and a
  ``ModeledMeter`` driven by ``PowerProfile`` watts (Atom-class host vs
  blade-class device) — attributing joules to ``StageStats`` by
  active-wall share. Disabled by default via ``NullMeter``.
- ``obs.metrics``: a counters/gauges/histograms registry with JSON/text
  export, fed live by ``serving.mr_service`` (qps, queue depth, p50/p99).
"""
from repro.obs.energy import (ATOM_HOST, BLADE_DEVICE, EnergyMeter,
                              ModeledMeter, NullMeter, NvmlMeter,
                              PowerProfile, RaplMeter, get_meter, pick_meter,
                              set_meter, use_meter)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               get_metrics)
from repro.obs.trace import (NullTracer, Tracer, get_tracer, set_tracer,
                             use_tracer)

__all__ = [
    "ATOM_HOST", "BLADE_DEVICE", "Counter", "EnergyMeter", "Gauge",
    "Histogram", "MetricsRegistry", "ModeledMeter", "NullMeter",
    "NullTracer", "NvmlMeter", "PowerProfile", "RaplMeter", "Tracer",
    "get_meter", "get_metrics", "get_tracer", "pick_meter", "set_meter",
    "set_tracer", "use_meter", "use_tracer",
]
