"""What a driver hands back, the measured window, and set-up assertions."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile

import numpy as np

from benchlib import catalog


@dataclasses.dataclass
class Outcome:
    catalog: np.ndarray           # the rows the reference recounts
    queries: list                 # one query per answer in ``got``
    got: list                     # what the timed path answered
    missing: int                  # requests that failed or never answered
    attempted: int
    failed: int
    window_start: float           # time.perf_counter() at its start
    window_s: float
    end_to_end: dict              # end-to-end metric name -> value
    layer: dict                   # what per-layer readers read
    trace: dict | None = None     # trace_reduce.reduce(...) of the window


class Window:
    """The measured window: host spans, and with ``trace`` a profiler trace
    of the whole window that ``trace_events`` hands to the reduction."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.dir = None

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        if self.trace:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._outer = jax.profiler.TraceAnnotation("bench:window")
            self._outer.__enter__()
        return self

    def __exit__(self, *exc):
        if self.trace:
            import jax
            self._outer.__exit__(*exc)
            jax.profiler.stop_trace()
        return False

    def trace_events(self) -> dict | None:
        """Read the trace back and delete it."""
        if not self.trace or self.dir is None:
            return None
        from benchlib import trace_reduce
        try:
            return trace_reduce.extract_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def check_partition_counts(part, xyz, cfg):
    """The program's own zone assignment gives the configured counts, so
    tier shapes, and the compiled programs, do not depend on the seed."""
    import jax.numpy as jnp
    keys = np.asarray(part.assign_device(jnp.asarray(xyz)))
    got = np.bincount(keys, minlength=part.n_partitions(xyz))
    want = catalog.partition_counts(cfg)
    if not np.array_equal(got, want):
        bad = np.flatnonzero(got != want)[:5]
        raise AssertionError(f"zone counts differ from the configuration at "
                             f"partitions {bad.tolist()}: {got[bad]} != "
                             f"{want[bad]}")


def check_paths(stats):
    """On a TPU the timed path is the device engine, the jnp shuffle and
    the Pallas reduce."""
    import jax
    if jax.default_backend() != "tpu":
        return
    from repro.kernels.zones_pairs.ops import masked_uses_pallas
    if not (masked_uses_pallas() and stats.engine == "device"
            and stats.shuffle_index_impl == "jnp"):
        raise AssertionError(f"not the device path: engine={stats.engine} "
                             f"shuffle={stats.shuffle_index_impl} "
                             f"pallas={masked_uses_pallas()}")
