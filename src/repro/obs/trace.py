"""Structured tracing: nestable spans on a monotonic clock.

A ``Tracer`` records complete spans (Chrome trace-event ``ph: "X"``) and
instant marks (``ph: "i"``) from any thread. Spans carry the recording
thread id plus whatever correlation ids the caller attaches (lane /
split / request / attempt ...), either per-span or ambiently via the
``ids()`` context so nested spans inherit them — the lane worker opens
``ids(lane=..., split=...)`` once and every stage span recorded inside
the task picks the ids up.

Export targets:

- ``chrome_trace()`` / ``export_json()`` / ``save(path)``: the Chrome
  trace-event JSON object format (``{"traceEvents": [...]}``), loadable
  in Perfetto or chrome://tracing. Timestamps are microseconds relative
  to tracer construction.
- ``summary()``: a per-span-name text table (count / total / mean / max).
- the profiler: while a ``jax.profiler`` session records (for instance
  inside ``jax.profiler.trace(dir)``), every ``span()`` of either tracer
  also opens a ``jax.profiler.TraceAnnotation`` named ``mr:<span name>``,
  so the program's spans land in the session's ``.xplane.pb`` as host
  events on the profiler's own clock, beside the device ops.

Compile counters: each span opened by an enabled ``Tracer`` or under a
profiler session counts JAX's compile-pipeline events (``jax.monitoring``)
that its thread runs while it is open, nested spans inclusively, and
carries them on exit: ``jax_traces``, ``jax_lowerings``, ``jax_cache_hits``
(persistent-cache reads), ``jax_compiles`` (backend compiles the cache did
not serve) and ``jax_compile_s`` (the three durations summed, cache reads
included). They go to the profiler event's stats always, and to the Chrome
event's ``args`` where any is non-zero. The listener is registered the
first time such a span opens, and never in a run without one. A span may
also carry counters of its own, known when it opens (``span(name,
counters={...})``: the cluster shuffle's ``exchange_bytes`` and
``exchange_rows``); they go to the same two places, always.

The module-level current tracer defaults to ``NullTracer`` whose
``span()`` / ``ids()`` return a shared reentrant no-op context manager,
so instrumented hot paths cost one attribute lookup, one method call and
one ``TraceAnnotation.is_enabled()`` when tracing and profiling are off.

Spans close in a ``finally`` block, so an exception thrown mid-stage (a
chaos-killed lane, a cancelled clone) still closes every opened span —
``open_spans`` returning 0 after a crashy run is a tested invariant.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

# True while a jax.profiler session records (one C++ call)
_profiling = TraceAnnotation.is_enabled

PREFIX = "mr:"                  # profiler event name = PREFIX + span name
COUNTERS = ("jax_traces", "jax_lowerings", "jax_cache_hits", "jax_compiles",
            "jax_compile_s")
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lowerings",
    "/jax/core/compile/backend_compile_duration": "jax_compiles",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _CompileCounts:
    """JAX's compile-pipeline events, counted into every span open on the
    thread that runs them. A persistent-cache hit is reported just before
    the backend-compile event it stands for, so that event counts as a hit,
    not as a compile."""

    def __init__(self):
        self._tls = threading.local()
        self._lock = threading.Lock()
        self.registered = False

    def _frames(self) -> List[Dict[str, float]]:
        frames = getattr(self._tls, "frames", None)
        if frames is None:
            frames = self._tls.frames = []
        return frames

    def open(self) -> Dict[str, float]:
        if not self.registered:
            self._register()
        counts = dict.fromkeys(COUNTERS, 0)
        counts["jax_compile_s"] = 0.0
        self._frames().append(counts)
        return counts

    def close(self, counts: Dict[str, float]) -> None:
        frames = self._frames()
        if frames and frames[-1] is counts:
            frames.pop()
        else:                       # spans closed out of nesting order
            frames[:] = [f for f in frames if f is not counts]

    def _register(self) -> None:
        import jax
        with self._lock:
            if self.registered:
                return
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
            self.registered = True

    def _on_duration(self, event: str, duration: float, **_) -> None:
        key = _DURATION_EVENTS.get(event)
        if key is None:
            return
        if key == "jax_compiles" and getattr(self._tls, "hit", False):
            self._tls.hit = False
            key = None              # a cache read: counted in jax_cache_hits
        for counts in getattr(self._tls, "frames", ()):
            counts["jax_compile_s"] += duration
            if key is not None:
                counts[key] += 1

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self._tls.hit = True
            for counts in getattr(self._tls, "frames", ()):
                counts["jax_cache_hits"] += 1


_COUNTS = _CompileCounts()


class _ProfiledSpan:
    """What every span shares: the compile counters, and under a profiler
    session a ``mr:<name>`` annotation that carries them as its stats."""

    __slots__ = ("name", "extra", "counts", "_ann")

    def __init__(self, name: str, counters=None):
        self.name = name
        self.extra = counters or {}

    def __enter__(self) -> Dict[str, float]:
        self.counts = _COUNTS.open()
        self._ann = None
        if _profiling():
            self._ann = TraceAnnotation(PREFIX + self.name)
            self._ann.__enter__()
        return self.counts

    def __exit__(self, *exc):
        _COUNTS.close(self.counts)
        if self._ann is not None:
            self._ann.set_metadata(**self.counts, **self.extra)
            self._ann.__exit__(*exc)
        return False


class _NullCtx:
    """Reentrant no-op context manager shared by every NullTracer call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class NullTracer:
    """Disabled tracer: every call is a no-op returning shared objects,
    except ``span()`` under a profiler session, which still annotates."""

    enabled = False

    def span(self, name: str, cat: str = "stage", counters=None, **ids):
        if not _profiling():
            return _NULL_CTX
        return _ProfiledSpan(name, counters)

    def ids(self, **ids) -> _NullCtx:
        return _NULL_CTX

    def record(self, name: str, t0_s: float, t1_s: float,
               cat: str = "stage", **ids) -> None:
        return None

    def instant(self, name: str, cat: str = "mark", **ids) -> None:
        return None

    @property
    def events(self) -> tuple:
        return ()

    @property
    def open_spans(self) -> int:
        return 0


class Tracer:
    """Thread-safe span recorder with ambient correlation ids."""

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self.events: List[Dict[str, Any]] = []
        self._tls = threading.local()
        self._opened = 0
        self._closed = 0

    # -- ambient correlation ids -------------------------------------
    def _id_stack(self) -> List[Dict[str, Any]]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _ambient(self) -> Dict[str, Any]:
        merged: Dict[str, Any] = {}
        for frame in self._id_stack():
            merged.update(frame)
        return merged

    @contextlib.contextmanager
    def ids(self, **ids) -> Iterator[None]:
        """Attach correlation ids to every span opened in this thread."""
        stack = self._id_stack()
        stack.append(ids)
        try:
            yield
        finally:
            stack.pop()

    # -- recording ---------------------------------------------------
    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self.events.append(ev)

    def _event(self, name: str, cat: str, ph: str, t0_s: float,
               dur_s: Optional[float], ids: Dict[str, Any]) -> Dict[str, Any]:
        args = self._ambient()
        args.update(ids)
        ev: Dict[str, Any] = {
            "name": name,
            "cat": cat,
            "ph": ph,
            "ts": (t0_s - self._t0) * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": args,
        }
        if dur_s is not None:
            ev["dur"] = dur_s * 1e6
        if ph == "i":
            ev["s"] = "t"  # instant scope: thread
        return ev

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "stage", counters=None,
             **ids) -> Iterator[None]:
        """Record a complete span around the with-body (closes in finally);
        ``counters`` go to its args."""
        prof = _ProfiledSpan(name, counters)
        counts = prof.__enter__()
        t0 = self._clock()
        with self._lock:
            self._opened += 1
        try:
            yield
        finally:
            t1 = self._clock()
            prof.__exit__(None, None, None)
            ev = self._event(name, cat, "X", t0, t1 - t0, ids)
            if any(counts.values()):
                ev["args"].update(counts)
            ev["args"].update(prof.extra)
            with self._lock:
                self._closed += 1
                self.events.append(ev)

    def record(self, name: str, t0_s: float, t1_s: float,
               cat: str = "stage", **ids) -> None:
        """Record a span retroactively from caller-measured timestamps.

        ``t0_s``/``t1_s`` must come from the tracer's clock (default
        ``time.perf_counter``) — used for waits measured before the span
        is known to matter, e.g. the prefetch fetch-wait.
        """
        self._append(self._event(name, cat, "X", t0_s,
                                 max(t1_s - t0_s, 0.0), ids))

    def instant(self, name: str, cat: str = "mark", **ids) -> None:
        self._append(self._event(name, cat, "i", self._clock(), None, ids))

    def now(self) -> float:
        return self._clock()

    @property
    def open_spans(self) -> int:
        with self._lock:
            return self._opened - self._closed

    # -- export ------------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        with self._lock:
            events = list(self.events)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_json(self) -> str:
        return json.dumps(self.chrome_trace())

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.export_json())
        return path

    def summary(self) -> str:
        """Per-name text table: count, total/mean/max duration in ms."""
        with self._lock:
            events = list(self.events)
        agg: Dict[str, List[float]] = {}
        marks: Dict[str, int] = {}
        for ev in events:
            if ev["ph"] == "X":
                agg.setdefault(ev["name"], []).append(ev["dur"])
            else:
                marks[ev["name"]] = marks.get(ev["name"], 0) + 1
        lines = [f"{'span':<16} {'count':>6} {'total_ms':>10} "
                 f"{'mean_ms':>9} {'max_ms':>9}"]
        for name in sorted(agg, key=lambda n: -sum(agg[n])):
            durs = agg[name]
            lines.append(
                f"{name:<16} {len(durs):>6} {sum(durs) / 1e3:>10.3f} "
                f"{sum(durs) / len(durs) / 1e3:>9.3f} "
                f"{max(durs) / 1e3:>9.3f}")
        for name in sorted(marks):
            lines.append(f"{name:<16} {marks[name]:>6} {'(instant)':>10}")
        return "\n".join(lines)


_CURRENT: Any = NullTracer()
_CURRENT_LOCK = threading.Lock()


def get_tracer() -> Any:
    """Current tracer (a ``Tracer`` or the default ``NullTracer``)."""
    return _CURRENT


def set_tracer(tracer: Any) -> Any:
    """Install ``tracer`` globally; returns the previous tracer."""
    global _CURRENT
    with _CURRENT_LOCK:
        prev, _CURRENT = _CURRENT, tracer
    return prev


@contextlib.contextmanager
def use_tracer(tracer: Any) -> Iterator[Any]:
    """Scoped ``set_tracer``: restores the previous tracer on exit."""
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)
