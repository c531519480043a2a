"""The chip a run measures, its published peaks, and JAX's compile events."""
from __future__ import annotations

import json
import threading
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def device_info(chips: int) -> dict:
    """The devices JAX sees; a CPU, or too few chips, is refused."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU found: jax.devices()[0].platform == "
                     f"{d0.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX sees "
                     f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())
    if kind not in table["chips"]:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{PEAKS.name}")
    return table["chips"][kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where none is reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileClock:
    """JAX's trace, lowering and compile events.

    ``compiles`` counts programs that XLA really compiled: compile events
    less persistent-cache hits (JAX times a cache read as a compile event).
    ``seconds`` sums the durations of all three kinds of event, cache reads
    included: the host time a call spends turning Python into an executable.
    ``compiled`` names the programs really compiled, in order.
    """

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiled = []
        self._hit = threading.local()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name=None, **_):
        if event in self.DURATIONS:
            self.seconds += duration
            if event == self.DURATIONS[2]:
                # a cache hit is reported just before its compile event
                if getattr(self._hit, "pending", False):
                    self._hit.pending = False
                else:
                    self.compiled.append(fun_name)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._hit.pending = True

    @property
    def compiles(self) -> int:
        return len(self.compiled)

    def mark(self) -> tuple:
        return (self.seconds, self.compiles)

    def since(self, mark) -> tuple:
        """-> (host seconds, real compiles) since ``mark``."""
        return (self.seconds - mark[0], self.compiles - mark[1])
