"""What the per-layer readers share: device time by kernel, per-call
averages and the idle share. A reader returns ``None`` where it finds
nothing to read, and the harness then leaves its metric out of the line."""
from __future__ import annotations

# names the device trace gives the program's work (op or module names): the
# pair kernel is the program's one Pallas kernel, a TPU custom call
PAIR_KERNEL = ("%tpu_custom_call",)
SCATTER = ("_scatter_tiers_jit",)


def device_seconds(trace, names) -> float | None:
    """Device seconds of every op or module whose name contains one of
    ``names`` (an op inside a matching module counts once)."""
    if not trace:
        return None
    mods = sum(s for m, s in trace["module_s"].items()
               if any(n in m for n in names))
    ops = sum(s for o, s in trace["op_s"].items()
              if any(n in o for n in names))
    total = max(mods, ops)
    return total if total > 0 else None


def per_call_ms(seconds, calls) -> float | None:
    if seconds is None or not calls:
        return None
    return 1e3 * seconds / calls


def idle_pct(trace) -> float | None:
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

