"""Share of the window in which no operation ran on the chip (trace)."""
from benchlib import layers


def read(ctx):
    return layers.idle_pct(ctx["trace"])
