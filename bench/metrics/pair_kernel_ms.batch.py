"""Device time of the Pallas pair kernel per job (trace)."""
from benchlib import layers


def read(ctx):
    return layers.per_call_ms(layers.device_seconds(ctx["trace"],
                                                    layers.PAIR_KERNEL),
                              ctx["outcome"].layer["jobs"])
