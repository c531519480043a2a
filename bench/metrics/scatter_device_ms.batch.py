"""Device time of the shuffle's scatter program per job (trace)."""
from benchlib import layers


def read(ctx):
    return layers.per_call_ms(layers.device_seconds(ctx["trace"],
                                                    layers.SCATTER),
                              ctx["outcome"].layer["jobs"])
