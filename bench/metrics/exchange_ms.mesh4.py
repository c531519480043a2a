"""Device time of the cluster shuffle's all-to-all per chip per job
(trace): every collective op named ``all-to-all`` on any chip, over chips
and jobs."""
from benchlib import exchange, layers


def read(ctx):
    out = ctx["outcome"]
    return layers.per_call_ms(
        layers.device_seconds(ctx["trace"], exchange.ALL_TO_ALL),
        out.layer["jobs"] * out.layer["chips"])
