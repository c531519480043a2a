"""Host wall of the shuffle per job (StageStats.shuffle_wall_s): the
per-chip counts, tier planning and the exchange of rows between chips,
fenced."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.mean(ctx["outcome"].layer["shuffle_s"]))
