"""Host wall of the shuffle per job (StageStats.shuffle_wall_s): device sync
of the map, host bincount and tier planning, the scatter, fenced."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.mean(ctx["outcome"].layer["shuffle_s"]))
