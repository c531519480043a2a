"""Host time per job that JAX spends tracing, lowering and fetching
executables from its cache (its compile events): the program builds each
Pallas call anew on every call."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.mean(ctx["outcome"].layer["retrace_s"]))
