"""Decode-cache sharding specs must resolve as designed on the production mesh.

Regression guard for two §Perf findings: (1) schema-time divisibility checks see the
wrong mesh context (caches silently fell back to batch-only sharding -> 16x per-chip
cache), and (2) contraction-dim sharding makes GSPMD re-gather the cache per token.
This test resolves the specs the dry-run would use, without any device allocation.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import sys
sys.path.insert(0, %r)
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_arch
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import cache_schema
from repro.parallel.sharding import make_rules, spec_for, use_mesh

mesh = make_production_mesh()
rules = make_rules(mesh)
checks = {
    # arch: (group, layer, entry, leaf, expected sharded dims count > 1)
    "tinyllama-1.1b": ("g0", "l0", "attn", "k"),
    "gemma2-2b": ("g0", "l0", "attn", "k"),
    "musicgen-medium": ("g0", "l0", "attn", "k"),
    "internvl2-2b": ("g0", "l0", "attn", "k"),
    "olmo-1b": ("g0", "l0", "attn", "k"),
}
with use_mesh(mesh, rules):
    for arch, (g, l, e, leaf) in checks.items():
        cfg = get_arch(arch)
        sch = cache_schema(cfg, 128, 32768)
        pd = sch[g][l][e][leaf]
        spec = spec_for(pd.shape[1:], pd.dims[1:], mesh, rules)  # drop stack dim
        flat = [a for part in spec if part for a in
                (part if isinstance(part, tuple) else (part,))]
        # every cache must shard over BOTH a batch axis and the model axis
        assert "data" in flat, (arch, spec)
        assert "model" in flat, (arch, spec)
        # internvl2 opts into seq-sharding; others must not use seq
        pos_model = [i for i, part in enumerate(spec) if part and
                     ("model" == part or (isinstance(part, tuple) and "model" in part))]
        if arch == "internvl2-2b":
            assert pos_model == [1], (arch, spec)   # seq dim (after batch)
        else:
            assert pos_model != [1], (arch, spec)
print("cache specs OK")
"""


@pytest.mark.slow
def test_cache_specs_on_production_mesh():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT % os.path.abspath(src)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "cache specs OK" in r.stdout
