"""Serve a small model with batched requests (continuous slot batching).

    PYTHONPATH=src python examples/serve_lm.py --requests 16 --slots 4
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import RunConfig, get_arch
from repro.core.compile_cache import use_compile_cache
from repro.launch.mesh import make_cpu_mesh
from repro.models import model as mdl
from repro.parallel.sharding import make_rules, use_mesh
from repro.serving.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch).reduced()
    rc = RunConfig(remat="none")
    mesh = make_cpu_mesh()
    with use_mesh(mesh, make_rules(mesh)):
        params, biases = mdl.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, rc, params, biases, mesh, slots=args.slots,
                      max_len=256)
    rng = np.random.default_rng(0)
    reqs = []
    for rid in range(args.requests):
        r = Request(rid=rid,
                    prompt=rng.integers(0, cfg.vocab, rng.integers(4, 16)).tolist(),
                    max_new=args.max_new)
        reqs.append(r)
        eng.submit(r)
    t0 = time.time()
    steps = eng.run(max_steps=250)
    dt = time.time() - t0
    finished = sum(r.done for r in reqs)
    toks = sum(len(r.out) for r in reqs)
    print(f"[serve] {finished}/{args.requests} requests, {toks} tokens, "
          f"{steps} steps in {dt:.1f}s -> {toks/dt:.1f} tok/s "
          f"(slot util {toks/max(steps*args.slots,1):.0%})")


if __name__ == "__main__":
    main()
