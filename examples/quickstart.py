"""Quickstart: train a tiny llama-family model for 30 steps, then generate.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import RunConfig, get_arch
from repro.core.compile_cache import use_compile_cache
from repro.launch.mesh import make_cpu_mesh
from repro.launch.train import train
from repro.models import model as mdl
from repro.parallel.sharding import make_rules, use_mesh
from repro.serving.engine import Request, ServeEngine


def main():
    use_compile_cache()
    cfg = get_arch("tinyllama-1.1b").reduced()
    rc = RunConfig(remat="none", steps=30, warmup_steps=3, learning_rate=1e-3)
    mesh = make_cpu_mesh()
    print(f"== training {cfg.name} (reduced) for 30 steps ==")
    state, losses = train(cfg, rc, batch=8, seq=64, steps=30, mesh=mesh,
                          log_every=10)
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")

    print("== generating with the serving engine ==")
    eng = ServeEngine(cfg, rc, state["params"], state["biases"], mesh,
                      slots=2, max_len=64)
    eng.submit(Request(rid=0, prompt=[1, 2, 3, 4], max_new=12))
    eng.submit(Request(rid=1, prompt=[5, 6, 7], max_new=12))
    reqs = list(eng.active)
    eng.run(max_steps=40)
    print("generation finished; engine processed both requests.")


if __name__ == "__main__":
    main()
