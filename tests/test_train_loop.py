"""End-to-end training loop: runs, checkpoints, resumes, recovers from failure."""
import os

import jax
import numpy as np
import pytest

from repro.configs import RunConfig, get_arch
from repro.launch.train import train


def _rc(steps):
    return RunConfig(remat="none", steps=steps, warmup_steps=2,
                     learning_rate=1e-3)


def test_loss_decreases_on_learnable_data(cpu_mesh, tmp_path):
    """Deterministic memorization check: repeated steps on one fixed batch must
    drive the loss down (hash-random streams only admit unigram learning, which
    is too noisy for a strict monotonicity assertion)."""
    import jax
    import jax.numpy as jnp
    from repro.parallel.sharding import use_mesh
    from repro.training.state import init_state
    from repro.training.step import make_train_step
    cfg = get_arch("tinyllama-1.1b").reduced()
    rc = _rc(20)
    step_fn, _, _, rules = make_train_step(cfg, rc, cpu_mesh)
    with use_mesh(cpu_mesh, rules):
        state = init_state(cfg, rc, jax.random.PRNGKey(0), cpu_mesh)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                          cfg.vocab)}
    losses = []
    for _ in range(20):
        state, mets = step_fn(state, batch)
        losses.append(float(mets["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0] - 0.5, losses[:3] + losses[-3:]


def test_checkpoint_resume_matches_uninterrupted(cpu_mesh, tmp_path):
    cfg = get_arch("tinyllama-1.1b").reduced()
    d1 = str(tmp_path / "a")
    d2 = str(tmp_path / "b")
    # uninterrupted 8 steps
    _, losses_full = train(cfg, _rc(8), batch=4, seq=32, steps=8,
                           ckpt_dir=d1, ckpt_every=100, mesh=cpu_mesh,
                           log_every=1000)
    # 4 steps, checkpoint, resume 4 more
    train(cfg, _rc(8), batch=4, seq=32, steps=4, ckpt_dir=d2, ckpt_every=4,
          mesh=cpu_mesh, log_every=1000)
    _, losses_resumed = train(cfg, _rc(8), batch=4, seq=32, steps=4,
                              ckpt_dir=d2, ckpt_every=100, mesh=cpu_mesh,
                              log_every=1000)
    np.testing.assert_allclose(losses_full[4:], losses_resumed, rtol=1e-4)


def test_failure_injection_and_restart(cpu_mesh, tmp_path):
    cfg = get_arch("tinyllama-1.1b").reduced()
    d = str(tmp_path / "ckpt")
    with pytest.raises(RuntimeError, match="injected failure"):
        train(cfg, _rc(10), batch=4, seq=32, steps=10, ckpt_dir=d,
              ckpt_every=3, inject_failure_at=7, mesh=cpu_mesh, log_every=1000)
    # restart resumes from the last checkpoint and completes
    state, losses = train(cfg, _rc(10), batch=4, seq=32, steps=4,
                          ckpt_dir=d, ckpt_every=100, mesh=cpu_mesh,
                          log_every=1000)
    assert len(losses) == 4 and all(np.isfinite(l) for l in losses)


def test_serve_engine_completes_requests(cpu_mesh):
    from repro.models import model as mdl
    from repro.parallel.sharding import make_rules, use_mesh
    from repro.serving.engine import Request, ServeEngine
    cfg = get_arch("tinyllama-1.1b").reduced()
    rc = RunConfig(remat="none")
    with use_mesh(cpu_mesh, make_rules(cpu_mesh)):
        params, biases = mdl.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, rc, params, biases, cpu_mesh, slots=2, max_len=64)
    assert not eng.closed
    for rid in range(4):
        eng.submit(Request(rid=rid, prompt=[1, 2, 3], max_new=5))
    eng.run(max_steps=60)
    assert len(eng.queue) == 0
    assert all(s is None for s in eng.active)
    # drained -> closed: a late submission would never be served, so it
    # must be rejected instead of silently enqueued into a dead engine
    assert eng.closed
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(Request(rid=9, prompt=[1], max_new=2))
