"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

Uses the full substrate — pipeline, bucketed fused optimizer, checkpointing with
replication + checksums, straggler monitor. This is the (b)-deliverable driver; on a
CPU container a step takes a few seconds, so the default is 200 steps (override with
--steps 20 for a quick look).

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses

from repro.configs import RunConfig, get_arch
from repro.core.compile_cache import use_compile_cache
from repro.launch.mesh import make_cpu_mesh
from repro.launch.train import train


def lm_100m():
    """~100M-param llama-family config (a real small LM, not a smoke stub)."""
    base = get_arch("tinyllama-1.1b")
    return dataclasses.replace(
        base, name="lm-100m", n_layers=10, d_model=640, n_heads=10,
        n_kv_heads=5, head_dim=64, d_ff=1792, vocab=32000)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_lm100m_ckpt")
    args = ap.parse_args()
    use_compile_cache()

    cfg = lm_100m()
    from repro.models.model import count_params_total
    print(f"== {cfg.name}: {count_params_total(cfg)/1e6:.1f}M params ==")
    rc = RunConfig(arch=cfg.name, steps=args.steps,
                   warmup_steps=max(args.steps // 20, 1),
                   learning_rate=3e-4, remat="none", bucketed_updates=True)
    state, losses = train(cfg, rc, batch=args.batch, seq=args.seq,
                          steps=args.steps, ckpt_dir=args.ckpt,
                          ckpt_every=max(args.steps // 4, 10), log_every=10)
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
