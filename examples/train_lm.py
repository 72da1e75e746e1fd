"""Train a reduced-config LM for a few hundred steps with the full
fault-tolerant loop: checkpointing, resume, straggler watchdog.

    PYTHONPATH=src python examples/train_lm.py --arch granite-8b --steps 200
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.pipeline import lm_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.training.loop import LoopConfig, run_training
from repro.training.optimizer import adamw_init
from repro.training.train_step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    print(f"[train_lm] {cfg.name}: {cfg.n_params / 1e6:.2f}M params")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step_fn = jax.jit(make_train_step(cfg, lr=1e-3, remat=False))

    embeds_dim = cfg.d_model if cfg.frontend != "none" else None
    raw = lm_batches(cfg.vocab_size, args.batch, args.seq,
                     embeds_dim=embeds_dim)

    def stream():
        for b in raw:
            yield {k: jnp.asarray(v) for k, v in b.items()}

    ckpt_dir = tempfile.mkdtemp(prefix="train_lm_ckpt_")
    params, opt, rep = run_training(
        step_fn, params, opt, stream(),
        LoopConfig(total_steps=args.steps, ckpt_every=50,
                   ckpt_dir=ckpt_dir))
    print(f"[train_lm] loss {rep.losses[0]:.3f} -> {rep.losses[-1]:.3f} "
          f"over {rep.steps_run} steps; ckpts={len(rep.ckpts)} "
          f"stragglers={rep.straggler_events}")
    assert rep.losses[-1] < rep.losses[0], "loss must decrease"
    print(f"[train_lm] checkpoints in {ckpt_dir} (resume by rerunning)")


if __name__ == "__main__":
    main()
