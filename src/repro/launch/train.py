"""Training launcher.

Trains a scaled-down variant of ``--arch`` on synthetic tokens with the
framework's train_step.

  PYTHONPATH=src python -m repro.launch.train --arch yi_6b --steps 30
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.lm_synth import synth_lm_batch
from repro.launch.steps import make_train_step
from repro.models.lm import init_params, reduced
from repro.train.adamw import adamw_init
from repro.train.checkpoint import save_pytree


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, lr=args.lr), donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    t0 = time.time()
    for it in range(args.steps):
        toks, labels = synth_lm_batch(rng, args.batch, args.seq, cfg.vocab_size)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
        if cfg.arch_type == "vlm":
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.vision_tokens, cfg.d_model), jnp.float32
            )
            batch["positions_3d"] = jnp.broadcast_to(
                jnp.arange(args.seq)[None, None], (3, args.batch, args.seq)
            )
        if cfg.arch_type == "encdec":
            batch["audio_frames"] = jnp.asarray(
                rng.normal(0, 1, (args.batch, cfg.encoder_frames, cfg.d_model)),
                jnp.float32,
            )
        params, opt, loss = step(params, opt, batch)
        if it % 10 == 0 or it == args.steps - 1:
            print(f"[{cfg.name}] step {it} loss {float(loss):.4f} "
                  f"({(it+1)/(time.time()-t0):.2f} it/s)")
    if args.ckpt:
        save_pytree(args.ckpt, params)
        print("saved", args.ckpt)


if __name__ == "__main__":
    main()
