"""End-to-end driver: train a ~100M-parameter dense LM for a few hundred
steps on synthetic token streams using the framework's train_step.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 150] [--arch yi_6b]

The model is the assigned architecture's family scaled to ~100M params so
the driver completes on CPU.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.lm_synth import synth_lm_batch
from repro.launch.steps import make_train_step
from repro.models.lm import init_params
from repro.train.adamw import adamw_init
from repro.train.checkpoint import save_pytree


def scaled_100m(arch: str):
    """~100M-param variant of the assigned arch family."""
    cfg = get_config(arch)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-100m",
        num_layers=10,
        d_model=640,
        num_heads=10,
        num_kv_heads=2,  # must divide num_heads (GQA)
        head_dim=64,
        d_ff=2560,
        vocab_size=32768,
        dtype="float32",
        vision_tokens=0,
        mrope_sections=None,
        attn_chunk=0,
    )


def n_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    cfg = scaled_100m(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    print(f"model {cfg.name}: {n_params(params)/1e6:.1f}M params")
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, lr=args.lr), donate_argnums=(0, 1))

    rng = np.random.default_rng(0)
    t0 = time.time()
    losses = []
    for it in range(args.steps):
        toks, labels = synth_lm_batch(rng, args.batch, args.seq, cfg.vocab_size)
        params, opt, loss = step(
            params, opt, {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
        )
        losses.append(float(loss))
        if it % 10 == 0 or it == args.steps - 1:
            rate = (it + 1) / (time.time() - t0)
            print(f"step {it:4d}  loss {float(loss):.4f}  ({rate:.2f} it/s)")
    print(f"loss: first10={np.mean(losses[:10]):.4f}  last10={np.mean(losses[-10:]):.4f}")
    save_pytree("artifacts/lm_100m.npz", params)
    print("checkpoint written to artifacts/lm_100m.npz")


if __name__ == "__main__":
    main()
