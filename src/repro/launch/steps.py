"""Step builders: train_step / prefill_step / serve_step per architecture.

These are the functions the drivers jit.  All take
``cfg`` statically (closures) so ``jax.jit`` sees pure array signatures.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.lm import LMConfig, decode_step, loss_fn, prefill
from repro.train.adamw import AdamWState, adamw_init, adamw_update

PyTree = Any


def make_train_step(cfg: LMConfig, lr: float = 1e-4):
    """(params, opt_state, batch) -> (params, opt_state, loss)."""

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
        params, opt_state = adamw_update(grads, opt_state, params, lr)
        return params, opt_state, loss

    return train_step


def make_prefill_step(cfg: LMConfig, capacity: int):
    """(params, batch) -> (last-token logits, decode cache)."""

    def prefill_step(params, batch):
        return prefill(params, cfg, batch, capacity=capacity)

    return prefill_step


def make_serve_step(cfg: LMConfig):
    """(params, cache, tokens, pos) -> (logits, cache) — ONE new token
    against a seq_len-deep cache (the decode shapes' hot path)."""

    def serve_step(params, cache, tokens, pos):
        if cfg.arch_type == "vlm":
            p3d = jnp.broadcast_to(pos, (3, tokens.shape[0], 1)).astype(jnp.int32)
            return decode_step(params, cfg, cache, tokens, pos, p3d)
        return decode_step(params, cfg, cache, tokens, pos)

    return serve_step


def abstract_opt_state(params_abstract: PyTree) -> AdamWState:
    """ShapeDtypeStruct AdamW state matching abstract params."""
    zeros = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params_abstract
    )
    return AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=zeros,
        nu=jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params_abstract),
    )
