"""Plain reference of DeepSeek-V2-Lite's forward pass, written from the
published configuration and modelling code (arXiv 2405.04434; the
``config.json`` of deepseek-ai/DeepSeek-V2-Lite) and imported from nothing
of the program.  One request at a time, float32, every matmul at
``jax.default_matmul_precision("highest")``; no cache, no kernels.

A layer: ``h + MLA(rms(h))``, then ``+ MLP(rms(.))``: the first
``first_k_dense_replace`` layers a SwiGLU of ``intermediate_size``, the
rest a MoE layer.

- MLA in the expanded form, no ``q_lora``: ``q = x Wq`` split into
  ``qk_nope_head_dim`` and ``qk_rope_head_dim`` per head; the latent
  ``c = rms(x W_dkv)`` of ``kv_lora_rank``; keys ``[c W_uk, rope(x W_kr)]``
  (the rope key shared by the heads), values ``c W_uv``; causal softmax
  at ``(qk_nope + qk_rope)^-0.5 · mscale(factor, mscale_all_dim)²``.
- RoPE with YaRN (``rope_scaling``): ``inv_freq = freq/factor · ramp +
  freq · (1 - ramp)`` with the linear ramp over the correction range of
  ``beta_fast`` and ``beta_slow``; cos and sin scaled by ``mscale(factor,
  mscale) / mscale(factor, mscale_all_dim)``.
- MoE: ``softmax(x W_router)`` over ``n_routed_experts``, greedy top
  ``num_experts_per_tok``, gates renormalised only if ``norm_topk_prob``,
  times ``routed_scaling_factor``; a loop over the experts, each applied
  to the tokens that picked it with their gate, no capacity; plus the
  ``n_shared_experts`` shared experts as one SwiGLU.
- RMSNorm with ``rms_norm_eps``; an untied head after a final RMSNorm.
- ``features``: ``logits_features`` of the repo's LM cascade on the full
  logits of the prompt: mean and max entropy, mean top-1 − top-2 margin,
  mean top-1 probability, mean of each of the top-k probabilities.

Departures from the published model:

- The rotary dims are laid out split (first half, second half) rather
  than interleaved: a fixed permutation of the rope columns of ``Wq`` and
  ``W_kr``, immaterial under random weights.
- Depth is the configuration's ``num_hidden_layers`` (cut to fit a chip).
- So that it fits a chip, attention runs over blocks of queries and the
  head over blocks of positions, each block exact, and a sequence is
  right-padded to a multiple of ``PAD`` tokens (causal: no real position
  sees a pad) so one compiled layer serves many lengths.

Weights are the program's own pytree (``embed``, ``unembed``,
``final_norm``, ``dense_layers``/``moe_layers``, either stacked on a
leading layer axis or a list of per-layer pytrees), read one layer at a
time and widened to float32.  ``rnd`` rounds every weight
and every activation a layer produces; the control passes a rounding to
``float8_e4m3fn``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: sequences are right-padded to a multiple of this many tokens (a
#: multiple of BLOCK), so few lengths are compiled
PAD = 2048
#: queries per attention block, positions per head block
BLOCK = 1024


def exact(x):
    return x


def to_fp8(x):
    """The control's rounding: through ``float8_e4m3fn`` and back."""
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_tables(cfg: Dict):
    """(inv_freq (P/2,), cos/sin factor, softmax scale) of the config."""
    P, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    freq = 1.0 / theta ** (np.arange(0, P, 2, dtype=np.float64) / P)
    scale = (int(cfg["qk_nope_head_dim"]) + P) ** -0.5
    rs = cfg.get("rope_scaling")
    if not rs:
        return freq, 1.0, scale
    f, orig = float(rs["factor"]), int(rs["original_max_position_embeddings"])

    def dim_of(rot):
        return P * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), P - 1)
    ramp = np.clip((np.arange(P // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = freq / f * ramp + freq * (1 - ramp)
    m_all = float(rs.get("mscale_all_dim", 0))
    attn = _mscale(f, rs["mscale"]) / _mscale(f, m_all) if m_all else _mscale(f, rs["mscale"])
    if m_all:
        scale *= _mscale(f, m_all) ** 2
    return inv, attn, scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, inv, m):
    """x (S, h, P), rotary halves split."""
    ang = pos[:, None].astype(F32) * jnp.asarray(inv, F32)[None, :]
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(w, x, rnd):
    g = rnd(x @ rnd(w["gate"]))
    u = rnd(x @ rnd(w["up"]))
    return rnd(rnd(jax.nn.silu(g) * u) @ rnd(w["down"]))


def _mla(w, x, cfg, rnd):
    S = x.shape[0]
    H, N = int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"])
    P, V = int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    inv, m, scale = rope_tables(cfg)
    pos = jnp.arange(S)
    q = rnd(x @ rnd(w["wq"])).reshape(S, H, N + P)
    qn, qr = q[..., :N], rnd(_rope(q[..., N:], pos, inv, m))
    c = rnd(_rms(rnd(x @ rnd(w["w_dkv"])), rnd(w["kv_norm"]["scale"]), cfg["rms_norm_eps"]))
    kr = rnd(_rope(rnd(x @ rnd(w["w_kr"]))[:, None, :], pos, inv, m))[:, 0]
    kn = rnd(c @ rnd(w["w_uk"])).reshape(S, H, N)
    v = rnd(c @ rnd(w["w_uv"])).reshape(S, H, V)
    outs = []
    for lo in range(0, S, BLOCK):
        hi = min(lo + BLOCK, S)
        s = (jnp.einsum("shn,thn->hst", qn[lo:hi], kn)
             + jnp.einsum("shp,tp->hst", qr[lo:hi], kr)) * scale
        causal = jnp.arange(S)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = rnd(jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1))
        outs.append(rnd(jnp.einsum("hst,thv->shv", p, v)).reshape(hi - lo, H * V))
    return rnd(jnp.concatenate(outs) @ rnd(w["wo"]))


def _moe(w, x, cfg, rnd):
    E, k = int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    probs = jax.nn.softmax(rnd(x @ rnd(w["router"].astype(F32))), axis=-1)
    gate, ids = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * float(cfg["routed_scaling_factor"])

    def expert(e, out):
        g = jnp.sum(jnp.where(ids == e, gate, 0.0), axis=-1)  # 0 where e was not picked
        we = {n: w[n][e] for n in ("w_gate", "w_up", "w_down")}
        y = _swiglu({"gate": we["w_gate"].astype(F32), "up": we["w_up"].astype(F32),
                     "down": we["w_down"].astype(F32)}, x, rnd)
        return out + g[:, None] * y

    out = jax.lax.fori_loop(0, E, expert, jnp.zeros_like(x))
    shared = {n: v.astype(F32) for n, v in w["shared"].items()}
    return rnd(out + _swiglu(shared, x, rnd))


@partial(jax.jit, static_argnames=("moe", "cfg_key", "rnd"))
def _layer(w, h, *, moe: bool, cfg_key, rnd):
    """One layer with weights ``w`` over the stream ``h``."""
    cfg = _cfg(cfg_key)
    eps = cfg["rms_norm_eps"]
    f = jax.tree.map(lambda a: a.astype(F32), {k: v for k, v in w.items() if k != "moe"})
    h = rnd(h + _mla(f["attn"], rnd(_rms(h, f["norm1"]["scale"], eps)), cfg, rnd))
    x = rnd(_rms(h, f["norm2"]["scale"], eps))
    if not moe:
        return rnd(h + _swiglu(f["mlp"], x, rnd))
    experts = {n: w["moe"][n] for n in ("router", "w_gate", "w_up", "w_down", "shared")}
    return rnd(h + _moe(experts, x, cfg, rnd))


def _key(cfg: Dict):
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rms_norm_eps", "rope_theta", "n_routed_experts", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor")
    rs = cfg.get("rope_scaling") or {}
    return tuple((k, cfg[k]) for k in keys) + (("rope_scaling", tuple(sorted(rs.items()))),)


def _cfg(key) -> Dict:
    cfg = dict(key)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


def hidden(weights: Dict, cfg: Dict, tokens: np.ndarray, layers: range,
           h: Optional[jnp.ndarray] = None, rnd: Callable = exact) -> jnp.ndarray:
    """The residual stream after ``layers`` for one request's ``tokens``
    (S,): from the embedding, or from ``h`` (the stream before
    ``layers[0]``), padded to a multiple of ``PAD``."""
    S = len(tokens)
    Sp = -(-S // PAD) * PAD
    dense = int(cfg["first_k_dense_replace"])
    with jax.default_matmul_precision("highest"):
        if h is None:
            tok = np.zeros(Sp, np.int32)
            tok[:S] = tokens
            h = _embed(weights["embed"], tok, rnd=rnd)
        key = _key(cfg)
        for i in layers:
            moe = i >= dense
            stack = weights["moe_layers" if moe else "dense_layers"]
            j = i - dense if moe else i
            w = stack[j] if isinstance(stack, (list, tuple)) else jax.tree.map(lambda a: a[j], stack)
            h = _layer(w, h, moe=moe, cfg_key=key, rnd=rnd)
    return h


@partial(jax.jit, static_argnames=("rnd",))
def _embed(table, tok, *, rnd):
    return rnd(table[tok].astype(F32))


@partial(jax.jit, static_argnames=("count", "eps", "rnd"))
def _head(h, start, norm, unembed, *, count, eps, rnd):
    """float32 logits of positions ``[start, start + count)`` of ``h``."""
    x = jax.lax.dynamic_slice_in_dim(h, start, count)
    x = rnd(_rms(x, norm.astype(F32), eps))
    return rnd(x @ rnd(unembed.astype(F32)))


def logits(weights: Dict, cfg: Dict, h: jnp.ndarray, start: int, count: int,
           rnd: Callable = exact) -> jnp.ndarray:
    """float32 logits (count, V) of positions ``[start, start + count)``."""
    with jax.default_matmul_precision("highest"):
        return _head(h, np.int32(start), weights["final_norm"]["scale"], weights["unembed"],
                     count=count, eps=cfg["rms_norm_eps"], rnd=rnd)


@partial(jax.jit, static_argnames=("top_k",))
def _feature_sums(lg, n, *, top_k):
    """Sums over the first ``n`` rows of (entropy, margin, top-1, top-k)
    and their max entropy."""
    v = (jnp.arange(lg.shape[0]) < n).astype(F32)
    lp = jax.nn.log_softmax(lg, axis=-1)
    e = -(jnp.exp(lp) * lp).sum(-1)
    top = jax.lax.top_k(jnp.exp(lp), top_k)[0]
    return ((e * v).sum(), (e * v).max(), ((top[:, 0] - top[:, 1]) * v).sum(),
            (top[:, 0] * v).sum(), (top * v[:, None]).sum(0))


def features(weights: Dict, cfg: Dict, h: jnp.ndarray, n: int, top_k: int = 8,
             rnd: Callable = exact) -> np.ndarray:
    """The 4 + ``top_k`` logits features of the first ``n`` positions of
    ``h``: from their full logits, a block of positions at a time."""
    ent, ent_max, margin, top1, topk = 0.0, 0.0, 0.0, 0.0, np.zeros(top_k)
    for lo in range(0, n, BLOCK):
        lg = logits(weights, cfg, h, lo, BLOCK, rnd)
        e, em, mg, t1, tk = jax.device_get(_feature_sums(lg, np.int32(n - lo), top_k=top_k))
        ent += float(e)
        ent_max = max(ent_max, float(em))
        margin += float(mg)
        top1 += float(t1)
        topk += np.asarray(tk, np.float64)
    return np.concatenate([[ent / n, ent_max, margin / n, top1 / n], topk / n])


def estimate(x, p: Dict, rnd: Callable = exact) -> np.ndarray:
    """The decision's estimator (``reference/estimator.py``'s ``forward``:
    standardise, ``sigmoid(gelu(z W1 + b1) w2 + b2)``) in float32 with the
    MLP's weights and activations rounded by ``rnd``; the control's
    estimates.  The standardisation stays in float32: the features' own
    scales (about 1e-5 for the top-k probabilities over 102,400 ids) lie
    below float8_e4m3fn's smallest step, which would turn them into 0/0."""
    with jax.default_matmul_precision("highest"):
        q = {k: jnp.asarray(v, F32) for k, v in p.items()}
        z = rnd((jnp.asarray(x, F32) - q["mu"]) / q["sigma"])
        h = rnd(jax.nn.gelu(rnd(z @ rnd(q["w1"]) + rnd(q["b1"])), approximate=True))
        o = rnd(h @ rnd(q["w2"]) + rnd(q["b2"]))
    return np.asarray(jax.nn.sigmoid(o), np.float64).reshape(-1)
