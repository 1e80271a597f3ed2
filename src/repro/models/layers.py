"""Transformer / SSM layer zoo for the assigned architectures.

Everything is functional: ``*_init(key, ...) -> params`` (nested dicts) and
``*_apply(params, x, ...)``.  Dtype policy: params in ``param_dtype``
(default float32), activations cast to ``dtype`` (bf16 for the production
dry-run).  All sequence stacks use ``jax.lax.scan``-compatible shapes so a
64-layer model lowers to depth-independent HLO.

Covered: RMSNorm/LayerNorm, RoPE + M-RoPE + YaRN, GQA attention (bias,
qk_norm, sliding window, KV-cache decode), SwiGLU/GELU MLP, MoE (shared +
routed top-k, load-balance aux; capacity-based or dropless), MLA
(compressed KV), RWKV6 time/channel mix, Mamba2 (SSD) block.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape: Tuple[int, ...], dtype=jnp.float32, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else (1.0 / fan_in) ** 0.5
    return jax.random.normal(key, shape, dtype) * scale


def rmsnorm_init(dim: int, dtype=jnp.float32) -> PyTree:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: PyTree, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return y * params["scale"].astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32) -> PyTree:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params: PyTree, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return y * params["scale"].astype(x.dtype) + params["bias"].astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked scan: O(S/chunk) residency for recurrent backward passes
# ---------------------------------------------------------------------------

def chunked_scan(step, init, xs, chunk: int = 0):
    """``jax.lax.scan`` with chunk-level activation checkpointing.

    A plain scan saves every per-step carry for the backward pass — for a
    4k-token RWKV/Mamba layer that is seq_len × state bytes.  Chunking nests
    an inner scan (rematerialised via ``jax.checkpoint``) inside an outer
    scan, so only chunk-boundary states are saved: memory drops by ``chunk``×
    at the cost of one extra forward over each chunk in the backward pass.
    Exact (bitwise same forward); ``chunk=0`` or non-divisible S falls back.
    """
    S = jax.tree.leaves(xs)[0].shape[0]
    if chunk <= 1 or S <= chunk or S % chunk != 0:
        return jax.lax.scan(step, init, xs)
    xs_c = jax.tree.map(lambda a: a.reshape(S // chunk, chunk, *a.shape[1:]), xs)

    @jax.checkpoint
    def outer(carry, xc):
        return jax.lax.scan(step, carry, xc)

    carry, ys = jax.lax.scan(outer, init, xs_c)
    ys = jax.tree.map(lambda a: a.reshape(S, *a.shape[2:]), ys)
    return carry, ys


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e6) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor ``0.1·m·ln(s) + 1`` (1 for s <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(
    dim: int, theta: float, factor: float, original_max: int,
    beta_fast: float, beta_slow: float,
) -> jnp.ndarray:
    """YaRN rotary frequencies as DeepSeek-V2 defines them: the dims whose
    wavelength fits ``beta_fast`` times into ``original_max`` keep their
    frequency, those fitting fewer than ``beta_slow`` times are divided by
    ``factor``, and a linear ramp over ``[low, high]`` blends between."""
    def dim_of(rotations: float) -> float:
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), dim - 1)
    extra = rope_freqs(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float = 1e6,
    freqs: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S).  ``freqs``
    (D/2,) replaces the plain ``theta`` frequencies (YaRN)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if freqs is None else freqs  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out1 = x1 * cos.astype(x.dtype) - x2 * sin.astype(x.dtype)
    out2 = x2 * cos.astype(x.dtype) + x1 * sin.astype(x.dtype)
    return jnp.concatenate([out1, out2], axis=-1)


def apply_mrope(
    x: jnp.ndarray,
    positions_3d: jnp.ndarray,
    sections: Tuple[int, int, int],
    theta: float = 1e6,
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.  ``positions_3d``: (3, B, S) —
    (temporal, height, width) position ids; ``sections`` split the D/2
    frequency bands among the three axes (e.g. (16, 24, 24) for D=128).
    Text tokens carry identical t/h/w ids, recovering 1-D RoPE exactly.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    # angles per axis, then band-select by section
    ang = positions_3d[..., None].astype(jnp.float32) * freqs  # (3, B, S, D/2)
    sec = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )  # (D/2,)
    angles = jnp.take_along_axis(
        jnp.moveaxis(ang, 0, -1), sec[None, None, :, None], axis=-1
    )[..., 0]  # (B, S, D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out1 = x1 * cos.astype(x.dtype) - x2 * sin.astype(x.dtype)
    out2 = x2 * cos.astype(x.dtype) + x1 * sin.astype(x.dtype)
    return jnp.concatenate([out1, out2], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

class AttnConfig(NamedTuple):
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    window: int = 0  # 0 = full causal; >0 = sliding window
    rope_theta: float = 1e6
    use_rope: bool = True  # False: absolute positions only (whisper)
    mrope_sections: Optional[Tuple[int, int, int]] = None
    attn_chunk: int = 0  # >0: query-chunked SDPA (bounds the S×T logits
    #                      working set — flash-attention-style residency)
    seq_shard: bool = False  # shard the query dim over `model` inside each
    #   chunk (sequence/context parallelism). Use when num_heads is not
    #   divisible by the model axis: head-sharded attention then forces
    #   SPMD to all-reduce the full S×T logits (§Perf hillclimb #2).


def attention_init(key, cfg: AttnConfig, dtype=jnp.float32) -> PyTree:
    ks = jax.random.split(key, 4)
    H, K, D, M = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    p: PyTree = {
        "wq": dense_init(ks[0], (M, H * D), dtype),
        "wk": dense_init(ks[1], (M, K * D), dtype),
        "wv": dense_init(ks[2], (M, K * D), dtype),
        "wo": dense_init(ks[3], (H * D, M), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * D,), dtype)
        p["bk"] = jnp.zeros((K * D,), dtype)
        p["bv"] = jnp.zeros((K * D,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(D, dtype)
        p["k_norm"] = rmsnorm_init(D, dtype)
    return p


def _project_qkv(params, cfg: AttnConfig, x, positions, positions_3d=None):
    B, S, _ = x.shape
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, D)
    k = k.reshape(B, S, K, D)
    v = v.reshape(B, S, K, D)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if not cfg.use_rope:
        pass
    elif cfg.mrope_sections is not None and positions_3d is not None:
        q = apply_mrope(q, positions_3d, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions_3d, cfg.mrope_sections, cfg.rope_theta)
    elif positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, num_kv_heads: int, num_heads: int):
    """q: (B,S,H,D), k/v: (B,T,K,D); GQA via head grouping."""
    B, S, H, D = q.shape
    T = k.shape[1]
    G = H // num_kv_heads
    q = q.reshape(B, S, num_kv_heads, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k) / jnp.sqrt(D).astype(q.dtype)
    logits = jnp.where(mask[:, None, None, :, :], logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H * D)


def _chunked_sdpa(
    q, k, v, mask, num_kv_heads: int, num_heads: int, chunk: int,
    seq_shard: bool = False,
):
    """Query-chunked SDPA: the (S, T) logits tensor never materialises —
    only (chunk, T) per map step.  Exact; used when S is large (prefill/
    train at long seq_len) so per-device attention residency is bounded.

    ``seq_shard``: shard each chunk's query rows over `model` and keep K/V
    replicated — context parallelism.  Attention becomes fully local per
    device regardless of head-count divisibility (the head-sharded layout
    degenerates to a full-logits all-reduce when H % mesh_model != 0)."""
    from repro.launch.meshctx import constrain  # local import (no cycle)

    B, S, H, D = q.shape
    n = S // chunk
    qc = jnp.moveaxis(q.reshape(B, n, chunk, H, D), 1, 0)  # (n, B, c, H, D)
    mb = jnp.broadcast_to(mask, (mask.shape[0], S, mask.shape[2]))
    mc = jnp.moveaxis(mb.reshape(mask.shape[0], n, chunk, mask.shape[2]), 1, 0)
    if seq_shard:
        k = constrain(k, "batch", None, None, None)  # replicated over model
        v = constrain(v, "batch", None, None, None)

    def f(args):
        qi, mi = args
        if seq_shard:
            qi = constrain(qi, "batch", "model", None, None)
            out_i = _sdpa(qi, k, v, mi, num_kv_heads, num_heads)
            return constrain(out_i, "batch", "model", None)
        return _sdpa(qi, k, v, mi, num_kv_heads, num_heads)

    out = jax.lax.map(f, (qc, mc))  # (n, B, c, H*D)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H * D)


def causal_mask(S: int, T: int, offset: int, window: int = 0) -> jnp.ndarray:
    """(1, S, T) bool; query i (global pos offset+i) sees key j iff
    j <= offset+i and (window == 0 or j > offset+i-window)."""
    qpos = offset + jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m[None]


def attention_apply(
    params: PyTree,
    cfg: AttnConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    positions_3d: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
    return_kv: bool = False,
):
    """Full (training/prefill) self-attention.  ``return_kv`` additionally
    returns the rotated (k, v) for prefill cache construction."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions, positions_3d)
    if mask is None:
        mask = causal_mask(S, S, 0, cfg.window)
    if cfg.attn_chunk and S > cfg.attn_chunk and S % cfg.attn_chunk == 0:
        out = _chunked_sdpa(q, k, v, mask, cfg.num_kv_heads, cfg.num_heads,
                            cfg.attn_chunk, seq_shard=cfg.seq_shard)
    elif cfg.seq_shard:
        from repro.launch.meshctx import constrain

        q = constrain(q, "batch", "model", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
        out = _sdpa(q, k, v, mask, cfg.num_kv_heads, cfg.num_heads)
        out = constrain(out, "batch", "model", None)
    else:
        out = _sdpa(q, k, v, mask, cfg.num_kv_heads, cfg.num_heads)
    out = out @ params["wo"].astype(x.dtype)
    if return_kv:
        return out, (k, v)
    return out


def kv_quantize(k: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(slot, head) symmetric int8 quantisation: k ≈ q · s.
    k: (..., K, D) -> (q int8 (..., K, D), s f32 (..., K))."""
    amax = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s


def kv_dequantize(q: jnp.ndarray, s: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * s[..., None].astype(jnp.float32)).astype(dtype)


def attention_decode(
    params: PyTree,
    cfg: AttnConfig,
    x: jnp.ndarray,  # (B, 1, M)
    cache_k: jnp.ndarray,  # (B, C, K, D) — C = cache capacity (window or max)
    cache_v: jnp.ndarray,
    pos: jnp.ndarray,  # () int32 — global position of this token
    positions_3d: Optional[jnp.ndarray] = None,
    cache_scales: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
):
    """One-token decode against a (possibly rotating) KV cache.

    For ``cfg.window > 0`` the cache has capacity ``window`` and is written
    at ``pos % window`` (ring buffer); keys carry their true positions via
    RoPE applied at write time, so no re-rotation is needed.

    ``cache_scales`` = (k_s, v_s) (B, C, K) enables the int8-quantised
    cache (§Perf hillclimb #3): k/v are stored int8 with per-(slot, head)
    scales — half the HBM residency and copy traffic of bf16.  Returns
    (out, cache_k, cache_v[, new_scales]).
    """
    B = x.shape[0]
    C = cache_k.shape[1]
    q, k, v = _project_qkv(
        params, cfg, x, jnp.full((B, 1), pos), positions_3d
    )
    slot = (pos % C) if cfg.window > 0 else pos
    quant = cache_scales is not None
    if quant:
        k_s, v_s = cache_scales
        k_q, k_sc = kv_quantize(k)  # (B,1,K,D) int8, (B,1,K)
        v_q, v_sc = kv_quantize(v)
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_q, slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_q, slot, axis=1)
        k_s = jax.lax.dynamic_update_slice_in_dim(k_s, k_sc, slot, axis=1)
        v_s = jax.lax.dynamic_update_slice_in_dim(v_s, v_sc, slot, axis=1)
        k_full = kv_dequantize(cache_k, k_s, x.dtype)
        v_full = kv_dequantize(cache_v, v_s, x.dtype)
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)
        k_full, v_full = cache_k, cache_v
    # valid = slots holding tokens in [max(0, pos-window+1), pos]
    slot_ids = jnp.arange(C)
    if cfg.window > 0:
        valid = slot_ids <= jnp.minimum(pos, C - 1)  # ring filled up to min(pos, C-1)
    else:
        valid = slot_ids <= pos
    mask = valid[None, None, :]  # (1, 1, C)
    out = _sdpa(q, k_full, v_full, mask, cfg.num_kv_heads, cfg.num_heads)
    out = out @ params["wo"].astype(x.dtype)
    if quant:
        return out, cache_k, cache_v, (k_s, v_s)
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, d_model: int, d_ff: int, dtype=jnp.float32) -> PyTree:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, (d_model, d_ff), dtype),
        "up": dense_init(k2, (d_model, d_ff), dtype),
        "down": dense_init(k3, (d_ff, d_model), dtype),
    }


def swiglu(params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    g = jax.nn.silu(x @ params["gate"].astype(x.dtype))
    u = x @ params["up"].astype(x.dtype)
    return (g * u) @ params["down"].astype(x.dtype)


def gelu_mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32) -> PyTree:
    k1, k2 = jax.random.split(key)
    return {
        "up": dense_init(k1, (d_model, d_ff), dtype),
        "up_b": jnp.zeros((d_ff,), dtype),
        "down": dense_init(k2, (d_ff, d_model), dtype),
        "down_b": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.gelu(x @ params["up"].astype(x.dtype) + params["up_b"].astype(x.dtype))
    return h @ params["down"].astype(x.dtype) + params["down_b"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based static dispatch; shared + routed)
# ---------------------------------------------------------------------------

class MoEConfig(NamedTuple):
    d_model: int
    d_ff_expert: int
    num_experts: int
    top_k: int
    num_shared: int = 0
    capacity_factor: float = 1.25
    aux_weight: float = 0.001
    groups: int = 0  # >0: group-local dispatch (see moe_apply_grouped)
    norm_topk: bool = True  # renormalise the k gates to sum to 1
    dropless: bool = False  # sorted ragged dispatch (moe_apply_dropless)


def moe_init(key, cfg: MoEConfig, dtype=jnp.float32) -> PyTree:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    E, M, F = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    p: PyTree = {
        "router": dense_init(k1, (M, E), jnp.float32),  # router in fp32
        "w_gate": dense_init(k2, (E, M, F), dtype),
        "w_up": dense_init(k3, (E, M, F), dtype),
        "w_down": dense_init(k4, (E, F, M), dtype),
    }
    if cfg.num_shared:
        p["shared"] = swiglu_init(k5, M, cfg.num_shared * F, dtype)
    return p


def moe_apply(
    params: PyTree, cfg: MoEConfig, x: jnp.ndarray, valid: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatches to the dropless path when ``cfg.dropless``; else to the
    grouped (SPMD-shardable) path when cfg.groups > 0 and the token count
    divides; otherwise the flat-capacity path below.  ``valid`` (B, S)
    marks the tokens the dropless path routes (pads are left out); the
    capacity paths route every token."""
    if cfg.dropless:
        return moe_apply_dropless(params, cfg, x, valid)
    if cfg.groups and (x.shape[0] * x.shape[1]) % cfg.groups == 0:
        return moe_apply_grouped(params, cfg, x)
    return moe_apply_flat(params, cfg, x)


def _topk_gates(probs: jnp.ndarray, cfg: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy top-k of the router's softmax: the k gates (renormalised to
    sum to 1 when ``cfg.norm_topk``) and the expert ids, in float32."""
    gate, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, ids


def _route(params: PyTree, cfg: MoEConfig, tok: jnp.ndarray):
    """float32 router over (T, M) tokens: (probs (T, E), gates, ids (T, k))."""
    probs = jax.nn.softmax(tok.astype(jnp.float32) @ params["router"].astype(jnp.float32), -1)
    return (probs,) + _topk_gates(probs, cfg)


def _switch_aux(probs: jnp.ndarray, ids: jnp.ndarray, cfg: MoEConfig) -> jnp.ndarray:
    """Switch load-balance loss ``E·Σ_e f_e·p̄_e`` over (T, E) probs."""
    E = cfg.num_experts
    f = jnp.mean((jax.nn.one_hot(ids, E).sum(axis=1) > 0).astype(jnp.float32), axis=0)
    return cfg.aux_weight * E * jnp.sum(f * probs.mean(axis=0))


def _sorted_pairs(ids: jnp.ndarray, valid: Optional[jnp.ndarray], E: int):
    """(token, expert) pairs sorted by expert: the sort order over the
    flat (T·k,) pairs and the pair count of each expert.  Pairs of
    invalid tokens take the id ``E``, sort last and fall in no group."""
    flat_e = ids.reshape(-1)
    if valid is not None:
        flat_e = jnp.where(jnp.repeat(valid.reshape(-1), ids.shape[1]), flat_e, E)
    order = jnp.argsort(flat_e, stable=True)
    # a one-hot count, not a scatter-add: 64 bins take every pair
    sizes = (flat_e[:, None] == jnp.arange(E)[None, :]).sum(0, dtype=jnp.int32)
    return order, sizes


def moe_load(
    params: PyTree, cfg: MoEConfig, x: jnp.ndarray, valid: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """(E,) routed (token, expert) pairs per expert over the valid tokens
    of ``x`` (B, S, M), as the router of ``moe_apply`` picks them."""
    _, _, ids = _route(params, cfg, x.reshape(-1, x.shape[-1]))
    return _sorted_pairs(ids, valid, cfg.num_experts)[1]


def moe_apply_dropless(
    params: PyTree, cfg: MoEConfig, x: jnp.ndarray, valid: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, M) -> (out, aux_loss) with no capacity: every (token,
    expert) pair the router picks is computed.  The pairs are sorted by
    expert and the three expert matmuls run as ``jax.lax.ragged_dot`` over
    the sorted rows, one group per expert; the outputs are put back in
    pair order and summed with their gates.  Tokens where ``valid`` is
    False fall in no group, so pads cost no expert work and get no routed
    output (the shared experts still see them)."""
    B, S, M = x.shape
    T, K = B * S, cfg.top_k
    tok = x.reshape(T, M)
    probs, gate, ids = _route(params, cfg, tok)
    order, sizes = _sorted_pairs(ids, valid, cfg.num_experts)
    rows = tok[order // K]  # (T·k, M), grouped by expert
    dt = x.dtype
    g = jax.nn.silu(jax.lax.ragged_dot(rows, params["w_gate"].astype(dt), sizes))
    u = jax.lax.ragged_dot(rows, params["w_up"].astype(dt), sizes)
    y = jax.lax.ragged_dot(g * u, params["w_down"].astype(dt), sizes)
    back = jnp.argsort(order)  # the inverse permutation
    y = y[back].reshape(T, K, M).astype(jnp.float32)
    if valid is not None:  # rows outside every group: zero them whatever they hold
        y = jnp.where(valid.reshape(T, 1, 1), y, 0.0)
    out = jnp.einsum("tkm,tk->tm", y, gate).astype(dt)
    if cfg.num_shared and "shared" in params:
        out = out + swiglu(params["shared"], tok)
    return out.reshape(B, S, M), _switch_aux(probs, ids, cfg)


def moe_apply_flat(
    params: PyTree, cfg: MoEConfig, x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, M) -> (out, aux_loss).  Static-capacity dispatch:
    tokens route to top-k experts; each expert processes at most
    C = ceil(T·k/E · capacity_factor) tokens (overflow dropped), giving a
    fixed (E, C, M) compute shape that shards expert-parallel on `model`.

    CAVEAT (found by the dry-run, fixed by moe_apply_grouped): the global
    scatter's position indices depend on a cumsum over ALL tokens, so the
    SPMD partitioner cannot shard the dispatch — on a 256-chip mesh every
    device re-does near-global work.  Kept as the paper-faithful/naive
    baseline for §Perf.
    """
    B, S, M = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.top_k
    cap = int((T * K / E) * cfg.capacity_factor) + 1
    tok = x.reshape(T, M)
    probs, gate, expert_ids = _route(params, cfg, tok)  # (T, E), (T, K), (T, K)
    gate = gate.astype(x.dtype)

    flat_e = expert_ids.reshape(-1)  # (T*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*K, E)
    pos_all = jnp.cumsum(onehot, axis=0) - 1  # (T*K, E)
    pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]  # (T*K,)
    keep = pos < cap
    # scatter tokens into (E, cap, M)
    tok_rep = jnp.repeat(tok, K, axis=0)  # (T*K, M)
    safe_pos = jnp.where(keep, pos, cap - 1)
    buf = jnp.zeros((E, cap, M), x.dtype)
    buf = buf.at[flat_e, safe_pos].add(
        tok_rep * keep[:, None].astype(x.dtype), mode="drop"
    )
    # expert-parallel: dispatch buffer sharded on experts -> the scatter
    # above lowers to the MoE all-to-all under SPMD
    from repro.launch.meshctx import constrain  # local import (no cycle)

    buf = constrain(buf, "expert", None, None)
    # expert FFN: (E, cap, M) x (E, M, F)
    g = jax.nn.silu(jnp.einsum("ecm,emf->ecf", buf, params["w_gate"].astype(x.dtype)))
    u = jnp.einsum("ecm,emf->ecf", buf, params["w_up"].astype(x.dtype))
    y = jnp.einsum("ecf,efm->ecm", g * u, params["w_down"].astype(x.dtype))
    y = constrain(y, "expert", None, None)
    # gather back and combine with gates
    out_tok = y[flat_e, safe_pos] * (gate.reshape(-1, 1) * keep[:, None].astype(x.dtype))
    out = out_tok.reshape(T, K, M).sum(axis=1)
    if cfg.num_shared and "shared" in params:
        out = out + swiglu(params["shared"], tok)
    return out.reshape(B, S, M), _switch_aux(probs, expert_ids, cfg)


def moe_apply_grouped(
    params: PyTree, cfg: MoEConfig, x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Group-local MoE dispatch (§Perf hillclimb #1; GShard-style groups).

    Tokens are reshaped to (G, Tg, M) with the group axis sharded over
    `data`; routing, the position-in-expert cumsum, the dispatch scatter
    and the combine gather are all BATCHED over g, so SPMD keeps them
    local to each data shard.  The only cross-device traffic is the
    (g→data)⇄(e→model) resharding of the (G, E, Cg, M) expert buffer —
    the canonical MoE all-to-all — plus the usual output reduction.
    Per-group capacity Cg = Tg·k/E · capacity_factor bounds imbalance
    within a group (drops are per-group, slightly stricter than the flat
    path's global capacity; aux load-balance loss unchanged).
    """
    from repro.launch.meshctx import constrain  # local import (no cycle)

    B, S, M = x.shape
    T = B * S
    G = cfg.groups
    Tg = T // G
    E, K = cfg.num_experts, cfg.top_k
    cap = int((Tg * K / E) * cfg.capacity_factor) + 1
    tok = constrain(x.reshape(G, Tg, M), "batch", None, None)
    tok_f32 = constrain(tok.astype(jnp.float32), "batch", None, None)
    logits = tok_f32 @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (G, Tg, E)
    gate, expert_ids = _topk_gates(probs, cfg)  # (G, Tg, K)
    gate = gate.astype(x.dtype)

    flat_e = expert_ids.reshape(G, Tg * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (G, TgK, E)
    pos_all = jnp.cumsum(onehot, axis=1) - 1  # group-local positions
    pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=2)[..., 0]
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap - 1)
    tok_rep = constrain(jnp.repeat(tok, K, axis=1), "batch", None, None)  # (G, TgK, M)
    g_idx = jnp.broadcast_to(jnp.arange(G)[:, None], (G, Tg * K))
    buf = jnp.zeros((G, E, cap, M), x.dtype)
    buf = buf.at[g_idx, flat_e, safe_pos].add(
        tok_rep * keep[..., None].astype(x.dtype), mode="drop"
    )
    # pin the scatter result g-sharded BEFORE the expert reshard so neither
    # the forward scatter nor its transpose (a gather) replicates
    buf = constrain(buf, "batch", None, None, None)
    # slot-side bookkeeping for the combine scatter: the owning token id and
    # the routing gate per (e, cap) slot (0 on dropped/empty slots)
    tok_ids = jnp.broadcast_to(
        (jnp.arange(Tg * K) // K)[None, :], (G, Tg * K)
    )
    # dropped entries scatter to index `cap` (out of bounds -> mode="drop"),
    # so they cannot clobber a legitimate occupant of the last slot
    oob_pos = jnp.where(keep, pos, cap)
    slot_tok = constrain(
        jnp.zeros((G, E, cap), jnp.int32).at[g_idx, flat_e, oob_pos].set(
            tok_ids, mode="drop"
        ),
        "batch", "expert", None,
    )
    slot_gate = constrain(
        jnp.zeros((G, E, cap), x.dtype).at[g_idx, flat_e, oob_pos].set(
            gate.reshape(G, Tg * K), mode="drop"
        ),
        "batch", "expert", None,
    )
    # (g→data) ⇄ (e→model): the MoE all-to-all happens at this constraint
    buf = constrain(buf, "batch", "expert", None, None)
    g_ = jax.nn.silu(jnp.einsum("gecm,emf->gecf", buf, params["w_gate"].astype(x.dtype)))
    u_ = jnp.einsum("gecm,emf->gecf", buf, params["w_up"].astype(x.dtype))
    y = jnp.einsum("gecf,efm->gecm", g_ * u_, params["w_down"].astype(x.dtype))
    y = constrain(y, "batch", "expert", None, None)
    # combine by scattering slots back to their tokens (partial per expert
    # shard + reduction) instead of gathering the e-sharded buffer — avoids
    # an all-gather of y over the model axis (§Perf hillclimb #1, iter 2)
    g_idx3 = jnp.broadcast_to(jnp.arange(G)[:, None, None], (G, E, cap))
    contrib = y * slot_gate[..., None]
    out = jnp.zeros((G, Tg, M), x.dtype).at[g_idx3, slot_tok].add(contrib, mode="drop")
    out = constrain(out, "batch", None, None).reshape(B, S, M)
    if cfg.num_shared and "shared" in params:
        out = out + swiglu(params["shared"], tok.reshape(T, M)).reshape(B, S, M)
    return out, _switch_aux(probs.reshape(T, E), expert_ids.reshape(T, K), cfg)


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

class MLAConfig(NamedTuple):
    d_model: int
    num_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 1e6
    attn_chunk: int = 0  # query chunking, as in AttnConfig
    # YaRN (DeepSeek-V2's ``rope_scaling``): (factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim); None = plain RoPE
    rope_scaling: Optional[Tuple[float, int, float, float, float, float]] = None


def mla_rope(cfg: MLAConfig) -> Tuple[Optional[jnp.ndarray], float, float]:
    """``(inv_freq, rope_mscale, softmax_scale)`` of MLA's rotary dims.
    Plain RoPE: ``(None, 1, (qk_nope + qk_rope)^-0.5)``.  YaRN: the YaRN
    frequencies, cos/sin scaled by ``mscale(f, mscale) / mscale(f,
    mscale_all_dim)`` and the softmax scale by ``mscale(f, mscale_all_dim)²``."""
    base = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_scaling is None:
        return None, 1.0, base
    factor, orig, beta_fast, beta_slow, mscale, mscale_all = cfg.rope_scaling
    inv = yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, factor, orig, beta_fast, beta_slow)
    rope_m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    return inv, rope_m, base * yarn_mscale(factor, mscale_all) ** 2


def _mla_rotate(x, positions, cfg: MLAConfig, inv, rope_m):
    out = apply_rope(x, positions, cfg.rope_theta, freqs=inv)
    return out if rope_m == 1.0 else out * jnp.asarray(rope_m, out.dtype)


def mla_init(key, cfg: MLAConfig, dtype=jnp.float32) -> PyTree:
    ks = jax.random.split(key, 6)
    M, H = cfg.d_model, cfg.num_heads
    R, N, P, V = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
    return {
        "wq": dense_init(ks[0], (M, H * (N + P)), dtype),
        "w_dkv": dense_init(ks[1], (M, R), dtype),  # compress
        "w_kr": dense_init(ks[2], (M, P), dtype),  # shared rope key
        "w_uk": dense_init(ks[3], (R, H * N), dtype),  # decompress K
        "w_uv": dense_init(ks[4], (R, H * V), dtype),  # decompress V
        "wo": dense_init(ks[5], (H * V, M), dtype),
        "kv_norm": rmsnorm_init(R, dtype),
    }


def mla_apply(
    params: PyTree, cfg: MLAConfig, x: jnp.ndarray, positions: jnp.ndarray,
    return_kv: bool = False,
):
    """Training/prefill: expanded-KV form (matches reference semantics).
    Scores and the softmax are float32."""
    B, S, M = x.shape
    H, N, P, V = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
    inv, rope_m, scale = mla_rope(cfg)
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, S, H, N + P)
    q_nope, q_rope = q[..., :N], q[..., N:]
    q_rope = _mla_rotate(q_rope, positions, cfg, inv, rope_m)
    c_kv = rmsnorm(params["kv_norm"], x @ params["w_dkv"].astype(x.dtype))  # (B,S,R)
    k_rope = _mla_rotate(
        (x @ params["w_kr"].astype(x.dtype))[:, :, None, :], positions, cfg, inv, rope_m
    )  # (B,S,1,P) shared across heads
    k_nope = (c_kv @ params["w_uk"].astype(x.dtype)).reshape(B, S, H, N)
    v = (c_kv @ params["w_uv"].astype(x.dtype)).reshape(B, S, H, V)
    mask = causal_mask(S, S, 0)  # (1, S, T)
    f32 = jnp.float32

    def _attend(qn, qr, m):
        # qn: (B, s, H, N), qr: (B, s, H, P), m: (1, s, T)
        logits = (
            jnp.einsum("bshn,bthn->bhst", qn, k_nope, preferred_element_type=f32)
            + jnp.einsum("bshp,btp->bhst", qr, k_rope[:, :, 0], preferred_element_type=f32)
        ) * scale
        logits = jnp.where(m[:, None], logits, jnp.finfo(f32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        s_len = qn.shape[1]
        return jnp.einsum("bhst,bthv->bshv", probs, v).reshape(B, s_len, H * V)

    chunk = cfg.attn_chunk
    if chunk and S > chunk and S % chunk == 0:
        n = S // chunk
        qn_c = jnp.moveaxis(q_nope.reshape(B, n, chunk, H, N), 1, 0)
        qr_c = jnp.moveaxis(q_rope.reshape(B, n, chunk, H, P), 1, 0)
        m_c = jnp.moveaxis(mask.reshape(1, n, chunk, S), 1, 0)
        out = jax.lax.map(lambda a: _attend(*a), (qn_c, qr_c, m_c))
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, H * V)
    else:
        out = _attend(q_nope, q_rope, mask)
    out = out @ params["wo"].astype(x.dtype)
    if return_kv:
        return out, (c_kv, k_rope[:, :, 0])
    return out


def mla_decode(
    params: PyTree,
    cfg: MLAConfig,
    x: jnp.ndarray,  # (B, 1, M)
    cache_c: jnp.ndarray,  # (B, C, R)   compressed latent cache
    cache_kr: jnp.ndarray,  # (B, C, P)  shared rope-key cache
    pos: jnp.ndarray,  # () or (B,) int32 — position of this token, per row
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Decode with the **absorbed** form: the cache stores only the R-dim
    latent + P-dim rope key per token (the paper-headline KV saving);
    W_uk is absorbed into the query, W_uv into the output projection.
    Each row writes its slot ``pos[b]`` and attends to slots ``<= pos[b]``,
    so rows of a block may sit at different positions (ragged prompts).
    """
    B, _, M = x.shape
    H, N, P, V, R = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim, cfg.kv_lora_rank
    inv, rope_m, scale = mla_rope(cfg)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, 1, H, N + P)
    q_nope, q_rope = q[..., :N], q[..., N:]
    q_rope = _mla_rotate(q_rope, pos[:, None], cfg, inv, rope_m)
    c_new = rmsnorm(params["kv_norm"], x @ params["w_dkv"].astype(x.dtype))  # (B,1,R)
    kr_new = _mla_rotate(
        (x @ params["w_kr"].astype(x.dtype))[:, :, None, :], pos[:, None], cfg, inv, rope_m
    )[:, :, 0]  # (B,1,P)
    rows = jnp.arange(B)
    cache_c = cache_c.at[rows, pos].set(c_new[:, 0])
    cache_kr = cache_kr.at[rows, pos].set(kr_new[:, 0])
    # absorb W_uk into q: q_lat (B,H,R)
    w_uk = params["w_uk"].astype(x.dtype).reshape(R, H, N)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
    f32 = jnp.float32
    logits = (
        jnp.einsum("bhr,bcr->bhc", q_lat, cache_c, preferred_element_type=f32)
        + jnp.einsum("bhp,bcp->bhc", q_rope[:, 0], cache_kr, preferred_element_type=f32)
    ) * scale
    C = cache_c.shape[1]
    valid = (jnp.arange(C)[None, :] <= pos[:, None])[:, None, :]  # (B, 1, C)
    logits = jnp.where(valid, logits, jnp.finfo(f32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhc,bcr->bhr", probs, cache_c)  # attend in latent space
    w_uv = params["w_uv"].astype(x.dtype).reshape(R, H, V)
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv).reshape(B, 1, H * V)
    return out @ params["wo"].astype(x.dtype), cache_c, cache_kr


# ---------------------------------------------------------------------------
# RWKV6 ("Finch") — data-dependent decay linear attention
# ---------------------------------------------------------------------------

class RWKV6Config(NamedTuple):
    d_model: int
    head_size: int = 64
    lora_rank: int = 32
    ffn_mult: float = 3.5  # d_ff = 7168 for d=2048

    @property
    def num_heads(self) -> int:
        return self.d_model // self.head_size


def rwkv6_init(key, cfg: RWKV6Config, dtype=jnp.float32) -> PyTree:
    ks = jax.random.split(key, 12)
    M, Hd = cfg.d_model, cfg.head_size
    H = cfg.num_heads
    r = cfg.lora_rank
    d_ff = int(cfg.ffn_mult * M)
    return {
        # time-mix lerp factors (data-independent base + lora modulation)
        "mix_base": jax.random.uniform(ks[0], (5, M), dtype, 0.0, 1.0),
        "mix_lora_a": dense_init(ks[1], (M, 5 * r), dtype),
        "mix_lora_b": dense_init(ks[2], (5 * r, 5 * M), dtype, scale=0.01),
        "wr": dense_init(ks[3], (M, M), dtype),
        "wk": dense_init(ks[4], (M, M), dtype),
        "wv": dense_init(ks[5], (M, M), dtype),
        "wg": dense_init(ks[6], (M, M), dtype),
        "wo": dense_init(ks[7], (M, M), dtype),
        # data-dependent decay lora
        "decay_base": jnp.zeros((M,), dtype),
        "decay_lora_a": dense_init(ks[8], (M, 2 * r), dtype),
        "decay_lora_b": dense_init(ks[9], (2 * r, M), dtype, scale=0.01),
        "bonus": jnp.zeros((H, Hd), dtype),  # per-head u term
        "ln_x": layernorm_init(M, dtype),  # group-norm-ish output norm
        # channel mix
        "cm_mix": jax.random.uniform(ks[10], (M,), dtype, 0.0, 1.0),
        "cm_k": dense_init(ks[11], (M, d_ff), dtype),
        "cm_v": dense_init(jax.random.fold_in(key, 99), (d_ff, M), dtype),
        "cm_r": dense_init(jax.random.fold_in(key, 98), (M, M), dtype),
    }


def _rwkv6_mix(params, x, x_prev):
    """Data-dependent token-shift lerp producing the 5 mixed streams
    (r, k, v, g, w).  x: (B,S,M); x_prev: x shifted right by one."""
    B, S, M = x.shape
    dx = x_prev - x
    base = params["mix_base"].astype(x.dtype)  # (5, M)
    lora = jnp.tanh(x @ params["mix_lora_a"].astype(x.dtype))  # (B,S,5r)
    lora = (lora @ params["mix_lora_b"].astype(x.dtype)).reshape(B, S, 5, M)
    mix = base[None, None] + lora  # (B,S,5,M)
    return x[:, :, None, :] + dx[:, :, None, :] * mix  # (B,S,5,M)


def rwkv6_time_mix(
    params: PyTree,
    cfg: RWKV6Config,
    x: jnp.ndarray,
    state: Optional[jnp.ndarray] = None,  # (B, H, Hd, Hd) wkv state
    x_last: Optional[jnp.ndarray] = None,  # (B, M) last token (for decode)
    chunk: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (out, new_state, new_x_last).  Handles S>=1 via scan."""
    B, S, M = x.shape
    H, Hd = cfg.num_heads, cfg.head_size
    if x_last is None:
        x_last = jnp.zeros((B, M), x.dtype)
    x_prev = jnp.concatenate([x_last[:, None, :], x[:, :-1, :]], axis=1)
    mixed = _rwkv6_mix(params, x, x_prev)  # (B,S,5,M)
    xr, xk, xv, xg, xw = [mixed[:, :, i] for i in range(5)]
    r = (xr @ params["wr"].astype(x.dtype)).reshape(B, S, H, Hd)
    k = (xk @ params["wk"].astype(x.dtype)).reshape(B, S, H, Hd)
    v = (xv @ params["wv"].astype(x.dtype)).reshape(B, S, H, Hd)
    g = jax.nn.silu(xg @ params["wg"].astype(x.dtype))
    # data-dependent decay w_t = exp(-exp(base + lora(xw)))
    dl = jnp.tanh(xw @ params["decay_lora_a"].astype(x.dtype))
    dl = dl @ params["decay_lora_b"].astype(x.dtype)
    w = jnp.exp(-jnp.exp((params["decay_base"].astype(x.dtype) + dl).astype(jnp.float32)))
    w = w.reshape(B, S, H, Hd).astype(jnp.float32)
    u = params["bonus"].astype(jnp.float32)  # (H, Hd)
    if state is None:
        state = jnp.zeros((B, H, Hd, Hd), jnp.float32)

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    rf = r.astype(jnp.float32)

    def step(s, inp):
        rt, kt, vt, wt = inp  # (B,H,Hd) each
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        out_t = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        s = wt[..., None] * s + kv
        return s, out_t

    state, out = chunked_scan(
        step,
        state,
        (
            jnp.moveaxis(rf, 1, 0),
            jnp.moveaxis(kf, 1, 0),
            jnp.moveaxis(vf, 1, 0),
            jnp.moveaxis(w, 1, 0),
        ),
        chunk=chunk,
    )
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, M).astype(x.dtype)
    out = layernorm(params["ln_x"], out) * g
    out = out @ params["wo"].astype(x.dtype)
    return out, state, x[:, -1, :]


def rwkv6_channel_mix(
    params: PyTree, x: jnp.ndarray, x_last: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, M = x.shape
    if x_last is None:
        x_last = jnp.zeros((B, M), x.dtype)
    x_prev = jnp.concatenate([x_last[:, None, :], x[:, :-1, :]], axis=1)
    mix = params["cm_mix"].astype(x.dtype)
    xk = x + (x_prev - x) * mix
    k = jnp.square(jax.nn.relu(xk @ params["cm_k"].astype(x.dtype)))
    rgate = jax.nn.sigmoid(xk @ params["cm_r"].astype(x.dtype))
    return rgate * (k @ params["cm_v"].astype(x.dtype)), x[:, -1, :]


# ---------------------------------------------------------------------------
# Mamba2 (SSD) block
# ---------------------------------------------------------------------------

class Mamba2Config(NamedTuple):
    d_model: int
    d_state: int = 64
    expand: int = 2
    head_dim: int = 64
    conv_width: int = 4

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def num_heads(self) -> int:
        return self.d_inner // self.head_dim


def mamba2_init(key, cfg: Mamba2Config, dtype=jnp.float32) -> PyTree:
    ks = jax.random.split(key, 5)
    M, Di, N, H = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.num_heads
    # in_proj -> [z (Di), x (Di), B (N), C (N), dt (H)]
    return {
        "in_proj": dense_init(ks[0], (M, 2 * Di + 2 * N + H), dtype),
        "conv_w": dense_init(ks[1], (cfg.conv_width, Di + 2 * N), dtype, scale=0.5),
        "conv_b": jnp.zeros((Di + 2 * N,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "D": jnp.ones((H,), dtype),
        "dt_bias": jnp.zeros((H,), dtype),
        "norm": rmsnorm_init(Di, dtype),
        "out_proj": dense_init(ks[2], (Di, M), dtype),
    }


def _causal_conv(x, w, b, conv_state=None):
    """Depthwise causal conv over time. x: (B,S,C), w: (W,C).
    conv_state: (B, W-1, C) trailing context (decode)."""
    W = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state
    xp = jnp.concatenate([pad, x], axis=1)  # (B, S+W-1, C)
    out = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(W))
    new_state = xp[:, -(W - 1) :, :]
    return out + b[None, None, :], new_state


def mamba2_apply(
    params: PyTree,
    cfg: Mamba2Config,
    x: jnp.ndarray,
    ssm_state: Optional[jnp.ndarray] = None,  # (B, H, head_dim, N)
    conv_state: Optional[jnp.ndarray] = None,  # (B, W-1, Di+2N)
    chunk: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (out, new_ssm_state, new_conv_state)."""
    B, S, M = x.shape
    Di, N, H, P = cfg.d_inner, cfg.d_state, cfg.num_heads, cfg.head_dim
    zxbcdt = x @ params["in_proj"].astype(x.dtype)
    z = zxbcdt[..., :Di]
    xbc = zxbcdt[..., Di : Di + Di + 2 * N]
    dt = zxbcdt[..., -H:]
    xbc, new_conv = _causal_conv(
        xbc, params["conv_w"].astype(x.dtype), params["conv_b"].astype(x.dtype),
        conv_state,
    )
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :Di].reshape(B, S, H, P)
    Bmat = xbc[..., Di : Di + N]  # (B,S,N)
    Cmat = xbc[..., Di + N :]  # (B,S,N)
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )  # (B,S,H)
    A = -jnp.exp(params["A_log"])  # (H,)
    decay = jnp.exp(dt * A[None, None, :])  # (B,S,H)
    if ssm_state is None:
        ssm_state = jnp.zeros((B, H, P, N), jnp.float32)

    xf = xs.astype(jnp.float32)
    Bf = Bmat.astype(jnp.float32)
    Cf = Cmat.astype(jnp.float32)

    def step(s, inp):
        xt, bt, ct, dct, dtt = inp  # (B,H,P), (B,N), (B,N), (B,H), (B,H)
        dbx = jnp.einsum("bhp,bn,bh->bhpn", xt, bt, dtt)
        s = dct[..., None, None] * s + dbx
        yt = jnp.einsum("bhpn,bn->bhp", s, ct)
        return s, yt

    ssm_state, y = chunked_scan(
        step,
        ssm_state,
        (
            jnp.moveaxis(xf, 1, 0),
            jnp.moveaxis(Bf, 1, 0),
            jnp.moveaxis(Cf, 1, 0),
            jnp.moveaxis(decay, 1, 0),
            jnp.moveaxis(dt, 1, 0),
        ),
        chunk=chunk,
    )
    y = jnp.moveaxis(y, 0, 1)  # (B,S,H,P)
    y = y + params["D"].astype(jnp.float32)[None, None, :, None] * xf
    y = y.reshape(B, S, Di).astype(x.dtype)
    y = rmsnorm(params["norm"], y) * jax.nn.silu(z)
    out = y @ params["out_proj"].astype(x.dtype)
    return out, ssm_state, new_conv
