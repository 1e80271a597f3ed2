"""Unified LM assembly covering all assigned architecture families.

One ``LMConfig`` drives six block patterns: dense decoder (GQA), MoE decoder
(shared+routed experts, optional MLA), RWKV6 stack, Mamba2/attention hybrid
(Zamba2), encoder-decoder (Whisper), and VLM decoder (M-RoPE, vision-prefix
splice).  Layer stacks are ``jax.lax.scan`` over stacked params so HLO size
is depth-independent; per-layer bodies are ``jax.checkpoint``-ed for
training when ``cfg.remat``.

API (all functional):
  init_params(cfg, key)       real params (smoke scale)
  abstract_params(cfg)        ShapeDtypeStruct pytree (dry-run, no alloc)
  forward(params, cfg, batch) logits (B, S, V) — train/prefill path
  forward_hidden(params, cfg, batch) final hidden state (B, S, M) before the head
  loss_fn(params, cfg, batch) scalar CE (+ MoE aux)
  init_cache(cfg, B, capacity[, abstract]) decode cache pytree
  prefill(params, cfg, batch, capacity) -> (last_logits, cache)
  latent_prefill(params, cfg, batch, capacity[, start]) -> (hidden, cache, loads)  (MLA)
  decode_step(params, cfg, cache, tokens, pos) -> (logits, cache)

Ragged rows (MLA stacks): ``batch["lengths"]`` (B,) marks each row's
prompt as its first ``lengths[b]`` tokens, right-padded.  Attention is
causal, so the pads never reach a real position; they are left out of the
routed experts, ``prefill`` reads each row's logits at its own last token,
and ``decode_step`` takes a per-row ``pos`` (B,) for the next slot.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.meshctx import constrain
from repro.models.layers import (
    AttnConfig,
    Mamba2Config,
    MLAConfig,
    MoEConfig,
    RWKV6Config,
    attention_apply,
    attention_decode,
    attention_init,
    dense_init,
    gelu_mlp,
    gelu_mlp_init,
    layernorm,
    layernorm_init,
    mamba2_apply,
    mamba2_init,
    mla_apply,
    mla_decode,
    mla_init,
    moe_apply,
    moe_init,
    moe_load,
    rmsnorm,
    rmsnorm_init,
    rwkv6_channel_mix,
    rwkv6_init,
    rwkv6_time_mix,
    swiglu,
    swiglu_init,
)

PyTree = Any


def _scan(cfg: "LMConfig"):
    """Layer-stack scan with a config-controlled unroll factor.

    ``layer_unroll = 1``: normal scan (depth-independent HLO).
    ``layer_unroll = -1``: fully unrolled — used ONLY by the dry-run's
    cost probes, because XLA's cost_analysis counts a while-loop body
    once regardless of trip count (see EXPERIMENTS.md §Roofline).
    """
    unroll = True if cfg.layer_unroll == -1 else max(cfg.layer_unroll, 1)
    return functools.partial(jax.lax.scan, unroll=unroll)



@dataclass(frozen=True)
class LMConfig:
    name: str
    arch_type: str  # dense | moe | rwkv | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e6
    window: int = 0  # >0: sliding-window attention (long-context variant)
    tie_embeddings: bool = False
    # MoE
    num_experts: int = 0
    top_k: int = 0
    num_shared_experts: int = 0
    first_k_dense: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    moe_groups: int = 0  # >0: group-local dispatch (§Perf hillclimb #1)
    moe_dropless: bool = False  # sorted ragged dispatch, no capacity
    norm_topk_prob: bool = True  # renormalise the top-k gates
    # MLA (deepseek-v2)
    use_mla: bool = False
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    # YaRN rope_scaling of MLA: (factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale, mscale_all_dim); None = plain RoPE
    rope_scaling: Optional[Tuple[float, int, float, float, float, float]] = None
    # RWKV6
    rwkv_head_size: int = 64
    # hybrid (zamba2)
    ssm_state: int = 64
    mamba_head_dim: int = 64
    shared_attn_period: int = 6  # every Nth layer = shared attention block
    # encdec (whisper)
    encoder_layers: int = 0
    encoder_frames: int = 1500
    # vlm (qwen2-vl)
    mrope_sections: Optional[Tuple[int, int, int]] = None
    vision_tokens: int = 0  # vision-prefix length in the token stream
    use_rope: bool = True  # False: absolute positions only (whisper)
    # numerics / execution
    dtype: str = "bfloat16"
    remat: bool = True
    scan_chunk: int = 128  # recurrent-scan remat chunk
    attn_chunk: int = 1024  # query-chunked SDPA block (0 = unchunked)
    attn_seq_shard: bool = False  # context-parallel attention (§Perf #2)
    kv_quant: bool = False  # int8 KV cache with per-slot scales (§Perf #3)
    layer_unroll: int = 1  # -1 = full unroll (dry-run cost probes only)

    # ---- derived sub-configs -------------------------------------------
    @property
    def act_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def attn(self, window: Optional[int] = None) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model,
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            qkv_bias=self.qkv_bias,
            qk_norm=self.qk_norm,
            window=self.window if window is None else window,
            rope_theta=self.rope_theta,
            use_rope=self.use_rope,
            mrope_sections=self.mrope_sections,
            attn_chunk=self.attn_chunk,
            seq_shard=self.attn_seq_shard,
        )

    def moe(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.d_model,
            d_ff_expert=self.d_ff_expert,
            num_experts=self.num_experts,
            top_k=self.top_k,
            num_shared=self.num_shared_experts,
            capacity_factor=self.capacity_factor,
            groups=self.moe_groups,
            norm_topk=self.norm_topk_prob,
            dropless=self.moe_dropless,
        )

    def mla(self) -> MLAConfig:
        return MLAConfig(
            d_model=self.d_model,
            num_heads=self.num_heads,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim,
            v_dim=self.head_dim,
            rope_theta=self.rope_theta,
            attn_chunk=self.attn_chunk,
            rope_scaling=self.rope_scaling,
        )

    def rwkv(self) -> RWKV6Config:
        return RWKV6Config(
            d_model=self.d_model,
            head_size=self.rwkv_head_size,
            ffn_mult=self.d_ff / self.d_model,
        )

    def mamba(self) -> Mamba2Config:
        return Mamba2Config(
            d_model=self.d_model,
            d_state=self.ssm_state,
            head_dim=self.mamba_head_dim,
        )

    @property
    def num_shared_attn(self) -> int:
        """#shared-attention applications in a hybrid stack."""
        if self.arch_type != "hybrid":
            return 0
        return self.num_layers // self.shared_attn_period

    @property
    def num_mamba_layers(self) -> int:
        return self.num_layers - self.num_shared_attn


def reduced(cfg: LMConfig, **overrides) -> LMConfig:
    """Smoke-test variant: 2 layers, d_model<=256, <=4 experts."""
    small: Dict[str, Any] = dict(
        num_layers=2,
        d_model=128,
        num_heads=4,
        num_kv_heads=max(1, min(cfg.num_kv_heads, 2)),
        head_dim=32,
        d_ff=256,
        vocab_size=512,
        dtype="float32",
        scan_chunk=16,
        encoder_frames=32 if cfg.arch_type == "encdec" else cfg.encoder_frames,
        vision_tokens=8 if cfg.arch_type == "vlm" else 0,
    )
    if cfg.arch_type == "encdec":
        small["encoder_layers"] = 2
    if cfg.num_experts:
        small.update(num_experts=4, top_k=2, d_ff_expert=64,
                     num_shared_experts=min(cfg.num_shared_experts, 1),
                     first_k_dense=min(cfg.first_k_dense, 1),
                     capacity_factor=8.0)  # no token drops at smoke scale
    if cfg.use_mla:
        small.update(kv_lora_rank=32, qk_nope_dim=32, qk_rope_dim=16, head_dim=32)
    if cfg.arch_type == "rwkv":
        small.update(rwkv_head_size=32, num_heads=4)
    if cfg.arch_type == "hybrid":
        small.update(num_layers=4, shared_attn_period=2, mamba_head_dim=32,
                     ssm_state=16, head_dim=32)
    if cfg.mrope_sections is not None:
        small["mrope_sections"] = (4, 6, 6)  # sums to head_dim/2 = 16
    small.update(overrides)
    return replace(cfg, name=cfg.name + "-smoke", **small)


# ===========================================================================
# init
# ===========================================================================

def _block_init(key, cfg: LMConfig, kind: str) -> PyTree:
    """One layer's params.  kind: dense|moe|moe_dense|rwkv|mamba|shared_attn|enc|dec."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    if kind in ("dense", "moe_dense"):
        d_ff = cfg.d_ff
        return {
            "norm1": rmsnorm_init(cfg.d_model),
            "attn": mla_init(k1, cfg.mla()) if cfg.use_mla else attention_init(k1, cfg.attn()),
            "norm2": rmsnorm_init(cfg.d_model),
            "mlp": swiglu_init(k2, cfg.d_model, d_ff),
        }
    if kind == "moe":
        return {
            "norm1": rmsnorm_init(cfg.d_model),
            "attn": mla_init(k1, cfg.mla()) if cfg.use_mla else attention_init(k1, cfg.attn()),
            "norm2": rmsnorm_init(cfg.d_model),
            "moe": moe_init(k2, cfg.moe()),
        }
    if kind == "rwkv":
        return {
            "ln1": layernorm_init(cfg.d_model),
            "tm": rwkv6_init(k1, cfg.rwkv()),
            "ln2": layernorm_init(cfg.d_model),
        }
    if kind == "mamba":
        return {
            "norm": rmsnorm_init(cfg.d_model),
            "mamba": mamba2_init(k1, cfg.mamba()),
        }
    if kind == "shared_attn":
        return {
            "norm1": rmsnorm_init(cfg.d_model),
            "attn": attention_init(k1, cfg.attn()),
            "norm2": rmsnorm_init(cfg.d_model),
            "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff),
        }
    if kind == "enc":
        return {
            "norm1": layernorm_init(cfg.d_model),
            "attn": attention_init(k1, cfg.attn()._replace(mrope_sections=None)),
            "norm2": layernorm_init(cfg.d_model),
            "mlp": gelu_mlp_init(k2, cfg.d_model, cfg.d_ff),
        }
    if kind == "dec":
        return {
            "norm1": layernorm_init(cfg.d_model),
            "self_attn": attention_init(k1, cfg.attn()),
            "norm_x": layernorm_init(cfg.d_model),
            "cross_attn": attention_init(k2, cfg.attn()),
            "norm2": layernorm_init(cfg.d_model),
            "mlp": gelu_mlp_init(k3, cfg.d_model, cfg.d_ff),
        }
    raise ValueError(kind)


def _stack_init(key, cfg: LMConfig, kind: str, n: int) -> PyTree:
    keys = jax.random.split(key, max(n, 1))
    layers = [_block_init(keys[i], cfg, kind) for i in range(n)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers) if n else None


def init_params(cfg: LMConfig, key) -> PyTree:
    ks = jax.random.split(key, 8)
    p: PyTree = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), scale=0.02),
        "final_norm": (
            layernorm_init(cfg.d_model)
            if cfg.arch_type in ("rwkv", "encdec")
            else rmsnorm_init(cfg.d_model)
        ),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), scale=0.02)
    at = cfg.arch_type
    if at in ("dense", "vlm"):
        p["layers"] = _stack_init(ks[2], cfg, "dense", cfg.num_layers)
    elif at == "moe":
        if cfg.first_k_dense:
            p["dense_layers"] = _stack_init(ks[2], cfg, "moe_dense", cfg.first_k_dense)
        p["moe_layers"] = _stack_init(
            ks[3], cfg, "moe", cfg.num_layers - cfg.first_k_dense
        )
    elif at == "rwkv":
        p["layers"] = _stack_init(ks[2], cfg, "rwkv", cfg.num_layers)
    elif at == "hybrid":
        G = cfg.num_shared_attn
        per = cfg.shared_attn_period - 1  # mamba layers per group
        p["mamba_groups"] = jax.tree.map(
            lambda a: a.reshape(G, per, *a.shape[1:]),
            _stack_init(ks[2], cfg, "mamba", G * per),
        )
        p["shared_block"] = _block_init(ks[3], cfg, "shared_attn")
    elif at == "encdec":
        p["enc_layers"] = _stack_init(ks[2], cfg, "enc", cfg.encoder_layers)
        p["dec_layers"] = _stack_init(ks[3], cfg, "dec", cfg.num_layers)
        p["enc_norm"] = layernorm_init(cfg.d_model)
    else:
        raise ValueError(at)
    return p


def abstract_params(cfg: LMConfig) -> PyTree:
    """ShapeDtypeStruct pytree — no allocation (dry-run)."""
    out = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    # dry-run params are bf16-weight: re-type leaves to act dtype except norms
    def retype(x):
        return jax.ShapeDtypeStruct(x.shape, cfg.act_dtype if x.dtype == jnp.float32 else x.dtype)
    return jax.tree.map(retype, out)


# ===========================================================================
# forward (train / prefill)
# ===========================================================================

def _positions(batch: Dict, B: int, S: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.arange(S)[None], (B, S))


def takes_ragged_rows(cfg: LMConfig) -> bool:
    """Whether the stack takes ragged rows (``lengths``, per-row ``pos``)."""
    return cfg.arch_type == "moe" and cfg.use_mla


def _valid(cfg: LMConfig, batch: Dict, S: int) -> Optional[jnp.ndarray]:
    """(B, S) real-token mask of a ragged batch (``lengths``), else None."""
    if "lengths" not in batch:
        return None
    if not takes_ragged_rows(cfg):
        raise ValueError(f"ragged rows (lengths) need an MLA stack, not {cfg.arch_type!r}")
    return jnp.arange(S)[None, :] < jnp.asarray(batch["lengths"])[:, None]


def _over_layers(cfg: LMConfig, body, carry, layers, *per_layer):
    """``body(carry, (layer, *per_layer[i]))`` over a layer stack, with each
    of ``per_layer`` (stacked on axis 0) sliced alongside.  A stacked pytree
    is scanned; a list of per-layer pytrees (``unstack_layers``: each layer
    its own buffers, as serving keeps them) is looped, so a kernel's operands
    are whole buffers and not slices of a stack copied on every call.
    Returns ``(carry, ys)`` with ``ys`` stacked on axis 0 as a scan gives."""
    if not isinstance(layers, (list, tuple)):
        xs = (layers,) + per_layer if per_layer else layers
        return _scan(cfg)(body, carry, xs)
    if not layers:
        raise ValueError("an empty list of layers")
    ys = []
    for i, lp in enumerate(layers):
        carry, y = body(carry, (lp,) + tuple(a[i] for a in per_layer) if per_layer else lp)
        ys.append(y)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


def unstack_layers(params: PyTree) -> PyTree:
    """``params`` with each stacked layer group (``layers``, ``dense_layers``,
    ``moe_layers``) as a list of per-layer pytrees, each its own buffers."""
    out = dict(params)
    for k in ("layers", "dense_layers", "moe_layers"):
        if k in params and not isinstance(params[k], (list, tuple)):
            n = jax.tree.leaves(params[k])[0].shape[0]
            out[k] = [jax.tree.map(lambda a: a[i], params[k]) for i in range(n)]
    return out


def _maybe_remat(fn, cfg: LMConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _embed(params, cfg: LMConfig, batch) -> jnp.ndarray:
    tok = batch["tokens"]
    emb = params["embed"].astype(cfg.act_dtype)[tok]
    if cfg.arch_type == "vlm" and cfg.vision_tokens:
        ve = batch["vision_embeds"].astype(cfg.act_dtype)
        emb = jnp.concatenate([ve, emb[:, cfg.vision_tokens :]], axis=1)
    return constrain(emb, "batch", None, None)


def _logits(params, cfg: LMConfig, h, f32: bool = False) -> jnp.ndarray:
    """The head over (B, S, M) hidden states; ``f32`` accumulates the
    logits into float32 (serving's few positions) instead of the
    activation dtype (training's every position)."""
    h = (
        layernorm(params["final_norm"], h)
        if cfg.arch_type in ("rwkv", "encdec")
        else rmsnorm(params["final_norm"], h)
    )
    if cfg.tie_embeddings:
        # the tied table is d_model-sharded for the lookup; reshard it
        # vocab-sharded here (one table-sized collective) so the logits
        # matmul partitions on vocab instead of all-reducing (B,S,V)
        w = constrain(params["embed"].astype(h.dtype).T, None, "model")
    else:
        w = params["unembed"].astype(h.dtype)
    logits = jnp.matmul(h, w, preferred_element_type=jnp.float32 if f32 else None)
    return constrain(logits, "batch", None, "model")


def lm_head(params: PyTree, cfg: LMConfig, h: jnp.ndarray) -> jnp.ndarray:
    """float32 logits of (B, S, M) final hidden states (``forward_hidden``)."""
    return _logits(params, cfg, h, f32=True)


def _dense_block(lp, cfg: LMConfig, h, positions, positions_3d):
    a = attention_apply(
        lp["attn"], cfg.attn(), rmsnorm(lp["norm1"], h), positions, positions_3d
    )
    h = h + constrain(a, "batch", None, None)
    h = h + swiglu(lp["mlp"], rmsnorm(lp["norm2"], h))
    return h


def _moe_block(lp, cfg: LMConfig, h, positions, aux, valid=None):
    """One MoE layer: (h, aux + its aux loss, its (E,) expert load)."""
    a = attention_apply(lp["attn"], cfg.attn(), rmsnorm(lp["norm1"], h), positions)
    h = h + constrain(a, "batch", None, None)
    hn = rmsnorm(lp["norm2"], h)
    out, aux_l = moe_apply(lp["moe"], cfg.moe(), hn, valid)
    return h + out, aux + aux_l, moe_load(lp["moe"], cfg.moe(), hn, valid)


def _mla_layer(lp, cfg: LMConfig, h, positions, valid, capacity: Optional[int]):
    """One layer of an MLA stack: latent attention, then the dense SwiGLU
    MLP or the MoE layer (whose dropless experts route the ``valid``
    tokens).  Returns (h, its aux loss, its latent cache (ckv, kr) filled
    into ``capacity`` slots or None without one, its (E,) expert load or
    None on a dense layer)."""
    a, kv = mla_apply(lp["attn"], cfg.mla(), rmsnorm(lp["norm1"], h), positions,
                      return_kv=True)
    h = h + constrain(a, "batch", None, None)
    hn = rmsnorm(lp["norm2"], h)
    kv = None if capacity is None else tuple(_fill_slots(x, capacity) for x in kv)
    if "mlp" in lp:
        return h + swiglu(lp["mlp"], hn), jnp.zeros((), jnp.float32), kv, None
    out, aux = moe_apply(lp["moe"], cfg.moe(), hn, valid)
    return h + out, aux, kv, moe_load(lp["moe"], cfg.moe(), hn, valid)


def _mla_stack(params, cfg: LMConfig, h, positions, valid, *, start: int = 0,
               capacity: Optional[int] = None):
    """Layers ``start`` … L−1 of an MLA stack (``dense_layers`` then
    ``moe_layers``, stacked or per-layer lists) over hidden states ``h``,
    the one layer body of ``forward_hidden`` and ``prefill``.  Returns (h,
    aux loss, the latent cache {"c", "kr"} of those layers at ``capacity``
    slots or None without one, their (MoE layers, E) expert loads or
    None)."""
    def body(carry, lp):
        hh, ax = carry
        hh, a, kv, load = _mla_layer(lp, cfg, hh, positions, valid, capacity)
        return (hh, ax + a), (kv, load)

    body = _maybe_remat(body, cfg)
    carry = (h, jnp.zeros((), jnp.float32))
    kvs, loads, first = [], [], 0
    for group in ("dense_layers", "moe_layers"):
        layers = params.get(group)
        n = 0 if layers is None else (
            len(layers) if isinstance(layers, (list, tuple))
            else jax.tree.leaves(layers)[0].shape[0])
        skip, first = min(max(start - first, 0), n), first + n
        if skip == n:
            continue
        if skip:
            layers = (layers[skip:] if isinstance(layers, (list, tuple))
                      else jax.tree.map(lambda a: a[skip:], layers))
        carry, (kv, load) = _over_layers(cfg, body, carry, layers)
        kvs.append(kv)
        if load is not None:
            loads.append(load)
    cache = None if capacity is None else {
        "c": jnp.concatenate([c for c, _ in kvs]), "kr": jnp.concatenate([r for _, r in kvs])}
    return carry + (cache, jnp.concatenate(loads) if loads else None)


def _rwkv_block(lp, cfg: LMConfig, h, state, x_tm, x_cm):
    a, state, x_tm = rwkv6_time_mix(
        lp["tm"], cfg.rwkv(), layernorm(lp["ln1"], h), state, x_tm,
        chunk=cfg.scan_chunk,
    )
    h = h + a
    c, x_cm = rwkv6_channel_mix(lp["tm"], layernorm(lp["ln2"], h), x_cm)
    return h + c, state, x_tm, x_cm


def _mamba_block(lp, cfg: LMConfig, h, ssm, conv):
    out, ssm, conv = mamba2_apply(
        lp["mamba"], cfg.mamba(), rmsnorm(lp["norm"], h), ssm, conv,
        chunk=cfg.scan_chunk,
    )
    return h + out, ssm, conv


def _shared_attn_block(sp, cfg: LMConfig, h, positions):
    a = attention_apply(sp["attn"], cfg.attn(), rmsnorm(sp["norm1"], h), positions)
    h = h + constrain(a, "batch", None, None)
    return h + swiglu(sp["mlp"], rmsnorm(sp["norm2"], h))


def forward(params: PyTree, cfg: LMConfig, batch: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.  Returns (logits, moe_aux)."""
    h, aux, _ = forward_hidden(params, cfg, batch)
    return _logits(params, cfg, h), aux


def forward_hidden(params: PyTree, cfg: LMConfig, batch: Dict):
    """Full-sequence forward up to the head.  Returns (final hidden state
    (B, S, M) before the final norm, moe_aux, loads): ``loads`` is the
    (num MoE layers, E) routed pairs per expert over the real tokens, or
    None for a stack without experts."""
    h = _embed(params, cfg, batch)
    B, S, _ = h.shape
    positions = _positions(batch, B, S)
    positions_3d = batch.get("positions_3d")
    valid = _valid(cfg, batch, S)
    aux = jnp.zeros((), jnp.float32)
    loads = None
    at = cfg.arch_type

    if at in ("dense", "vlm"):
        body = _maybe_remat(
            lambda hh, lp: (_dense_block(lp, cfg, hh, positions, positions_3d), None),
            cfg,
        )
        h, _ = _scan(cfg)(body, h, params["layers"])
    elif at == "moe" and cfg.use_mla:
        h, aux, _, loads = _mla_stack(params, cfg, h, positions, valid)
    elif at == "moe":
        if cfg.first_k_dense:
            body_d = _maybe_remat(
                lambda hh, lp: (_dense_block(lp, cfg, hh, positions, None), None), cfg
            )
            h, _ = _over_layers(cfg, body_d, h, params["dense_layers"])

        def moe_body(carry, lp):
            hh, ax = carry
            hh, ax, load = _moe_block(lp, cfg, hh, positions, ax, valid)
            return (hh, ax), load

        (h, aux), loads = _over_layers(
            cfg, _maybe_remat(moe_body, cfg), (h, aux), params["moe_layers"]
        )
    elif at == "rwkv":
        def rwkv_body(hh, lp):
            hh, _, _, _ = _rwkv_block(lp, cfg, hh, None, None, None)
            return hh, None

        h, _ = _scan(cfg)(_maybe_remat(rwkv_body, cfg), h, params["layers"])
    elif at == "hybrid":
        sp = params["shared_block"]

        def group_body(hh, gp):
            def mamba_body(hhh, lp):
                hhh, _, _ = _mamba_block(lp, cfg, hhh, None, None)
                return hhh, None

            hh, _ = _scan(cfg)(mamba_body, hh, gp)
            hh = _shared_attn_block(sp, cfg, hh, positions)
            return hh, None

        h, _ = _scan(cfg)(_maybe_remat(group_body, cfg), h, params["mamba_groups"])
    elif at == "encdec":
        enc = _encode(params, cfg, batch)
        h = _decode_stack(params, cfg, h, enc, positions)
    else:
        raise ValueError(at)
    return h, aux, loads


def _encode(params, cfg: LMConfig, batch) -> jnp.ndarray:
    """Whisper encoder over precomputed frame embeddings (conv stub)."""
    x = batch["audio_frames"].astype(cfg.act_dtype)
    B, F, _ = x.shape
    pos = _sinusoid(F, cfg.d_model, x.dtype)
    h = x + pos[None]
    full = jnp.ones((1, F, F), bool)  # bidirectional
    positions = None  # whisper: no rope; absolute sinusoid added above

    def body(hh, lp):
        a = attention_apply(
            lp["attn"], cfg.attn()._replace(rope_theta=0.0, mrope_sections=None),
            layernorm(lp["norm1"], hh), None, None, mask=full,
        )
        hh = hh + a
        hh = hh + gelu_mlp(lp["mlp"], layernorm(lp["norm2"], hh))
        return hh, None

    h, _ = _scan(cfg)(_maybe_remat(body, cfg), h, params["enc_layers"])
    return layernorm(params["enc_norm"], h)


def _sinusoid(n: int, d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(n)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def _decode_stack(params, cfg: LMConfig, h, enc, positions):
    """Whisper decoder: causal self-attn + cross-attn + GELU MLP.
    Sinusoidal decoder positions (deviation from learned; see DESIGN.md)."""
    B, S, _ = h.shape
    F = enc.shape[1]
    h = h + _sinusoid(S, cfg.d_model, h.dtype)[None]
    cross_mask = jnp.ones((1, S, F), bool)

    def body(hh, lp):
        a = attention_apply(
            lp["self_attn"], cfg.attn()._replace(mrope_sections=None),
            layernorm(lp["norm1"], hh), None, None, mask=None,
        )
        hh = hh + a
        x = _cross_attention(lp["cross_attn"], cfg, layernorm(lp["norm_x"], hh), enc, cross_mask)
        hh = hh + x
        hh = hh + gelu_mlp(lp["mlp"], layernorm(lp["norm2"], hh))
        return hh, None

    h, _ = _scan(cfg)(_maybe_remat(body, cfg), h, params["dec_layers"])
    return h


def _cross_attention(ap, cfg: LMConfig, x, enc, mask):
    """Cross-attention reusing GQA projections (q from x, k/v from enc)."""
    from repro.models.layers import _project_qkv, _sdpa  # internal reuse

    acfg = cfg.attn()._replace(mrope_sections=None)
    B, S, _ = x.shape
    q = (x @ ap["wq"].astype(x.dtype))
    if acfg.qkv_bias:
        q = q + ap["bq"].astype(x.dtype)
    q = q.reshape(B, S, acfg.num_heads, acfg.head_dim)
    k = (enc @ ap["wk"].astype(x.dtype)).reshape(B, -1, acfg.num_kv_heads, acfg.head_dim)
    v = (enc @ ap["wv"].astype(x.dtype)).reshape(B, -1, acfg.num_kv_heads, acfg.head_dim)
    out = _sdpa(q, k, v, mask, acfg.num_kv_heads, acfg.num_heads)
    return out @ ap["wo"].astype(x.dtype)


def loss_fn(params: PyTree, cfg: LMConfig, batch: Dict) -> jnp.ndarray:
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    # vocab-sharded CE: keep the f32 logits sharded on the vocab axis and
    # select the gold logit with an iota mask (a take_along_axis over a
    # sharded vocab dim forces an all-gather of the full-vocab f32 logits
    # in the backward pass — found via the §Perf HLO forensics)
    logits = constrain(logits.astype(jnp.float32), "batch", None, "model")
    lse = jax.nn.logsumexp(logits, axis=-1)
    v_iota = jnp.arange(logits.shape[-1])[None, None, :]
    masked = constrain(
        jnp.where(v_iota == safe[..., None], logits, 0.0),
        "batch", None, "model",
    )
    gold = jnp.sum(masked, axis=-1)
    nll = (lse - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1) + aux


# ===========================================================================
# decode path (serve_step)
# ===========================================================================

def init_cache(
    cfg: LMConfig, batch: int, capacity: int, abstract: bool = False
) -> PyTree:
    """Decode-cache pytree.  ``capacity`` = KV slots (window size when
    cfg.window>0).  Includes a scalar position is NOT stored here — the
    caller passes ``pos`` each step."""
    L, B, C = cfg.num_layers, batch, capacity
    K, D = cfg.num_kv_heads, cfg.head_dim
    dt = cfg.act_dtype

    def z(shape, dtype):
        return (
            jax.ShapeDtypeStruct(shape, dtype)
            if abstract
            else jnp.zeros(shape, dtype)
        )

    at = cfg.arch_type
    if at in ("dense", "vlm") or (at == "moe" and not cfg.use_mla):
        if cfg.kv_quant:
            return {
                "k": z((L, B, C, K, D), jnp.int8),
                "v": z((L, B, C, K, D), jnp.int8),
                "k_s": z((L, B, C, K), jnp.float32),
                "v_s": z((L, B, C, K), jnp.float32),
            }
        cache = {"k": z((L, B, C, K, D), dt), "v": z((L, B, C, K, D), dt)}
        return cache
    if at == "moe" and cfg.use_mla:
        return {
            "c": z((L, B, C, cfg.kv_lora_rank), dt),
            "kr": z((L, B, C, cfg.qk_rope_dim), dt),
        }
    if at == "rwkv":
        H = cfg.d_model // cfg.rwkv_head_size
        hd = cfg.rwkv_head_size
        M = cfg.d_model
        return {
            "state": z((L, B, H, hd, hd), jnp.float32),
            "tm_x": z((L, B, M), dt),
            "cm_x": z((L, B, M), dt),
        }
    if at == "hybrid":
        mc = cfg.mamba()
        G, per = cfg.num_shared_attn, cfg.shared_attn_period - 1
        return {
            "ssm": z((G, per, B, mc.num_heads, mc.head_dim, mc.d_state), jnp.float32),
            "conv": z((G, per, B, mc.conv_width - 1, mc.d_inner + 2 * mc.d_state), dt),
            "shared_k": z((G, B, C, K, D), dt),
            "shared_v": z((G, B, C, K, D), dt),
        }
    if at == "encdec":
        Ld = cfg.num_layers
        F = cfg.encoder_frames
        return {
            "k": z((Ld, B, C, K, D), dt),
            "v": z((Ld, B, C, K, D), dt),
            "xk": z((Ld, B, F, K, D), dt),
            "xv": z((Ld, B, F, K, D), dt),
        }
    raise ValueError(at)


def decode_step(
    params: PyTree,
    cfg: LMConfig,
    cache: PyTree,
    tokens: jnp.ndarray,  # (B,) next input token ids
    pos: jnp.ndarray,  # () int32 current position; (B,) per row on MLA stacks
    positions_3d: Optional[jnp.ndarray] = None,  # (3, B, 1) for vlm
) -> Tuple[jnp.ndarray, PyTree]:
    """One-token decode; returns (logits (B, V), new cache)."""
    h = params["embed"].astype(cfg.act_dtype)[tokens][:, None, :]  # (B,1,M)
    h = constrain(h, "batch", None, None)
    at = cfg.arch_type

    if at in ("dense", "vlm") or (at == "moe" and not cfg.use_mla):
        quant = cfg.kv_quant

        def body(hh, inp):
            if quant:
                lp, ck, cv, ks, vs = inp
            else:
                lp, ck, cv = inp
            hn = rmsnorm(lp["norm1"], hh)
            if quant:
                a, ck, cv, (ks, vs) = attention_decode(
                    lp["attn"], cfg.attn(), hn, ck, cv, pos, positions_3d,
                    cache_scales=(ks, vs),
                )
            else:
                a, ck, cv = attention_decode(
                    lp["attn"], cfg.attn(), hn, ck, cv, pos, positions_3d
                )
            hh = hh + a
            if "mlp" in lp:
                hh = hh + swiglu(lp["mlp"], rmsnorm(lp["norm2"], hh))
            else:
                out, _ = moe_apply(lp["moe"], cfg.moe(), rmsnorm(lp["norm2"], hh))
                hh = hh + out
            return hh, (ck, cv, ks, vs) if quant else (ck, cv)

        def cache_slices(sl):
            fields = ("k", "v", "k_s", "v_s") if quant else ("k", "v")
            return tuple(cache[f][sl] for f in fields)

        def pack(ys):
            fields = ("k", "v", "k_s", "v_s") if quant else ("k", "v")
            return dict(zip(fields, ys))

        if at == "moe" and cfg.first_k_dense:
            nD = cfg.first_k_dense
            h, ys0 = _scan(cfg)(body, h, (params["dense_layers"],) + cache_slices(slice(None, nD)))
            h, ys1 = _scan(cfg)(body, h, (params["moe_layers"],) + cache_slices(slice(nD, None)))
            cache = {
                f: jnp.concatenate([a, b])
                for f, a, b in zip(pack(ys0).keys(), ys0, ys1)
            }
        else:
            h, ys = _scan(cfg)(body, h, (params["layers"],) + cache_slices(slice(None)))
            cache = pack(ys)
    elif at == "moe" and cfg.use_mla:
        def body(hh, inp):
            lp, cc, ckr = inp
            hn = rmsnorm(lp["norm1"], hh)
            a, cc, ckr = mla_decode(lp["attn"], cfg.mla(), hn, cc, ckr, pos)
            hh = hh + a
            if "mlp" in lp:
                hh = hh + swiglu(lp["mlp"], rmsnorm(lp["norm2"], hh))
            else:
                out, _ = moe_apply(lp["moe"], cfg.moe(), rmsnorm(lp["norm2"], hh))
                hh = hh + out
            return hh, (cc, ckr)

        nD = cfg.first_k_dense
        if nD:
            h, (c0, r0) = _over_layers(
                cfg, body, h, params["dense_layers"], cache["c"][:nD], cache["kr"][:nD]
            )
            h, (c1, r1) = _over_layers(
                cfg, body, h, params["moe_layers"], cache["c"][nD:], cache["kr"][nD:]
            )
            cache = {"c": jnp.concatenate([c0, c1]), "kr": jnp.concatenate([r0, r1])}
        else:
            h, (cc, ckr) = _over_layers(cfg, body, h, params["layers"], cache["c"], cache["kr"])
            cache = {"c": cc, "kr": ckr}
    elif at == "rwkv":
        def body(hh, inp):
            lp, st, xt, xc = inp
            hh2, st, xt, xc = _rwkv_block(lp, cfg, hh, st, xt, xc)
            return hh2, (st, xt, xc)

        h, (st, xt, xc) = _scan(cfg)(
            body, h, (params["layers"], cache["state"], cache["tm_x"], cache["cm_x"])
        )
        cache = {"state": st, "tm_x": xt, "cm_x": xc}
    elif at == "hybrid":
        sp = params["shared_block"]

        def group(hh, inp):
            gp, ssm_g, conv_g, sk, sv = inp

            def mb(hhh, minp):
                lp, s1, c1 = minp
                hhh, s1, c1 = _mamba_block(lp, cfg, hhh, s1, c1)
                return hhh, (s1, c1)

            hh, (ssm_g, conv_g) = _scan(cfg)(mb, hh, (gp, ssm_g, conv_g))
            hn = rmsnorm(sp["norm1"], hh)
            a, sk, sv = attention_decode(sp["attn"], cfg.attn(), hn, sk, sv, pos)
            hh = hh + a
            hh = hh + swiglu(sp["mlp"], rmsnorm(sp["norm2"], hh))
            return hh, (ssm_g, conv_g, sk, sv)

        h, (ssm, conv, sk, sv) = _scan(cfg)(
            group,
            h,
            (params["mamba_groups"], cache["ssm"], cache["conv"],
             cache["shared_k"], cache["shared_v"]),
        )
        cache = {"ssm": ssm, "conv": conv, "shared_k": sk, "shared_v": sv}
    elif at == "encdec":
        h = h + _sinusoid_at(pos, cfg.d_model, h.dtype)[None, None]
        F = cfg.encoder_frames
        cross_mask = jnp.ones((1, 1, F), bool)

        def body(hh, inp):
            lp, ck, cv, xk, xv = inp
            hn = layernorm(lp["norm1"], hh)
            a, ck, cv = attention_decode(
                lp["self_attn"], cfg.attn()._replace(mrope_sections=None),
                hn, ck, cv, pos,
            )
            hh = hh + a
            xq = layernorm(lp["norm_x"], hh)
            x = _cross_attention_cached(lp["cross_attn"], cfg, xq, xk, xv, cross_mask)
            hh = hh + x
            hh = hh + gelu_mlp(lp["mlp"], layernorm(lp["norm2"], hh))
            return hh, (ck, cv)

        h, (ck, cv) = _scan(cfg)(
            body, h, (params["dec_layers"], cache["k"], cache["v"], cache["xk"], cache["xv"])
        )
        cache = dict(cache, k=ck, v=cv)
    else:
        raise ValueError(at)

    logits = _logits(params, cfg, h, f32=True)[:, 0, :]
    return logits, cache


def _sinusoid_at(pos, d: int, dtype) -> jnp.ndarray:
    dim = jnp.arange(d // 2).astype(jnp.float32)
    ang = pos.astype(jnp.float32) / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)]).astype(dtype)


def _cross_attention_cached(ap, cfg: LMConfig, x, xk, xv, mask):
    from repro.models.layers import _sdpa

    acfg = cfg.attn()
    B, S, _ = x.shape
    q = x @ ap["wq"].astype(x.dtype)
    if acfg.qkv_bias:
        q = q + ap["bq"].astype(x.dtype)
    q = q.reshape(B, S, acfg.num_heads, acfg.head_dim)
    out = _sdpa(q, xk, xv, mask, acfg.num_kv_heads, acfg.num_heads)
    return out @ ap["wo"].astype(x.dtype)


def _fill_slots(arr: jnp.ndarray, C: int) -> jnp.ndarray:
    """(B, S, ...) sequence -> (B, C, ...) cache slots.  When S > C (sliding
    window prefill) the last C tokens land at their ring slots pos % C."""
    B, S = arr.shape[0], arr.shape[1]
    if S == C:
        return arr  # exact fit: the sequence IS the cache
    out = jnp.zeros((B, C) + arr.shape[2:], arr.dtype)
    if S > C:
        tail = arr[:, S - C :]
        slots = jnp.arange(S - C, S) % C
        return out.at[:, slots].set(tail)
    return out.at[:, :S].set(arr)


def prefill(
    params: PyTree, cfg: LMConfig, batch: Dict, capacity: Optional[int] = None
) -> Tuple[jnp.ndarray, PyTree]:
    """Parallel prefill: full forward, collecting the decode cache
    (rotated K/V, SSM/conv states, or MLA latents) in the same pass.
    Returns (last-token logits (B, V), cache ready for ``decode_step`` at
    position S).  With ``batch["lengths"]`` (MLA stacks) the logits are
    each row's at its last real token, and row b decodes next at position
    ``lengths[b]``; only those rows' logits go through the head."""
    h = _embed(params, cfg, batch)
    B, S, _ = h.shape
    C = capacity or S
    positions = _positions(batch, B, S)
    positions_3d = batch.get("positions_3d")
    valid = _valid(cfg, batch, S)
    at = cfg.arch_type

    if at in ("dense", "vlm", "moe") and not cfg.use_mla:
        from repro.models.layers import kv_quantize

        quant = cfg.kv_quant
        fields = ("k", "v", "k_s", "v_s") if quant else ("k", "v")

        def body(hh, lp):
            hn = rmsnorm(lp["norm1"], hh)
            a, (k, v) = attention_apply(
                lp["attn"], cfg.attn(), hn, positions, positions_3d, return_kv=True
            )
            hh = hh + constrain(a, "batch", None, None)
            if "mlp" in lp:
                hh = hh + swiglu(lp["mlp"], rmsnorm(lp["norm2"], hh))
            else:
                out, _ = moe_apply(lp["moe"], cfg.moe(), rmsnorm(lp["norm2"], hh))
                hh = hh + out
            if quant:
                k_q, k_s = kv_quantize(k)
                v_q, v_s = kv_quantize(v)
                return hh, (_fill_slots(k_q, C), _fill_slots(v_q, C),
                            _fill_slots(k_s, C), _fill_slots(v_s, C))
            return hh, (_fill_slots(k, C), _fill_slots(v, C))

        if at == "moe" and cfg.first_k_dense:
            h, ys0 = _scan(cfg)(body, h, params["dense_layers"])
            h, ys1 = _scan(cfg)(body, h, params["moe_layers"])
            cache = {f: jnp.concatenate([a, b]) for f, a, b in zip(fields, ys0, ys1)}
        else:
            h, ys = _scan(cfg)(body, h, params["layers"])
            cache = dict(zip(fields, ys))
    elif at == "moe" and cfg.use_mla:
        h, _, cache, _ = _mla_stack(params, cfg, h, positions, valid, capacity=C)
    elif at == "rwkv":
        def body(hh, lp):
            hh, st, xt, xc = _rwkv_block(lp, cfg, hh, None, None, None)
            return hh, (st, xt, xc)

        h, (st, xt, xc) = _scan(cfg)(body, h, params["layers"])
        cache = {"state": st, "tm_x": xt, "cm_x": xc}
    elif at == "hybrid":
        sp = params["shared_block"]

        def group(hh, gp):
            def mb(hhh, lp):
                hhh, s1, c1 = _mamba_block(lp, cfg, hhh, None, None)
                return hhh, (s1, c1)

            hh, (ssm_g, conv_g) = _scan(cfg)(mb, hh, gp)
            hn = rmsnorm(sp["norm1"], hh)
            a, (k, v) = attention_apply(
                sp["attn"], cfg.attn(), hn, positions, return_kv=True
            )
            hh = hh + a
            hh = hh + swiglu(sp["mlp"], rmsnorm(sp["norm2"], hh))
            return hh, (ssm_g, conv_g, _fill_slots(k, C), _fill_slots(v, C))

        h, (ssm, conv, sk, sv) = _scan(cfg)(group, h, params["mamba_groups"])
        cache = {"ssm": ssm, "conv": conv, "shared_k": sk, "shared_v": sv}
    elif at == "encdec":
        enc = _encode(params, cfg, batch)
        F = enc.shape[1]
        h = h + _sinusoid(S, cfg.d_model, h.dtype)[None]
        cross_mask = jnp.ones((1, S, F), bool)
        acfg = cfg.attn()._replace(mrope_sections=None)
        K_, D_ = acfg.num_kv_heads, acfg.head_dim

        def body(hh, lp):
            hn = layernorm(lp["norm1"], hh)
            a, (k, v) = attention_apply(lp["self_attn"], acfg, hn, None, None,
                                        mask=None, return_kv=True)
            hh = hh + a
            xq = layernorm(lp["norm_x"], hh)
            x = _cross_attention(lp["cross_attn"], cfg, xq, enc, cross_mask)
            hh = hh + x
            hh = hh + gelu_mlp(lp["mlp"], layernorm(lp["norm2"], hh))
            xk = (enc @ lp["cross_attn"]["wk"].astype(hh.dtype)).reshape(B, F, K_, D_)
            xv = (enc @ lp["cross_attn"]["wv"].astype(hh.dtype)).reshape(B, F, K_, D_)
            return hh, (_fill_slots(k, C), _fill_slots(v, C), xk, xv)

        h, (ck, cv, xk, xv) = _scan(cfg)(body, h, params["dec_layers"])
        cache = {"k": ck, "v": cv, "xk": xk, "xv": xv}
    else:
        raise ValueError(at)

    return last_logits(params, cfg, h, None if valid is None else batch["lengths"]), cache


def latent_prefill(params: PyTree, cfg: LMConfig, batch: Dict, capacity: int, *,
                   start: int = 0):
    """Prefill of an MLA stack that keeps what ``prefill`` drops: layers
    ``start`` … L−1 over ``batch``, from its token ids (``start`` 0) or from
    ``batch["hidden"]`` (B, S, M), the hidden states after layer ``start``
    of an earlier pass over the same prompts.  Returns (the hidden states
    (B, S, M) before the final norm, the latent cache {"c", "kr"} of those
    layers at ``capacity`` slots, their (MoE layers, E) expert loads over
    the real tokens)."""
    h = batch["hidden"] if start else _embed(params, cfg, batch)
    B, S, _ = h.shape
    h, _, cache, loads = _mla_stack(params, cfg, h, _positions(batch, B, S),
                                    _valid(cfg, batch, S), start=start, capacity=capacity)
    return h, cache, loads


def last_logits(params: PyTree, cfg: LMConfig, h: jnp.ndarray,
                lengths: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """float32 logits (B, V) of (B, S, M) hidden states at each row's last
    real token (``lengths``), or at the last position; only those rows'
    positions go through the head."""
    if lengths is None:
        last = h[:, -1:, :]
    else:
        at_last = jnp.maximum(jnp.asarray(lengths) - 1, 0)
        last = jnp.take_along_axis(h, at_last[:, None, None], axis=1)
    return _logits(params, cfg, last, f32=True)[:, 0, :]


__all__ = [
    "LMConfig",
    "reduced",
    "init_params",
    "abstract_params",
    "forward",
    "forward_hidden",
    "lm_head",
    "takes_ragged_rows",
    "unstack_layers",
    "latent_prefill",
    "last_logits",
    "loss_fn",
    "init_cache",
    "decode_step",
    "prefill",
]
