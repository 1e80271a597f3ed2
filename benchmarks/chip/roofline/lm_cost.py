"""Operations and bytes of a DeepSeek-V2 stack's prefill and decode, from
a configuration's ``config.json`` keys alone, whatever implements them.

A layer is MLA then a SwiGLU MLP (the first ``first_k_dense_replace``
layers) or a MoE layer: router, ``num_experts_per_tok`` routed experts of
``moe_intermediate_size`` and ``n_shared_experts`` shared ones.  A
multiply-add counts 2; norms, rotary, softmax and activations are left
out (under 1 % of a layer's matmuls at these widths).

- prefill over a prompt of ``L`` tokens: the expanded form (keys and
  values decompressed from the latent for every position), causal
  attention over ``L(L+1)/2`` query-key pairs;
- decode of one token at context ``c`` (the tokens before it and itself):
  the absorbed form (``W_uk`` into the query, ``W_uv`` after the
  attention), attention over ``c`` latent slots.

The head costs ``2·hidden·vocab`` per position it is computed at: every
prompt position in the weak pass (its features), the last in a prefill,
one per decode step.  A decode step reads every weight of its stack once
but the routed experts, of which it reads those its rows pick: under
uniform routing ``E·(1 − (1 − k/E)^rows)`` per MoE layer expected; and
the latent cache of every row up to its context.
"""
from __future__ import annotations

from typing import Dict, Sequence

#: the programs' names in a trace's ``XLA Modules`` lines
PROGRAMS = ("jit__weak_pass", "jit__prefill", "jit__decode")


def _d(cfg: Dict):
    return (int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
            int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]))


def attention_params(cfg: Dict) -> int:
    """MLA's weights: Wq, W_dkv, W_kr, W_uk, W_uv, Wo."""
    M, H, N, P, V, R = _d(cfg)
    return M * H * (N + P) + M * R + M * P + R * H * N + R * H * V + H * V * M


def mlp_params(cfg: Dict, moe: bool):
    """(weights every token reads, weights of one routed expert)."""
    M = int(cfg["hidden_size"])
    if not moe:
        return 3 * M * int(cfg["intermediate_size"]), 0
    F = int(cfg["moe_intermediate_size"])
    return 3 * M * F * int(cfg["n_shared_experts"]), 3 * M * F


def _mlp_flops(cfg: Dict, moe: bool) -> float:
    shared, expert = mlp_params(cfg, moe)
    if not moe:
        return 2.0 * shared
    M, E, k = int(cfg["hidden_size"]), int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    return 2.0 * (M * E + shared + k * expert)


def layer_prefill_flops(cfg: Dict, L: int, moe: bool) -> float:
    """One layer over a prompt of ``L`` tokens."""
    M, H, N, P, V, R = _d(cfg)
    pairs = L * (L + 1) / 2
    return L * (2.0 * attention_params(cfg) + _mlp_flops(cfg, moe)) + pairs * 2.0 * H * (N + P + V)


def layer_decode_flops(cfg: Dict, c: int, moe: bool) -> float:
    """One layer, one token at context ``c`` (absorbed attention)."""
    M, H, N, P, V, R = _d(cfg)
    proj = M * H * (N + P) + M * R + M * P + H * N * R + H * R * V + H * V * M
    return 2.0 * proj + c * 2.0 * H * (R + P + R) + _mlp_flops(cfg, moe)


def depth(cfg: Dict, stack: str) -> int:
    return int(cfg["exit_layer"]) if stack == "weak" else int(cfg["num_hidden_layers"])


def _moe_flags(cfg: Dict, stack: str):
    dense = int(cfg["first_k_dense_replace"])
    return [i >= dense for i in range(depth(cfg, stack))]


def head_flops(cfg: Dict, positions: float) -> float:
    return 2.0 * int(cfg["hidden_size"]) * int(cfg["vocab_size"]) * positions


def call_flops(cfg: Dict, mix: Dict, program: str, stack: str, rows: int, length: int,
               lengths: Sequence[int]) -> float:
    """Model FLOPs of one program call on the real prompts ``lengths``
    (pad rows and positions not counted)."""
    flags = _moe_flags(cfg, stack)
    steps = int(mix["answer_tokens"])
    total = 0.0
    for L in (int(x) for x in lengths):
        if program == "weak_pass":
            total += sum(layer_prefill_flops(cfg, L, m) for m in flags) + head_flops(cfg, L)
        elif program == "prefill":
            total += sum(layer_prefill_flops(cfg, L, m) for m in flags) + head_flops(cfg, 1)
        elif program == "decode":
            for t in range(1, steps):
                total += sum(layer_decode_flops(cfg, L + t, m) for m in flags) + head_flops(cfg, 1)
        else:
            raise ValueError(f"unknown program {program!r}")
    return total


def expected_experts(cfg: Dict, rows: int) -> float:
    E, k = int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    return E * (1.0 - (1.0 - k / E) ** rows)


def decode_step_bytes(cfg: Dict, stack: str, contexts: Sequence[int]) -> float:
    """Least bytes of one decode step of rows at ``contexts``: the stack's
    weights (routed experts as expected to be touched), the head, and each
    row's latent cache up to its context."""
    M, H, N, P, V, R = _d(cfg)
    width = 2 if cfg.get("dtype", "bfloat16") == "bfloat16" else 4
    E = int(cfg["n_routed_experts"])
    weights = 0.0
    for moe in _moe_flags(cfg, stack):
        shared, expert = mlp_params(cfg, moe)
        weights += width * (attention_params(cfg) + shared + expected_experts(cfg, len(contexts))
                            * expert) + (4 * M * E if moe else 0)
    head = width * M * int(cfg["vocab_size"])
    cache = width * (R + P) * len(_moe_flags(cfg, stack)) * float(sum(contexts))
    return weights + head + cache


def decode_least_seconds(cfg: Dict, mix: Dict, stack: str, rows: int, length: int,
                         lengths: Sequence[int], peak: Dict[str, float]) -> float:
    """Least seconds of one decode loop: per step, the larger of its FLOPs
    and its bytes at the chip's peaks."""
    flags = _moe_flags(cfg, stack)
    total = 0.0
    for t in range(1, int(mix["answer_tokens"])):
        ctx = [int(L) + t for L in lengths]
        flops = sum(layer_decode_flops(cfg, c, m) for c in ctx for m in flags)
        flops += head_flops(cfg, len(ctx))
        total += max(flops / peak["flops_per_s"],
                     decode_step_bytes(cfg, stack, ctx) / peak["bytes_per_s"])
    return total
