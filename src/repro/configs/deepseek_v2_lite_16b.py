"""DeepSeek-V2-Lite (16B) [arXiv:2405.04434] — MLA + fine-grained MoE.

27L d_model=2048 16H vocab=102400 (untied).  MLA: no q_lora,
kv_lora_rank=512, qk_nope=128, qk_rope=64, v_head_dim=128 (decode caches
only the 512-d latent + 64-d rope key — the paper-headline KV saving,
implemented in the absorbed form); RoPE with YaRN (factor 40 over 4096
original positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim =
0.707, theta 1e4), which also scales the softmax by mscale(40, 0.707)^2.
MoE: 64 routed experts top-6 (softmax gate, greedy, gates NOT
renormalised, routed_scaling_factor 1) + 2 shared, expert d_ff=1408, first
layer dense (d_ff=10944).  The routed experts drop no token, as the
published model at inference: the sorted ragged dispatch.

``PUBLISHED`` holds the model's ``config.json`` keys this repo reads;
``from_hf`` maps such a dict (the published one or a cut of it) onto an
``LMConfig`` and refuses what the program does not implement.
"""
from repro.models.lm import LMConfig

SOURCE = "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json"

PUBLISHED = {
    "num_hidden_layers": 27,
    "hidden_size": 2048,
    "intermediate_size": 10944,
    "num_attention_heads": 16,
    "num_key_value_heads": 16,
    "q_lora_rank": None,
    "kv_lora_rank": 512,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "v_head_dim": 128,
    "n_routed_experts": 64,
    "num_experts_per_tok": 6,
    "n_shared_experts": 2,
    "moe_intermediate_size": 1408,
    "first_k_dense_replace": 1,
    "moe_layer_freq": 1,
    "n_group": 1,
    "topk_group": 1,
    "topk_method": "greedy",
    "scoring_func": "softmax",
    "norm_topk_prob": False,
    "routed_scaling_factor": 1,
    "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {
        "type": "yarn",
        "factor": 40,
        "original_max_position_embeddings": 4096,
        "beta_fast": 32,
        "beta_slow": 1,
        "mscale": 0.707,
        "mscale_all_dim": 0.707,
    },
    "vocab_size": 102400,
    "tie_word_embeddings": False,
}

#: what the program implements of DeepSeek-V2's options
_FIXED = {
    "q_lora_rank": None, "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
    "topk_method": "greedy", "scoring_func": "softmax", "routed_scaling_factor": 1,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
}


def from_hf(hf: dict, **overrides) -> LMConfig:
    """The ``LMConfig`` of a DeepSeek-V2 ``config.json`` dict; ``overrides``
    set ``LMConfig`` fields (dtype, chunking)."""
    for k, v in _FIXED.items():
        if hf.get(k, v) != v:
            raise ValueError(f"DeepSeek-V2 {k}={hf[k]!r} is not implemented (only {v!r})")
    rs = hf.get("rope_scaling")
    if rs and rs.get("type", "yarn") != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r} is not implemented")
    kw = dict(
        name="deepseek-v2",
        arch_type="moe",
        num_layers=int(hf["num_hidden_layers"]),
        d_model=int(hf["hidden_size"]),
        num_heads=int(hf["num_attention_heads"]),
        num_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["v_head_dim"]),
        d_ff=int(hf["intermediate_size"]),  # the leading dense layers
        d_ff_expert=int(hf["moe_intermediate_size"]),
        num_experts=int(hf["n_routed_experts"]),
        top_k=int(hf["num_experts_per_tok"]),
        num_shared_experts=int(hf["n_shared_experts"]),
        first_k_dense=int(hf["first_k_dense_replace"]),
        moe_dropless=True,
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        use_mla=True,
        kv_lora_rank=int(hf["kv_lora_rank"]),
        qk_nope_dim=int(hf["qk_nope_head_dim"]),
        qk_rope_dim=int(hf["qk_rope_head_dim"]),
        rope_scaling=None if not rs else (
            float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs["beta_fast"]), float(rs["beta_slow"]),
            float(rs["mscale"]), float(rs["mscale_all_dim"]),
        ),
        vocab_size=int(hf["vocab_size"]),
        rope_theta=float(hf["rope_theta"]),
    )
    kw.update(overrides)
    return LMConfig(**kw)


CONFIG = from_hf(PUBLISHED, name="deepseek-v2-lite-16b")
