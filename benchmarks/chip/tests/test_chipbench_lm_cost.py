"""The LM cascade's operation and byte counts at DeepSeek-V2-Lite's
published widths, against counts written out by hand."""
import json
import os

import pytest

from roofline import cost, lm_cost

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "configs", "dsv2_lite_cascade.json")) as f:
    CFG = json.load(f)
MIX = {"answer_tokens": 16}

# MLA: Wq 2048·16·192 + W_dkv 2048·512 + W_kr 2048·64 + W_uk 512·16·128
# + W_uv 512·16·128 + Wo 16·128·2048
ATTN = 6_291_456 + 1_048_576 + 131_072 + 1_048_576 + 1_048_576 + 4_194_304
# MoE per token: router 2·2048·64, 2 shared experts 2·(3·2048·2·1408),
# 6 routed experts 6·2·(3·2048·1408)
MOE = 262_144 + 34_603_008 + 103_809_024


def test_attention_weights():
    assert lm_cost.attention_params(CFG) == ATTN == 13_762_560


def test_one_moe_layer_prefill_by_hand():
    L = 2048
    # per token 2·ATTN + MOE; per causal query-key pair 2·16·(128 + 64 + 128)
    want = L * (2 * ATTN + MOE) + L * (L + 1) // 2 * 10_240
    assert want == 361_861_480_448
    assert lm_cost.layer_prefill_flops(CFG, L, moe=True) == want


def test_one_dense_layer_decode_by_hand():
    c = 3000
    # absorbed: Wq, W_dkv, W_kr, q·W_uk (16·128·512), ctx·W_uv (16·512·128), Wo
    proj = 6_291_456 + 1_048_576 + 131_072 + 1_048_576 + 1_048_576 + 4_194_304
    # per slot: scores over the latent and the rope key, then the latent sum
    attn = c * 2 * 16 * (512 + 64 + 512)
    mlp = 2 * 3 * 2048 * 10_944
    assert lm_cost.layer_decode_flops(CFG, c, moe=False) == 2 * proj + attn + mlp


def test_decode_step_bytes_of_the_weak_stack():
    ctx = [2048, 1000]
    E, k = 64, 6
    touched = E * (1 - (1 - k / E) ** 2)
    dense = ATTN + 3 * 2048 * 10_944
    moe = ATTN + 3 * 2048 * 1408 * 2 + touched * 3 * 2048 * 1408
    want = 2 * (dense + 2 * moe) + 2 * 4 * 2048 * 64 + 2 * 2048 * 102_400 \
        + 2 * (512 + 64) * 3 * sum(ctx)
    assert lm_cost.decode_step_bytes(CFG, "weak", ctx) == pytest.approx(want)


def test_weak_pass_counts_the_head_at_every_prompt_position():
    one = lm_cost.call_flops(CFG, MIX, "weak_pass", "weak", 1, 2048, [1000])
    layers = lm_cost.layer_prefill_flops(CFG, 1000, False) \
        + 2 * lm_cost.layer_prefill_flops(CFG, 1000, True)
    assert one == layers + 2 * 2048 * 102_400 * 1000
    pre = lm_cost.call_flops(CFG, MIX, "prefill", "strong", 1, 2048, [1000])
    assert pre == layers + 2 * lm_cost.layer_prefill_flops(CFG, 1000, True) + 2 * 2048 * 102_400


def test_decode_loop_is_bandwidth_bound():
    peak = cost.peaks("TPU v5 lite")
    t = lm_cost.decode_least_seconds(CFG, MIX, "strong", 2, 4096, [3000, 2500], peak)
    steps = [lm_cost.decode_step_bytes(CFG, "strong", [3000 + s, 2500 + s]) for s in range(1, 16)]
    assert t == pytest.approx(sum(steps) / 819e9)
    # two rows touch about 11 of 64 experts per layer: 1.5-2 GB a step, not
    # the 5.7 GB of every weight
    assert all(1.5e9 < b < 2.0e9 for b in steps)
