"""DeepSeek-V2 through the LM cascade's serving path against the plain
float32 reference (``benchmarks/chip/reference/deepseek_v2.py``), at a
small size on seeded random weights: widths cut, every mechanism kept
(MLA with YaRN, a leading dense layer, dropless routed experts with
unnormalised gates plus shared experts, an untied head).

Both sides run in float32 here.  The program attends through the
absorbed latent cache and sums in other orders than the reference's
expanded form, so logits agree to float32 rounding of O(1) values
through three layers: 2e-5 absolute, about 100x the observed gap."""
import importlib.util
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import LMLogitsFeatures, MLPRewardModel, OffloadEngine
from repro.api.features import chunked_logits_features, logits_features
from repro.api.policies import make_policy
from repro.configs.deepseek_v2_lite_16b import PUBLISHED, from_hf
from repro.models.layers import (
    MoEConfig,
    mla_rope,
    moe_apply,
    moe_apply_flat,
    yarn_inv_freq,
)
from repro.models.lm import init_params
from repro.obs import Obs, jit_stats
from repro.serving.cascade_serving import truncate_params, truncated_config
from repro.serving.decode_loop import CascadePrograms, cascade_generate, generate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "dsv2_reference", os.path.join(ROOT, "benchmarks", "chip", "reference", "deepseek_v2.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

#: every published key, widths cut to a CPU's size; 3 layers: dense + 2 MoE
TINY = dict(PUBLISHED, num_hidden_layers=3, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
            num_experts_per_tok=3, n_shared_experts=2, moe_intermediate_size=32,
            vocab_size=256)
EXIT = 2
STEPS = 4
ATOL = 2e-5
LENGTHS = np.array([5, 9, 12])


@pytest.fixture(scope="module")
def obs():
    return Obs(metrics=True, tracing=False, profiling=False)


@pytest.fixture(scope="module")
def model(obs):
    cfg = from_hf(TINY, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    progs = CascadePrograms(params, cfg, EXIT, steps=STEPS, row_buckets=[1, 2, 4],
                            length_buckets=[16], max_tokens=64, obs=obs)
    tokens = np.random.default_rng(1).integers(0, TINY["vocab_size"], (3, 12)).astype(np.int32)
    for i, n in enumerate(LENGTHS):
        tokens[i, n:] = 0
    return cfg, params, progs, tokens


def _ref_answer_logits(params, tokens, length, answer, depth):
    """The reference's logits at every answer position: one forward over
    the prompt and the program's own answer tokens."""
    seq = np.concatenate([tokens[:length], answer[: STEPS - 1]])
    h = ref.hidden(params, TINY, seq, range(depth))
    return np.asarray(ref.logits(params, TINY, h, length - 1, STEPS))


@pytest.mark.parametrize("stack,depth", [("weak", EXIT), ("strong", 3)])
def test_ragged_prefill_then_decode_matches_reference(model, stack, depth):
    _, params, progs, tokens = model
    answers, logits = progs.generate(stack, tokens, LENGTHS)
    assert answers.shape == (3, STEPS) and logits.shape[1] == STEPS
    for i, n in enumerate(LENGTHS):
        want = _ref_answer_logits(params, tokens[i], n, answers[i], depth)
        np.testing.assert_allclose(np.asarray(logits[i]), want, atol=ATOL)


def test_each_prompt_alone_decodes_as_in_the_block(model):
    _, _, progs, tokens = model
    answers, logits = progs.generate("strong", tokens, LENGTHS)
    for i, n in enumerate(LENGTHS):
        alone, alone_logits = progs.generate("strong", tokens[i: i + 1, :n], LENGTHS[i: i + 1])
        np.testing.assert_array_equal(alone[0], answers[i])
        # another row count and pad length: only summation order differs
        np.testing.assert_allclose(np.asarray(alone_logits[0]), np.asarray(logits[i]),
                                   atol=1e-5)


def test_generate_runs_the_cascade_programs_on_ragged_rows(model):
    cfg, params, progs, tokens = model
    answers, _ = progs.generate("strong", tokens, LENGTHS)
    got = generate(params, cfg, {"tokens": tokens, "lengths": LENGTHS}, STEPS)
    np.testing.assert_array_equal(np.asarray(got), answers)


def test_ragged_rows_are_refused_on_a_stack_without_latent_attention():
    from repro.configs import get_config
    from repro.models.lm import reduced

    cfg = reduced(get_config("yi_6b"), num_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.ones((2, 8), np.int32)
    with pytest.raises(ValueError, match="ragged rows"):
        generate(params, cfg, {"tokens": tokens, "lengths": np.array([8, 5])}, 2)
    progs = CascadePrograms(params, cfg, 1, steps=2)
    with pytest.raises(ValueError, match="ragged rows"):
        progs.generate("strong", tokens, np.array([8, 5]))
    # full rows decode as before
    assert np.asarray(generate(params, cfg, {"tokens": tokens}, 2)).shape == (2, 2)


def test_weak_pass_features_match_reference(model):
    _, params, progs, tokens = model
    feats, share = progs.weak_pass(tokens, LENGTHS)
    for i, n in enumerate(LENGTHS):
        h = ref.hidden(params, TINY, tokens[i, :n], range(EXIT))
        # the features are means of O(1)-O(1e-2) values over a few positions
        np.testing.assert_allclose(feats[i], ref.features(params, TINY, h, n), rtol=1e-5,
                                   atol=1e-7)
    # 3 of 8 experts per token: the heaviest takes between 1/8 and 1/3
    assert 1 / 8 <= share <= 1 / 3


class _GivenDecisions:
    """A session whose decisions are given: row i offloads where
    ``offload[i]``."""

    def __init__(self, offload):
        self.offload = offload
        self.telemetry = SimpleNamespace(as_dict=dict)

    def submit_batch(self, features):
        assert len(features) == len(self.offload)
        return [SimpleNamespace(offload=bool(o), estimate=float(o)) for o in self.offload]


@pytest.mark.parametrize("offload", [[False, False, False], [True, True, True],
                                     [True, False, True]], ids=["weak", "strong", "mixed"])
def test_one_weak_prefill_answers_as_two_prefills(model, offload):
    """The weak pass's kept cache and logits answer the weak rows, and the
    strong rows start at the exit layer from its hidden states: the same
    tokens as ``generate`` on each stack alone, and the same answer logits
    as each stack's own prefill of its rows."""
    cfg, params, progs, tokens = model
    out = cascade_generate(params, cfg, {"tokens": tokens, "lengths": LENGTHS}, STEPS,
                           session=_GivenDecisions(offload), exit_layer=EXIT, programs=progs)
    stacks = {"weak": (truncate_params(params, cfg, EXIT), truncated_config(cfg, EXIT)),
              "strong": (params, cfg)}
    assert sorted(out["answer_logits"]) == sorted({"strong" if o else "weak" for o in offload})
    for stack, (idx, logits) in out["answer_logits"].items():
        np.testing.assert_array_equal(idx, np.flatnonzero(np.array(offload) == (stack == "strong")))
        s = int(LENGTHS[idx].max())
        rows = {"tokens": tokens[idx, :s], "lengths": LENGTHS[idx]}
        alone = np.asarray(generate(*stacks[stack], rows, STEPS))
        np.testing.assert_array_equal(out["tokens"][idx], alone)
        answers, two_pass = progs.generate(stack, rows["tokens"], rows["lengths"])
        np.testing.assert_array_equal(answers, alone)
        # a mixed block's rows went through the first layers in a batch of
        # another row count: only summation order differs, which near-zero
        # logits carry as float32 rounding of O(1) sums
        np.testing.assert_allclose(np.asarray(logits[: idx.size]),
                                   np.asarray(two_pass[: idx.size]), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("arch", ["deepseek_v2", "yi_6b"])
def test_reused_tokens_count_what_the_weak_pass_prefilled(model, obs, arch):
    """``repro_cascade_reused_tokens_total`` counts every real prompt token
    of an MLA block, under the stack that answers it, and none on a stack
    that prefills each stack's rows again."""
    if arch == "yi_6b":
        from repro.configs import get_config
        from repro.models.lm import reduced

        cfg = reduced(get_config("yi_6b"), num_layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0))
        obs = Obs(metrics=True, tracing=False, profiling=False)
        progs = CascadePrograms(params, cfg, 1, steps=2, obs=obs)
        tokens, lengths = np.ones((3, 8), np.int32), np.full(3, 8)
    else:
        cfg, params, progs, tokens = model
        lengths = LENGTHS
    before = obs.metrics.snapshot()
    offload = [True, False, True]
    cascade_generate(params, cfg, {"tokens": tokens, "lengths": lengths}, progs.steps,
                     session=_GivenDecisions(offload), exit_layer=progs.exit_layer,
                     programs=progs)
    got = obs.metrics.delta(before, obs.metrics.snapshot())
    real = {"strong": int(lengths[[0, 2]].sum()), "weak": int(lengths[1])}
    for stack in ("weak", "strong"):
        prefilled = got[f'repro_cascade_tokens_total{{phase="prefill",stack="{stack}"}}']
        reused = got[f'repro_cascade_reused_tokens_total{{stack="{stack}"}}']
        assert prefilled == real[stack]
        assert reused == (real[stack] if arch == "deepseek_v2" else 0)


def _moe_case(norm_topk: bool, forced: bool):
    cfg = MoEConfig(d_model=16, d_ff_expert=8, num_experts=8, top_k=3, num_shared=2,
                    norm_topk=norm_topk, dropless=True)
    from repro.models.layers import moe_init

    params = moe_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 16))
    if forced:  # every token's first pick is expert 5
        x = jnp.abs(x)
        params["router"] = jnp.zeros_like(params["router"]).at[:, 5].set(10.0)
    rcfg = {"n_routed_experts": 8, "num_experts_per_tok": 3, "norm_topk_prob": norm_topk,
            "routed_scaling_factor": 1}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda t: ref._moe(params, t, rcfg, ref.exact))(x)
    return cfg, params, x, want


def test_dropless_moe_loses_no_token_when_routing_piles_on_one_expert():
    cfg, params, x, want = _moe_case(norm_topk=False, forced=True)
    out, _ = moe_apply(params, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    # the capacity path holds int(64·3/8·1.25)+1 = 25 of expert 5's 64 tokens
    capped, _ = moe_apply_flat(params, cfg._replace(dropless=False, capacity_factor=1.25), x)
    assert np.abs(np.asarray(capped) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("norm_topk", [False, True])
def test_gates_follow_norm_topk_prob(norm_topk):
    cfg, params, x, want = _moe_case(norm_topk=norm_topk, forced=False)
    out, _ = moe_apply(params, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    other, _ = moe_apply(params, cfg._replace(norm_topk=not norm_topk), x)
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-3


def test_yarn_frequencies_and_softmax_scale_at_published_widths():
    cfg = from_hf(PUBLISHED)
    inv = np.asarray(yarn_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0), np.float64)
    freq = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    # 64·ln(4096/(β·2π))/(2·ln 1e4) is 10.47 at β=32 and 22.51 at β=1
    low, high = 10, 23
    np.testing.assert_allclose(inv[: low + 1], freq[: low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[high:], freq[high:] / 40, rtol=1e-6)
    ramp = (np.arange(32) - low) / (high - low)
    mid = slice(low + 1, high)
    np.testing.assert_allclose(inv[mid], freq[mid] / 40 * ramp[mid] + freq[mid] * (1 - ramp[mid]),
                               rtol=1e-6)
    _, rope_m, scale = mla_rope(cfg.mla())
    assert rope_m == 1.0
    # mscale(40, 0.707) = 0.1·0.707·ln 40 + 1 = 1.260804; squared 1.589627
    assert scale == pytest.approx(192 ** -0.5 * 1.589627, rel=1e-6)
    r_inv, r_m, r_scale = ref.rope_tables(PUBLISHED)
    np.testing.assert_allclose(inv, r_inv, rtol=1e-6)
    assert (r_m, r_scale) == pytest.approx((rope_m, scale))


@pytest.mark.parametrize("chunk", [3, 4, 16])
def test_chunked_features_equal_features_of_full_logits(chunk):
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(3, 16, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 50)), jnp.float32)
    labels = np.where(np.arange(16)[None] < np.array([[16], [7], [1]]), 1, -1)
    full = logits_features(h @ w, jnp.asarray(labels))
    got = chunked_logits_features(lambda x: x @ w, h, jnp.asarray(labels >= 0), chunk=chunk)
    # a 16-position sum in another order
    np.testing.assert_allclose(np.asarray(got), full, rtol=2e-6, atol=1e-7)


def test_cascade_compiles_nothing_for_a_new_row_count_inside_the_buckets(model):
    cfg, params, progs, tokens = model
    rng = np.random.default_rng(6)
    arrays = {"params": {"layer0": {"w": rng.normal(size=(12, 16)).astype(np.float32),
                                    "b": np.zeros(16, np.float32)},
                         "layer1": {"w": rng.normal(size=(16, 1)).astype(np.float32),
                                    "b": np.zeros(1, np.float32)}},
              "mu": np.zeros(12, np.float32), "sigma": np.ones(12, np.float32)}
    meta = {"kind": "mlp", "in_dim": 12, "use_fused": True,
            "config": {"hidden": [16], "sigmoid_out": True, "standardize": True}}
    engine = OffloadEngine(feature_extractor=LMLogitsFeatures(),
                           reward_model=MLPRewardModel.from_state(arrays, meta), ratio=0.5)
    engine.calibration_scores = np.linspace(0, 1, 64)
    engine.policy = make_policy("threshold", engine.calibration_scores, 0.5)
    progs.warm()
    for rows in range(1, 5):  # the estimator's chunk of each row count
        engine.score_device(features=np.zeros((rows, 12), np.float32)).block_until_ready()
    from repro.runtime.session import OffloadSession

    session = OffloadSession(engine, micro_batch=8)
    kw = dict(session=session, exit_layer=EXIT, programs=progs)
    cascade_generate(params, cfg, {"tokens": tokens, "lengths": LENGTHS}, STEPS, **kw)
    compiled = []
    listener = lambda event, duration, **_: compiled.append(event)
    jax.monitoring.register_event_duration_secs_listener(listener)
    before = jit_stats.snapshot()
    try:
        out = cascade_generate(params, cfg, {"tokens": tokens[:1, :5], "lengths": LENGTHS[:1]},
                               STEPS, **kw)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    grown = jit_stats.delta(before, jit_stats.snapshot())
    assert all(grown[s][0] == 0 for s in ("cascade.weak_pass", "cascade.prefill",
                                          "cascade.decode"))
    assert "/jax/core/compile/backend_compile_duration" not in compiled
    assert out["tokens"].shape == (1, STEPS) and out["features"].shape == (1, 12)


def test_from_hf_refuses_what_the_program_does_not_implement():
    with pytest.raises(ValueError, match="n_group"):
        from_hf(dict(PUBLISHED, n_group=8))
    with pytest.raises(ValueError, match="q_lora_rank"):
        from_hf(dict(PUBLISHED, q_lora_rank=1536))
