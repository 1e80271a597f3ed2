"""DeepSeek-V2 served through the LM early-exit cascade
(``repro.serving.decode_loop.cascade_generate``) with a long-lived
``OffloadSession`` and ``CascadePrograms``.

Set-up: the configuration (``config.json`` keys) as the program's
``LMConfig``; seeded random weights made on the chip; a seeded pool of
prompts; the weak pass over a seeded calibration block gives the
estimator's feature scales and the threshold's calibration scores; then
every program of every bucket (``CascadePrograms.warm``), the estimator's
chunk of every row count, and one block through the whole path.

Window: open loop.  Each turn takes the requests that are due, at most
``max_block`` of them and at most ``max_block_tokens`` padded prompt
tokens, and serves them as one block through ``cascade_generate``: the
weak pass and the decision for all, then each row's answer of
``answer_tokens`` greedy tokens through the stack it was given.  A
request has returned when its block's decisions and answer tokens have.

Check (after ``release``): the plain float32 reference
(``reference/deepseek_v2.py``) on a seeded sample of the window's
requests plus those with the highest and lowest estimates: the weak
pass's features (``feature_gap``, in units of the estimator's feature
scales), each estimate as log-odds against the reference estimator's
on the same features (``logit_gap``: the estimator alone, apart from the
features' rounding that ``feature_gap`` bounds), and the chosen
stack's logits at every answer position against one reference forward
over the prompt and the program's own answer tokens (``weak_logit_rms``,
``strong_logit_rms``: RMS of the difference over the reference's RMS);
``decision_mismatch`` over every decided request, ``unscored_sampled``.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import LMConfig

_NEEDS = ("rope_scaling", "moe_dropless", "norm_topk_prob")
_missing = [f for f in _NEEDS if f not in LMConfig.__dataclass_fields__]
if _missing:
    raise ImportError(f"this program's LMConfig has no {', '.join(_missing)}: it cannot serve "
                      "DeepSeek-V2 (YaRN attention, dropless experts, unnormalised gates)")

from core import weights as W  # noqa: E402
from core.spans import Spans  # noqa: E402
from reference import deepseek_v2 as ref  # noqa: E402
from reference import estimator as est_ref  # noqa: E402
from traffic import prompts  # noqa: E402

from repro.api import LMLogitsFeatures, MLPRewardModel, OffloadEngine  # noqa: E402
from repro.api.policies import make_policy  # noqa: E402
from repro.configs.deepseek_v2_lite_16b import from_hf  # noqa: E402
from repro.models.lm import init_params, unstack_layers  # noqa: E402
from repro.obs import Obs  # noqa: E402
from repro.runtime.session import OffloadSession  # noqa: E402
from repro.serving.decode_loop import CascadePrograms, cascade_generate  # noqa: E402

#: window requests the check samples, and those with the highest and the
#: lowest estimates it adds (each)
SAMPLE = 16
EXTREMES = 4
#: seconds past the window's close the loop keeps serving requests due in it
DRAIN_S = 30.0
#: prompts in the pool the window cycles through, prompts in the
#: calibration block, positions per chunk of the weak pass's features
POOL_PROMPTS = 512
CALIBRATION_PROMPTS = 64
FEATURE_TOKENS = 512
TOP_K = 8
#: the estimator's feature scale is at least this share of the feature's
#: calibration mean
SCALE_FLOOR = 1e-2
#: threads that compile the cascade's programs side by side (into the
#: persistent compilation cache, where one is on)
WARM_THREADS = 6


def lm_config(cfg: Dict) -> LMConfig:
    return from_hf(cfg, name=cfg["name"], dtype=cfg["dtype"], remat=False)


def make_weights(lcfg: LMConfig, seed: int) -> Dict:
    """Seeded weights made on the chip in the activation dtype, one buffer
    per leaf and per layer, as serving keeps them (``unstack_layers``), so
    no stacked copy is ever held beside them: N(0, 1/fan_in) for matrices
    (fan-in = the second-to-last axis), N(0, 1) for the embedding, ones for
    norms; the router in float32.  The same seed gives the same weights on
    the same backend: the bits come from the backend's ``RngBitGenerator``
    (the "rbg" key), many times faster on a TPU than threefry."""
    shapes = jax.eval_shape(lambda k: unstack_layers(init_params(lcfg, k)),
                            jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    words = np.random.SeedSequence([seed, 100]).generate_state(4, np.uint32)
    keys = jax.random.split(jax.random.wrap_key_data(jnp.asarray(words), impl="rbg"), len(flat))
    leaves = []
    for i, (path, sd) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            leaves.append(jnp.ones(sd.shape, jnp.float32))
            continue
        dtype = jnp.float32 if "router" in name else lcfg.act_dtype
        std = 1.0 if name == "['embed']" else float(sd.shape[-2]) ** -0.5
        leaves.append(_normal(keys[i], sd.shape, jnp.dtype(dtype), std))
    return jax.tree_util.tree_unflatten(tree, leaves)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class Served:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, devices, spans: Spans,
                 profile: bool = False):
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, seed, spans
        self.setup_phases: List[Tuple[str, float]] = []
        t = time.perf_counter()

        def phase(name: str) -> None:
            nonlocal t
            now = time.perf_counter()
            self.setup_phases.append((name, now - t))
            t = now

        lcfg = self.lcfg = lm_config(cfg)
        self.steps = int(mix["answer_tokens"])
        self.max_block, self.cap = int(mix["max_block"]), int(mix["max_block_tokens"])
        self.pool, self.offsets = prompts.draw(np.random.default_rng([seed, 0]), POOL_PROMPTS,
                                               mix, lcfg.vocab_size)
        cal, cal_off = prompts.draw(np.random.default_rng([seed, 1]), CALIBRATION_PROMPTS,
                                    mix, lcfg.vocab_size)
        phase("prompts")
        self.obs = Obs(metrics=True, tracing=False, profiling=True, annotate=profile)
        self.programs = CascadePrograms(
            make_weights(lcfg, seed), lcfg, int(cfg["exit_layer"]), steps=self.steps,
            row_buckets=[1, self.max_block], length_buckets=mix["length_buckets"],
            max_tokens=self.cap, top_k=TOP_K, feature_tokens=FEATURE_TOKENS, obs=self.obs)
        jax.block_until_ready(self.programs.params)
        phase("weights")
        cached = jax.config.jax_compilation_cache_dir is not None
        n_shapes = self.programs.warm(threads=WARM_THREADS if cached else 1)
        phase(f"programs_{n_shapes}_shapes")
        feats = np.concatenate([
            self.programs.weak_pass(*self._block(cal, cal_off, lo, hi))[0]
            for lo, hi in self._blocks(np.diff(cal_off))])
        p = dict(W._make(W.key_for(seed, 7), feats.shape[1], int(cfg["estimator_hidden"])))
        p["mu"] = feats.mean(0).astype(np.float32)
        # under random weights the features barely vary from prompt to
        # prompt; a scale floored at SCALE_FLOOR of the feature's size keeps
        # the rounding of a near-constant feature from setting the estimate
        p["sigma"] = np.maximum(feats.std(0), SCALE_FLOOR * np.abs(p["mu"]) + 1e-12
                                ).astype(np.float32)
        self.est_params = p
        model = MLPRewardModel.from_state(*W.artifact(p, hidden=int(cfg["estimator_hidden"])))
        engine = self.engine = OffloadEngine(
            feature_extractor=LMLogitsFeatures(top_k=TOP_K), reward_model=model,
            policy=cfg["policy"], ratio=float(cfg["ratio"]))
        mb = int(cfg["micro_batch"])
        engine.calibration_scores = np.concatenate([
            np.asarray(engine.score_device(features=feats[lo: lo + mb]), np.float64)
            for lo in range(0, len(feats), mb)])
        engine.policy = make_policy(engine.policy_name, engine.calibration_scores, engine.ratio)
        for r in range(1, mb + 1):  # every chunk a block leaves
            engine.score_device(features=feats[:r]).block_until_ready()
        self.session = OffloadSession(engine, micro_batch=mb, obs=self.obs)
        phase("calibration")
        lengths = np.diff(self.offsets)
        lo, hi = next(iter(self._blocks(lengths)))
        self._serve(*self._block(self.pool, self.offsets, lo, hi))
        phase("serve_path")

    # ------------------------------------------------------------ blocks

    def _fits(self, rows: int, longest: int) -> bool:
        try:
            self.programs.shape(rows, longest)
            return rows <= self.max_block
        except ValueError:
            return False

    def _blocks(self, lengths: np.ndarray):
        """Consecutive blocks of prompts within the caps."""
        lo = 0
        while lo < len(lengths):
            hi = lo + 1
            while hi < len(lengths) and self._fits(hi + 1 - lo, int(lengths[lo: hi + 1].max())):
                hi += 1
            yield lo, hi
            lo = hi

    @staticmethod
    def _block(tokens: np.ndarray, offsets: np.ndarray, lo: int, hi: int):
        lengths = np.diff(offsets[lo: hi + 1])
        block = np.zeros((hi - lo, int(lengths.max())), np.int32)
        for j in range(hi - lo):
            block[j, : lengths[j]] = tokens[offsets[lo + j]: offsets[lo + j + 1]]
        return block, lengths.astype(np.int32)

    def _serve(self, tokens: np.ndarray, lengths: np.ndarray) -> Dict:
        return cascade_generate(
            self.programs.params, self.lcfg, {"tokens": tokens, "lengths": lengths}, self.steps,
            session=self.session, exit_layer=int(self.cfg["exit_layer"]),
            programs=self.programs)

    # ------------------------------------------------------------ window

    def window(self, due: np.ndarray, seconds: float,
               probe: Callable[[float], None]) -> Dict:
        n = len(due)
        P = POOL_PROMPTS
        lengths = np.diff(self.offsets)
        rng = np.random.default_rng([self.seed, 2])
        sample = set(rng.choice(n, min(SAMPLE, n), replace=False).tolist())
        est = np.full(n, np.nan)
        off = np.zeros(n, bool)
        kept: Dict[int, Dict] = {}  # request -> what the check reads
        extremes: List[Tuple[float, int]] = []
        lat = np.full(n, np.nan)
        blocks: List[Tuple[float, float, int]] = []
        self.calls: List[Tuple[float, str, str, int, int, np.ndarray]] = []
        spans, programs = self.spans, self.programs
        self.obs.profiler.clear()
        self._counts0 = self.obs.metrics.snapshot()
        k = 0
        t0 = time.perf_counter()
        deadline = seconds + DRAIN_S
        while k < n:
            now = time.perf_counter() - t0
            wait = due[k] - now
            if wait > 0:
                if wait > 0.0015:
                    time.sleep(wait - 0.001)
                continue
            if now > deadline:
                break
            probe(now)
            k1 = k + 1
            while (k1 < n and due[k1] <= now
                   and self._fits(k1 + 1 - k, int(lengths[np.arange(k, k1 + 1) % P].max()))):
                k1 += 1
            with spans("bench.generate"):
                ids = np.arange(k, k1) % P
                tok = np.zeros((k1 - k, int(lengths[ids].max())), np.int32)
                for j, i in enumerate(ids):
                    tok[j, : lengths[i]] = self.pool[self.offsets[i]: self.offsets[i + 1]]
                lens = lengths[ids].astype(np.int32)
            with spans("bench.submit"):
                res = self._serve(tok, lens)
            t = time.perf_counter() - t0
            lat[k:k1] = t - due[k:k1]
            est[k:k1], off[k:k1] = res["estimates"], res["offload"]
            blocks.append((now, t, k1 - k))
            R, S = programs.shape(k1 - k, int(lens.max()))
            self.calls.append((now, "weak_pass", "weak", R, S, lens))
            rows_of = {}
            for stack, (idx, logits) in res["answer_logits"].items():
                R, S = programs.shape(len(idx), int(lens[idx].max()))
                self.calls.append((now, "prefill", stack, R, S, lens[idx]))
                self.calls.append((now, "decode", stack, R, S, lens[idx]))
                rows_of.update({int(j): (stack, int(r), logits) for r, j in enumerate(idx)})
            for j in (int(np.argmax(res["estimates"])), int(np.argmin(res["estimates"]))):
                extremes.append((float(res["estimates"][j]), k + j))
            extremes.sort()
            keep_ext = {i for _, i in extremes[:EXTREMES] + extremes[-EXTREMES:]}
            extremes = [e for e in extremes if e[1] in keep_ext]
            for j in range(k1 - k):
                i = k + j
                if i in sample or i in keep_ext and i not in kept:
                    stack, r, logits = rows_of[j]
                    kept[i] = {"pool": int(ids[j]), "stack": stack, "logits": (logits, r),
                               "answer": res["tokens"][j].copy(),
                               "features": res["features"][j].copy()}
            for i in [i for i in kept if i not in sample and i not in keep_ext]:
                del kept[i]
            k = k1
        end = time.perf_counter() - t0
        self._result = {"est": est, "offload": off, "kept": kept, "sample": sorted(sample)}
        return {"lat": lat, "end": end, "blocks": blocks, "decided": k}

    def scoring_calls(self, blocks, t_from: float, t_to: float) -> List[Tuple]:
        """``(program, stack, rows, length, prompt lengths)`` of each LM
        program the blocks picked up in ``[t_from, t_to]`` ran."""
        return [c[1:] for c in self.calls if t_from <= c[0] <= t_to]

    def layer_seconds(self, spans: Dict[str, float]) -> Dict[str, float]:
        """Seconds of the serve loop and of the cascade's phases in the
        window, with the counts their metrics divide by."""
        prof = self.obs.profiler.totals()
        delta = self.obs.metrics.delta(self._counts0, self.obs.metrics.snapshot())
        count = lambda prefix: float(sum(v for k, v in delta.items() if k.startswith(prefix)))
        decided = np.isfinite(self._result["est"])
        return {
            "serve": spans.get("bench.submit", 0.0),
            "weak_pass": prof.get("cascade.weak_pass", 0.0),
            "prefill": prof.get("cascade.prefill", 0.0),
            "decode": prof.get("cascade.decode", 0.0),
            "prompt_tokens": float(np.diff(self.offsets)[
                np.flatnonzero(decided) % POOL_PROMPTS].sum()),
            "decode_steps": float(sum(1 for c in self.calls if c[1] == "decode")
                                  * (self.steps - 1)),
            "prefill_tokens": count('repro_cascade_tokens_total{phase="prefill"'),
            "pad_tokens": count("repro_cascade_pad_tokens_total"),
        }

    # ------------------------------------------------------------- check

    def release(self) -> None:
        """Drop the program's state, its weights included: each check
        makes them again from the seed."""
        self.cal_scores = np.asarray(self.engine.calibration_scores, np.float64)
        self.host_est = {k: np.asarray(v, np.float64) for k, v in self.est_params.items()}
        for r in self._result["kept"].values():
            logits, row = r["logits"]
            r["logits"] = np.asarray(logits[row], np.float64)
        del self.session, self.engine, self.programs, self.est_params

    def _reference(self, rnd: Callable) -> Dict[int, Dict]:
        """Features, estimate and answer logits of every kept request by
        the reference (rounded by ``rnd``)."""
        cfg, exit_layer = self.cfg, int(self.cfg["exit_layer"])
        depth = int(cfg["num_hidden_layers"])
        params = make_weights(self.lcfg, self.seed)
        out = {}
        for i, r in sorted(self._result["kept"].items()):
            p = r["pool"]
            prompt = self.pool[self.offsets[p]: self.offsets[p + 1]]
            L = len(prompt)
            seq = np.concatenate([prompt, r["answer"][: self.steps - 1]])
            h = ref.hidden(params, cfg, seq, range(exit_layer), rnd=rnd)
            feats = ref.features(params, cfg, h, L, TOP_K, rnd=rnd)
            if r["stack"] == "strong":
                h = ref.hidden(params, cfg, seq, range(exit_layer, depth), h=h, rnd=rnd)
            logits = np.asarray(ref.logits(params, cfg, h, L - 1, self.steps, rnd),
                                np.float64)
            out[i] = {"features": feats, "logits": logits,
                      "est": float(ref.estimate(feats[None], self.host_est, rnd)[0])}
        return out

    def check(self, control: bool = False) -> List[Tuple[str, float, float]]:
        """The compared numbers with their limits.  ``control`` puts the
        reference rounded to float8_e4m3fn in the program's place."""
        cfg, res = self.cfg, self._result
        want = self._reference(ref.exact)
        got = self._reference(ref.to_fp8) if control else {
            i: {"features": r["features"], "logits": r["logits"],
                "est": float(res["est"][i])} for i, r in res["kept"].items()}
        sigma = self.host_est["sigma"]
        feature_gap = logit_gap = est_gap = 0.0
        rms = {"weak": 0.0, "strong": 0.0}
        unscored = 0
        for i, w in want.items():
            g = got[i]
            if not np.isfinite(g["est"]):
                unscored += 1
                continue
            feature_gap = max(feature_gap, float(np.max(np.abs(g["features"] - w["features"])
                                                        / sigma)))
            # the estimator alone: the reference's estimate of the same features
            own = float(est_ref.forward(g["features"][None], self.host_est)[0])
            logit_gap = max(logit_gap, float(abs(est_ref.logit(g["est"]) - est_ref.logit(own))))
            est_gap = max(est_gap, abs(g["est"] - own))
            d = np.sqrt(np.mean((g["logits"] - w["logits"]) ** 2) / np.mean(w["logits"] ** 2))
            stack = res["kept"][i]["stack"]
            rms[stack] = max(rms[stack], float(d))
        unscored += sum(1 for i in res["sample"] if i not in want)
        thr = float(np.quantile(np.sort(self.cal_scores), 1.0 - float(cfg["ratio"])))
        decided = np.isfinite(res["est"])
        mismatch = int(np.sum(res["offload"][decided] != (res["est"][decided] > thr)))
        self.est_gap = est_gap
        lim = cfg["limits"]
        return [("feature_gap", feature_gap, float(lim["feature_gap"])),
                ("logit_gap", logit_gap, float(lim["logit_gap"])),
                ("weak_logit_rms", rms["weak"], float(lim["weak_logit_rms"])),
                ("strong_logit_rms", rms["strong"], float(lim["strong_logit_rms"])),
                ("decision_mismatch", float(mismatch), 0.0),
                ("unscored_sampled", float(unscored), 0.0)]

    def check_control(self) -> List[Tuple[str, float, float]]:
        """The check with the control in the program's place: the
        reference with weights and activations rounded to float8_e4m3fn."""
        return self.check(control=True)
