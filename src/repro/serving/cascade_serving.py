"""ORIC-gated cascade serving for LMs (paper §V-A / §VII-B transfer).

The paper's weak/strong detector cascade maps onto LM serving as an
**early-exit cascade**: the "weak detector" is the model truncated at layer
k with the shared LM head (local device); the "strong detector" is the full
depth (edge pod).  The decision system transfers wholesale and is owned by
one :class:`repro.api.OffloadEngine`:

  reward      R_i  = per-request quality delta (NLL_weak − NLL_strong)
  rank xform  cdf fit on a CONTEXT batch of reference requests (Eq. 6) —
              for mAP the context enters the metric itself; for corpus-mean
              quality metrics (NLL) the metric is linear in per-request
              terms, so the context's role reduces to calibrating the
              reward CDF/threshold.  Recorded in DESIGN.md §4.
  estimator   MLP on weak-head logits features (top-k probs, entropy,
              margin — the analogue of top-25 box confidences), trained
              with the Eq. 7 weighted MSE; single hidden layer so batched
              scoring runs the fused Pallas ``estimator_mlp`` kernel.
  policy      quantile threshold, ratio adjustable at runtime.

Supports dense / vlm / moe / rwkv stacks (any arch whose layers are a
single scan stack, plus MoE's two-stack split).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import LMLogitsFeatures, MLPRewardModel, OffloadEngine
from repro.api.features import logits_features  # re-export (moved to repro.api)
from repro.core.estimator import EstimatorConfig
from repro.models.lm import LMConfig, forward

PyTree = dict

__all__ = [
    "LMCascade",
    "logits_features",
    "sequence_nll",
    "truncate_params",
    "truncated_config",
]


def truncate_params(params: PyTree, cfg: LMConfig, exit_layer: int) -> PyTree:
    """Early-exit params: first ``exit_layer`` layers + shared head.  Layer
    lists (``unstack_layers``) are cut without copying a weight."""
    def head(stack, n):
        if isinstance(stack, (list, tuple)):
            return list(stack[:n])
        return jax.tree.map(lambda a: a[:n], stack)

    p = {k: v for k, v in params.items() if k not in ("layers", "dense_layers", "moe_layers")}
    if "layers" in params:
        p["layers"] = head(params["layers"], exit_layer)
    else:  # moe two-stack
        nD = cfg.first_k_dense
        take_dense = min(exit_layer, nD)
        take_moe = max(exit_layer - nD, 0)
        if take_dense:
            p["dense_layers"] = head(params["dense_layers"], take_dense)
        p["moe_layers"] = head(params["moe_layers"], take_moe)
    return p


def truncated_config(cfg: LMConfig, exit_layer: int) -> LMConfig:
    import dataclasses

    kw = {"num_layers": exit_layer}
    if cfg.arch_type == "moe":
        kw["first_k_dense"] = min(cfg.first_k_dense, exit_layer)
    return dataclasses.replace(cfg, **kw)


def sequence_nll(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-sequence mean NLL.  logits (B,S,V), labels (B,S) with -1 pad."""
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, safe[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * valid
    return nll.sum(-1) / jnp.maximum(valid.sum(-1), 1)


@dataclass
class LMCascade:
    """Trained ORIC-style cascade for an LM: truncation point + the unified
    decision engine (features → estimator → rank transform → policy)."""

    cfg: LMConfig
    exit_layer: int
    engine: OffloadEngine

    # -- back-compat views of the engine's stack ---------------------------
    @property
    def estimator(self):
        return self.engine.reward_model.estimator

    @property
    def cdf(self):
        return self.engine.transform

    @property
    def policy(self):
        return self.engine.policy

    @classmethod
    def fit(
        cls,
        params: PyTree,
        cfg: LMConfig,
        exit_layer: int,
        calib_batches,  # iterable of training batches (tokens+labels)
        ratio: float = 0.2,
        epochs: int = 40,
        seed: int = 0,
    ) -> "LMCascade":
        """Compute oracle rewards on calibration data, then fit the engine
        (MORIC-style estimator + quantile threshold) in one step."""
        wcfg = truncated_config(cfg, exit_layer)
        extractor = LMLogitsFeatures()
        feats, rewards = [], []
        for batch in calib_batches:
            wparams = truncate_params(params, cfg, exit_layer)
            wlogits, _ = forward(wparams, wcfg, batch)
            slogits, _ = forward(params, cfg, batch)
            nll_w = sequence_nll(wlogits, batch["labels"])
            nll_s = sequence_nll(slogits, batch["labels"])
            rewards.append(np.asarray(nll_w - nll_s))  # >0: offload helps
            feats.append(extractor((wlogits, batch["labels"])))
        engine = OffloadEngine(
            feature_extractor=extractor,
            reward_model=MLPRewardModel(
                config=EstimatorConfig(hidden=(64,), epochs=epochs, seed=seed)
            ),
            ratio=ratio,
        )
        engine.fit(features=np.concatenate(feats), rewards=np.concatenate(rewards))
        return cls(cfg=cfg, exit_layer=exit_layer, engine=engine)

    def serve_batch(self, params: PyTree, batch: Dict) -> Dict:
        """Weak pass for everyone; strong pass only for offloaded requests.
        Returns per-request NLLs, decisions, and the blended quality."""
        wcfg = truncated_config(self.cfg, self.exit_layer)
        wparams = truncate_params(params, self.cfg, self.exit_layer)
        wlogits, _ = forward(wparams, wcfg, batch)
        decision = self.engine.decide((wlogits, batch["labels"]))
        offload = decision.offload
        nll_w = np.asarray(sequence_nll(wlogits, batch["labels"]))
        # strong pass (in a real deployment only offloaded rows cross the
        # pod axis; here we compute the full batch and select)
        slogits, _ = forward(params, self.cfg, batch)
        nll_s = np.asarray(sequence_nll(slogits, batch["labels"]))
        nll_final = np.where(offload, nll_s, nll_w)
        return {
            "estimates": decision.estimates,
            "offload": offload,
            "nll_weak": nll_w,
            "nll_strong": nll_s,
            "nll_final": nll_final,
            "offload_ratio": decision.ratio,
        }

    def serve_stream(
        self,
        params: PyTree,
        batches,
        *,
        micro_batch: int = 8,
        ratio: "float | None" = None,
        session=None,
        set_ratio_at: "Dict[int, float] | None" = None,
    ) -> Dict:
        """Streaming serve: requests arrive batch by batch and flow through
        one :class:`repro.runtime.OffloadSession` in arrival order — the
        stateful counterpart of ``serve_batch`` (policy state, realized-ratio
        telemetry, and mid-stream ``set_ratio_at`` re-budgets carry across
        batches).  Realized rewards (NLL_weak − NLL_strong of each request
        that actually went to the strong model) are recorded into the
        session telemetry, so ``reward_sum / rewards_recorded`` is the mean
        realized quality delta of the offloaded traffic.

        ``set_ratio_at`` maps global request index -> new target ratio.
        Returns concatenated per-request results plus the telemetry."""
        from repro.runtime.session import OffloadSession

        if session is None:
            session = OffloadSession(self.engine, ratio=ratio, micro_batch=micro_batch)
        rebudget = dict(set_ratio_at or {})
        wcfg = truncated_config(self.cfg, self.exit_layer)
        wparams = truncate_params(params, self.cfg, self.exit_layer)
        served = 0
        est, off, nw, ns = [], [], [], []
        for batch in batches:
            # re-budgets land at the nearest batch boundary, in step order
            for step in sorted(rebudget):
                if step < served + int(batch["tokens"].shape[0]):
                    session.set_ratio(rebudget.pop(step))
            wlogits, _ = forward(wparams, wcfg, batch)
            decisions = session.submit_batch((wlogits, batch["labels"]))
            mask = np.array([d.offload for d in decisions], bool)
            nll_w = np.asarray(sequence_nll(wlogits, batch["labels"]))
            slogits, _ = forward(params, self.cfg, batch)
            nll_s = np.asarray(sequence_nll(slogits, batch["labels"]))
            for r in (nll_w - nll_s)[mask]:
                session.record_reward(float(r))
            est.append(np.array([d.estimate for d in decisions]))
            off.append(mask)
            nw.append(nll_w)
            ns.append(nll_s)
            served += len(mask)
        offload = np.concatenate(off) if off else np.zeros(0, bool)
        nll_w = np.concatenate(nw) if nw else np.zeros(0)
        nll_s = np.concatenate(ns) if ns else np.zeros(0)
        return {
            "estimates": np.concatenate(est) if est else np.zeros(0),
            "offload": offload,
            "nll_weak": nll_w,
            "nll_strong": nll_s,
            "nll_final": np.where(offload, nll_s, nll_w),
            "offload_ratio": float(offload.mean()) if offload.size else 0.0,
            "telemetry": session.telemetry.as_dict(),
        }

    def set_ratio(self, ratio: float) -> None:
        """Runtime offload-budget adjustment (delegates to the engine)."""
        self.engine.set_ratio(ratio)

    def save(self, path: str) -> None:
        """Persist the decision stack (not the LM weights) as one artifact."""
        self.engine.save(
            path, extra_meta={"exit_layer": self.exit_layer, "cfg_name": self.cfg.name}
        )

    @classmethod
    def load(cls, path: str, cfg: LMConfig) -> "LMCascade":
        """Rebuild from a saved engine; the LM config/params are supplied by
        the caller (the engine artifact carries only the decision stack)."""
        engine = OffloadEngine.load(path)
        return cls(
            cfg=cfg, exit_layer=int(engine.extra_meta["exit_layer"]), engine=engine
        )
