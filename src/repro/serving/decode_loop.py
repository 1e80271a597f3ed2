"""Batched autoregressive decode: one jitted prefill and one jitted loop of
``decode_step`` (``generate``), the programs the engine-gated weak/strong
cascade runs on them (``CascadePrograms``), and the cascade itself
(``cascade_generate``)."""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.features import chunked_logits_features
from repro.models.lm import (
    LMConfig,
    decode_step,
    forward_hidden,
    last_logits,
    latent_prefill,
    lm_head,
    prefill,
    takes_ragged_rows,
    unstack_layers,
)
from repro.obs import jit_stats

# ---------------------------------------------------------------------------
# the cascade's programs: built once per config, warmed per bucket
# ---------------------------------------------------------------------------

def _pick(logits, key, greedy: bool):
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def _batch(cfg: LMConfig, tokens, lengths) -> Dict:
    """The model's batch of right-padded ``tokens`` (B, S).  ``lengths``
    goes in on a stack that takes ragged rows, and wherever a row is
    shorter than S, so that a stack that cannot take them refuses them
    (``lm.prefill``) rather than decoding from the pads."""
    if takes_ragged_rows(cfg) or bool(np.any(np.asarray(lengths) < tokens.shape[1])):
        return {"tokens": tokens, "lengths": lengths}
    return {"tokens": tokens}


def _weak_pass(params, batch, fmask, *, cfg: LMConfig, top_k: int, chunk: int,
               capacity: int = 0):
    """The decision's input: the 4 + top_k logits features of every row
    from the weak stack's last hidden state, ``chunk`` positions at a time
    (no (B, S, V) logits), and the stack's (MoE layers, E) expert load.
    With ``capacity`` (a stack that takes ragged rows) the pass is also the
    weak stack's prefill, and keeps for the answers (third output) its
    latent cache at ``capacity`` slots, the float32 logits at each row's
    last real token and the hidden states after its last layer; else that
    output is None."""
    if capacity:
        h, cache, loads = latent_prefill(params, cfg, batch, capacity)
        kept = (cache, last_logits(params, cfg, h, batch["lengths"]), h)
    else:
        h, _, loads = forward_hidden(params, cfg, batch)
        kept = None
    feats = chunked_logits_features(
        lambda x: lm_head(params, cfg, x), h, fmask, top_k, chunk
    )
    return feats, loads, kept


def _prefill(params, batch, key, *, cfg: LMConfig, capacity: Optional[int], greedy: bool,
             start: int = 0):
    """Prefill into a ``capacity``-slot cache: (first answer token, its
    logits (B, V) float32, cache).  With ``start`` > 0 the prompts are rows
    of a weak pass's block and are not prefilled again: ``batch`` holds
    the pass's kept outputs (``kept``), the block's ``rows`` to answer and
    their ``lengths``.  The cache of layers 0 … ``start``−1 is those rows'
    (at the pass's capacity); a deeper stack runs only its layers
    ``start`` … L−1, from the rows' hidden states, and the weak stack takes
    its logits from the pass."""
    if not start:
        logits, cache = prefill(params, cfg, batch, capacity=capacity)
        return _pick(logits, key, greedy), logits, cache
    (cache, logits, h), rows = batch["kept"], batch["rows"]
    cache = jax.tree.map(lambda a: a[:, rows], cache)
    if start < cfg.num_layers:
        sub = {"hidden": h[rows], "lengths": batch["lengths"]}
        h, tail, _ = latent_prefill(params, cfg, sub, cache["c"].shape[2], start=start)
        cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), cache, tail)
        logits = last_logits(params, cfg, h, batch["lengths"])
    else:
        logits = logits[rows]
    return _pick(logits, key, greedy), logits, cache


def _decode(params, cache, tok, logits0, pos, key, *, cfg: LMConfig, steps: int,
            greedy: bool):
    """``steps`` decode steps from ``tok`` at ``pos`` (per row on MLA
    stacks): (tokens (B, steps), logits (B, 1 + steps, V) float32 of every
    answer position, ``logits0`` the prefill's)."""
    def body(carry, k):
        tok, pos, cache = carry
        logits, cache = decode_step(params, cfg, cache, tok, pos)
        nxt = _pick(logits, k, greedy)
        return (nxt, pos + 1, cache), (nxt, logits)

    _, (toks, logits) = jax.lax.scan(body, (tok, pos, cache), jax.random.split(key, steps))
    return toks.T, jnp.concatenate([logits0[:, None], jnp.moveaxis(logits, 0, 1)], axis=1)


_WEAK_PASS = jit_stats.register_jit(
    "cascade.weak_pass",
    jax.jit(_weak_pass, static_argnames=("cfg", "top_k", "chunk", "capacity")),
)
_PREFILL = jit_stats.register_jit(
    "cascade.prefill",
    jax.jit(_prefill, static_argnames=("cfg", "capacity", "greedy", "start")),
)
_DECODE = jit_stats.register_jit(
    "cascade.decode",
    jax.jit(_decode, static_argnames=("cfg", "steps", "greedy")),
)


def generate(
    params,
    cfg: LMConfig,
    batch: Dict,
    steps: int,
    capacity: Optional[int] = None,
    greedy: bool = True,
    key=None,
) -> jnp.ndarray:
    """Prefill + ``steps`` greedy/sampled tokens.  Returns (B, steps).
    ``batch["lengths"]`` (B,), where given, marks ragged right-padded
    prompts; each row then decodes from its own last token (MLA stacks;
    others refuse them)."""
    B, S = batch["tokens"].shape
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    first, logits0, cache = _PREFILL(params, batch, k1, cfg=cfg, capacity=capacity or S + steps,
                                     greedy=greedy)
    pos = jnp.asarray(batch["lengths"], jnp.int32) if "lengths" in batch else jnp.int32(S)
    rest, _ = _DECODE(params, cache, first, logits0, pos, k2, cfg=cfg, steps=steps - 1,
                      greedy=greedy)
    return jnp.concatenate([first[:, None], rest], axis=1)


def _bucket(n: int, buckets: Optional[Sequence[int]]) -> int:
    if not buckets:
        return n
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {buckets[-1]}")


@dataclass
class WeakBlock:
    """One block's weak pass (``CascadePrograms.prefill_weak``): the
    decision's ``features`` (n, 4 + top_k) and the expert ``share`` on the
    host, the block's prompts (``tokens`` (n, s), ``lengths`` (n,)), the
    (rows, length) ``shape`` it was padded to and the cache ``capacity``
    asked for.  On a stack that takes ragged rows, ``kept`` holds on the
    device what the answers start from: the weak stack's latent cache, the
    float32 logits at each row's last real token and the hidden states
    after layer ``exit_layer``; elsewhere it is None."""

    features: np.ndarray
    share: Optional[float]
    tokens: np.ndarray
    lengths: np.ndarray
    shape: Tuple[int, int]
    capacity: Optional[int] = None
    kept: Any = None


class CascadePrograms:
    """The jitted programs of one early-exit cascade, built once and kept:
    the params (``params``; on MLA stacks one buffer set per layer, of which
    the weak stack takes the first ``exit_layer`` without a copy), the weak
    pass, and per stack the prefill and the decode loop (``cascade.weak_pass``,
    ``cascade.prefill``, ``cascade.decode`` in ``repro.obs.jit_stats``).

    On a stack that takes ragged rows (MLA) a block's prompts are prefilled
    once: the weak pass is the weak stack's prefill and keeps its latent
    cache, last-token logits and hidden states (``prefill_weak``); a weak
    row's answer starts from them, and an offloaded row's runs only the
    strong stack's layers past ``exit_layer`` (``answer``).  Other stacks
    prefill each stack's rows again from their tokens (``generate``).

    Programs are keyed on their shapes.  With ``row_buckets`` and
    ``length_buckets`` every block is padded up to a (rows, length) pair
    of the buckets (pads: token 0, length 0, masked out), so serving
    builds no program that ``warm`` did not; without them the shapes are
    the block's own.  A stack's rows of a kept block are padded to the
    row bucket of their count at the block's length.  ``max_tokens``
    bounds rows x length.

    ``obs`` (a ``repro.obs.Obs``) gets the profiler phases
    ``cascade.weak_pass``, ``cascade.prefill`` and ``cascade.decode`` and,
    with a metrics plane, ``repro_cascade_tokens_total{phase, stack}``
    (real prompt tokens prefilled, counted once under the stack that
    answers; answer tokens decoded), ``repro_cascade_reused_tokens_total
    {stack}`` (real prompt tokens whose first ``exit_layer`` layers came
    from the weak pass), ``repro_cascade_pad_tokens_total`` (pad tokens of
    the prompt passes run: the weak pass's block and each prefill) and the
    gauge ``repro_cascade_expert_share`` (the weak pass's heaviest expert's
    share of its layer's routed pairs, the largest over its MoE layers).
    """

    def __init__(
        self,
        params,
        cfg: LMConfig,
        exit_layer: int,
        *,
        steps: int,
        row_buckets: Optional[Sequence[int]] = None,
        length_buckets: Optional[Sequence[int]] = None,
        max_tokens: Optional[int] = None,
        top_k: int = 8,
        feature_tokens: int = 512,
        greedy: bool = True,
        obs=None,
    ):
        from repro.serving.cascade_serving import truncate_params, truncated_config

        self.steps = int(steps)
        if takes_ragged_rows(cfg):
            # each layer its own buffers: a kernel reads whole weights, not
            # a slice of a stack copied on every call
            params = unstack_layers(params)
        self.params = params
        self.exit_layer = int(exit_layer)
        self.stacks = {
            "weak": (truncate_params(params, cfg, exit_layer), truncated_config(cfg, exit_layer)),
            "strong": (params, cfg),
        }
        self.row_buckets = sorted(row_buckets) if row_buckets else None
        self.length_buckets = sorted(length_buckets) if length_buckets else None
        self.max_tokens = max_tokens
        self.top_k, self.feature_tokens, self.greedy = int(top_k), int(feature_tokens), greedy
        self._prof = obs.profiler if obs is not None else None
        reg = obs.metrics if obs is not None else None
        self._count = None if reg is None else {
            (ph, st): reg.counter("repro_cascade_tokens_total", {"phase": ph, "stack": st},
                                  help="real prompt tokens prefilled, answer tokens decoded")
            for ph in ("prefill", "decode") for st in self.stacks
        }
        self._reused = None if reg is None else {
            st: reg.counter("repro_cascade_reused_tokens_total", {"stack": st},
                            help="real prompt tokens whose first exit_layer layers came "
                                 "from the weak pass")
            for st in self.stacks
        }
        self._pads = None if reg is None else reg.counter(
            "repro_cascade_pad_tokens_total", help="pad tokens of the prompt passes run")
        self._share = None if reg is None else reg.gauge(
            "repro_cascade_expert_share",
            help="heaviest expert's share of routed pairs in the weak pass of the last block")

    # ----------------------------------------------------------- shapes

    def shape(self, rows: int, length: int) -> Tuple[int, int]:
        """The (rows, length) bucket a block of ``rows`` prompts of at
        most ``length`` tokens is padded to: the next length bucket, and
        the next row bucket but never past ``max_tokens`` / length."""
        S = _bucket(length, self.length_buckets)
        R = _bucket(rows, self.row_buckets)
        if self.max_tokens:
            R = min(R, max(self.max_tokens // S, rows))
            if R * S > self.max_tokens:
                raise ValueError(f"{rows} prompts of {S} tokens exceed {self.max_tokens}")
        return R, S

    def shapes(self):
        """Every bucket pair a block can be padded to."""
        cap = self.max_tokens or float("inf")
        return sorted({(min(R, cap // S), S) for S in self.length_buckets or ()
                       for R in self.row_buckets or () if S <= cap})

    def _stack_rows(self, R: int, S: int):
        """The fewest prompts of a block padded to (R, S) that give a
        stack each of its padded row counts."""
        return sorted({self.shape(n, S)[0]: n for n in range(R, 0, -1)}.values())

    def _pad(self, tokens: np.ndarray, lengths: np.ndarray, R: int, S: int, cfg: LMConfig):
        """``tokens`` and ``lengths`` padded to (R, S): pad rows are empty
        on a stack that takes ragged rows, else full (and discarded)."""
        n, s = tokens.shape
        tok = np.zeros((R, S), np.int32)
        tok[:n, :s] = tokens
        lens = np.full((R,), 0 if takes_ragged_rows(cfg) else S, np.int32)
        lens[:n] = lengths
        return tok, lens

    def _weak_args(self, tok: np.ndarray, lens: np.ndarray, capacity: Optional[int] = None):
        """The weak pass's batch and static keywords on a padded block:
        on a stack that takes ragged rows it keeps its cache at
        ``capacity`` slots (the block's length and the answer by default)."""
        R, S = tok.shape
        cfg = self.stacks["weak"][1]
        C = (capacity or S + self.steps) if takes_ragged_rows(cfg) else 0
        return _batch(cfg, tok, lens), dict(cfg=cfg, top_k=self.top_k,
                                            chunk=max(self.feature_tokens // R, 1), capacity=C)

    def _rows_args(self, kept, rows: np.ndarray, lengths: np.ndarray, S: int):
        """The prefill's batch of a stack's ``rows`` of a kept block of
        length ``S``, padded to the row bucket (pad rows: row 0, length 0)."""
        n = len(rows)
        R = self.shape(n, S)[0]
        idx, lens = np.zeros(R, np.int32), np.zeros(R, np.int32)
        idx[:n], lens[:n] = rows, lengths
        return {"kept": kept, "rows": idx, "lengths": lens}, R

    def _phase(self, name: str, rows: int):
        return self._prof.begin(name, None, rows) if self._prof is not None else None

    def _end(self, name: str, t0) -> None:
        if self._prof is not None:
            self._prof.add(name, t0)

    # --------------------------------------------------------- programs

    def prefill_weak(self, tokens: np.ndarray, lengths: np.ndarray,
                     fmask: Optional[np.ndarray] = None,
                     capacity: Optional[int] = None) -> WeakBlock:
        """The weak pass over ``n`` prompts (right-padded ``tokens`` (n, s),
        ``lengths`` (n,)): the features and the expert share read back at
        once (phase ``cascade.weak_pass``), and on a stack that takes ragged
        rows the weak stack's prefill, kept on the device for ``answer``."""
        n, s = tokens.shape
        t0 = self._phase("cascade.weak_pass", n)
        R, S = self.shape(n, s)
        params, wcfg = self.stacks["weak"]
        tok, lens = self._pad(tokens, lengths, R, S, wcfg)
        mask = np.arange(S)[None, :] < lens[:, None]
        if fmask is not None:
            mask[:n, : fmask.shape[1]] &= fmask
        batch, kw = self._weak_args(tok, lens, capacity)
        feats, loads, kept = _WEAK_PASS(params, batch, mask, **kw)
        feats, loads = jax.device_get((feats, loads))
        share = None
        if loads is not None:
            loads = np.asarray(loads, np.float64)
            share = float(np.max(loads.max(-1) / np.maximum(loads.sum(-1), 1.0)))
            if self._share is not None:
                self._share.set(share)
        self._end("cascade.weak_pass", t0)
        if self._pads is not None:
            self._pads.inc(R * S - int(np.sum(lengths)))
        return WeakBlock(np.asarray(feats[:n], np.float32), share, tokens, lengths, (R, S),
                         capacity, kept)

    def weak_pass(self, tokens: np.ndarray, lengths: np.ndarray,
                  fmask: Optional[np.ndarray] = None):
        """Features (n, 4 + top_k) and the expert share of ``n`` prompts
        (``prefill_weak``)."""
        block = self.prefill_weak(tokens, lengths, fmask)
        return block.features, block.share

    def answer(self, stack: str, block: WeakBlock, rows: np.ndarray, key=None):
        """Answers of the block's prompts ``rows`` through one stack:
        the tokens (n, steps) and the device logits (R, steps, V) of every
        answer position, row r for prompt ``rows[r]`` (rows past n are
        pads).  A kept block's prompts are not prefilled again: phase
        ``cascade.prefill`` takes the rows' cache and logits from the weak
        pass and, on the strong stack, runs its layers past ``exit_layer``
        over their hidden states; otherwise ``generate`` prefills them."""
        rows = np.asarray(rows)
        lengths = block.lengths[rows]
        if block.kept is None:
            return self.generate(stack, block.tokens[rows, : int(lengths.max())], lengths,
                                 key=key, capacity=block.capacity)
        S = block.shape[1]
        batch, R = self._rows_args(block.kept, rows, lengths, S)
        real = int(np.sum(lengths))
        deeper = self.exit_layer < self.stacks[stack][1].num_layers
        return self._run(stack, batch, batch["lengths"], len(rows), key, start=self.exit_layer,
                         real=real, pads=R * S - real if deeper else 0)

    def generate(self, stack: str, tokens: np.ndarray, lengths: np.ndarray, key=None,
                 capacity: Optional[int] = None):
        """Answers of ``n`` prompts through one stack, prefilled from their
        tokens (phase ``cascade.prefill``, ended once the first tokens are
        on the host) then the decode loop (``cascade.decode``).  Returns the
        tokens (n, steps) and the device logits (R, steps, V) of every
        answer position (rows past ``n`` are pads)."""
        n, s = tokens.shape
        cfg = self.stacks[stack][1]
        R, S = self.shape(n, s)
        tok, lens = self._pad(tokens, lengths, R, S, cfg)
        pos = lens if takes_ragged_rows(cfg) else np.int32(S)
        real = int(np.sum(lengths))
        return self._run(stack, _batch(cfg, tok, lens), pos, n, key,
                         capacity=capacity or S + self.steps, real=real, pads=R * S - real)

    def _run(self, stack: str, batch: Dict, pos, n: int, key, *,
             capacity: Optional[int] = None, start: int = 0, real: int, pads: int):
        """The prefill (``_prefill`` from layer ``start``) and the decode
        loop of one stack's ``n`` prompts, with their phases and counts:
        ``real`` prompt tokens (reused from the weak pass where ``start``)
        and ``pads``."""
        params, cfg = self.stacks[stack]
        key = key if key is not None else jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        t0 = self._phase("cascade.prefill", n)
        first, logits0, cache = _PREFILL(params, batch, k1, cfg=cfg, capacity=capacity,
                                         greedy=self.greedy, start=start)
        first.block_until_ready()
        self._end("cascade.prefill", t0)
        t0 = self._phase("cascade.decode", n)
        rest, logits = _DECODE(params, cache, first, logits0, pos, k2, cfg=cfg,
                               steps=self.steps - 1, greedy=self.greedy)
        toks = np.concatenate([np.asarray(first)[:, None], np.asarray(rest)], axis=1)
        self._end("cascade.decode", t0)
        if self._count is not None:
            self._count[("prefill", stack)].inc(real)
            self._count[("decode", stack)].inc(n * self.steps)
            self._reused[stack].inc(real if start else 0)
            self._pads.inc(pads)
        return toks[:n], logits

    def warm(self, threads: int = 1) -> int:
        """Run every program on every bucket pair once, so serving builds
        none; returns how many pairs were warmed.  On a kept block each
        stack answers every padded row count its share of the block can
        take.  With ``threads`` > 1 the programs are first compiled side by
        side (into JAX's persistent cache, which should be on), then run one
        by one."""
        def zeros(R, S):
            return np.zeros((R, S), np.int32), np.full((R,), S, np.int32)

        def like(tree):
            return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), tree)

        if threads > 1:
            k1, k2 = jax.random.split(jax.random.PRNGKey(0))

            def compile_one(job):
                (R, S), stack = job
                tok, lens = zeros(R, S)
                wparams = self.stacks["weak"][0]
                wbatch, wkw = self._weak_args(tok, lens)
                if stack == "weak_pass":
                    _WEAK_PASS.lower(wparams, wbatch, tok > -1, **wkw).compile()
                    return
                params, cfg = self.stacks[stack]
                if wkw["capacity"]:
                    kept = like(jax.eval_shape(functools.partial(_weak_pass, **wkw),
                                               wparams, wbatch, tok > -1)[2])
                    batches = [self._rows_args(kept, np.arange(n), lens[:n], S)[0]
                               for n in self._stack_rows(R, S)]
                    calls = [(b, b["lengths"], dict(capacity=None, start=self.exit_layer))
                             for b in batches]
                else:
                    pos = lens if takes_ragged_rows(cfg) else np.int32(S)
                    calls = [(_batch(cfg, tok, lens), pos, dict(capacity=S + self.steps))]
                for batch, pos, kw in calls:
                    kw = dict(kw, cfg=cfg, greedy=self.greedy)
                    _PREFILL.lower(params, batch, k1, **kw).compile()
                    first, logits0, cache = like(
                        jax.eval_shape(functools.partial(_prefill, **kw), params, batch, k1))
                    _DECODE.lower(params, cache, first, logits0, pos, k2, cfg=cfg,
                                  steps=self.steps - 1, greedy=self.greedy).compile()

            from concurrent.futures import ThreadPoolExecutor

            jobs = [(sh, st) for sh in self.shapes() for st in ("weak_pass", "weak", "strong")]
            with ThreadPoolExecutor(threads) as ex:
                list(ex.map(compile_one, jobs))
        for R, S in self.shapes():
            block = self.prefill_weak(*zeros(R, S))
            for stack in self.stacks:
                for n in (self._stack_rows(R, S) if block.kept is not None else [R]):
                    self.answer(stack, block, np.arange(n))
        return len(self.shapes())


def cascade_generate(
    params,
    cfg: LMConfig,
    batch: Dict,
    steps: int,
    *,
    engine=None,
    session=None,
    exit_layer: int,
    micro_batch: int = 8,
    capacity: Optional[int] = None,
    greedy: bool = True,
    key=None,
    programs: Optional[CascadePrograms] = None,
) -> Dict:
    """Session-gated decode: every request decodes through the early-exit
    (weak) stack; rows the ``OffloadSession`` offloads decode at full depth
    instead.  The decision reads only the weak stack's prompt features —
    the same deployability constraint as the detection cascade — computed
    on the device from its last hidden state (``CascadePrograms.prefill_weak``);
    on MLA stacks that pass is also the prefill both stacks' answers
    start from.

    Requests flow through a stream session in arrival (row) order, so
    stateful policies (``token_bucket``) carry across calls when the caller
    passes a long-lived ``session``; passing just ``engine`` opens a
    throwaway session for this batch.  Likewise a long-lived ``programs``
    keeps the weak stack's params and every compiled program across calls;
    without it they are built for this call.  ``batch["lengths"]`` (B,)
    gives ragged, right-padded prompts (MLA stacks); ``batch["labels"]``,
    where given, marks the positions the features read (>= 0).  Returns
    the generated tokens, the decision trace and session telemetry, the
    features, the weak pass's expert share, and per stack the rows it
    served with the device logits of their answer positions
    (``answer_logits``: ``{stack: (rows, (R, steps, V))}``).
    """
    from repro.runtime.session import OffloadSession

    if session is None:
        if engine is None:
            raise ValueError("pass engine= or session=")
        session = OffloadSession(engine, micro_batch=micro_batch)
    if programs is None:
        top_k = getattr(session.engine.feature_extractor, "top_k", 8)
        programs = CascadePrograms(params, cfg, exit_layer, steps=steps, top_k=top_k,
                                   greedy=greedy)
    tokens = np.asarray(batch["tokens"], np.int32)
    B, S = tokens.shape
    lengths = np.asarray(batch.get("lengths", np.full((B,), S)), np.int32)
    labels = batch.get("labels")
    fmask = None if labels is None else np.asarray(labels) >= 0
    block = programs.prefill_weak(tokens, lengths, fmask, capacity)
    decisions = session.submit_batch(features=block.features)
    offload = np.array([d.offload for d in decisions], bool)
    estimates = np.array([d.estimate for d in decisions])

    # decisions are known before decoding (they read only prompt features),
    # so each row decodes through exactly one stack
    out = np.zeros((B, steps), dtype=np.int32)
    answer_logits = {}
    for stack, idx in (("weak", np.where(~offload)[0]), ("strong", np.where(offload)[0])):
        if idx.size:
            toks, logits = programs.answer(stack, block, idx, key=key)
            out[idx] = toks
            answer_logits[stack] = (idx, logits)
    return {
        "tokens": out,
        "offload": offload,
        "estimates": estimates,
        "offload_ratio": float(offload.mean()) if offload.size else 0.0,
        "telemetry": session.telemetry.as_dict(),
        "features": block.features,
        "expert_share": block.share,
        "answer_logits": answer_logits,
    }
