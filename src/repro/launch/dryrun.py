"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combo.

MUST be run as a module entry point (``python -m repro.launch.dryrun``) —
the first two lines below force 512 host platform devices BEFORE any jax
import so ``jax.make_mesh`` can build the production meshes.  Do not import
this module from tests (they need the real 1-device view).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS  # noqa: E402
from repro.launch.input_specs import SHAPES, input_specs  # noqa: E402
from repro.launch.mesh import logical_axes, make_production_mesh  # noqa: E402
from repro.launch.meshctx import bind_mesh  # noqa: E402
from repro.launch.sharding import (  # noqa: E402
    batch_shardings,
    cache_shardings,
    param_shardings,
    replicated,
)
from repro.launch.steps import (  # noqa: E402
    abstract_opt_state,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models.lm import abstract_params, init_cache  # noqa: E402

ARTIFACTS = os.path.join(os.path.dirname(__file__), "../../../artifacts/dryrun")

# Per-chip roofline terms of the dry-run estimate, TPU v5e (Google Cloud
# documentation, "TPU v5e"); a planning figure, never a measurement.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_COLL_LINE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]\S*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-collective-type bytes (per-device result shapes) from HLO text."""
    out = {c: 0.0 for c in COLLECTIVES}
    count = {c: 0 for c in COLLECTIVES}
    for m in _COLL_LINE.finditer(hlo_text):
        result_ty, op = m.group(1), m.group(2)
        b = 0.0
        for dt, dims in _SHAPE.findall(result_ty):
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            b += n * _DTYPE_BYTES.get(dt, 4)
        out[op] += b
        count[op] += 1
    out_all = dict(out)
    out_all["total"] = sum(out.values())
    out_all["counts"] = count
    return out_all


def _lower(arch: str, shape: str, multi_pod: bool, overrides: Optional[Dict] = None):
    overrides = overrides or {}
    cfg, specs = input_specs(arch, shape)
    cfg_over = {k: v for k, v in overrides.items()
                if k not in ("param_mode", "cache_mode")}
    if cfg_over:
        import dataclasses

        cfg = dataclasses.replace(cfg, **cfg_over)
        _, specs = input_specs(arch, shape)  # re-derive shapes if needed
        if specs["kind"] == "decode":
            cap = min(SHAPES[shape]["seq_len"], cfg.window) if cfg.window > 0 else SHAPES[shape]["seq_len"]
            specs["cache"] = init_cache(cfg, SHAPES[shape]["batch"], cap, abstract=True)
    param_mode = overrides.get("param_mode", "tp")
    cache_mode = overrides.get("cache_mode", "seq")
    mesh = make_production_mesh(multi_pod=multi_pod)
    mapping = logical_axes(multi_pod=multi_pod)
    params_abs = abstract_params(cfg)
    with bind_mesh(mesh, mapping):
        p_sh = param_shardings(params_abs, mesh, mapping, mode=param_mode)
        if specs["kind"] == "train":
            opt_abs = abstract_opt_state(params_abs)
            opt_sh = param_shardings(opt_abs, mesh, mapping, mode=param_mode)
            b_sh = batch_shardings(specs["batch"], mesh, mapping)
            step = make_train_step(cfg)
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(p_sh, opt_sh, b_sh),
                    out_shardings=(p_sh, opt_sh, replicated(mesh)),
                    donate_argnums=(0, 1),
                ).lower(params_abs, opt_abs, specs["batch"])
        elif specs["kind"] == "prefill":
            b_sh = batch_shardings(specs["batch"], mesh, mapping)
            seq = SHAPES[shape]["seq_len"]
            step = make_prefill_step(cfg, capacity=seq)
            B = SHAPES[shape]["batch"]
            cache_abs = init_cache(cfg, B, seq, abstract=True)
            c_sh = cache_shardings(cache_abs, mesh, mapping, mode=cache_mode)
            vocab_ax = mapping["model"] if cfg.vocab_size % 16 == 0 else None
            logits_sh = NamedSharding(mesh, P(mapping["batch"], vocab_ax))
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(p_sh, b_sh),
                    out_shardings=(logits_sh, c_sh),
                ).lower(params_abs, specs["batch"])
        else:  # decode
            c_sh = cache_shardings(specs["cache"], mesh, mapping, mode=cache_mode)
            B = specs["tokens"].shape[0]
            tok_sh = NamedSharding(
                mesh, P(mapping["batch"] if B % 16 == 0 else None)
            )
            vocab_ax = mapping["model"] if cfg.vocab_size % 16 == 0 else None
            logits_sh = NamedSharding(
                mesh,
                P(mapping["batch"] if B % 16 == 0 else None, vocab_ax),
            )
            step = make_serve_step(cfg)
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(p_sh, c_sh, tok_sh, replicated(mesh)),
                    out_shardings=(logits_sh, c_sh),
                    donate_argnums=(1,),
                ).lower(params_abs, specs["cache"], specs["tokens"], specs["pos"])
    return cfg, lowered, mesh


def model_flops(cfg, shape_name: str) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE), D = tokens."""
    meta = SHAPES[shape_name]
    D = meta["batch"] * (meta["seq_len"] if meta["kind"] != "decode" else 1)
    # active params per token
    M, L = cfg.d_model, cfg.num_layers
    emb = 2 * cfg.vocab_size * M  # embed+unembed
    if cfg.arch_type == "moe":
        if cfg.use_mla:
            attn = M * cfg.num_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim) + \
                M * (cfg.kv_lora_rank + cfg.qk_rope_dim) + \
                cfg.kv_lora_rank * cfg.num_heads * (cfg.qk_nope_dim + cfg.head_dim) + \
                cfg.num_heads * cfg.head_dim * M
        else:
            attn = 2 * M * cfg.num_heads * cfg.head_dim + 2 * M * cfg.num_kv_heads * cfg.head_dim
        ff_act = 3 * M * cfg.d_ff_expert * (cfg.top_k + cfg.num_shared_experts)
        dense_ff = 3 * M * cfg.d_ff
        n_active = (L - cfg.first_k_dense) * (attn + ff_act) + cfg.first_k_dense * (attn + dense_ff) + emb
    elif cfg.arch_type == "rwkv":
        per = 5 * M * M + M * M + 2 * M * cfg.d_ff  # time-mix + channel-mix
        n_active = L * per + emb
    elif cfg.arch_type == "hybrid":
        mc = cfg.mamba()
        per_m = M * (2 * mc.d_inner + 2 * mc.d_state + mc.num_heads) + mc.d_inner * M
        shared = 4 * M * cfg.num_heads * cfg.head_dim + 3 * M * cfg.d_ff
        n_active = cfg.num_mamba_layers * per_m + cfg.num_shared_attn * shared + emb
    elif cfg.arch_type == "encdec":
        per_dec = 8 * M * cfg.num_heads * cfg.head_dim + 2 * M * cfg.d_ff
        per_enc = 4 * M * cfg.num_heads * cfg.head_dim + 2 * M * cfg.d_ff
        n_active = L * per_dec + cfg.encoder_layers * per_enc + emb
    else:  # dense / vlm
        attn = 2 * M * cfg.num_heads * cfg.head_dim + 2 * M * cfg.num_kv_heads * cfg.head_dim
        n_active = L * (attn + 3 * M * cfg.d_ff) + emb
    mult = 6 if meta["kind"] == "train" else 2
    return float(mult) * n_active * D


def _probe_depths(cfg) -> tuple:
    """Two reduced depths preserving per-layer structure for linear
    extrapolation of cost in depth (see cost_probe)."""
    if cfg.arch_type == "hybrid":
        p = cfg.shared_attn_period
        return p, 2 * p  # 1 group, 2 groups
    if cfg.arch_type == "moe" and cfg.first_k_dense:
        return cfg.first_k_dense + 1, cfg.first_k_dense + 2
    return 2, 4


def _probe_cfg(cfg, L: int):
    import dataclasses

    kw = dict(num_layers=L, layer_unroll=-1, attn_chunk=0)
    if cfg.arch_type == "encdec":
        kw["encoder_layers"] = L  # enc+dec scale together; full depths equal
    return dataclasses.replace(cfg, **kw)


def cost_probe(arch: str, shape: str, multi_pod: bool = False,
               overrides: Optional[Dict] = None) -> Dict[str, float]:
    """Depth-corrected HLO cost: XLA's cost_analysis counts a while-loop
    body ONCE regardless of trip count, so the plain dry-run undercounts
    everything inside the layer scan by ~num_layers.  We lower the same
    config at two reduced depths with the layer scan FULLY UNROLLED and
    attention unchunked (lax.map has the same once-counting problem), then
    extrapolate linearly in depth:

        cost(L) = outside + L · per_layer
        per_layer = (c_b - c_a) / (L_b - L_a)

    Exact for every term linear in depth (flops, bytes, grad all-reduces,
    MoE all-to-alls).  Residual undercount: the time-recurrence inner scans
    of RWKV/Mamba (elementwise outer products; added analytically in
    `recurrence_flops`).
    """
    cfg0, _ = input_specs(arch, shape)
    La, Lb = _probe_depths(cfg0)
    Lfull = cfg0.num_layers
    costs = []
    for L in (La, Lb):
        import repro.launch.input_specs as ispec

        orig = ispec.resolve_config
        try:
            ispec.resolve_config = lambda a, s: _probe_cfg(orig(a, s), L)  # noqa: B023
            _, lowered, mesh = _lower(arch, shape, multi_pod, overrides)
        finally:
            ispec.resolve_config = orig
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        coll = collective_bytes(compiled.as_text())
        costs.append(
            {
                "flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "coll": coll["total"],
            }
        )
    out = {}
    for k in ("flops", "bytes", "coll"):
        per_layer = (costs[1][k] - costs[0][k]) / (Lb - La)
        out[k] = max(costs[0][k] + (Lfull - La) * per_layer, 0.0)
    out["flops"] += recurrence_flops(cfg0, shape, multi_pod)
    return out


def recurrence_flops(cfg, shape: str, multi_pod: bool) -> float:
    """Analytic per-device flops of time-recurrence scan bodies (counted
    once by cost_analysis even in the probes)."""
    meta = SHAPES[shape]
    n_batch_shards = (32 if multi_pod else 16) if meta["batch"] % 16 == 0 else 1
    B = meta["batch"] / n_batch_shards
    S = meta["seq_len"] if meta["kind"] != "decode" else 1
    if cfg.arch_type == "rwkv":
        # per step/head: 3 outer-product-scale ops on (K,V) + readout
        return 8.0 * B * S * cfg.num_layers * cfg.d_model * cfg.rwkv_head_size
    if cfg.arch_type == "hybrid":
        mc = cfg.mamba()
        return 8.0 * B * S * cfg.num_mamba_layers * mc.d_inner * mc.d_state
    return 0.0


def dryrun_one(arch: str, shape: str, multi_pod: bool, save: bool = True,
               overrides: Optional[Dict] = None, tag_suffix: str = "") -> Dict:
    tag = f"{arch}_{shape}_{'multipod' if multi_pod else 'singlepod'}{tag_suffix}"
    t0 = time.time()
    cfg, lowered, mesh = _lower(arch, shape, multi_pod, overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    n_dev = mesh.devices.size
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    # depth-corrected costs (see cost_probe docstring); single-pod only to
    # bound sweep time — multi-pod reuses the structure proof, not the table
    corrected = None
    if not multi_pod:
        try:
            corrected = cost_probe(arch, shape, multi_pod, overrides)
        except Exception as e:  # noqa: BLE001
            print(f"[dryrun] cost_probe failed for {tag}: {e}")
    if corrected is not None:
        flops_dev, bytes_dev = corrected["flops"], corrected["bytes"]
        coll_total = corrected["coll"]
    else:
        coll_total = coll["total"]
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "per_device": {
            "hlo_flops": flops_dev,
            "hlo_bytes": bytes_dev,
            "collective_bytes": coll_total,
            "raw_uncorrected": {
                "hlo_flops": float(cost.get("flops", 0.0)),
                "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
                "collective_bytes": coll["total"],
            },
            "depth_corrected": corrected is not None,
            "collectives": {k: v for k, v in coll.items() if k != "counts"},
            "collective_counts": coll["counts"],
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "roofline": {
            "compute_s": flops_dev / PEAK_FLOPS_BF16,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_total / ICI_BW,
        },
        "model_flops_total": model_flops(cfg, shape),
    }
    r = result["roofline"]
    result["roofline"]["dominant"] = max(r, key=lambda k: r[k])
    result["model_flops_ratio"] = (
        result["model_flops_total"] / (flops_dev * n_dev) if flops_dev else None
    )
    if save:
        os.makedirs(ARTIFACTS, exist_ok=True)
        with open(os.path.join(ARTIFACTS, tag + ".json"), "w") as f:
            json.dump(result, f, indent=2)
    print(
        f"[dryrun] {tag}: compile {t_compile:.1f}s  "
        f"flops/dev {flops_dev:.3e}  bytes/dev {bytes_dev:.3e}  "
        f"coll/dev {coll['total']:.3e}  dominant={result['roofline']['dominant']}"
    )
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="input shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--override", default=None,
                    help='JSON dict of perf overrides, e.g. '
                         '\'{"param_mode": "fsdp", "capacity_factor": 1.0}\'')
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    args = ap.parse_args()
    overrides = json.loads(args.override) if args.override else None
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multipod' if mp else 'singlepod'}{args.tag}"
                path = os.path.join(ARTIFACTS, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] skip {tag} (exists)")
                    continue
                try:
                    dryrun_one(arch, shape, mp, overrides=overrides,
                               tag_suffix=args.tag)
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] FAIL {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("[dryrun] all combinations lowered and compiled OK")


if __name__ == "__main__":
    main()
