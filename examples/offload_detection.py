"""The paper's full evaluation (Figs. 5/6/9/10, Table II) from the cached
pipeline — runs the complete experiment suite, prints a summary, then fits
the deployable ``OffloadEngine`` artifact and round-trips it through
save/load.

Run:  python examples/offload_detection.py [--quick] [--force]
      (after `pip install -e .`, or prefix with PYTHONPATH=src)
"""
import argparse
import os

from repro.api import OffloadEngine
from repro.experiments.detection_repro import build_engine, build_pipeline, run_all

ARTIFACTS = os.path.join(os.path.dirname(__file__), "../artifacts")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    results = run_all(force=args.force, quick=args.quick)

    print("\n===== summary =====")
    print(f"weak mAP {results['weak_map']:.4f}   strong mAP {results['strong_map']:.4f}")
    print("\nFig. 5 (oracle mAP vs |E|, r=0.2):")
    f5 = results["figure5"]
    for e, m in zip(f5["context_sizes"], f5["curves"]["r=0.2"]["mean"]):
        print(f"  |E|={e:4d}: {m:.4f}")
    print("\nTable II (share of images, mAP weak / strong, by reward sign):")
    for k, v in results["table2"].items():
        print(f"  {k:12s} {v['pct']:5.1f}%  {v['weak_map']:.4f} / {v['strong_map']:.4f}")
    print("\nFig. 6 (TIDE delta-mAP per error type):")
    for policy, e in results["figure6"].items():
        cats = ("cls", "loc", "cls_loc", "dupe", "bkg", "miss")
        print(f"  {policy:6s} " + " ".join(f"{c}={e[c]:.4f}" for c in cats))
    print("\nFig. 10 (normalized mAP, % of weak->strong gap closed):")
    for name, cur in results["figure9_10"]["curves"].items():
        pts = ", ".join(f"{v:.0f}" for v in cur["norm"][:6])
        print(f"  {name:18s} [{pts}]  @ratios {results['figure9_10']['ratios'][:6]}")

    # ---- deployable artifact: fit, save, reload, verify ------------------
    print("\n===== OffloadEngine artifact =====")
    state = build_pipeline()  # cached by run_all above
    ctx = 400 if args.quick else 800
    engine = build_engine(
        state, context_size=ctx, ratio=0.2, epochs=10 if args.quick else 40
    )
    path = os.path.join(ARTIFACTS, "offload_engine")
    engine.save(path)
    reloaded = OffloadEngine.load(path)
    probe = state.weak_dets_val[:64]
    d1, d2 = engine.decide(probe), reloaded.decide(probe)
    assert (d1.offload == d2.offload).all(), "save/load round trip diverged"
    print(f"saved {path}.npz  (fused Pallas scoring: {engine.reward_model.fused})")
    print(f"decisions on 64 probe images: ratio={d1.ratio:.2f}, round trip exact")
    reloaded.set_ratio(0.5)
    print(f"runtime re-budget to 0.5: ratio={reloaded.decide(probe).ratio:.2f}")


if __name__ == "__main__":
    main()
