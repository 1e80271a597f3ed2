"""CPU rehearsals of every cell, and of the city cells held out of the
benchmark (``fixtures/city``), the refusal of a machine with no TPU, and
the check's control and faults: each planted in the timed path of a run
at a tiny size must turn ``correct`` false.

Each run is its own process (``drive.py``), so that a four-device cell can
ask XLA for four CPU devices before JAX starts."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))

# Sizes a CPU holds.  The widths are the cells' own.  The calibration
# blocks hold thousands of images, so their most extreme estimates (which
# the check compares) reach the range where the control's bfloat16
# estimates saturate; the city serves enough ticks that its policy windows
# (512 estimates per district) fill and wrap.
TINY = {
    "cam_coco.poisson": ({"POOL_FRAMES": 4096, "CALIBRATION_FRAMES": 8192, "micro_batch": 16},
                         {"rate": 4000, "max_block": 256}),
    "city.ticks": ({"cameras": 256, "POOL_TICKS": 4, "CALIBRATION_TICKS": 32}, {"rate": 30}),
    "city.ticks4": ({"cameras": 64, "POOL_TICKS": 4, "CALIBRATION_TICKS": 2}, {"rate": 4}),
}
SECONDS = {"cam_coco.poisson": 1.0, "city.ticks": 2.0, "city.ticks4": 1.0}
HELD = os.path.join(HERE, "fixtures", "city", "spec.json")


def _env(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cell.endswith("4"):
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return env


def drive(cell, trace=0, fault=""):
    over, mix = TINY[cell]
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive.py"), cell, json.dumps(over),
         json.dumps(mix), str(trace), str(SECONDS[cell])] + ([fault] if fault else []),
        env=_env(cell), capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _spec(path=os.path.join(ROOT, "BENCHMARK.json")):
    with open(path) as f:
        return json.load(f)


def test_every_cell_is_rehearsed():
    cells = {c["name"] for c in _spec()["workloads"]} | {c["name"] for c in _spec(HELD)["workloads"]}
    assert cells == set(TINY)


@pytest.mark.parametrize("cell", sorted(TINY))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_correct(cell, trace):
    res = drive(cell, trace)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    spec = _spec() if cell in {c["name"] for c in _spec()["workloads"]} else _spec(HELD)
    names = {m["name"] for m in spec["per_layer" if trace else "end_to_end"]
             if cell in m.get("workloads", [cell])}
    host = {"serve_ms_per_kframe", "decide_p95_ms", "dispatch_ms_per_kframe", "featurize_ms_per_kframe",
            "fleet_decide_ms_per_kframe", "compiles_in_window"}
    # a CPU trace holds no TPU plane: the device metrics stay silent
    want = names & host if trace else names
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for k, m in res["metrics"].items() if k != "compiles_in_window")


@pytest.mark.parametrize("cell,fault", [
    ("cam_coco.poisson", "estimate"),
    ("cam_coco.poisson", "decision"),
    ("cam_coco.poisson", "control"),
    ("city.ticks", "estimate"),
    ("city.ticks", "decision"),
    ("city.ticks", "control"),
    ("city.ticks4", "exchange"),
])
def test_fault_turns_correct_false(cell, fault):
    res = drive(cell, 0, fault)
    assert res["correct"] is False, res["checks"]


def test_run_refuses_a_machine_without_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload", "cam_coco.poisson",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        env=_env("cam_coco.poisson"), capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_spec_names_only_files_under_paths():
    spec = _spec()
    assert spec["paths"] == ["benchmarks/chip"]
    for c in spec["configs"]:
        assert c["file"].startswith("benchmarks/chip/") and os.path.exists(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(CHIP, "traffic", w["traffic"] + ".json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(CHIP, "metrics", m["name"] + ".py"))
