"""``chip_smoke.py``'s phases on the CPU at tiny sizes.

The script itself refuses any backend but a TPU; here each phase function
runs with the kernel paths this host resolves to, and the fused score
kernel is steered onto the Pallas interpreter so its phase still runs the
kernel code.  The four-chip phase runs in a subprocess on four forced CPU
devices, as the fleet-plane property in ``test_sharding.py`` does.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.kernels.score_pipeline import ops as score_ops  # noqa: E402

CPU = cs.Paths(pipeline="lax", kernel="reference", tracker_interpret=True, mosaic=False)


@pytest.fixture(scope="module")
def engine():
    eng, out = cs.phase_fit(0, n_images=512, epochs=10, n_eval=256)
    assert out["check"].startswith("corr(estimate,reward)=")
    return eng


def test_main_refuses_cpu_backend(capsys):
    assert cs.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line
    assert "'cpu'" in captured.err


def test_decide_phase_runs_fused_kernel_interpreted(engine, monkeypatch):
    auto = score_ops.resolve_pipeline_path
    monkeypatch.setattr(
        score_ops, "resolve_pipeline_path",
        lambda path=None: "pallas_interpret" if path is None else auto(path),
    )
    paths = cs.Paths(
        pipeline="pallas_interpret", kernel="reference",
        tracker_interpret=True, mosaic=False,
    )
    out = cs.phase_decide(engine, 0, paths, n_images=200)
    assert "path=pallas_interpret" in out["check"]


def test_decide_phase_refuses_unexpected_path(engine):
    tpu = cs.Paths.tpu()
    with pytest.raises(cs.CheckFailed, match="kernel paths"):
        cs.phase_decide(engine, 0, tpu, n_images=8)


def test_simulate_phase(engine):
    out = cs.phase_simulate(engine, 0, CPU, n_frames=256)
    assert "outcomes=" in out["check"]


def test_session_phase_routes_agree_exactly(engine):
    out = cs.phase_session(engine, 0, CPU, n_images=96, micro_batch=32)
    assert "max|fast-buffered|=0.000e+00" in out["check"]
    assert "decisions_differing=0" in out["check"]


def test_packed_phase_bit_identical_interpreted(monkeypatch):
    auto = score_ops.resolve_pipeline_path
    monkeypatch.setattr(
        score_ops, "resolve_pipeline_path",
        lambda path=None: "pallas_interpret" if path is None else auto(path),
    )
    paths = cs.Paths(
        pipeline="pallas_interpret", kernel="reference",
        tracker_interpret=True, mosaic=False,
    )
    out = cs.phase_packed(0, paths, rows=(1, 5), per_size=2)
    assert "paths=pallas_interpret,lax" in out["check"]
    assert "max|packed-arrays|=0.000e+00" in out["check"]
    assert "bit_identical_blocks=8/8 packed_calls=8" in out["check"]


def test_tracker_phase():
    out = cs.phase_tracker(0, CPU, n_streams=2, n_frames=8)
    assert out["check"].startswith("association==track_clip_ref")


def test_fleet_phase():
    out = cs.phase_fleet(0, CPU, n_streams=256, n_ticks=32, calibration_frames=2048)
    assert "eff_acc coordinated=" in out["check"]


_FOUR_CHIPS = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, sys.argv[1])
    import chip_smoke as cs

    engine, _ = cs.phase_fit(0, n_images=512, epochs=5, n_eval=256)
    out = cs.phase_four_chips(engine, 0, n_images=250, n_streams=64, n_ticks=8)
    print(out["check"])
    """
)


def test_four_chips_phase_on_forced_cpu_devices():
    """On XLA:CPU the sharded scoring, feature and matching paths are
    bit-identical to one device.  The city run's MLP head at shard-local
    row counts may round differently in the last place from the whole
    tick's rows, which must not move a decision."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIPS, ROOT],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    line = proc.stdout.strip().splitlines()[-1]
    assert "fleet_decisions_differing=0" in line
    assert '"score": true' in line and '"score_detections": true' in line
    assert '"match": true' in line
