"""The trace reduction on a trace recorded on a TPU v5e (the first traced
run of ``city_coco.ticks``, trimmed to 400 ms by ``fixtures/trim.py``),
and its interval arithmetic on hand-made events."""
import os

import pytest

from core import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "city_coco.ticks.xplane.pb")


def test_fixture_planes_and_names():
    t = trace.load(FIXTURE)
    assert sorted(t["devices"]) == [0]
    chip = t["devices"][0]
    assert len(chip["ops"]) == 228 and len(chip["modules"]) == 21
    names = {trace.short_name(n) for n, _, _ in chip["modules"]}
    assert names == {"jit_box_feature_stack", "jit__estimator_mlp_pallas",
                     "jit_convert_element_type", "jit_dynamic_slice"}
    assert {n for n, _, _ in t["spans"]} == {"bench.generate", "bench.featurize",
                                             "bench.fleet_step"}


def test_fixture_reduction():
    r = trace.reduce(FIXTURE, n_chips=1)
    assert r["busy_s"] == pytest.approx(0.003070336, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.387546825, rel=1e-9)
    assert r["modules"]["jit_box_feature_stack"] == pytest.approx(0.00300004, rel=1e-9)
    assert r["modules"]["jit__estimator_mlp_pallas"] == pytest.approx(7.0946e-05, rel=1e-9)
    ops = r["breakdown"]["device_ops"]
    assert ops[0] == ["%fusion pred[25600]", pytest.approx(0.00080244, rel=1e-9)]
    assert len(ops) == 10
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert set(gaps) == {"bench.fleet_step", "bench.featurize"}
    assert gaps["bench.fleet_step"] == pytest.approx(0.27279296, rel=1e-6)
    # every op's time lies inside the busy union, which lies inside the window
    assert r["busy_s"] <= sum(v for _, v in ops) + 1e-3 and r["busy_s"] < r["window_s"]


def test_window_given_by_the_host_clock_wins():
    assert trace.reduce(FIXTURE, n_chips=1, window_s=0.5)["window_s"] == 0.5


def test_union_and_short_names():
    assert trace._union([(0, 2), (1, 3), (5, 6), (6, 7), (10, 11)]) == [(0, 3), (5, 7), (10, 11)]
    assert trace.short_name("%slice-done.4 = f32[8700,128]{1,0:T(8,128)S(1)} async-done(x)") \
        == "%slice-done.4 f32[8700,128]"
    assert trace.short_name("jit__score_pipeline_pallas(1234567)") == "jit__score_pipeline_pallas"


def test_a_trace_without_tpu_planes_reduces_to_nothing(tmp_path):
    from tests_helpers import empty_xspace
    p = tmp_path / "empty.xplane.pb"
    p.write_bytes(empty_xspace())
    assert trace.reduce(str(p), n_chips=1) is None
