"""The roofline arithmetic at the cells' shapes, against counts written out
by hand."""
import pytest

from roofline import cost


def test_feature_widths():
    assert cost.feature_dim(80, 100) == 8784
    assert cost.feature_dim(80, 25) == 2259


@pytest.mark.parametrize("rows,k,weight_floats", [
    # cam_coco: one 64-frame chunk, W1 8784 x 128
    (64, 100, 8784 * 128 + 128 + 128 + 1 + 2 * 8784),
    # city_coco: one tick of 1024 cameras, W1 2259 x 128
    (1024, 25, 2259 * 128 + 128 + 128 + 1 + 2 * 2259),
])
def test_call_bytes(rows, k, weight_floats):
    slot = 4 * 4 + 4 + 4 + 1
    want = rows * 100 * slot + 4 * weight_floats + 4 * rows
    assert cost.call_bytes(rows, 100, k, 80, 128) == want


def test_cam_chunk_is_bandwidth_bound_near_5_8_us():
    peak = cost.peaks("TPU v5 lite")
    flops = cost.call_flops(64, 100, 100, 80, 128)
    assert flops == pytest.approx(2 * 64 * 8784 * 128, rel=0.05)
    t = cost.least_seconds(flops, cost.call_bytes(64, 100, 100, 80, 128), peak)
    assert t["bound"] == "bytes"
    assert t["seconds"] == pytest.approx(4728964 / 819e9)
    assert t["flops_s"] == pytest.approx(flops / 197e12)


def test_city_tick_is_bandwidth_bound():
    peak = cost.peaks("TPU v5 lite")
    nbytes = cost.call_bytes(1024, 100, 25, 80, 128)
    assert nbytes == 1024 * 2500 + 4 * (2259 * 128 + 257 + 2 * 2259) + 4096
    t = cost.least_seconds(cost.call_flops(1024, 100, 25, 80, 128), nbytes, peak)
    assert t["bound"] == "bytes"
    assert 3.0e-6 < t["flops_s"] < t["bytes_s"]


def test_stage_flops_cover_every_stage():
    st = cost.stage_flops(64, 100, 100, 80, 128)
    assert set(st) == {"topk", "features", "standardize", "mlp"}
    assert st["standardize"] == 2 * 64 * 8784
    assert all(v > 0 for v in st.values())


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        cost.peaks("TPU v9 imaginary")
