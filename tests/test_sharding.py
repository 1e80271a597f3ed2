"""Sharding rules + mesh context (1-device CPU view; the 512-device mesh
is not built in these tests).  The fleet-plane bit-identity
property runs in a subprocess with a forced multi-device host view — the
in-process suite deliberately keeps the real 1-device view."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch.meshctx import bind_mesh, constrain
from repro.launch.mesh import FLEET_AXIS, make_fleet_mesh, make_production_mesh
from repro.launch.sharding import (
    CACHE_RULES,
    batch_shardings,
    cache_shardings,
    param_shardings,
)
from repro.models.lm import abstract_params, init_cache, reduced


@pytest.fixture
def mesh1():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


MAPPING = {"batch": "data", "model": "model", "expert": "model"}


def test_constrain_is_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)
    assert y is x


def test_constrain_applies_under_mesh(mesh1):
    x = jnp.ones((4, 4))
    with bind_mesh(mesh1, MAPPING):
        y = jax.jit(lambda a: constrain(a, "batch", None))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


@pytest.mark.parametrize("arch", ["yi_6b", "deepseek_moe_16b", "rwkv6_1b6", "zamba2_2b7", "whisper_base"])
def test_param_rules_cover_big_leaves(arch, mesh1):
    """Every >1M-element parameter must hit a sharding rule (not end up
    replicated by fallthrough) — guards against rule-table rot."""
    cfg = get_config(arch)
    params = abstract_params(cfg)
    sh = param_shardings(params, mesh1, MAPPING)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(sh)
    uncovered = []
    for (path, leaf), s in zip(flat_p, flat_s):
        n = int(np.prod(leaf.shape))
        if n >= 1_000_000 and s.spec == P():
            uncovered.append("/".join(str(p) for p in path))
    assert not uncovered, uncovered


def test_divisibility_fallback(mesh1):
    """A dim not divisible by the mesh axis must replicate, not fail."""
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    # pretend a model axis of size 16 via resolver arithmetic on mesh1 (size
    # 1 divides everything), so instead test _resolve directly:
    from repro.launch.sharding import _resolve

    class FakeMesh:
        shape = {"data": 1, "model": 16}

    spec = _resolve((None, "model"), MAPPING, (10, 24), FakeMesh())
    assert spec == P(None, None)  # 24 % 16 != 0 -> replicated
    spec2 = _resolve((None, "model"), MAPPING, (10, 32), FakeMesh())
    assert spec2 == P(None, "model")


@pytest.mark.parametrize("arch", ["yi_6b", "deepseek_v2_lite_16b", "rwkv6_1b6", "zamba2_2b7", "whisper_base"])
def test_cache_rules_cover_all_fields(arch, mesh1):
    cfg = reduced(get_config(arch))
    cache = init_cache(cfg, batch=2, capacity=16, abstract=True)
    for k in cache:
        assert k in CACHE_RULES, k
    sh = cache_shardings(cache, mesh1, MAPPING)
    assert set(sh) == set(cache)


def test_batch_shardings_positions_3d(mesh1):
    import jax as _jax

    specs = {
        "tokens": _jax.ShapeDtypeStruct((8, 16), jnp.int32),
        "positions_3d": _jax.ShapeDtypeStruct((3, 8, 16), jnp.int32),
    }
    sh = batch_shardings(specs, mesh1, MAPPING)
    assert sh["tokens"].spec[0] == "data"
    assert sh["positions_3d"].spec == P(None, "data", None)


# ------------------------------------------------------------ fleet meshes


def test_make_fleet_mesh_defaults_and_clamps():
    """On this 1-device view: None takes the device, oversubscription
    clamps instead of failing (the CI degrade path)."""
    mesh = make_fleet_mesh()
    assert mesh.axis_names == (FLEET_AXIS,)
    assert mesh.shape[FLEET_AXIS] == len(jax.devices())
    clamped = make_fleet_mesh(64)
    assert clamped.shape[FLEET_AXIS] == min(64, len(jax.devices()))
    with pytest.raises(ValueError):
        make_fleet_mesh(0)


def test_make_production_mesh_degrades_to_available_devices():
    """Asking for the 256/512-chip mesh on a small host must fold the
    available devices into the data axis, keeping every axis name."""
    mesh = make_production_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == len(jax.devices())
    assert mesh.shape["model"] == 1
    multi = make_production_mesh(multi_pod=True)
    assert multi.axis_names == ("pod", "data", "model")
    assert multi.shape["pod"] == 1 and multi.shape["model"] == 1


def test_fleet_plane_single_device_falls_through():
    """On a 1-shard mesh the plane must return the exact single-device
    results (it routes straight to the unsharded implementations)."""
    from repro.core.features import extract_features_batch
    from repro.detection.batch import (
        DetectionsBatch,
        GroundTruthBatch,
        match_batch,
    )
    from repro.detection.map_engine import Detections, GroundTruth
    from repro.fleet import FleetPlane

    plane = FleetPlane(make_fleet_mesh(1))
    assert plane.n_devices == 1
    r = np.random.default_rng(0)
    dets = [
        Detections(
            np.abs(r.normal(10, 3, (4, 4))), r.uniform(0.1, 1, 4),
            r.integers(0, 5, 4),
        )
        for _ in range(9)
    ]
    gts = [
        GroundTruth(np.abs(r.normal(10, 3, (3, 4))), r.integers(0, 5, 3))
        for _ in range(9)
    ]
    db, gb = DetectionsBatch.from_list(dets), GroundTruthBatch.from_list(gts)
    ref = match_batch(db, gb)
    out = plane.match(db, gb)
    np.testing.assert_array_equal(ref.tp, out.tp)
    np.testing.assert_array_equal(ref.match_gt, out.match_gt)
    np.testing.assert_array_equal(
        extract_features_batch(db, 5, 10, 64.0),
        plane.extract_features(db, 5, 10, 64.0),
    )


def test_fleet_plane_rejects_multi_axis_mesh(mesh1):
    from repro.fleet import FleetPlane

    with pytest.raises(ValueError):
        FleetPlane(mesh1)  # ("data", "model") is not a 1-D serving mesh


_FLEET_PLANE_PROPERTY = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax

    assert len(jax.devices()) == 4, jax.devices()

    from repro.api import MLPRewardModel, OffloadEngine
    from repro.core import EstimatorConfig
    from repro.core.features import extract_features_batch
    from repro.detection.batch import (
        DetectionsBatch, GroundTruthBatch, match_batch,
    )
    from repro.detection.map_engine import Detections, GroundTruth
    from repro.fleet import FleetPlane
    from repro.launch.mesh import make_fleet_mesh

    def synth(n_images, seed, num_classes=8, size=64.0):
        r = np.random.default_rng(seed)
        dets, gts = [], []
        for _ in range(n_images):
            m = int(r.integers(1, 6))
            b = r.uniform(0, size - 25, (m, 2))
            wh = r.uniform(5, 20, (m, 2))
            gts.append(GroundTruth(
                np.concatenate([b, b + wh], 1), r.integers(0, num_classes, m)
            ))
            k = int(r.integers(0, 12))
            b = r.uniform(0, size - 25, (k, 2))
            wh = r.uniform(5, 20, (k, 2))
            dets.append(Detections(
                np.concatenate([b, b + wh], 1), r.uniform(0.1, 1.0, k),
                r.integers(0, num_classes, k),
            ))
        return dets, gts

    mesh = make_fleet_mesh()
    assert mesh.shape["shard"] == 4
    plane = FleetPlane(mesh)

    # B=13: global grid_b == 1; B=150: grid_b >= 2 — the two XLA:CPU
    # compilation regimes of the batched IoU kernel.  Both are ragged
    # against the 4-way shard split (13 = 4+4+4+1, 150 = 38*3+36).
    for B in (13, 150):
        dets, gts = synth(B, seed=B)
        db = DetectionsBatch.from_list(dets)
        gb = GroundTruthBatch.from_list(gts)
        ref = match_batch(db, gb, (0.5, 0.75))
        out = plane.match(db, gb, (0.5, 0.75))
        assert np.array_equal(ref.tp, out.tp), B
        assert np.array_equal(ref.match_gt, out.match_gt), B
        fref = extract_features_batch(db, 8, 25, 64.0)
        fout = plane.extract_features(db, 8, 25, 64.0)
        assert np.array_equal(fref, fout), B

    # fused estimator scoring, ragged (B=250 over 4 shards) and aligned
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (256, 32)).astype(np.float32)
    eng = OffloadEngine(reward_model=MLPRewardModel(
        config=EstimatorConfig(hidden=(16,), epochs=2, batch_size=64)
    ))
    eng.fit(features=x, rewards=rng.normal(0, 1, 256))
    assert eng.reward_model.fused
    for B in (7, 250, 256):
        ref = np.asarray(eng.score(features=x[:B]))
        out = plane.score(eng, x[:B])
        assert np.array_equal(ref, out), B

    # fused boxes→estimates pipeline, sharded: score_detections must be
    # bit-identical to the single-device fused dispatch AND the composed
    # features→score route, over ragged shard splits
    from repro.api import DetectionBoxFeatures

    dets_cal, _ = synth(120, seed=3)
    dcal = DetectionsBatch.from_list(dets_cal)
    fx = DetectionBoxFeatures(num_classes=8, top_k=25, image_size=64.0)
    eng2 = OffloadEngine(
        feature_extractor=fx,
        reward_model=MLPRewardModel(
            config=EstimatorConfig(hidden=(16,), epochs=2, batch_size=64)
        ),
    )
    eng2.fit(
        features=extract_features_batch(dcal, 8, 25, 64.0),
        rewards=np.random.default_rng(1).uniform(0, 1, 120),
    )
    assert eng2.reward_model.fused
    # 7/13 land in small shard blocks, 150/250 in the large-block gemm
    # regime (250 is also ragged: 63*3+61) — the gather-before-head split
    # must hold bit-identity in all of them
    for B in (7, 13, 150, 250):
        dets, _ = synth(B, seed=100 + B)
        db = DetectionsBatch.from_list(dets)
        ref = np.asarray(eng2.score_device(db))
        assert np.array_equal(ref, eng2.score(db)), B
        out = plane.score_detections(eng2, db)
        assert np.array_equal(ref, out), B

    print("FLEET-PLANE-BITIDENT-OK")
    """
)


def test_fleet_plane_sharded_is_bit_identical_multi_device():
    """The PR's core property: scoring, matching, and feature extraction
    through the 4-device sharded plane are bit-for-bit equal to the
    single-device results, including ragged last-shard padding, in both
    IoU-grid compilation regimes.  Runs in a subprocess because the forced
    multi-device host view must be set before jax initializes."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the script sets its own
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    proc = subprocess.run(
        [sys.executable, "-c", _FLEET_PLANE_PROPERTY],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "FLEET-PLANE-BITIDENT-OK" in proc.stdout
