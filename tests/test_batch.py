"""Batched detection data plane: padded containers, the device matcher vs
per-image ``match_detections``, batched features, and consumers."""
import numpy as np
import pytest

from repro.core.features import extract_features, extract_features_batch
from repro.core.reward import (
    RewardOracle,
    match_pairs,
    match_pairs_batched,
    ori,
    ori_batch,
)
from repro.detection.batch import (
    DetectionsBatch,
    GroundTruthBatch,
    match_batch,
    to_image_evals,
)
from repro.detection.map_engine import (
    Detections,
    GroundTruth,
    match_detections,
)

THRESHOLDS = (0.5, 0.75)


def empty_dets() -> Detections:
    return Detections(np.zeros((0, 4)), np.zeros(0), np.zeros(0, int))


def empty_gt() -> GroundTruth:
    return GroundTruth(np.zeros((0, 4)), np.zeros(0, int))


# ------------------------------------------------------------- containers

def test_batch_round_trip(noisy_pair):
    gts, weak, _ = noisy_pair
    db = DetectionsBatch.from_list(weak)
    gb = GroundTruthBatch.from_list(gts)
    assert len(db) == len(weak) and len(gb) == len(gts)
    assert db.boxes.dtype == np.float32 and db.classes.dtype == np.int32
    assert np.array_equal(db.counts, [len(d) for d in weak])
    for i in (0, len(weak) - 1):
        d = db[i]
        np.testing.assert_allclose(d.boxes, weak[i].boxes.astype(np.float32))
        np.testing.assert_allclose(d.scores, weak[i].scores.astype(np.float32))
        assert np.array_equal(d.classes, weak[i].classes)
    rt = gb.to_list()
    for g0, g1 in zip(gts, rt):
        np.testing.assert_allclose(g1.boxes, g0.boxes.astype(np.float32))
        assert np.array_equal(g1.classes, g0.classes)


def test_batch_round_trip_with_empty_images():
    dets = [empty_dets(), Detections([[0.0, 0, 5, 5]], [0.7], [2])]
    db = DetectionsBatch.from_list(dets)
    assert db.counts.tolist() == [0, 1]
    assert len(db[0]) == 0 and len(db[1]) == 1
    assert db.max_boxes >= 8  # padded to the bucket floor


@pytest.mark.parametrize("cls", [DetectionsBatch, GroundTruthBatch])
def test_slice_images_keeps_type_and_rows(noisy_pair, cls):
    gts, weak, _ = noisy_pair
    batch = cls.from_list(weak if cls is DetectionsBatch else gts)
    part = batch.slice_images(3, 9)
    assert type(part) is cls and len(part) == 6
    assert part.max_boxes == batch.max_boxes
    for i in range(6):
        np.testing.assert_array_equal(part[i].boxes, batch[3 + i].boxes)
    assert len(batch.slice_images(4, 4)) == 0


def test_from_list_empty_is_explicit_zero_length_batch():
    """``from_list([])`` is a well-defined zero-length batch for BOTH
    containers — not an incidental numpy stack error."""
    db = DetectionsBatch.from_list([])
    gb = GroundTruthBatch.from_list([])
    for batch in (db, gb):
        assert len(batch) == 0
        assert batch.boxes.shape == (0, batch.max_boxes, 4)
        assert batch.counts.shape == (0,)
        assert batch.to_list() == []
    assert db.scores.shape == (0, db.max_boxes)
    # and the zero-length batch flows through the matcher + eval conversion
    res = match_batch(db, gb, THRESHOLDS)
    assert res.tp.shape == (0, len(THRESHOLDS), db.max_boxes)
    assert to_image_evals(db, gb, res) == []


def test_from_list_empty_respects_explicit_max_boxes():
    db = DetectionsBatch.from_list([], max_boxes=32)
    gb = GroundTruthBatch.from_list([], max_boxes=32)
    assert db.max_boxes == 32 and gb.max_boxes == 32


def test_from_list_overflow_raises():
    d = Detections(np.zeros((5, 4)), np.zeros(5), np.zeros(5, int))
    with pytest.raises(ValueError):
        DetectionsBatch.from_list([d], max_boxes=4)
    g = GroundTruth(np.zeros((5, 4)), np.zeros(5, int))
    with pytest.raises(ValueError):
        GroundTruthBatch.from_list([g], max_boxes=4)


def test_match_batch_size_mismatch_raises():
    db = DetectionsBatch.from_list([empty_dets()])
    gb = GroundTruthBatch.from_list([empty_gt(), empty_gt()])
    with pytest.raises(ValueError):
        match_batch(db, gb)


# ---------------------------------------------------------------- matcher

def assert_matches_reference(dets, gts, thresholds=THRESHOLDS):
    db = DetectionsBatch.from_list(dets)
    gb = GroundTruthBatch.from_list(gts)
    res = match_batch(db, gb, thresholds)
    assert res.tp.shape == (len(dets), len(thresholds), db.max_boxes)
    # padded slots are never tp
    assert not res.tp[~np.broadcast_to(db.mask[:, None, :], res.tp.shape)].any()
    evs = to_image_evals(db, gb, res)
    for ev, d, g in zip(evs, dets, gts):
        ref = match_detections(d, g, thresholds)
        assert ev.gt_counts == ref.gt_counts
        assert set(ev.per_class) == set(ref.per_class)
        for c in ref.per_class:
            s_ref, tp_ref = ref.per_class[c]
            s_got, tp_got = ev.per_class[c]
            assert np.array_equal(tp_got, tp_ref)  # bit-for-bit tp flags
            assert np.array_equal(ev.matched_gt[c], ref.matched_gt[c])
            np.testing.assert_allclose(s_got, s_ref, rtol=1e-6)


def test_match_batch_equals_match_detections(noisy_pair):
    gts, weak, strong = noisy_pair
    assert_matches_reference(weak, gts)
    assert_matches_reference(strong, gts)


def test_match_batch_empty_rows():
    gts = [empty_gt(), GroundTruth([[0.0, 0, 10, 10]], [1]), empty_gt()]
    dets = [
        Detections([[0.0, 0, 10, 10]], [0.9], [1]),  # dets, no GT
        empty_dets(),                                # GT, no dets
        empty_dets(),                                # nothing at all
    ]
    assert_matches_reference(dets, gts)


def test_match_batch_greedy_one_gt_per_detection():
    """Two detections over one GT: only the higher-scored one matches."""
    gt = GroundTruth([[0.0, 0, 10, 10]], [0])
    det = Detections(
        [[0.0, 0, 10, 10], [0.5, 0.5, 10.5, 10.5]], [0.6, 0.9], [0, 0]
    )
    assert_matches_reference([det], [gt], thresholds=(0.5,))
    db = DetectionsBatch.from_list([det])
    gb = GroundTruthBatch.from_list([gt])
    res = match_batch(db, gb, (0.5,))
    assert res.tp[0, 0, :2].tolist() == [False, True]  # score order wins
    assert res.match_gt[0, 0, 1] == 0 and res.match_gt[0, 0, 0] == -1


def test_match_batch_respects_classes():
    gt = GroundTruth([[0.0, 0, 10, 10]], [3])
    det = Detections([[0.0, 0, 10, 10]], [0.9], [2])  # perfect box, wrong class
    res = match_batch(
        DetectionsBatch.from_list([det]), GroundTruthBatch.from_list([gt]), (0.5,)
    )
    assert not res.tp.any()


# ----------------------------------------------------------- reward layer

def test_match_pairs_batched_equals_match_pairs(noisy_pair):
    gts, weak, strong = noisy_pair
    ref = match_pairs(weak, strong, gts)
    got = match_pairs_batched(weak, strong, gts)
    # identical tp flags -> identical ORI / ORIC rewards
    np.testing.assert_allclose(ori_batch(got), ori_batch(ref), atol=1e-12)
    rng = np.random.default_rng(0)
    pool = [im.weak for im in ref[:30]]
    oracle = RewardOracle.from_pool(pool, 20, rng)
    np.testing.assert_allclose(
        oracle.oric_batch(got), oracle.oric_batch(ref), atol=1e-12
    )


def test_match_pairs_batched_accepts_batches(noisy_pair):
    gts, weak, strong = noisy_pair
    wb = DetectionsBatch.from_list(weak)
    sb = DetectionsBatch.from_list(strong)
    gb = GroundTruthBatch.from_list(gts)
    a = match_pairs_batched(wb, sb, gb)
    b = match_pairs_batched(weak, strong, gts)
    np.testing.assert_allclose(ori_batch(a), ori_batch(b), atol=1e-12)


def test_ori_batch_equals_scalar_ori(noisy_pair):
    gts, weak, strong = noisy_pair
    imgs = match_pairs(weak[:25], strong[:25], gts[:25])
    np.testing.assert_allclose(
        ori_batch(imgs), np.array([ori(im) for im in imgs]), atol=1e-12
    )


# --------------------------------------------------------------- features

def test_features_batched_equals_per_image(noisy_pair):
    _, weak, _ = noisy_pair
    num_classes = 8
    ref = np.stack([extract_features(d, num_classes, 25, 64.0) for d in weak])
    got = extract_features_batch(weak, num_classes, 25, 64.0)
    assert got.shape == ref.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)
    # DetectionsBatch input is the same path
    got2 = extract_features_batch(
        DetectionsBatch.from_list(weak), num_classes, 25, 64.0
    )
    np.testing.assert_array_equal(got, got2)


def test_features_batched_empty_and_overflow():
    num_classes = 4
    many = Detections(
        np.concatenate([np.zeros((30, 2)), np.ones((30, 2))], 1) * 10.0
        + np.arange(30)[:, None],
        np.linspace(0.9, 0.1, 30),
        np.arange(30) % num_classes,
    )
    dets = [empty_dets(), many]
    ref = np.stack([extract_features(d, num_classes, 25, 64.0) for d in dets])
    got = extract_features_batch(dets, num_classes, 25, 64.0)
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)
    assert np.all(got[0] == 0.0)  # empty image -> all-zero feature row


# -------------------------------------------------------------- consumers

def test_detection_box_features_accepts_batch(noisy_pair):
    from repro.api import DetectionBoxFeatures

    _, weak, _ = noisy_pair
    fx = DetectionBoxFeatures(num_classes=8, image_size=64.0)
    np.testing.assert_array_equal(
        fx(DetectionsBatch.from_list(weak)), fx(weak)
    )


def test_session_scores_prebatched_features_without_item_conversion():
    from repro.api import MLPRewardModel, OffloadEngine
    from repro.core import EstimatorConfig
    from repro.runtime import OffloadSession

    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (64, 16)).astype(np.float32)
    eng = OffloadEngine(
        reward_model=MLPRewardModel(config=EstimatorConfig(hidden=(8,), epochs=1)),
        ratio=0.3,
    )
    eng.fit(features=x, rewards=rng.normal(0, 1, 64))
    session = OffloadSession(eng, micro_batch=8)
    # partial flushes keep a trailing sub-micro-batch pending as one block
    out = session.submit_batch(features=x[:21], flush=False)
    assert [d.step for d in out] == list(range(16))
    assert session.telemetry.pending == 5
    out2 = session.flush()
    assert [d.step for d in out2] == [16, 17, 18, 19, 20]
    # decisions equal the engine's one-shot mask regardless of batching
    mask = eng.decide(features=x[:21]).offload
    np.testing.assert_array_equal(
        np.array([d.offload for d in out + out2]), mask
    )


def test_topk_session_invariant_to_micro_batch():
    """Streaming decisions under the topk policy must not depend on
    buffering: per-batch top-k would offload nothing at micro_batch=1, so
    sessions keep decide()'s quantile-threshold semantics."""
    from repro.api import MLPRewardModel, OffloadEngine
    from repro.core import EstimatorConfig
    from repro.runtime import OffloadSession

    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (64, 16)).astype(np.float32)
    eng = OffloadEngine(
        reward_model=MLPRewardModel(config=EstimatorConfig(hidden=(8,), epochs=2)),
        policy="topk",
        ratio=0.25,
    )
    eng.fit(features=x, rewards=rng.normal(0, 1, 64))
    masks = []
    for mb in (1, 7, 64):
        session = OffloadSession(eng, micro_batch=mb)
        masks.append([d.offload for d in session.submit_batch(features=x)])
    assert masks[0] == masks[1] == masks[2]
    assert any(masks[0])  # micro_batch=1 must still offload


def test_iou_matrix_batch_matches_per_image(rng):
    import jax.numpy as jnp

    from repro.detection.boxes import box_iou_np
    from repro.kernels.iou_matrix import iou_matrix_batch

    B, K, M = 5, 9, 6
    a = rng.uniform(0, 50, (B, K, 2))
    a = np.concatenate([a, a + rng.uniform(1, 20, (B, K, 2))], -1).astype(np.float32)
    b = rng.uniform(0, 50, (B, M, 2))
    b = np.concatenate([b, b + rng.uniform(1, 20, (B, M, 2))], -1).astype(np.float32)
    got = np.asarray(iou_matrix_batch(jnp.asarray(a), jnp.asarray(b)))
    assert got.shape == (B, K, M)
    for i in range(B):
        np.testing.assert_allclose(got[i], box_iou_np(a[i], b[i]), atol=1e-6)
