"""The fused serve-time score pipeline: bit-identity against the composed
features→standardize→MLP route, Pallas-kernel agreement with the lax path,
param-bundle guards, and the session device fast path."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.api import DetectionBoxFeatures, MLPRewardModel, OffloadEngine
from repro.core import EstimatorConfig
from repro.core.features import extract_features_batch
from repro.detection.batch import DetectionsBatch
from repro.detection.map_engine import Detections
from repro.kernels.score_pipeline import (
    PIPELINE_PATHS,
    pipeline_params,
    resolve_pipeline_path,
    score_pipeline,
)
from repro.kernels.score_pipeline.ops import (
    PACKED_SLOT_BYTES,
    pack_detections,
    unpack_detections,
)

NUM_CLASSES = 7
TOP_K = 25


def make_batch(rng, n_images, kmax, frac_empty=0.2):
    """Ragged synthetic detections; ``kmax`` below TOP_K exercises the
    in-dispatch box-axis padding, above it the top-k selection."""
    dets = []
    for _ in range(n_images):
        n = 0 if rng.uniform() < frac_empty else int(rng.integers(1, kmax + 1))
        xy = rng.uniform(0, 0.8, (n, 2))
        wh = rng.uniform(0.01, 0.2, (n, 2))
        dets.append(
            Detections(
                np.concatenate([xy, xy + wh], 1).astype(np.float32),
                rng.uniform(0, 1, n).astype(np.float32),
                rng.integers(0, NUM_CLASSES, n).astype(np.int32),
            )
        )
    return DetectionsBatch.from_list(dets)


@pytest.fixture(scope="module")
def fitted_engine():
    rng = np.random.default_rng(0)
    cal = make_batch(rng, 200, 40)
    eng = OffloadEngine(
        feature_extractor=DetectionBoxFeatures(num_classes=NUM_CLASSES, top_k=TOP_K),
        reward_model=MLPRewardModel(
            config=EstimatorConfig(hidden=(32,), epochs=2, batch_size=64)
        ),
        ratio=0.3,
    )
    eng.fit(
        features=extract_features_batch(cal, NUM_CLASSES, TOP_K),
        rewards=rng.uniform(0, 1, 200),
    )
    assert eng.reward_model.fused
    return eng


@pytest.mark.parametrize("B,kmax", [(1, 12), (7, 40), (64, 12), (512, 40), (5, 3)])
def test_fused_bit_identical_to_composed(fitted_engine, B, kmax):
    """The PR's core contract: one-dispatch ``score_device`` on a padded
    block equals the composed extract_features_batch → predict route
    bit for bit (both box-axis regimes, rows below/above top_k)."""
    eng = fitted_engine
    db = make_batch(np.random.default_rng(B * 131 + kmax), B, kmax)
    x = extract_features_batch(db, NUM_CLASSES, TOP_K)
    composed = eng.score(features=x)
    fused = np.asarray(eng.score_device(db))
    assert fused.dtype == np.float32
    np.testing.assert_array_equal(composed, fused)
    # decide() consumes the same estimates
    dec = eng.decide(db)
    np.testing.assert_array_equal(dec.estimates, fused)


def test_fused_all_padded_rows(fitted_engine):
    """Rows with zero live detections must score like the composed path
    scores them (the features are the all-empty stats, not garbage)."""
    eng = fitted_engine
    empty = Detections(
        np.zeros((0, 4), np.float32), np.zeros(0, np.float32), np.zeros(0, np.int32)
    )
    db = DetectionsBatch.from_list([empty] * 5)
    x = extract_features_batch(db, NUM_CLASSES, TOP_K)
    np.testing.assert_array_equal(
        eng.score(features=x), np.asarray(eng.score_device(db))
    )


def test_fused_empty_batch(fitted_engine):
    out = np.asarray(fitted_engine.score_device(DetectionsBatch.from_list([])))
    assert out.shape == (0,)
    assert out.dtype == np.float32


def test_predict_device_matches_predict(fitted_engine):
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, (33, fitted_engine.reward_model.in_dim)).astype(np.float32)
    np.testing.assert_array_equal(
        fitted_engine.reward_model.predict(x),
        np.asarray(fitted_engine.reward_model.predict_device(x)),
    )


@pytest.mark.parametrize("B,kmax,tile_b", [(1, 12, 128), (64, 40, 128), (300, 12, 128), (512, 40, 128), (40, 18, 16)])
def test_pallas_kernel_matches_lax_path(fitted_engine, B, kmax, tile_b):
    """The fused Pallas kernel (interpreter here — compiled lowering needs
    TPU/GPU) agrees with the jitted lax composition in both batch-grid
    regimes (one tile and several)."""
    eng = fitted_engine
    db = make_batch(np.random.default_rng(B + kmax), B, kmax)
    params = eng.reward_model.pipeline_params()
    kw = dict(num_classes=NUM_CLASSES, top_k=TOP_K, image_size=1.0)
    lax_out = np.asarray(score_pipeline(db, params, path="lax", **kw))
    pal_out = np.asarray(
        score_pipeline(db, params, path="pallas_interpret", tile_b=tile_b, **kw)
    )
    np.testing.assert_allclose(pal_out, lax_out, atol=2e-6)


def test_score_pipeline_accepts_array_tuple(fitted_engine):
    eng = fitted_engine
    db = make_batch(np.random.default_rng(9), 16, 30)
    params = eng.reward_model.pipeline_params()
    kw = dict(num_classes=NUM_CLASSES, top_k=TOP_K, image_size=1.0)
    via_batch = np.asarray(score_pipeline(db, params, **kw))
    via_tuple = np.asarray(
        score_pipeline(
            (jnp.asarray(db.boxes), jnp.asarray(db.scores),
             jnp.asarray(db.classes), jnp.asarray(db.mask)),
            params, **kw,
        )
    )
    np.testing.assert_array_equal(via_batch, via_tuple)


def _packed_calls():
    from repro.obs import jit_stats

    return jit_stats.snapshot()["score_pipeline.packed"][1]


def _device_arrays(db):
    return (jnp.asarray(db.boxes), jnp.asarray(db.scores),
            jnp.asarray(db.classes), jnp.asarray(db.mask))


@pytest.mark.parametrize("path", ["lax", "pallas_interpret"])
@pytest.mark.parametrize("B", [1, 37, 64])
@pytest.mark.parametrize("K", [12, TOP_K, 40])
def test_packed_route_bit_identical_to_device_arrays(fitted_engine, path, B, K):
    """A host block crosses as one packed buffer; its estimates equal those
    of the same rows passed as device arrays (the four-array route) bit for
    bit, with the box axis below, at and above ``top_k``, all-masked rows
    and ``-1`` class slots."""
    rng = np.random.default_rng(1000 * B + K)
    db = make_batch(rng, B, K, frac_empty=0.3)
    db = DetectionsBatch.from_list(db.to_list(), max_boxes=K)
    db.mask[0] = False  # an all-masked row whatever the draw
    db.classes[db.mask & (rng.uniform(size=db.mask.shape) < 0.1)] = -1
    params = fitted_engine.reward_model.pipeline_params()
    kw = dict(num_classes=NUM_CLASSES, top_k=TOP_K, image_size=64.0, path=path)
    calls = _packed_calls()
    packed = np.asarray(score_pipeline(db, params, **kw))
    assert _packed_calls() == calls + 1
    unpacked = np.asarray(score_pipeline(_device_arrays(db), params, **kw))
    assert _packed_calls() == calls + 1
    assert packed.shape == (B,)
    np.testing.assert_array_equal(packed, unpacked)


def test_unpack_detections_round_trips_every_bit():
    """NaN payloads, signed zeros, subnormals and negative classes come
    back from the packed buffer with the same bits."""
    import jax

    rng = np.random.default_rng(7)
    B, K = 3, 5
    bits = rng.integers(0, 2**32, (B, K, 4), dtype=np.uint64).astype(np.uint32)
    boxes = bits.view(np.float32)
    scores = np.array([np.nan, -0.0, 1e-45, -np.inf, 0.5] * B, np.float32).reshape(B, K)
    classes = rng.integers(-(2**31), 2**31, (B, K), dtype=np.int64).astype(np.int32)
    mask = rng.uniform(size=(B, K)) < 0.5
    packed = pack_detections(boxes, scores, classes, mask)
    assert packed.dtype == np.uint8 and packed.shape == (B, PACKED_SLOT_BYTES * K)
    out = jax.jit(unpack_detections)(packed)
    for got, want in zip(out, (boxes, scores, classes, mask)):
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


def test_packed_calls_counted_per_chunk(fitted_engine):
    """``score_pipeline.packed`` counts one call per ``micro_batch`` chunk
    of the session's fused route, and none for device-resident inputs or
    the buffered feature route."""
    from repro.obs import Obs

    site = 'repro_jit_calls_total{site="score_pipeline.packed"}'
    rng = np.random.default_rng(37)
    blocks = [make_batch(rng, n, 20) for n in (40, 17)]  # 3 + 2 chunks of 16
    obs = Obs(tracing=False, profiling=False)
    _serve_blocks(fitted_engine, blocks, "fused", obs)
    assert obs.metrics.snapshot()[site] == 5

    obs = Obs(tracing=False, profiling=False)
    params = fitted_engine.reward_model.pipeline_params()
    score_pipeline(_device_arrays(blocks[0]), params,
                   num_classes=NUM_CLASSES, top_k=TOP_K).block_until_ready()
    _serve_blocks(fitted_engine, blocks, "buffered", obs)
    assert obs.metrics.snapshot().get(site, 0) == 0


def test_pipeline_params_requires_fused_model():
    model = MLPRewardModel(config=EstimatorConfig(hidden=(16, 16), epochs=1))
    rng = np.random.default_rng(0)
    model.fit(rng.normal(0, 1, (32, 8)).astype(np.float32), rng.uniform(0, 1, 32))
    assert not model.fused
    with pytest.raises(ValueError, match="fused"):
        pipeline_params(model)


def test_feature_dim_mismatch_raises(fitted_engine):
    db = make_batch(np.random.default_rng(1), 4, 10)
    params = fitted_engine.reward_model.pipeline_params()
    with pytest.raises(ValueError, match="features"):
        score_pipeline(db, params, num_classes=NUM_CLASSES + 1, top_k=TOP_K)


def test_resolve_pipeline_path():
    import jax

    assert resolve_pipeline_path("lax") == "lax"
    auto = resolve_pipeline_path(None)
    assert auto == ("lax" if jax.default_backend() == "cpu" else "pallas")
    assert auto in PIPELINE_PATHS
    with pytest.raises(ValueError):
        resolve_pipeline_path("jnp")


def test_pipeline_params_track_online_updates(fitted_engine):
    """The bundle must be rebuilt per call: online adaptation swaps
    estimator layers in place and a stale cache would serve old weights."""
    eng = fitted_engine
    db = make_batch(np.random.default_rng(5), 8, 20)
    before = np.asarray(eng.score_device(db))
    est = eng.reward_model.estimator
    p = est.params
    try:
        est.params = {
            "layer0": p["layer0"],
            "layer1": {"w": p["layer1"]["w"] + 0.25, "b": p["layer1"]["b"]},
        }
        after = np.asarray(eng.score_device(db))
        assert not np.array_equal(before, after)
        np.testing.assert_array_equal(
            after, eng.score(features=extract_features_batch(db, NUM_CLASSES, TOP_K))
        )
    finally:
        est.params = p


# ---------------------------------------------------------------- session


def test_session_fast_path_matches_buffered(fitted_engine):
    from repro.runtime.session import OffloadSession

    rng = np.random.default_rng(11)
    db = make_batch(rng, 96, 30)
    fast = OffloadSession(fitted_engine, micro_batch=32)
    direct = fast.submit_batch(db)  # nothing pending: device fast path
    buffered = OffloadSession(fitted_engine, micro_batch=32)
    via_queue = buffered.submit_batch(db, flush=False)  # 96 = 3 micro-batches
    via_queue += buffered.flush()
    assert len(direct) == len(via_queue) == 96
    np.testing.assert_array_equal(
        [d.estimate for d in direct], [d.estimate for d in via_queue]
    )
    assert [d.offload for d in direct] == [d.offload for d in via_queue]
    assert [d.step for d in direct] == [d.step for d in via_queue]


def test_session_buffer_interleaving(fitted_engine):
    """Mixed single-frame submits and batch submits drain in arrival order
    through the preallocated buffer, matching one flat scoring pass."""
    from repro.runtime.session import OffloadSession

    rng = np.random.default_rng(13)
    blocks = [make_batch(rng, n, 20) for n in (10, 3, 50, 1, 7)]
    sess = OffloadSession(fitted_engine, micro_batch=16)
    out = []
    for db in blocks:
        out += sess.submit_batch(db, flush=False)
    out += sess.flush()
    assert len(out) == 71
    assert [d.step for d in out] == list(range(71))
    feats = np.concatenate(
        [extract_features_batch(db, NUM_CLASSES, TOP_K) for db in blocks]
    )
    # every frame's estimate equals a micro-batched pass over the same rows
    ref = []
    for s in range(0, 71, 16):
        ref.extend(fitted_engine.score(features=feats[s : s + 16]).tolist())
    np.testing.assert_allclose([d.estimate for d in out], ref, rtol=0, atol=0)


def test_session_flush_scores_micro_batch_chunks(fitted_engine):
    """A flush with more than ``micro_batch`` rows pending scores them in
    the same consecutive chunks as every other route."""
    from repro.runtime.session import OffloadSession

    rng = np.random.default_rng(17)
    blocks = [make_batch(rng, 5, 20), make_batch(rng, 40, 20)]
    sess = OffloadSession(fitted_engine, micro_batch=16)
    out = sess.submit_batch(blocks[0], flush=False)
    out += sess.submit_batch(blocks[1])  # 45 pending, flushed at once
    assert [d.step for d in out] == list(range(45))
    feats = np.concatenate(
        [extract_features_batch(db, NUM_CLASSES, TOP_K) for db in blocks]
    )
    ref = np.concatenate(
        [fitted_engine.score(features=feats[s : s + 16]) for s in range(0, 45, 16)]
    )
    np.testing.assert_allclose([d.estimate for d in out], ref, rtol=0, atol=0)


@pytest.mark.parametrize("n_blocks", [40])
def test_session_buffer_growth_property(fitted_engine, n_blocks):
    """Property sweep (hypothesis when available): random block sizes and
    flush points never lose, duplicate, or reorder frames."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    from repro.runtime.session import OffloadSession

    @hyp.given(
        sizes=st.lists(st.integers(min_value=0, max_value=40), min_size=1, max_size=12),
        micro=st.integers(min_value=1, max_value=33),
    )
    @hyp.settings(max_examples=20, deadline=None)
    def check(sizes, micro):
        rng = np.random.default_rng(sum(sizes) + micro)
        sess = OffloadSession(fitted_engine, micro_batch=micro)
        total, out = 0, []
        for n in sizes:
            x = extract_features_batch(
                make_batch(rng, n, 15), NUM_CLASSES, TOP_K
            ) if n else np.zeros((0, fitted_engine.reward_model.in_dim), np.float32)
            out += sess.submit_batch(features=x, flush=False)
            total += n
        out += sess.flush()
        assert len(out) == total
        assert [d.step for d in out] == list(range(total))
        assert sess._pending_rows == 0

    check()


# ------------------------------------------------ session phases and bytes

SESSION_PHASES = ("session.score_enqueue", "session.score_wait", "session.decide")


def _serve_blocks(engine, blocks, route, obs, micro_batch=16):
    """Decisions for ``blocks`` served one ``submit_batch`` each, through
    the fused ``DetectionsBatch`` route or the buffered feature route."""
    from repro.runtime.session import OffloadSession

    sess = OffloadSession(engine, micro_batch=micro_batch, obs=obs)
    out = []
    for db in blocks:
        if route == "fused":
            out += sess.submit_batch(db)
        else:
            out += sess.submit_batch(
                features=extract_features_batch(db, NUM_CLASSES, TOP_K)
            )
    return sess, out


def _session_events(trace_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return sorted(
        (dict(e.stats)["step"], e.name, dict(e.stats)["frames"])
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name in SESSION_PHASES
    )


@pytest.mark.parametrize("route", ["fused", "buffered"])
def test_session_routes_record_phases_on_the_trace(fitted_engine, route, tmp_path):
    """Both routes time enqueue, wait and decide once per block, and with
    an annotating handle each phase is a host event of the profiler trace
    carrying the block's first step and its frames."""
    import jax

    from repro.obs import Obs

    rng = np.random.default_rng(23)
    blocks = [make_batch(rng, n, 20) for n in (40, 17)]  # 3 + 2 chunks of 16
    obs = Obs(tracing=False, annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    sess, out = _serve_blocks(fitted_engine, blocks, route, obs)
    jax.profiler.stop_trace()
    assert len(out) == 57
    rep = obs.profiler.report()
    assert {p: rep[p]["count"] for p in SESSION_PHASES} == dict.fromkeys(SESSION_PHASES, 2)
    assert _session_events(tmp_path) == sorted(
        (step, p, n) for step, n in ((0, 40), (40, 17)) for p in SESSION_PHASES
    )
    # one float32 estimate read back per frame, over 5 chunks
    snap = obs.metrics.snapshot()
    assert snap['repro_session_transfer_bytes_total{direction="d2h"}'] == 4 * 57


@pytest.mark.parametrize("obs_kind", ["none", "plain"])
def test_session_without_annotation_writes_no_phase(fitted_engine, obs_kind, tmp_path):
    import jax

    from repro.obs import Obs

    obs = None if obs_kind == "none" else Obs(tracing=False)
    jax.profiler.start_trace(str(tmp_path))
    _serve_blocks(fitted_engine, [make_batch(np.random.default_rng(3), 20, 20)], "fused", obs)
    jax.profiler.stop_trace()
    assert _session_events(tmp_path) == []
    if obs is not None:
        assert set(SESSION_PHASES) <= set(obs.profiler.totals())


@pytest.mark.parametrize("route", ["fused", "buffered"])
def test_session_transfer_bytes_counter(fitted_engine, route):
    """h2d counts the host arrays handed to the scoring calls (the padded
    detection batch, or the feature rows), d2h 4 B per estimate."""
    from repro.obs import Obs

    db = make_batch(np.random.default_rng(29), 37, 20)
    obs = Obs(tracing=False, profiling=False)
    _serve_blocks(fitted_engine, [db], route, obs)
    if route == "fused":
        h2d = db.boxes.nbytes + db.scores.nbytes + db.classes.nbytes + db.mask.nbytes
        assert h2d == 37 * db.max_boxes * (16 + 4 + 4 + 1)
    else:
        h2d = extract_features_batch(db, NUM_CLASSES, TOP_K).nbytes
    snap = obs.metrics.snapshot()
    assert snap['repro_session_transfer_bytes_total{direction="h2d"}'] == h2d
    assert snap['repro_session_transfer_bytes_total{direction="d2h"}'] == 4 * 37


@pytest.mark.parametrize("route", ["fused", "buffered"])
def test_session_decisions_identical_under_obs_handles(fitted_engine, route):
    from repro.obs import Obs

    rng = np.random.default_rng(31)
    blocks = [make_batch(rng, n, 20) for n in (33, 1, 16)]
    runs = [
        _serve_blocks(fitted_engine, blocks, route, obs)
        for obs in (None, Obs.noop(), Obs(annotate=True))
    ]
    base_sess, base = runs[0]
    for sess, out in runs[1:]:
        assert out == base
        assert sess.telemetry.as_dict() == base_sess.telemetry.as_dict()
