"""repro.obs — the unified observability plane.

Covers the instrument/registry core, the manual-clock tracer, jit retrace
accounting, the dispatch profiler, and the two contracts the serve stack
must hold when an ``Obs`` handle rides along:

- **byte-stability**: telemetry ``as_dict()`` payloads are identical with
  observability on, off, and noop — the counters ARE the instruments, so
  there is exactly one accounting path;
- **determinism**: under the manual clock the same seed produces
  byte-identical metrics and trace exports across runs.
"""
import json

import numpy as np
import pytest

from repro.api import MLPRewardModel, OffloadEngine
from repro.core import EstimatorConfig
from repro.fleet import simulate_fleet
from repro.obs import (
    DEFAULT_TIME_BUCKETS,
    Counter,
    DispatchProfiler,
    Gauge,
    Histogram,
    MetricsRegistry,
    Obs,
    Tracer,
    jit_stats,
)
from repro.runtime import (
    ManualClock,
    default_congested_fleet,
    default_edge_fleet,
    simulate,
)


def fit_engine(policy="threshold", ratio=0.3, n=256, d=12, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, d)).astype(np.float32)
    rewards = 2.0 * x[:, 0] + 0.3 * rng.normal(size=n)
    eng = OffloadEngine(
        reward_model=MLPRewardModel(
            config=EstimatorConfig(hidden=(16,), epochs=15, batch_size=64)
        ),
        policy=policy,
        ratio=ratio,
    )
    eng.fit(features=x, rewards=rewards)
    return eng, x


@pytest.fixture(scope="module")
def engine_and_features():
    return fit_engine()


# ------------------------------------------------------------- instruments


def test_counter_stays_int_under_int_increments():
    c = Counter("c")
    c.inc()
    c.inc(3)
    assert c.value == 4 and isinstance(c.value, int)
    c.inc(0.5)
    assert isinstance(c.value, float)


def test_gauge_set_and_callback():
    g = Gauge("g")
    g.set(2.5)
    assert g.value == 2.5
    state = {"x": 7}
    live = Gauge("live", fn=lambda: state["x"])
    assert live.value == 7
    state["x"] = 9
    assert live.value == 9


def test_histogram_buckets_and_stats():
    h = Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert list(h.counts) == [1, 1, 1, 1]  # one per bucket + overflow
    assert h.n == 4
    assert h.sum == pytest.approx(105.0)
    assert h.mean == pytest.approx(105.0 / 4)


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        Histogram("h", buckets=(2.0, 1.0))
    Histogram("h", buckets=(1.0, 2.0, 4.0))  # strictly increasing is fine


def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("hits", {"edge": "e0"})
    b = reg.counter("hits", {"edge": "e0"})
    c = reg.counter("hits", {"edge": "e1"})
    assert a is b and a is not c
    a.inc(2)
    snap = reg.snapshot()
    assert snap['hits{edge="e0"}'] == 2
    assert snap['hits{edge="e1"}'] == 0


def test_registry_callback_gauge_rebinds_fn():
    # a fresh fleet re-registering the same metric must win the callback
    reg = MetricsRegistry()
    reg.gauge("depth", fn=lambda: 1)
    g = reg.gauge("depth", fn=lambda: 2)
    assert g.value == 2
    assert reg.snapshot()["depth"] == 2


def test_registry_delta():
    reg = MetricsRegistry()
    c = reg.counter("n")
    prev = reg.snapshot()
    c.inc(5)
    d = MetricsRegistry.delta(prev, reg.snapshot())
    assert d["n"] == 5


def test_prometheus_exposition_cumulative_histogram():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 2.0))
    for v in (0.5, 1.5, 9.0):
        h.observe(v)
    text = reg.to_prometheus()
    assert '# TYPE lat histogram' in text
    assert 'lat_bucket{le="1.0"} 1' in text
    assert 'lat_bucket{le="2.0"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text


def test_registry_json_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    p = tmp_path / "m.json"
    reg.export_json(str(p))
    payload = json.loads(p.read_text())
    assert any(s["name"] == "a" and s["value"] == 3 for s in payload["series"])


# ------------------------------------------------------------------ tracer


def test_tracer_manual_clock_spans():
    clock = ManualClock()
    tr = Tracer()
    tr.bind_clock(clock)
    t0 = tr.clock()
    clock.advance(2.0)
    tr.add_span("work", t0, tr.clock(), tid=1, args={"k": 1})
    doc = tr.to_chrome()
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(evs) == 1
    assert evs[0]["name"] == "work" and evs[0]["dur"] == pytest.approx(2000.0)


def test_tracer_async_pairs_share_id():
    tr = Tracer()
    tr.bind_clock(ManualClock())
    jid = tr.next_id()
    tr.add_async_span("offload", 0.0, 3.0, id=jid, tid=5)
    evs = tr.to_chrome()["traceEvents"]
    b = [e for e in evs if e["ph"] == "b"]
    e = [e for e in evs if e["ph"] == "e"]
    assert len(b) == 1 and len(e) == 1
    assert b[0]["id"] == e[0]["id"]


def test_tracer_overflow_drops_not_grows():
    tr = Tracer(max_events=4)
    tr.bind_clock(ManualClock())
    for i in range(10):
        tr.add_span("s", 0.0, 1.0, tid=0)
    doc = tr.to_chrome()
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) <= 4
    meta = [e for e in doc["traceEvents"] if e.get("name") == "trace_overflow"]
    assert meta and meta[0]["args"]["dropped"] == 6


# --------------------------------------------------------------- jit stats


def test_jit_stats_sites_registered_by_kernel_imports():
    import repro.kernels.score_pipeline  # noqa: F401  (registers sites)

    sites = jit_stats.sites()
    assert "iou_matrix.batch_pallas" in sites
    assert "features.box_feature_stack" in sites


def test_register_jit_refuses_plain_function():
    """A site without a jit cache would have no retrace count to report."""
    with pytest.raises(TypeError, match="jax.jit"):
        jit_stats.register_jit("test.plain_function", lambda x: x)
    assert "test.plain_function" not in jit_stats.sites()


def test_count_call_loses_no_update_across_threads():
    """Scoring calls count from several threads at once (the warm-up of
    ragged chunk programs does); no increment may be lost."""
    import sys
    import threading

    site, n_threads, per_thread = "test.threaded_count", 16, 2000
    before = jit_stats.snapshot().get(site, (0, 0))[1]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(
                target=lambda: [jit_stats.count_call(site) for _ in range(per_thread)]
            )
            for _ in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert jit_stats.snapshot()[site][1] - before == n_threads * per_thread


def test_jit_stats_counts_retraces(engine_and_features):
    eng, x = engine_and_features
    before = jit_stats.snapshot()
    eng.score(features=x)
    eng.score(features=x[: len(x) // 2])  # new shape → retrace
    delta = jit_stats.delta(before, jit_stats.snapshot())
    assert sum(traces for traces, _ in delta.values()) >= 1


# ---------------------------------------------------------------- profiler


def test_profiler_report_shares_sum_to_one():
    prof = DispatchProfiler()
    for phase, n in (("a", 3), ("b", 2)):
        for _ in range(n):
            t0 = prof.begin(phase)
            prof.add(phase, t0)
    rep = prof.report()
    assert set(rep) == {"a", "b"}
    assert sum(row["share"] for row in rep.values()) == pytest.approx(1.0)
    assert {phase: row["count"] for phase, row in rep.items()} == {"a": 3, "b": 2}
    assert "phase" in prof.format_report()


def test_profiler_report_max_ms_at_least_mean():
    prof = DispatchProfiler()
    for spin in (0, 2000, 50):
        t0 = prof.begin("a")
        sum(range(spin))
        prof.add("a", t0)
    row = prof.report()["a"]
    assert row["count"] == 3
    assert row["max_ms"] >= row["mean_us"] / 1e3 > 0.0
    assert row["max_ms"] <= row["total_ms"]
    assert "max ms" in prof.format_report()


def _host_events(trace_dir):
    """``(name, stats)`` of every event on the host plane of the one
    profiler trace under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [
        (e.name, {k: v for k, v in e.stats})
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    ]


@pytest.mark.parametrize("annotate", [True, False])
def test_profiler_phases_in_jax_trace_only_when_annotating(tmp_path, annotate):
    """The annotating profiler writes each phase as a host event of the
    same name with its ``step``/``frames`` stats; the plain one writes
    nothing into the trace."""
    import jax

    from repro.obs import AnnotatingProfiler

    obs = Obs(metrics=False, tracing=False, annotate=annotate)
    prof = obs.profiler
    assert isinstance(prof, AnnotatingProfiler) is annotate
    jax.profiler.start_trace(str(tmp_path))
    t0 = prof.begin("session.decide", 40, 24)
    t1 = prof.begin("dispatch.admit")
    prof.add("dispatch.admit", t1)
    prof.add("session.decide", t0)
    jax.profiler.stop_trace()
    events = dict(
        (n, st) for n, st in _host_events(tmp_path)
        if n in ("session.decide", "dispatch.admit")
    )
    if annotate:
        assert events == {
            "session.decide": {"step": 40, "frames": 24},
            "dispatch.admit": {},
        }
    else:
        assert events == {}
    assert obs.profiler.report()["session.decide"]["count"] == 1


def test_annotate_needs_profiling():
    with pytest.raises(ValueError, match="profiling"):
        Obs(profiling=False, annotate=True)


# ------------------------------------------------------------- obs handle


def test_noop_handle_disables_every_plane():
    obs = Obs.noop()
    assert obs.metrics is None and obs.tracer is None and obs.profiler is None
    assert not obs.enabled
    assert Obs().enabled


# --------------------------------------------- byte-stability of telemetry


def test_session_telemetry_byte_stable_under_obs(engine_and_features):
    eng, x = engine_and_features
    feats = x[:128]

    def run(obs):
        return simulate(
            eng, features=feats, edges=default_congested_fleet(3, seed=0),
            ratio=0.3, micro_batch=16, seed=0, obs=obs,
        )

    base = run(None).telemetry
    for handle in (Obs(), Obs.noop(), Obs(metrics=False), Obs(tracing=False)):
        t = run(handle).telemetry
        for kwargs in (
            {},
            {"include_video": True},
            {"include_online": True},
            {"include_fleet": True},
        ):
            assert t.as_dict(**kwargs) == base.as_dict(**kwargs), kwargs


def test_fleet_telemetry_byte_stable_under_obs(engine_and_features):
    eng, x = engine_and_features
    rng = np.random.default_rng(3)
    feats = rng.normal(size=(10, 64, x.shape[1])).astype(np.float32)
    base = simulate_fleet(eng, feats, n_shards=4).telemetry
    observed = simulate_fleet(eng, feats, n_shards=4, obs=Obs()).telemetry
    assert observed.as_dict(include_per_shard=True) == base.as_dict(
        include_per_shard=True
    )


# --------------------------------------------- deterministic export bytes


def test_exports_byte_identical_across_seeded_runs(
    engine_and_features, tmp_path
):
    eng, x = engine_and_features
    feats = x[:96]
    payloads = []
    for i in range(2):
        obs = Obs()
        simulate(
            eng, features=feats, edges=default_edge_fleet(3, seed=0),
            ratio=0.3, micro_batch=16, seed=0, obs=obs,
        )
        mp, tp = tmp_path / f"m{i}.json", tmp_path / f"t{i}.json"
        obs.metrics.export_json(str(mp))
        obs.tracer.export(str(tp))
        payloads.append((mp.read_bytes(), tp.read_bytes()))
    assert payloads[0][0] == payloads[1][0], "metrics export not deterministic"
    assert payloads[0][1] == payloads[1][1], "trace export not deterministic"


# --------------------------------------- fleet trace validity and nesting


def test_simulate_fleet_trace_valid_and_nested(engine_and_features):
    eng, x = engine_and_features
    rng = np.random.default_rng(7)
    feats = rng.normal(size=(8, 64, x.shape[1])).astype(np.float32)
    obs = Obs()
    simulate_fleet(eng, feats, n_shards=4, obs=obs)

    doc = json.loads(json.dumps(obs.tracer.to_chrome()))  # valid JSON
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"fleet.tick", "session.flush", "offload"} <= names

    # track layout: driver on 0, sessions on 1+, edges on 100+
    tracks = {
        e["args"]["name"]: e["tid"] for e in evs if e["ph"] == "M"
    }
    assert tracks["fleet"] == 0
    assert all(tracks[f"shard:{s}"] == 1 + s for s in range(4))
    assert all(v >= 100 for k, v in tracks.items() if k.startswith("edge:"))

    # nesting: every session flush sits inside a fleet tick; every edge
    # offload group opens at a session-track decision time or later
    ticks = [
        (e["ts"], e["ts"] + e["dur"]) for e in evs
        if e["name"] == "fleet.tick"
    ]
    flushes = [e for e in evs if e["name"] == "session.flush"]
    assert flushes
    for f in flushes:
        assert 1 <= f["tid"] < 100
        end = f["ts"] + f["dur"]
        assert any(t0 <= f["ts"] and end <= t1 for t0, t1 in ticks)
    offloads = [e for e in evs if e["name"] == "offload" and e["ph"] == "b"]
    assert offloads
    first_flush = min(f["ts"] for f in flushes)
    for o in offloads:
        assert o["tid"] >= 100
        assert o["ts"] >= first_flush
    # children stay inside their offload slice, matched by async id
    ends = {
        e["id"]: e["ts"] for e in evs if e["name"] == "offload" and e["ph"] == "e"
    }
    for child in ("queue", "transmit", "service"):
        for e in evs:
            if e["name"] == child and e["ph"] == "b":
                parent_b = next(
                    o for o in offloads if o["id"] == e["id"]
                )
                assert parent_b["ts"] <= e["ts"] <= ends[e["id"]]


def test_fleet_prometheus_exposes_required_series(engine_and_features):
    eng, x = engine_and_features
    rng = np.random.default_rng(5)
    feats = rng.normal(size=(6, 64, x.shape[1])).astype(np.float32)
    obs = Obs()
    simulate_fleet(eng, feats, n_shards=2, obs=obs)
    text = obs.metrics.to_prometheus()
    for series in (
        "repro_realized_ratio",
        "repro_dispatch_total",
        "repro_edge_queue_depth",
        "repro_offload_rtt",
        "repro_jit_retraces_total",
    ):
        assert series in text, series


# --------------------------------------------------- runtime obs plumbing


def test_simulate_profiler_attributes_phases(engine_and_features):
    eng, x = engine_and_features
    obs = Obs(metrics=False, tracing=False)
    simulate(
        eng, features=x[:64], edges=default_edge_fleet(3, seed=0),
        ratio=0.3, micro_batch=16, seed=0, obs=obs,
    )
    phases = obs.profiler.totals()
    assert {
        "serve.submit", "serve.settle",
        "session.score_enqueue", "session.score_wait", "session.decide",
    } <= set(phases)


def test_adaptive_engine_obs_counters(engine_and_features):
    from repro.online import AdaptiveEngine, OnlineConfig

    eng, x = engine_and_features
    obs = Obs()
    ada = AdaptiveEngine(
        eng,
        OnlineConfig(min_observations=1, update_every=32, refit_every=10**9),
        obs=obs,
    )
    rng = np.random.default_rng(0)
    for _ in range(2):
        f = rng.normal(size=(32, x.shape[1])).astype(np.float32)
        est = np.asarray(eng.score(features=f))
        ada.observe(f, est, rng.uniform(size=32))
        ada.maybe_update()
    snap = obs.metrics.snapshot()
    assert snap.get('repro_adaptive_updates_total{kind="incremental"}', 0) >= 1
