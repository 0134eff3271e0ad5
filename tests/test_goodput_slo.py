"""Goodput accounting, time-series telemetry, SLO burn rates.

The ISSUE-14 observability tier: deterministic interval accounting
(explicit timestamps, no sleeps), the store's bounded rings + torn-tail
JSONL reader, burn-rate math for all three objective kinds with a real
breach bundle on disk, the disabled-is-free contract (no thread, no
hot-path allocation) and the histogram window cap.
"""

import json
import random
import threading
import tracemalloc

import pytest

from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.observability import (
    GoodputTracker,
    SLObjective,
    SLOEvaluator,
    TimeSeriesStore,
)
from deeplearning4j_tpu.observability.flightrec import FlightRecorder
from deeplearning4j_tpu.observability.goodput import STATES
from deeplearning4j_tpu.observability.metrics import Histogram, MetricsRegistry
from deeplearning4j_tpu.observability.timeseries import (
    read_back,
    read_back_series,
)


# ------------------------------------------------------------------ goodput


def test_goodput_exact_state_sequence_and_accounting():
    """A fixed transition plan with explicit timestamps yields an exact
    coalesced state sequence, and the per-state seconds sum to wall-clock
    with no drift at all (contiguous intervals by construction)."""
    gp = GoodputTracker(registry=MetricsRegistry())
    t = gp.started_at
    gp.transition("checkpoint", t + 1.0)
    gp.transition("productive", t + 1.5)
    gp.data_wait(t + 2.0, t + 2.3)           # >= threshold: carved as stall
    gp.transition("rollback", t + 3.0)
    gp.transition("restore", t + 3.5)
    gp.transition("productive", t + 4.0)
    gp.transition("drain", t + 5.0)
    rep = gp.finish(t + 5.5)

    assert rep["states"] == [
        "productive", "checkpoint", "productive", "stall", "productive",
        "rollback", "restore", "productive", "drain"]
    assert rep["wall_seconds"] == pytest.approx(5.5)
    assert rep["accounted_seconds"] == pytest.approx(rep["wall_seconds"],
                                                     abs=1e-9)
    assert rep["seconds"]["productive"] == pytest.approx(1.0 + 0.5 + 0.7 + 1.0)
    assert rep["seconds"]["stall"] == pytest.approx(0.3)
    assert rep["seconds"]["drain"] == pytest.approx(0.5)
    assert rep["fraction"] == pytest.approx(3.2 / 5.5)
    assert set(rep["seconds"]) == set(STATES)
    # finish() is idempotent: same report, clock does not move
    assert gp.finish() == rep


def test_goodput_subthreshold_wait_stays_productive():
    gp = GoodputTracker(registry=MetricsRegistry(), stall_threshold_s=0.5)
    t = gp.started_at
    gp.data_wait(t + 1.0, t + 1.2)           # under threshold: ignored
    rep = gp.finish(t + 2.0)
    assert rep["states"] == ["productive"]
    assert rep["seconds"]["stall"] == 0.0
    assert rep["fraction"] == pytest.approx(1.0)


def test_goodput_phase_restores_previous_state():
    gp = GoodputTracker(registry=MetricsRegistry())
    assert gp.state == "productive"
    with gp.phase("checkpoint"):
        assert gp.state == "checkpoint"
        with gp.phase("stall"):
            assert gp.state == "stall"
        assert gp.state == "checkpoint"
    assert gp.state == "productive"


def test_goodput_coalesces_repeated_state():
    gp = GoodputTracker(registry=MetricsRegistry())
    t = gp.started_at
    gp.transition("checkpoint", t + 1.0)
    gp.transition("checkpoint", t + 1.5)     # same state: merged
    gp.transition("productive", t + 2.0)
    rep = gp.finish(t + 3.0)
    assert rep["states"] == ["productive", "checkpoint", "productive"]
    assert rep["seconds"]["checkpoint"] == pytest.approx(1.0)


def test_goodput_timeline_cap_keeps_seconds_exact():
    gp = GoodputTracker(registry=MetricsRegistry(), timeline_cap=4)
    t = gp.started_at
    for i in range(20):
        gp.transition("stall" if i % 2 == 0 else "productive",
                      t + 0.1 * (i + 1))
    rep = gp.finish(t + 2.1)
    assert rep["timeline_dropped"] > 0
    assert len(rep["timeline"]) <= 4
    # the cap only bounds the *narrative*; the accounting stays exact
    assert rep["accounted_seconds"] == pytest.approx(rep["wall_seconds"],
                                                     abs=1e-9)


def test_goodput_publishes_gauges_on_finish():
    reg = MetricsRegistry()
    gp = GoodputTracker(registry=reg)
    t = gp.started_at
    gp.transition("checkpoint", t + 1.0)
    gp.transition("productive", t + 2.0)
    gp.finish(t + 4.0)
    gauges = reg.snapshot()["gauges"]
    assert gauges["goodput.fraction"] == pytest.approx(3.0 / 4.0)
    assert gauges["goodput.wall_seconds"] == pytest.approx(4.0)
    assert gauges["goodput.seconds.checkpoint"] == pytest.approx(1.0)
    for s in STATES:
        assert f"goodput.seconds.{s}" in gauges


def test_goodput_rejects_unknown_state():
    gp = GoodputTracker(registry=MetricsRegistry())
    with pytest.raises(ValueError):
        gp.transition("coffee_break")


# --------------------------------------------------------------- timeseries


def test_timeseries_ring_overflow_counts_dropped():
    reg = MetricsRegistry()
    store = TimeSeriesStore(registry=reg, ring=8)
    for i in range(20):
        reg.gauge("g", float(i))
        store.sample_once(t=100.0 + i)
    pts = store.series("g")
    assert len(pts) == 8                     # ring bound holds
    assert pts[-1] == (119.0, 19.0)
    assert pts[0] == (112.0, 12.0)           # oldest 12 evicted
    stats = store.stats()
    assert stats["dropped"]["g"] == 12
    assert stats["dropped_total"] == 12
    assert stats["samples"] == 20
    # window() trims by time, not count
    assert [v for _, v in store.window("g", 3.0, now=119.0)] == [
        16.0, 17.0, 18.0, 19.0]


def test_timeseries_samples_counters_gauges_and_quantiles():
    reg = MetricsRegistry()
    reg.increment("c", 3)
    reg.gauge("g", 2.5)
    for v in (0.1, 0.2, 0.3):
        reg.observe_time("op", v)
    store = TimeSeriesStore(registry=reg)
    n = store.sample_once(t=10.0)
    assert n == len(store.names())
    assert store.last("c") == 3.0
    assert store.last("g") == 2.5
    assert store.last("op.p50") == pytest.approx(0.2)
    assert "op.p99" in store.names()


def test_timeseries_jsonl_roundtrip_tolerates_torn_tail(tmp_path):
    reg = MetricsRegistry()
    store = TimeSeriesStore(registry=reg, out_dir=tmp_path)
    for i in range(5):
        reg.gauge("g", float(i))
        store.sample_once(t=50.0 + i)
    path = store.out_path
    assert path is not None and path.exists()
    with open(path, "a") as f:
        f.write('{"t": 55.0, "series": {"g": 5')     # killed mid-append
    rows = read_back(path)
    assert len(rows) == 5                            # torn tail skipped
    merged = read_back_series([path])
    assert merged["g"] == [(50.0 + i, float(i)) for i in range(5)]


def test_timeseries_background_thread_lifecycle():
    reg = MetricsRegistry()
    reg.gauge("g", 1.0)
    store = TimeSeriesStore(registry=reg, interval_s=0.01)
    assert store.start() is True
    assert store.start() is False            # second start refuses
    assert store.running
    deadline = threading.Event()
    for _ in range(200):                     # ~2 s worst case
        if store.stats()["samples"] >= 2:
            break
        deadline.wait(0.01)
    store.stop()
    assert not store.running
    assert store.stats()["samples"] >= 2
    assert store.last("g") == 1.0


def test_timeseries_evaluator_runs_after_sample():
    reg = MetricsRegistry()
    reg.gauge("g", 7.0)
    store = TimeSeriesStore(registry=reg)
    seen = []
    store.add_evaluator(lambda s, t: seen.append((s.last("g"), t)))
    store.sample_once(t=42.0)
    assert seen == [(7.0, 42.0)]


# ----------------------------------------------------------- disabled-free


def test_disabled_spawns_no_thread_and_allocates_nothing():
    """DL4J_TPU_OBS=0 contract: start() refuses to spawn, and the sample
    hot path performs zero allocations while disabled."""
    reg = MetricsRegistry()
    store = TimeSeriesStore(registry=reg, interval_s=0.01)
    obs.disable()
    try:
        before = threading.active_count()
        assert store.start() is False
        assert threading.active_count() == before
        assert not store.running
        assert store.sample_once() == 0
        assert store.stats()["samples"] == 0

        evaluator = SLOEvaluator(
            [SLObjective("x", "upper", "x", 1.0)], store, registry=reg,
            flightrec=FlightRecorder(), attach=False)
        assert evaluator.evaluate(store, now=1.0) == {}

        store.sample_once()                  # warm any lazy caches first
        tracemalloc.start()
        base = tracemalloc.get_traced_memory()[0]
        for _ in range(50):
            store.sample_once()
        grown = tracemalloc.get_traced_memory()[0] - base
        tracemalloc.stop()
        assert grown == 0, f"disabled sample path allocated {grown} bytes"
    finally:
        obs.enable()


# ---------------------------------------------------------------------- slo


def _fed_store(points, name="x", t0=100.0):
    """A store whose ``name`` ring holds ``points`` at 1 s spacing."""
    reg = MetricsRegistry()
    store = TimeSeriesStore(registry=reg)
    for i, v in enumerate(points):
        reg.gauge(name, v)
        store.sample_once(t=t0 + i)
    return reg, store


def test_slo_upper_burn_math_and_full_window():
    # 10 points, half above the 1.0 objective, budget 0.5
    # -> bad fraction 0.5, burn exactly 1.0
    reg, store = _fed_store([0.5, 2.0] * 5)
    obj = SLObjective("lat", "upper", "x", 1.0, budget=0.5, windows=(9.0,))
    ev = SLOEvaluator([obj], store, registry=reg,
                      flightrec=FlightRecorder(), attach=False)
    out = ev.evaluate(store, now=109.0)
    burn = out["lat"][0]
    assert burn.full                         # oldest point covers the window
    assert burn.samples == 10
    assert burn.burn == pytest.approx(1.0)
    assert reg.snapshot()["gauges"]["slo.burn_rate.lat"] == pytest.approx(1.0)


def test_slo_lower_and_rate_kinds():
    # lower: goodput floor 0.5; 4 of 5 points below -> burn (0.8)/0.2 = 4
    reg, store = _fed_store([0.4, 0.3, 0.6, 0.2, 0.1])
    low = SLObjective("gp", "lower", "x", 0.5, budget=0.2, windows=(4.0,))
    ev = SLOEvaluator([low], store, registry=reg,
                      flightrec=FlightRecorder(), attach=False,
                      breach_cooldown_s=0.0)
    out = ev.evaluate(store, now=104.0)
    assert out["gp"][0].burn == pytest.approx((4 / 5) / 0.2)

    # rate: 10 errors over 100 requests = 10%, objective 5% -> burn 2.0
    reg2 = MetricsRegistry()
    store2 = TimeSeriesStore(registry=reg2)
    for i in range(6):
        reg2.gauge("err", 2.0 * i)           # cumulative counters, sampled
        reg2.gauge("req", 20.0 * i)
        store2.sample_once(t=200.0 + i)
    rate = SLObjective("errs", "rate", "err", 0.05, denominator="req",
                       windows=(5.0,))
    ev2 = SLOEvaluator([rate], store2, registry=reg2,
                       flightrec=FlightRecorder(), attach=False)
    out2 = ev2.evaluate(store2, now=205.0)
    assert out2["errs"][0].burn == pytest.approx((10.0 / 100.0) / 0.05)


def test_slo_breach_dumps_bundle_with_series_tail(tmp_path):
    reg, store = _fed_store([5.0] * 12)      # everything bad: burn >> 1
    obj = SLObjective("lat", "upper", "x", 1.0, budget=0.5,
                      windows=(5.0, 10.0))
    ev = SLOEvaluator([obj], store, registry=reg,
                      flightrec=FlightRecorder(dump_dir=tmp_path),
                      attach=False, breach_cooldown_s=60.0)
    ev.evaluate(store, now=111.0)
    assert len(ev.breaches) == 1
    assert reg.snapshot()["counters"]["slo.breaches"] == 1

    bundle = json.loads(open(ev.breaches[0]).read())
    extra = bundle["extra"]
    assert extra["objective"] == "lat"
    assert extra["kind"] == "upper"
    assert extra["series"] == "x"
    assert len(extra["windows"]) == 2
    assert all(w["burn"] > 1.0 for w in extra["windows"])
    assert extra["series_tail"]              # the offending tail is included
    assert extra["series_tail"][-1] == [111.0, 5.0]

    # cooldown: an immediately-following evaluation does not double-dump
    ev.evaluate(store, now=112.0)
    assert len(ev.breaches) == 1


def test_slo_no_breach_without_a_full_window():
    # only 3 points over 2 s of history: the 30 s window is never covered,
    # so even an all-bad series must not page
    reg, store = _fed_store([5.0, 5.0, 5.0])
    obj = SLObjective("lat", "upper", "x", 1.0, windows=(30.0,))
    ev = SLOEvaluator([obj], store, registry=reg,
                      flightrec=FlightRecorder(), attach=False)
    out = ev.evaluate(store, now=102.0)
    assert not out["lat"][0].full
    assert ev.breaches == []


# ---------------------------------------------------------------- histogram


def test_histogram_window_cap_bounds_memory_and_keeps_quantiles():
    """At 10x the window cap the reservoir stays bounded, evictions are
    counted, and p50/p95/p99 of a stationary stream stay within
    tolerance of the true quantiles (the window IS the recent
    distribution)."""
    cap = 64
    h = Histogram(window=cap)
    rng = random.Random(7)
    for _ in range(10 * cap):
        h.observe(rng.random())              # uniform [0, 1)
    assert len(h.values) == cap
    s = h.summary()
    assert s["dropped"] == 10 * cap - cap
    assert s["count"] == 10 * cap            # cumulative count is unwindowed
    assert abs(s["p50_s"] - 0.50) < 0.15
    assert abs(s["p95_s"] - 0.95) < 0.10
    assert abs(s["p99_s"] - 0.99) < 0.10


def test_registry_surfaces_dropped_samples_counter():
    reg = MetricsRegistry()
    small = Histogram(window=16)
    with reg._lock:
        reg.timers["op"] = small
    for i in range(40):
        reg.observe_time("op", i * 0.001)
    snap = reg.snapshot()
    assert snap["counters"]["metrics.dropped_samples"] == 24.0
    assert "metrics_dropped_samples_total 24.0" in reg.to_prometheus()
