"""PR 10: analytic cost/MFU accounting, the failure flight recorder, the
device-memory degradation path, and the trace_report / metrics_dump tools.

The serving- and supervisor-side integration of these pieces is covered in
test_serving.py / test_resilience.py; this file owns the units.
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.observability import (
    COSTS,
    FLIGHTREC,
    METRICS,
    TRACER,
    CostInfo,
    trace,
)
from deeplearning4j_tpu.observability.cost import CostModel
from deeplearning4j_tpu.observability.flightrec import FlightRecorder


# --------------------------------------------------------------------------- cost model

@jax.jit
def _toy_step(x, w):
    return jnp.sum(x @ w)


def _toy_args(n=64):
    return (jnp.ones((n, n), jnp.float32), jnp.ones((n, n), jnp.float32))


def test_capture_pulls_xla_flops_on_cpu():
    model = CostModel()
    info = model.capture("toy.step", _toy_step, *_toy_args())
    assert info is not None and info.source == "xla"
    assert info.flops > 0 and math.isfinite(info.flops)
    assert model.get("toy.step") is info


def test_capture_caches_per_signature():
    model = CostModel()
    first = model.capture("toy.step", _toy_step, *_toy_args())
    calls = []
    real_lower = _toy_step.lower

    class Spy:
        def lower(self, *a):
            calls.append(a)
            return real_lower(*a)

    again = model.capture("toy.step", Spy(), *_toy_args())
    assert again is first            # signature hit: lower never invoked
    assert calls == []
    other = model.capture("toy.step", Spy(), *_toy_args(32))
    assert calls                     # new shapes -> new compile
    assert other is not first


def test_capture_falls_back_to_analytic_flops():
    model = CostModel()

    class NoCost:
        def lower(self, *a):
            raise RuntimeError("backend returned no cost_analysis")

    info = model.capture("fallback", NoCost(), *_toy_args(),
                         analytic_flops=123.0)
    assert info == CostInfo(123.0, 0.0, "analytic")
    assert model.capture("nothing", NoCost(), *_toy_args()) is None


def test_capture_is_noop_while_disabled():
    model = CostModel()
    obs.disable()
    try:
        assert model.capture("toy.step", _toy_step, *_toy_args()) is None
    finally:
        obs.enable()
    assert model.get("toy.step") is None


def test_publish_utilization_gauges_finite_mfu(cpu_peak_row):
    model = CostModel()
    info = model.capture("toy.step", _toy_step, *_toy_args())
    mfu = model.publish_utilization(info, 1e-3, "toy.mfu", "toy.mbu")
    gauges = METRICS.snapshot()["gauges"]
    assert mfu is not None and math.isfinite(mfu) and mfu > 0
    assert gauges["toy.mfu"] == pytest.approx(mfu)
    assert "toy.mbu" in gauges and math.isfinite(gauges["toy.mbu"])
    # None cost / zero time publish nothing rather than NaN
    assert model.publish_utilization(None, 1e-3, "x.mfu") is None
    assert model.publish_utilization(info, 0.0, "x.mfu") is None
    assert "x.mfu" not in METRICS.snapshot()["gauges"]


def test_trainer_publishes_train_mfu_on_cpu(cpu_peak_row):
    """Acceptance: a CPU fit publishes finite train.mfu/train.mbu from
    cost_analysis of the actual compiled step."""
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.parallel.trainer import DataParallelTrainer

    def loss_fn(p, x, y, key=None):
        return jnp.mean((x @ p["w"] - y) ** 2)

    tr = DataParallelTrainer(loss_fn, T.sgd_lr(0.1))
    state = tr.init_state({"w": np.zeros((4, 2), np.float32)})
    xs = np.ones((16, 4), np.float32)
    ys = np.ones((16, 2), np.float32)
    for _ in range(3):
        state, _ = tr.step(state, xs, ys)
    tr._resolve_pending()
    gauges = METRICS.snapshot()["gauges"]
    assert math.isfinite(gauges["train.mfu"]) and gauges["train.mfu"] > 0
    assert math.isfinite(gauges["train.mbu"]) and gauges["train.mbu"] > 0
    assert tr._step_cost is not None and tr._step_cost.flops > 0


# --------------------------------------------------------------------------- device memory degradation

def test_peak_table_agrees_with_the_benchmarks_row_by_row():
    """``cost.PEAKS`` (the library's gauges) and ``benchmark/peaks.json``
    (the judged ``mfu.train``) are two files: the same kinds, the same
    peaks."""
    from pathlib import Path

    from deeplearning4j_tpu.observability.cost import PEAKS
    rows = json.loads((Path(__file__).resolve().parents[1]
                       / "benchmark" / "peaks.json").read_text())
    assert set(rows) == set(PEAKS)
    for kind, row in rows.items():
        peak = PEAKS[kind]
        assert (row["bf16_flops_per_s"], row["hbm_bytes_per_s"],
                row["hbm_bytes"]) == (peak.flops, peak.bytes_per_s,
                                      peak.hbm_bytes), kind
        assert row["source"].startswith(peak.source), kind


def test_sample_device_memory_degrades_on_cpu():
    """Satellite 6: the CPU backend has no memory_stats — sampling stays
    a no-op gauge (supported=0) instead of raising or publishing junk."""
    from deeplearning4j_tpu.observability.device import sample_device_memory

    reported = sample_device_memory()
    gauges = METRICS.snapshot()["gauges"]
    assert reported == 0
    assert gauges["device.memory_stats_supported"] == 0.0
    assert not any(k.startswith("device.") and k.endswith("bytes_in_use")
                   for k in gauges)


# --------------------------------------------------------------------------- flight recorder

def test_flightrec_rings_capture_spans_metrics_and_faults(tmp_path):
    rec = FlightRecorder(dump_dir=tmp_path)
    rec.record_span({"name": "train_step", "ts": 1.0, "dur": 2.0,
                     "args": {"trace_id": "t1", "step": 7}})
    rec.record_metric("counter", "train.steps", 1.0)
    rec.record_metric("counter", "faults.injected.train.step", 1.0)
    assert rec.spans[-1]["step"] == 7
    assert ("counter", "train.steps", 1.0) in rec.metric_events
    assert rec.faults[-1]["site"] == "train.step"
    path = rec.dump("unit_test", extra={"k": "v"})
    bundle = json.loads(path.read_text())
    assert bundle["trigger"] == "unit_test"
    assert bundle["extra"] == {"k": "v"}
    assert bundle["spans"][-1]["name"] == "train_step"
    assert bundle["faults"][-1]["site"] == "train.step"
    assert "metrics" in bundle       # full registry snapshot rides along


def test_flightrec_global_wiring_sees_spans_and_chaos_fires():
    """The singleton listens passively: spans and faults.injected.*
    counters land in its rings with no caller-side wiring."""
    FLIGHTREC.clear()
    with trace.span("wired_span"):
        pass
    METRICS.increment("faults.injected.some.site")
    assert any(s["name"] == "wired_span" for s in FLIGHTREC.spans)
    assert any(f["site"] == "some.site" for f in FLIGHTREC.faults)


def test_flightrec_429_burst_dumps_once(tmp_path):
    rec = FlightRecorder(dump_dir=tmp_path)
    rec.burst_n = 5
    paths = [rec.note_429() for _ in range(12)]
    dumps = [p for p in paths if p is not None]
    assert len(dumps) == 1           # burst fired once, cooldown holds
    bundle = json.loads(dumps[0].read_text())
    assert bundle["trigger"] == "serving_429_burst"
    assert bundle["extra"]["rejections_in_window"] == 5


def test_flightrec_disabled_is_allocation_free(tmp_path):
    rec = FlightRecorder(dump_dir=tmp_path)
    obs.disable()
    try:
        rec.record_span({"name": "x", "ts": 0, "dur": 0, "args": {}})
        rec.record_metric("counter", "faults.injected.x", 1.0)
        assert rec.note_429() is None
        assert rec.dump("nope") is None
    finally:
        obs.enable()
    assert not rec.spans and not rec.metric_events and not rec.faults
    assert not list(tmp_path.iterdir())


# --------------------------------------------------------------------------- tools

def test_trace_report_merges_and_breaks_down(tmp_path):
    from tools.trace_report import load_events, merge, request_breakdowns

    def ev(name, ts, dur, trace_id, **args):
        return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
                "tid": 1, "args": dict(args, trace_id=trace_id)}

    chrome = {"traceEvents": [
        ev("serving.request", 0.0, 1000.0, "t1", tokens=5),
        ev("serving.queue_wait", 0.0, 100.0, "t1"),
        ev("serving.prefill", 100.0, 50.0, "t1"),
    ], "metadata": {"dropped": 2}}
    (tmp_path / "a.json").write_text(json.dumps(chrome))
    with open(tmp_path / "b.jsonl", "w") as f:
        f.write(json.dumps(ev("serving.decode.segment", 150.0, 700.0, "t1")) + "\n")
        f.write(json.dumps(ev("serving.emit", 900.0, 100.0, "t1")) + "\n")
        f.write(json.dumps(ev("serving.prefill", 0.0, 10.0, "t_inflight")) + "\n")
        f.write("{torn line")         # crashed streamer tail is tolerated

    merged = merge([str(tmp_path / "a.json"), str(tmp_path / "b.jsonl")])
    assert len(merged["traceEvents"]) == 6
    assert merged["metadata"]["dropped"] == 2
    ts = [e["ts"] for e in merged["traceEvents"]]
    assert ts == sorted(ts)

    rows = request_breakdowns(merged["traceEvents"])
    (row,) = rows                    # t_inflight has no root -> skipped
    assert row["trace_id"] == "t1"
    assert row["queue_wait_ms"] == pytest.approx(0.1)
    assert row["prefill_ms"] == pytest.approx(0.05)
    assert row["decode_ms"] == pytest.approx(0.7)
    assert row["emit_ms"] == pytest.approx(0.1)
    assert row["ttft_ms"] == pytest.approx(0.15)
    assert row["total_ms"] == pytest.approx(1.0)
    assert row["tokens"] == 5

    events, dropped = load_events(tmp_path / "b.jsonl")
    assert len(events) == 3 and dropped == 0


def test_metrics_dump_renders_serving_and_utilization_tables():
    from tools.metrics_dump import render_serving, render_utilization

    snap = {
        "counters": {},
        "gauges": {
            "serving.kv_pages_in_use": 12.0,
            "serving.prefix_hit_rate": 0.75,
            "serving.kv_bytes_per_slot": 4096.0,
            "train.mfu": 0.41,
            "serving.decode_mfu": 0.22,
            "serving.decode_mbu": 0.6,
        },
        "timers": {
            "serving.spec_accept_len": {"count": 9, "mean_s": 2.5,
                                        "p50_s": 2.0, "p95_s": 4.0,
                                        "p99_s": 4.0, "total_s": 22.5},
        },
    }
    serving = render_serving(snap)
    assert "kv_pages_in_use" in serving and "12" in serving
    assert "75.0%" in serving
    assert "4.00KiB" in serving
    assert "2.50 tok" in serving
    util = render_utilization(snap)
    assert "train.mfu" in util and "41.00%" in util
    assert "serving.decode_mfu" in util and "22.00%" in util
    # absent gauges -> absent tables, not crashes
    empty = {"counters": {}, "gauges": {}, "timers": {}}
    assert render_serving(empty) is None
    assert render_utilization(empty) is None


def test_metrics_dump_renders_kv_capacity_table():
    """The users-per-chip table (DESIGN.md §20): derived rows — pool
    bytes, bytes per slot, slots per pool — from the kv gauges; absent
    gauges mean no table, not a crash."""
    from tools.metrics_dump import render_kv_capacity

    snap = {
        "counters": {},
        "gauges": {
            "serving.kv_quant_bits": 8.0,
            "serving.kv_pages_total": 64.0,
            "serving.kv_page_bytes": 576.0,
            "serving.kv_pages_in_use": 16.0,
            "serving.kv_bytes_per_slot": 4032.0,
        },
        "timers": {},
    }
    table = render_kv_capacity(snap)
    assert "kv_storage_bits" in table and "8" in table
    assert "pool_pages" in table and "64" in table
    assert "slots_per_pool" in table
    # pool_bytes = page_bytes * pages_total = 36864 -> 36.00KiB
    assert "36.00KiB" in table
    # slots = pool_bytes // bytes_per_slot = 9
    assert "9" in table
    empty = {"counters": {}, "gauges": {}, "timers": {}}
    assert render_kv_capacity(empty) is None
