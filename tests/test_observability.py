"""Observability layer: spans/tracing, histogram metrics, Prometheus
exposition, status server, and end-to-end instrumentation of the training
stack (ISSUE 1 acceptance: Perfetto-valid Chrome trace + parseable
/metrics.prom + train_step percentiles from a tiny fit, and a disabled
mode that records nothing)."""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu import observability as obs
from deeplearning4j_tpu.observability import (
    METRICS,
    Histogram,
    MetricsRegistry,
    StatusServer,
    StepTimer,
    Tracer,
    trace,
)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel.trainer import DataParallelTrainer


# --------------------------------------------------------------------------- spans

def test_span_nesting_and_attrs():
    tracer = Tracer()
    with tracer.span("outer", phase="fit") as s:
        s.set(batch=3)
        with tracer.span("inner", idx=1):
            pass
    events = tracer.to_chrome_trace()["traceEvents"]
    assert [e["name"] for e in events] == ["inner", "outer"]  # close order
    inner, outer = events
    assert inner["args"]["parent"] == "outer"
    assert inner["args"]["depth"] == 1
    assert outer["args"]["parent"] is None
    assert outer["args"]["phase"] == "fit" and outer["args"]["batch"] == 3
    # inner is contained within outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_span_nesting_propagates_to_threads():
    tracer = Tracer()
    done = threading.Event()

    def worker():
        # fresh thread -> fresh context: no parent inherited
        with tracer.span("thread_span"):
            pass
        done.set()

    with tracer.span("main_span"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert done.wait(1)
    by_name = {e["name"]: e for e in tracer.to_chrome_trace()["traceEvents"]}
    assert by_name["thread_span"]["args"]["parent"] is None
    assert by_name["thread_span"]["tid"] != by_name["main_span"]["tid"]


def test_span_records_error_attr():
    tracer = Tracer()
    with pytest.raises(ValueError):
        with tracer.span("boom"):
            raise ValueError("x")
    (ev,) = tracer.to_chrome_trace()["traceEvents"]
    assert ev["args"]["error"] == "ValueError"


def test_chrome_trace_roundtrip(tmp_path):
    tracer = Tracer()
    with tracer.span("a", k="v"):
        with tracer.span("b"):
            pass
    path = tracer.save_chrome_trace(tmp_path / "trace.json")
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list) and len(doc["traceEvents"]) == 2
    for ev in doc["traceEvents"]:
        # the Chrome trace-event schema fields Perfetto requires
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str)


def test_jsonl_export_and_stream(tmp_path):
    tracer = Tracer()
    tracer.stream_jsonl(tmp_path / "stream.jsonl")
    with tracer.span("s1"):
        pass
    with tracer.span("s2"):
        pass
    tracer.stop_stream()
    streamed = [json.loads(l) for l in
                (tmp_path / "stream.jsonl").read_text().splitlines()]
    assert [e["name"] for e in streamed] == ["s1", "s2"]
    tracer.export_jsonl(tmp_path / "dump.jsonl")
    dumped = [json.loads(l) for l in
              (tmp_path / "dump.jsonl").read_text().splitlines()]
    assert dumped == streamed


def test_tracer_buffer_is_bounded():
    tracer = Tracer(max_events=16)
    for i in range(64):
        with tracer.span("s"):
            pass
    assert len(tracer.to_chrome_trace()["traceEvents"]) == 16


# --------------------------------------------------------------------------- metrics

def test_histogram_percentiles():
    h = Histogram()
    for v in [i / 1000 for i in range(1, 101)]:  # 1ms..100ms
        h.observe(v)
    s = h.summary()
    assert s["count"] == 100
    assert s["p50_s"] == pytest.approx(0.050, abs=0.002)
    assert s["p95_s"] == pytest.approx(0.095, abs=0.002)
    assert s["p99_s"] == pytest.approx(0.099, abs=0.002)
    assert s["max_s"] == pytest.approx(0.100)
    assert s["mean_s"] == pytest.approx(sum(range(1, 101)) / 100 / 1000)


def test_observe_time_is_the_locked_path():
    reg = MetricsRegistry()
    reg.observe_time("op", 0.25)
    snap = reg.snapshot()
    assert snap["timers"]["op"]["count"] == 1
    assert snap["timers"]["op"]["total_s"] == pytest.approx(0.25)
    # seed regression: StepTimer must route through observe_time, never
    # append to registry.timers[...] bare lists
    timer = StepTimer(reg, "step")
    timer.iteration_done(object(), 1)
    timer.iteration_done(object(), 2)
    assert reg.snapshot()["timers"]["step"]["count"] == 1
    assert isinstance(reg.timers["step"], Histogram)


def test_registry_reset():
    reg = MetricsRegistry()
    reg.increment("c")
    reg.gauge("g", 1.0)
    reg.observe_time("t", 0.1)
    reg.reset()
    snap = reg.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "timers": {}}


def test_concurrent_increments_from_threads():
    reg = MetricsRegistry()
    n_threads, n_iter = 8, 500

    def worker():
        for _ in range(n_iter):
            reg.increment("hits")
            reg.observe_time("lat", 0.001)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == n_threads * n_iter
    assert snap["timers"]["lat"]["count"] == n_threads * n_iter


PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_]'
    r'[a-zA-Z0-9_]*="[^"]*")*\})? (?:[0-9.eE+-]+|NaN|\+Inf)$')


def _check_prometheus(text: str) -> dict[str, str]:
    """Validate Prometheus text exposition; return {metric_name: type}."""
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = re.match(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
                         r"(counter|gauge|histogram|summary|untyped)$", line)
            assert m, f"bad comment line: {line!r}"
            types[m.group(1)] = m.group(2)
        else:
            assert PROM_SAMPLE.match(line), f"bad sample line: {line!r}"
    return types


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.increment("train.steps", 3)
    reg.gauge("loss", 0.5)
    reg.observe_time("step_time", 0.003)
    reg.observe_time("step_time", 0.3)
    text = reg.to_prometheus()
    types = _check_prometheus(text)
    assert types["train_steps_total"] == "counter"
    assert types["loss"] == "gauge"
    assert types["step_time_seconds"] == "histogram"
    # bucket counts are cumulative & monotone, +Inf == _count
    buckets = [int(m.group(1)) for m in
               re.finditer(r'step_time_seconds_bucket\{le="[^+]*"\} (\d+)', text)]
    assert buckets == sorted(buckets)
    inf = re.search(r'step_time_seconds_bucket\{le="\+Inf"\} (\d+)', text)
    count = re.search(r"^step_time_seconds_count (\d+)$", text, re.M)
    assert int(inf.group(1)) == int(count.group(1)) == 2


# --------------------------------------------------------------------------- server

class _VanishingTracker:
    """Tracker whose worker evaporates between workers() and the per-worker
    lookups — the eviction race the /status endpoint must survive."""

    def workers(self):
        return ["w0", "ghost"]

    def is_enabled(self, w):
        if w == "ghost":
            raise KeyError(w)
        return True

    def last_heartbeat(self, w):
        if w == "ghost":
            raise KeyError(w)
        return 0.0

    def current_jobs(self):
        return []

    def updates(self):
        return {}

    def is_done(self):
        return False


def test_status_server_partial_on_vanished_worker():
    srv = StatusServer(_VanishingTracker(), MetricsRegistry()).start()
    try:
        r = urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/status")
        assert r.status == 200
        status = json.loads(r.read())
        assert status["workers"] == ["w0", "ghost"]
        assert status["enabled"] == {"w0": True}      # ghost skipped
        assert "w0" in status["heartbeats_age_s"]
        assert any("ghost" in e for e in status["errors"])
    finally:
        srv.stop()


def test_status_server_metrics_prom_endpoint():
    reg = MetricsRegistry()
    reg.increment("served", 2)
    reg.observe_time("lat", 0.01)
    srv = StatusServer(None, reg).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        r = urllib.request.urlopen(base + "/metrics.prom")
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        types = _check_prometheus(r.read().decode())
        assert types["served_total"] == "counter"
        assert types["lat_seconds"] == "histogram"
        # JSON twin still serves
        snap = json.loads(urllib.request.urlopen(base + "/metrics").read())
        assert snap["counters"]["served"] == 2
    finally:
        srv.stop()


# --------------------------------------------------------------------------- e2e

def _loss_fn(params, x, y, key):
    return jnp.mean((x @ params["w"] - y) ** 2)


def _tiny_fit(n_batches=3, epochs=2):
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((4, 2), dtype=np.float32))}
    tr = DataParallelTrainer(_loss_fn, T.sgd_lr(1e-2))
    state = tr.init_state(params)
    batches = [DataSet(rng.standard_normal((16, 4), dtype=np.float32),
                       rng.standard_normal((16, 2), dtype=np.float32))
               for _ in range(n_batches)]
    return tr.fit(state, batches, epochs=epochs)


def test_end_to_end_training_instrumentation(tmp_path):
    state, losses = _tiny_fit()
    snap = METRICS.snapshot()
    n_steps = len(losses)
    assert snap["counters"]["train_step.iterations"] == n_steps
    assert snap["gauges"]["train_step.loss"] == pytest.approx(losses[-1])
    assert snap["gauges"]["train_step.samples_per_sec"] > 0
    # compile-vs-execute split: first call in .compile, rest in train_step
    assert snap["timers"]["train_step.compile"]["count"] == 1
    st = snap["timers"]["train_step"]
    assert st["count"] == n_steps - 1
    for q in ("p50_s", "p95_s", "p99_s"):
        assert st[q] > 0
    assert st["p50_s"] <= st["p95_s"] <= st["p99_s"] <= st["max_s"]
    # steady-state steps must not carry the compile cost
    assert st["max_s"] <= snap["timers"]["train_step.compile"]["max_s"]

    # the same run produced a Perfetto-loadable chrome trace
    doc = json.loads(obs.TRACER.save_chrome_trace(
        tmp_path / "trace.json").read_text())
    names = [e["name"] for e in doc["traceEvents"]]
    assert "trainer.fit" in names and "train_step.compile" in names
    assert names.count("train_step") == n_steps - 1
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X" and ev["ts"] >= 0 and ev["dur"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
    steps = [e for e in doc["traceEvents"] if e["name"] == "train_step"]
    assert all(e["args"]["parent"] == "trainer.fit" for e in steps)

    # and a parseable Prometheus exposition with the histogram in it
    types = _check_prometheus(METRICS.to_prometheus())
    assert types["train_step_seconds"] == "histogram"
    assert types["train_step_iterations_total"] == "counter"
    assert types["train_step_loss"] == "gauge"


def test_pad_batch_counter():
    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.standard_normal((4, 2), dtype=np.float32))}
    tr = DataParallelTrainer(_loss_fn, T.sgd_lr(1e-2))
    state = tr.init_state(params)
    # 15 % 8 != 0 -> every step pads
    b = DataSet(rng.standard_normal((15, 4), dtype=np.float32),
                rng.standard_normal((15, 2), dtype=np.float32))
    tr.fit(state, [b], epochs=2)
    snap = METRICS.snapshot()
    assert snap["counters"]["train_step.pad_batch"] == 2
    assert snap["counters"]["train_step.padded_samples"] == 2 * (8 - 15 % 8)


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts constructions
    and records enter/exit by name."""

    made: list = []
    log: list = []

    def __init__(self, name):
        self.name = name
        _CountingAnnotation.made.append(name)

    def __enter__(self):
        _CountingAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _CountingAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def counted_annotations(monkeypatch):
    from deeplearning4j_tpu.observability import tracing
    monkeypatch.setattr(_CountingAnnotation, "made", [])
    monkeypatch.setattr(_CountingAnnotation, "log", [])
    monkeypatch.setattr(tracing, "_annotation_cls", _CountingAnnotation)
    return _CountingAnnotation


def test_span_enters_and_leaves_one_annotation(counted_annotations):
    t = Tracer()
    with t.span("outer", k=1):
        with t.span("inner"):
            pass
    with pytest.raises(KeyError):
        with t.span("fails"):
            raise KeyError("x")
    assert counted_annotations.made == ["outer", "inner", "fails"]
    assert counted_annotations.log == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
        ("exit", "outer"), ("enter", "fails"), ("exit", "fails")]
    # record_span is given after the fact: host-only, no annotation
    t.record_span("later", 0.0, 1.0)
    assert counted_annotations.made == ["outer", "inner", "fails"]
    assert [e["name"] for e in t.events] == ["inner", "outer", "fails", "later"]


def test_spans_are_written_into_a_running_profiler_trace(tmp_path):
    """The real ``TraceAnnotation``: a span opened while a profiler session
    runs is an event of the same name on the host plane of its trace."""
    from jax.profiler import ProfileData

    with obs.profiler_trace(str(tmp_path)):
        with trace.span("trainer.fence", n=1):
            with trace.span("trainer.fence.wait"):
                jnp.ones(8).block_until_ready()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    found = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("trainer.")}
    assert set(found) == {"trainer.fence", "trainer.fence.wait"}
    (o0, o1), (i0, i1) = found["trainer.fence"], found["trainer.fence.wait"]
    assert o0 <= i0 < i1 <= o1


def test_fit_spans_name_the_host_phases():
    obs.TRACER.clear()
    state, losses = _tiny_fit(n_batches=3, epochs=1)
    by_name = {}
    for e in obs.TRACER.to_chrome_trace()["traceEvents"]:
        by_name.setdefault(e["name"], []).append(e["args"]["parent"])
    # one data_wait per batch and one for the end of the stream
    assert by_name["trainer.data_wait"] == ["trainer.fit"] * 4
    assert set(by_name["trainer.fence"]) == {"trainer.fit"}
    n_fences = len(by_name["trainer.fence"])
    # one wait per loss (a short span is whole inside a traced slice where a
    # window's whole wait is not), one read per fence
    assert by_name["trainer.fence.wait"] == ["trainer.fence"] * len(losses)
    assert by_name["trainer.fence.read"] == ["trainer.fence"] * n_fences


def test_disabled_mode_records_nothing(counted_annotations, monkeypatch):
    from deeplearning4j_tpu.observability import tracing
    spans_made = []
    real_init = tracing.Span.__init__
    monkeypatch.setattr(
        tracing.Span, "__init__",
        lambda self, *a, **k: (spans_made.append(a[1]), real_init(self, *a, **k))[1])
    obs.disable()
    try:
        state, losses = _tiny_fit(n_batches=2, epochs=1)
        assert len(losses) == 2          # training itself still works
        # no Span and no TraceAnnotation on the dispatch path
        assert spans_made == [] and counted_annotations.made == []
        snap = METRICS.snapshot()
        assert snap["counters"] == {} and snap["timers"] == {}
        assert snap["gauges"] == {}
        assert obs.TRACER.to_chrome_trace()["traceEvents"] == []
        # and span() hands back the shared no-op (no per-step allocation)
        assert trace.span("x") is obs.NOOP_SPAN
        assert METRICS.time("x") is obs.NOOP_SPAN
    finally:
        obs.enable()


def test_checkpoint_instrumentation(tmp_path):
    from deeplearning4j_tpu.parallel.checkpoint import CheckpointManager

    mgr = CheckpointManager(tmp_path)
    params = {"w": np.ones((2, 2), np.float32)}
    mgr.save(3, params)
    mgr.restore(params)
    snap = METRICS.snapshot()
    assert snap["counters"]["checkpoint.saves"] == 1
    assert snap["counters"]["checkpoint.restores"] == 1
    assert snap["timers"]["checkpoint.save"]["count"] == 1
    assert snap["timers"]["checkpoint.restore"]["count"] == 1


def test_scaleout_job_lifecycle_metrics():
    from deeplearning4j_tpu.parallel.scaleout import (
        CollectionJobIterator, DistributedRunner)

    class Performer:
        def __init__(self, tracker):
            pass

        def perform(self, job):
            job.result = np.asarray([float(job.work)])

        def update(self, *a):
            pass

    runner = DistributedRunner(CollectionJobIterator([1, 2, 3, 4]),
                               Performer, n_workers=2)
    out = runner.run(max_wall_s=30.0)
    assert out is not None
    snap = METRICS.snapshot()
    assert snap["counters"]["scaleout.runs"] == 1
    assert snap["counters"]["scaleout.jobs_dispatched"] == 4
    assert snap["counters"]["scaleout.jobs_completed"] == 4
    assert snap["counters"]["scaleout.updates"] == 4
    assert snap["timers"]["scaleout.job"]["count"] == 4


def test_multilayer_fit_instrumentation():
    from deeplearning4j_tpu.nn.conf import (
        NeuralNetConfiguration, OptimizationAlgorithm, list_builder)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    base = NeuralNetConfiguration(
        n_in=4, n_out=3, lr=0.1, num_iterations=2,
        optimization_algo=OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT,
        activation="tanh")
    conf = (list_builder(base, 2)
            .hidden_layer_sizes(8)
            .override(1, kind="output", activation="softmax", loss="mcxent")
            .pretrain(False)
            .build())
    net = MultiLayerNetwork(conf)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((12, 4)).astype(np.float32)
    labels = rng.integers(0, 3, 12)
    net.fit_arrays(x, labels)
    snap = METRICS.snapshot()
    assert snap["counters"]["multilayer.iterations"] >= 2
    assert snap["timers"]["multilayer.fit_iteration"]["count"] >= 2
    assert "multilayer.loss" in snap["gauges"]
    names = [e["name"] for e in obs.TRACER.to_chrome_trace()["traceEvents"]]
    assert "multilayer.fit" in names


def test_device_memory_sampler_is_safe_on_cpu():
    # CPU backend has no memory_stats — must be a clean no-op
    from deeplearning4j_tpu.observability import sample_device_memory
    assert sample_device_memory(METRICS) >= 0


def test_metrics_dump_rendering():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "metrics_dump",
        Path(__file__).resolve().parent.parent / "tools" / "metrics_dump.py")
    md = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(md)

    reg = MetricsRegistry()
    reg.increment("steps", 5)
    reg.gauge("loss", 0.25)
    reg.observe_time("step", 0.01)
    srv = StatusServer(None, reg).start()
    try:
        rc = md.main(["--port", str(srv.port)])
        assert rc == 0
        rc = md.main(["--url", f"http://127.0.0.1:{srv.port}", "--prom"])
        assert rc == 0
    finally:
        srv.stop()
    out = md.render_metrics(reg.snapshot())
    assert "steps" in out and "p95" in out
    # no state gauges published -> no state-memory section
    assert md.render_state_memory(reg.snapshot()) is None
    reg.gauge("train.params_bytes.device.0", 2048.0)
    reg.gauge("train.opt_state_bytes.device.0", 256.0)
    section = md.render_state_memory(reg.snapshot())
    assert "state memory" in section and "2.00KiB" in section \
        and "256B" in section
    assert "state memory" in md.render_metrics(reg.snapshot())


def test_sample_state_bytes_gauges_sharded_trees():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.observability import sample_state_bytes
    from deeplearning4j_tpu.parallel.mesh import DP, local_mesh

    mesh = local_mesh()
    n_dp = mesh.shape[DP]
    rep = jax.device_put(jnp.zeros((n_dp * 4,), jnp.float32),
                         NamedSharding(mesh, P()))
    shd = jax.device_put(jnp.zeros((n_dp * 4,), jnp.float32),
                         NamedSharding(mesh, P(DP)))
    assert sample_state_bytes({"w": rep}, {"m": shd}, METRICS) == n_dp
    g = METRICS.snapshot()["gauges"]
    # replicated: every device holds the whole leaf; sharded: 1/ndp each
    assert g["train.params_bytes.device.0"] == n_dp * 4 * 4
    assert g["train.opt_state_bytes.device.0"] == 4 * 4
    # non-array leaves pass through silently
    assert sample_state_bytes({"k": 3}, (), METRICS) == 0


def test_observe_shim_still_exports_legacy_names():
    from deeplearning4j_tpu.parallel import observe

    assert observe.METRICS is METRICS
    assert observe.MetricsRegistry is MetricsRegistry
    assert observe.StatusServer is StatusServer
    assert observe.StepTimer is StepTimer


# --------------------------------------------------------------------------- trace identity (PR 10)

def test_span_ids_mint_and_inherit():
    tracer = Tracer()
    with tracer.span("root") as r:
        with tracer.span("child") as c:
            assert c.trace_id == r.trace_id
            assert c.parent_id == r.span_id
            assert c.span_id != r.span_id
    assert re.fullmatch(r"[0-9a-f]{32}", r.trace_id)
    assert re.fullmatch(r"[0-9a-f]{16}", r.span_id)
    events = {e["name"]: e["args"] for e in tracer.to_chrome_trace()["traceEvents"]}
    assert events["child"]["trace_id"] == events["root"]["trace_id"]
    assert events["child"]["parent_span_id"] == events["root"]["span_id"]
    assert events["root"]["parent_span_id"] is None


def test_traceparent_roundtrip_and_rejection():
    tid, sid = trace.new_trace_id(), trace.new_span_id()
    header = f"00-{tid}-{sid}-01"
    assert trace.parse_traceparent(header) == (tid, sid)
    assert trace.parse_traceparent(header.upper()) == (tid, sid)
    for bad in (None, "", "garbage", "00-short-ids-01",
                f"00-{'0' * 32}-{sid}-01",        # all-zero trace id
                f"00-{tid}-{'0' * 16}-01",        # all-zero span id
                f"zz-{tid}-{sid}-01",             # non-hex version
                f"00-{tid}-{sid}"):               # missing flags
        assert trace.parse_traceparent(bad) is None, bad


def test_bind_adopts_remote_context():
    """A span opened inside ``bind`` joins the bound trace — the server
    side of traceparent propagation."""
    tid, parent = trace.new_trace_id(), trace.new_span_id()
    with trace.bind(tid, parent):
        assert trace.current_traceparent() == f"00-{tid}-{parent}-01"
        with trace.span("handler") as sp:
            assert sp.trace_id == tid
            assert sp.parent_id == parent
    assert trace.current_trace_context() is None


def test_current_traceparent_reflects_open_span():
    with trace.span("outer") as sp:
        tp = trace.current_traceparent()
        assert tp == f"00-{sp.trace_id}-{sp.span_id}-01"
    assert trace.current_traceparent() is None


def test_record_span_explicit_times():
    import time as _time

    tracer = Tracer()
    tid = trace.new_trace_id()
    t0 = _time.perf_counter()
    sid = tracer.record_span("explicit", t0, 0.25, trace_id=tid,
                             parent_id="a" * 16, request=7)
    (ev,) = tracer.to_chrome_trace()["traceEvents"]
    assert ev["args"]["trace_id"] == tid
    assert ev["args"]["span_id"] == sid
    assert ev["args"]["parent_span_id"] == "a" * 16
    assert ev["args"]["request"] == 7
    assert abs(ev["dur"] - 0.25e6) < 1.0      # 250ms in µs
    assert ev["ts"] >= 0


def test_dropped_events_counted_and_stamped():
    """Satellite 1: overrunning the bounded ring is observable — a
    counter increments and the export carries the drop count."""
    tracer = Tracer(max_events=16)
    before = METRICS.snapshot()["counters"].get("trace.dropped_events", 0)
    for _ in range(64):
        with tracer.span("s"):
            pass
    doc = tracer.to_chrome_trace()
    assert len(doc["traceEvents"]) == 16
    assert doc["metadata"]["dropped"] == 48
    after = METRICS.snapshot()["counters"].get("trace.dropped_events", 0)
    assert after - before == 48
    tracer.clear()
    assert tracer.to_chrome_trace()["metadata"]["dropped"] == 0


def test_chrome_trace_validity_and_nesting(tmp_path):
    """Satellite 3: exported traces parse, every ts/dur is non-negative,
    and expanding complete events to B/E pairs yields a properly nested
    per-thread stack (no partial overlap from the ``with`` API)."""
    tracer = Tracer()
    with tracer.span("a"):
        with tracer.span("b"):
            with tracer.span("c"):
                pass
        with tracer.span("d"):
            pass
    doc = json.loads(tracer.save_chrome_trace(tmp_path / "t.json").read_text())
    events = doc["traceEvents"]
    assert len(events) == 4
    be = []
    for ev in events:
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        be.append((ev["ts"], "B", ev["name"]))
        be.append((ev["ts"] + ev["dur"], "E", ev["name"]))
    # sort by time; at equal timestamps E comes before B (adjacent spans)
    be.sort(key=lambda t: (t[0], t[1] == "B"))
    stack = []
    for _, ph, name in be:
        if ph == "B":
            stack.append(name)
        else:
            assert stack and stack[-1] == name, \
                f"unbalanced B/E pairs: closing {name} with stack {stack}"
            stack.pop()
    assert stack == []


@pytest.mark.lockguard
def test_registry_and_tracer_survive_serving_style_contention():
    """Satellite 3: hammer observe_time/increment/to_prometheus (and the
    listener fan-out to the flight recorder) from concurrent threads the
    way the serving engine + HTTP scrape threads do, under instrumented
    locks — no deadlock, no lost-update assertion, no exception."""
    from deeplearning4j_tpu.observability import FLIGHTREC

    reg = METRICS           # the real singleton: listener fan-out included
    errors = []
    n_threads, n_iter = 6, 300

    def worker(i):
        try:
            for k in range(n_iter):
                reg.increment("hammer.count")
                reg.observe_time("hammer.lat", 0.001 * (k % 7 + 1))
                reg.gauge("hammer.gauge", float(k))
                if k % 50 == 0:
                    reg.to_prometheus()
                    reg.snapshot()
        except Exception as e:          # pragma: no cover - failure path
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errors
    snap = METRICS.snapshot()
    assert snap["counters"]["hammer.count"] == n_threads * n_iter
    assert snap["timers"]["hammer.lat"]["count"] == n_threads * n_iter
    # the passive listener saw the traffic too (bounded ring, no growth)
    assert len(FLIGHTREC.metric_events) <= FLIGHTREC.metric_events.maxlen
