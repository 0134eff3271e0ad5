"""Serving-subsystem tests (DESIGN.md §13): continuous-batching parity
with the offline sampler, admission control, hot reload, slot lifecycle,
compile-cache discipline, and the HTTP surface.

The acceptance contract is token-level: a request served through the
slot-pool engine must produce EXACTLY the tokens
``Transformer.sample(..., key=jax.random.key(seed), kv_cache=True)``
produces for the same (prompt, max_new, temperature, seed) — continuous
batching is an implementation detail, not a semantics change.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import TransformerConfig, TransformerLM
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.parallel.checkpoint import CheckpointManager
from deeplearning4j_tpu.serving import (BatchScorer, InferenceEngine,
                                        ModelServer, QueueFull, RequestQueue,
                                        ServingClient, ServingConfig,
                                        ServingError)
from deeplearning4j_tpu.serving.batcher import DeadlineExceeded, GenerateRequest


def tiny_cfg(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_layers", 2)
    kw.setdefault("d_ff", 64)
    kw.setdefault("max_len", 32)
    kw.setdefault("dtype", jnp.float32)  # exact parity comparisons
    kw.setdefault("remat", False)
    return TransformerConfig(**kw)


@pytest.fixture(scope="module")
def lm():
    """Untrained tiny LM — parity only needs determinism, not quality."""
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    return model, model.init(jax.random.key(7))


@pytest.fixture(scope="module")
def cycle_lm():
    """The test_transformer.py trained-cycle idiom: a model that greedily
    continues a periodic stream, so EOS/reload tests can assert exact
    token content, not just shapes."""
    from deeplearning4j_tpu.optimize import transforms as T

    period = [3, 1, 4, 1, 5, 9, 2, 6]
    cfg = tiny_cfg(vocab_size=16, causal=True)
    stream = np.array(period * 32, np.int32)
    span = cfg.max_len + 1
    n = len(stream) // span
    blocks = stream[:n * span].reshape(n, span)
    tokens = jnp.asarray(blocks[:, :-1])
    targets = jnp.asarray(blocks[:, 1:])
    model = TransformerLM(cfg)
    tx = T.adamw(0.01)
    params = model.init(jax.random.key(0))
    opt = model.init_opt(params, tx)
    step = model.build_train_step(tx)
    for _ in range(60):
        params, opt, _ = step(params, opt, tokens, targets)
    # fixture sanity: the offline sampler continues the cycle
    out = model.sample(params, period[:4], length=8, temperature=0.0)
    assert out == (period * 3)[:len(out)]
    return model, params, period


def _expected(model, params, prompt, n, temp, seed):
    return model.sample(params, prompt, n, temperature=temp,
                        key=jax.random.key(seed),
                        kv_cache=True)[len(prompt):]


# --------------------------------------------------------------- admission
def test_queue_backpressure_and_deadline():
    q = RequestQueue(max_depth=2, max_batch_delay_ms=0.0)

    def req(**kw):
        return GenerateRequest(prompt=[1], max_new_tokens=1, **kw)

    a, b = q.submit(req()), q.submit(req())
    with pytest.raises(QueueFull) as ei:
        q.submit(req())
    assert ei.value.status == 429
    assert q.take(8) == [a, b]      # FIFO, rejection freed no slot

    # a request whose deadline expired while queued never reaches a slot
    p = q.submit(req(deadline_s=time.monotonic() - 1.0))
    assert q.take(8) == []
    assert p.done()
    with pytest.raises(DeadlineExceeded) as ei:
        p.result(0)
    assert ei.value.status == 504

    counters = METRICS.snapshot()["counters"]
    assert counters["serving.rejected"] == 1
    assert counters["serving.deadline_dropped"] == 1


def test_submit_validation_and_engine_backpressure(lm):
    model, params = lm
    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=1, max_queue=2))
    with pytest.raises(ValueError, match="empty"):
        engine.submit([], 4)
    with pytest.raises(ValueError, match="out of range"):
        engine.submit([999], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit([1], 0)
    with pytest.raises(ValueError, match="max_len"):
        engine.submit([1] * 10, 30)
    engine.submit([1], 2)
    engine.submit([2], 2)
    with pytest.raises(QueueFull):   # engine not started: queue fills
        engine.submit([3], 2)
    engine.stop()                    # fails the two queued handles


# ----------------------------------------------------- continuous batching
@pytest.mark.lockguard
def test_continuous_batching_matches_offline_sample(lm):
    """The acceptance test: mixed greedy/temperature traffic through 3
    concurrent slots is token-identical to the sequential sampler — run
    with instrumented locks, so a lock-order inversion or unguarded
    shared write anywhere in the engine/queue path fails it too."""
    model, params = lm
    plans = [([5, 1, 4], 6, 0.0, 0),
             ([2, 8, 2, 8, 2, 8, 2, 8, 2], 4, 0.8, 123),
             ([7], 5, 0.0, 3),
             ([3, 2, 1, 0, 5], 6, 1.0, 9),
             ([11, 12], 3, 0.8, 77)]
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in plans]

    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=3, resolve_every=2))
    # submit everything BEFORE the loop starts, so the first device batch
    # is provably full (3/3 slots decoding concurrently)
    handles = [engine.submit(p, n, temperature=t, seed=s)
               for p, n, t, s in plans]
    # cold start on purpose: these plans touch only the 8/16 buckets, so
    # the warmup ladder would compile graphs this test never dispatches
    with engine.start(warmup=False):
        outs = [h.result(60.0) for h in handles]

    assert [o.tokens for o in outs] == want
    assert all(o.finish_reason == "length" for o in outs)
    assert all(o.ttft_s is not None and o.latency_s > 0 for o in outs)
    snap = METRICS.snapshot()
    assert snap["timers"]["serving.batch_fill_ratio"]["max_s"] == 1.0
    assert snap["counters"]["serving.completed"] == len(plans)
    assert snap["counters"]["serving.tokens"] == sum(len(w) for w in want)


def test_prefill_recompiles_bounded_by_bucket_count(lm):
    """PR-2 discipline: prompt lengths hash to a power-of-two bucket
    ladder, and ``warmup()`` precompiles EVERY rung up to ``max_len`` —
    so the recompile counter sits at the ladder size before the first
    request and NEVER moves under traffic, whatever the prompt length
    (first-request TTFT pays no compile stall)."""
    model, params = lm
    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=2, resolve_every=2))
    ladder = [8, 16, 32]                 # min_prefill_bucket=8 .. max_len=32
    with engine:   # warmup compiled the whole ladder
        assert METRICS.snapshot()["counters"][
            "serving.prefill.recompile"] == len(ladder)
        assert engine.stats()["prefill_buckets"] == ladder
        for p_len in (3, 5, 8, 9, 12, 16, 17, 25):   # every rung hit
            engine.generate([1] * p_len, 2)
        assert METRICS.snapshot()["counters"][
            "serving.prefill.recompile"] == len(ladder)
        assert engine.stats()["prefill_buckets"] == ladder


def test_eos_evicts_slot_and_reuses_it(cycle_lm):
    """4 requests through 2 slots, each finishing on EOS long before its
    length budget — completion requires eviction AND slot reuse."""
    model, params, period = cycle_lm
    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=2, resolve_every=2))
    with engine:
        handles = [engine.submit(period[:4], 8, eos_id=9, seed=i)
                   for i in range(4)]
        outs = [h.result(60.0) for h in handles]
    # greedy continuation is 5 9 2 6 ... -> stops at the injected EOS id 9
    assert all(o.tokens == [5, 9] for o in outs)
    assert all(o.finish_reason == "eos" for o in outs)
    st = engine.stats()
    assert st["admitted"] == 4 and st["completed"] == 4
    assert st["active"] == 0 and st["free"] == 2
    assert METRICS.snapshot()["counters"]["serving.completed"] == 4


# --------------------------------------------------------- int8 decode opt-in
def test_int8_decode_opt_in_matches_offline_quantized_sample(lm):
    """``int8_decode=True`` quantizes the SERVING copy of the params and
    must be token-identical to sampling offline with the same quantized
    tree (decode_step picks the int8 path on key presence).  The reload
    template stays float so checkpoint restore shapes are unchanged."""
    from deeplearning4j_tpu.ops.pallas.matmul_int8 import (
        quantize_params_for_decode)

    model, params = lm
    qp = quantize_params_for_decode(params, model.cfg)
    plans = [([5, 1, 4], 6, 0.0, 0),
             ([7], 5, 0.0, 3),
             ([2, 8, 2, 8], 4, 0.8, 123)]
    want = [_expected(model, qp, p, n, t, s) for p, n, t, s in plans]

    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=2, resolve_every=2,
                                               int8_decode=True))
    assert "head_q" in engine._params            # serving copy: quantized
    assert "head_q" not in engine._raw_params    # reload template: float
    handles = [engine.submit(p, n, temperature=t, seed=s)
               for p, n, t, s in plans]
    # cold start on purpose: these plans touch only the 8/16 buckets, so
    # the warmup ladder would compile graphs this test never dispatches
    with engine.start(warmup=False):
        outs = [h.result(60.0) for h in handles]
    assert [o.tokens for o in outs] == want
    assert "serving.quantize" in METRICS.snapshot()["timers"]


def test_int8_decode_is_off_by_default(lm):
    """The default-config engine must serve the float params untouched —
    int8 is strictly opt-in (the acceptance contract's parity tests above
    all run through this default path)."""
    model, params = lm
    engine = InferenceEngine(model, params=params, cfg=ServingConfig())
    assert ServingConfig().int8_decode is False
    assert engine._params is engine._raw_params
    assert "head_q" not in engine._params
    engine.stop()


# -------------------------------------------------------------- hot reload
def test_hot_reload_mid_traffic(cycle_lm, tmp_path):
    """Swap to a newer checkpoint WITHOUT draining: the in-flight request
    still completes, and post-reload traffic decodes with the new params."""
    model, trained, period = cycle_lm
    rand = model.init(jax.random.key(99))
    ckdir = tmp_path / "ck"
    mgr = CheckpointManager(ckdir, keep=5)
    mgr.save(1, rand)

    engine = InferenceEngine(model, checkpoint=str(ckdir),
                             cfg=ServingConfig(slots=2, resolve_every=2))
    assert engine.stats()["loaded_step"] == 1
    with engine:
        inflight = engine.submit(period[:4], 24)      # long, likely mid-decode
        mgr.save(2, trained)
        assert engine.reload() == 2
        out = inflight.result(60.0)
        assert out.finish_reason == "length" and len(out.tokens) == 24
        post = engine.generate(period[:4], 8)
        assert post.tokens == (period * 2)[4:12]      # trained-cycle greedy
    snap = METRICS.snapshot()
    assert snap["counters"]["serving.reloads"] == 1
    assert snap["gauges"]["serving.loaded_step"] == 2
    assert engine.reload() == 2                        # same step: no-op
    assert METRICS.snapshot()["counters"]["serving.reloads"] == 1


def test_checkpoint_read_only_serving_path(lm, tmp_path):
    model, _ = lm
    with pytest.raises(FileNotFoundError):
        CheckpointManager.open_read_only(tmp_path / "missing")
    ckdir = tmp_path / "ck"
    mgr = CheckpointManager(ckdir, keep=2)
    with pytest.raises(FileNotFoundError, match="no verified checkpoint"):
        InferenceEngine(model, checkpoint=str(ckdir))  # dir exists, no ckpt
    mgr.save(1, {"w": np.zeros(3, np.float32)})
    ro = CheckpointManager.open_read_only(ckdir)
    assert ro.latest_valid_step() == 1
    with pytest.raises(RuntimeError, match="read-only"):
        ro.save(2, {"w": np.zeros(3, np.float32)})


# ------------------------------------------------------------ chaos sites
def test_chaos_sites_fixed_plan(lm):
    """Deterministic twin of tools/chaos_smoke.py's serving leg: a decode
    fault skips the dispatch (state untouched -> tokens unchanged), a
    submit fault raises to the caller and a retry wins."""
    from deeplearning4j_tpu.resilience import FaultSpec, inject_faults
    from deeplearning4j_tpu.resilience.faults import FAULTS, InjectedFault

    model, params = lm
    plans = [([4, 2], 5, 0.0, 0), ([1, 2, 3], 4, 0.8, 5), ([9], 3, 0.0, 1)]
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in plans]
    specs = [FaultSpec("serving.decode", probability=1.0, max_fires=2),
             FaultSpec("serving.request", at_step=2)]
    retried = 0
    with inject_faults(*specs, seed=0):
        engine = InferenceEngine(
            model, params=params,
            cfg=ServingConfig(slots=2, resolve_every=2)).start()
        handles = []
        for p, n, t, s in plans:
            try:
                handles.append(engine.submit(p, n, temperature=t, seed=s))
            except InjectedFault:
                retried += 1
                handles.append(engine.submit(p, n, temperature=t, seed=s))
        outs = [h.result(60.0) for h in handles]
        engine.stop()
        assert FAULTS.fire_count("serving.decode") == 2
        assert FAULTS.fire_count("serving.request") == 1
    assert retried == 1
    assert [o.tokens for o in outs] == want
    assert METRICS.snapshot()["counters"]["serving.decode.faults"] == 2


# ------------------------------------------------------------ batch scorer
def test_batch_scorer_coalesces_and_matches_direct():
    calls = []
    w = np.arange(12, dtype=np.float32).reshape(4, 3)

    def fn(xs):
        calls.append(np.asarray(xs).shape[0])
        return np.asarray(xs) @ w

    xs = np.random.default_rng(0).normal(size=(6, 4)).astype(np.float32)
    with BatchScorer(fn, max_batch=8) as sc:
        np.testing.assert_allclose(sc.score_batch(xs), xs @ w, rtol=1e-6)
        np.testing.assert_allclose(sc.score(xs[0]), xs[0] @ w, rtol=1e-6)
        with pytest.raises(ValueError, match="row shape"):
            sc.submit(np.zeros((5,), np.float32))
    assert calls and all(c & (c - 1) == 0 for c in calls)  # pow2 buckets only
    counters = METRICS.snapshot()["counters"]
    assert counters["serving.score.rows"] == 7
    assert counters["serving.score.recompile"] == len(set(calls))


def test_batch_scorer_serves_multilayer_network():
    """The zoo/MultiLayerNetwork half of the serving story: ``net.output``
    drops into the scorer as-is."""
    from deeplearning4j_tpu.nn import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import (NeuralNetConfiguration,
                                            OptimizationAlgorithm,
                                            list_builder)

    base = NeuralNetConfiguration(
        n_in=4, n_out=3, lr=0.1, momentum=0.9, use_adagrad=True,
        num_iterations=1,
        optimization_algo=OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT,
        activation="tanh")
    conf = (list_builder(base, 2)
            .hidden_layer_sizes(8)
            .override(1, kind="output", activation="softmax", loss="mcxent")
            .pretrain(False)
            .build())
    net = MultiLayerNetwork(conf)
    net.init()
    x = np.random.default_rng(1).normal(size=(5, 4)).astype(np.float32)
    direct = np.asarray(net.output(x))
    with BatchScorer(net.output, max_batch=8) as sc:
        served = sc.score_batch(x)
    np.testing.assert_allclose(served, direct, rtol=1e-5, atol=1e-6)


# -------------------------------------------------------------- HTTP layer
def test_http_server_end_to_end(lm):
    model, params = lm
    w = np.arange(8, dtype=np.float32).reshape(4, 2)

    def score_fn(xs):
        return np.asarray(xs, np.float32) @ w

    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=2, resolve_every=2))
    scorer = BatchScorer(score_fn, max_batch=8)
    with engine, scorer, ModelServer(engine=engine, scorer=scorer) as server:
        client = ServingClient(port=server.port)
        prompt, n, seed = [5, 1, 4], 6, 11
        want = _expected(model, params, prompt, n, 0.8, seed)
        out = client.generate(prompt, max_new_tokens=n, temperature=0.8,
                              seed=seed)
        assert out["tokens"] == want and out["finish_reason"] == "length"

        rows = [[1.0, 2.0, 3.0, 4.0], [0.0, -1.0, 0.5, 2.0]]
        np.testing.assert_allclose(np.asarray(client.score(rows)),
                                   np.asarray(rows, np.float32) @ w,
                                   rtol=1e-6)
        health = client.healthz()
        assert health["ok"] and health["engine"]["slots"] == 2
        prom = client.metrics_prom()
        assert "serving_request_latency_seconds" in prom
        assert "serving_tokens_total" in prom

        with pytest.raises(ServingError) as e400:
            client._json("/v1/generate", {"max_new_tokens": 2})  # no prompt
        assert e400.value.status == 400
        with pytest.raises(ServingError) as e409:
            client.reload()                      # no checkpoint attached
        assert e409.value.status == 409
        with pytest.raises(ServingError) as e404:
            client._json("/v1/nope", {})
        assert e404.value.status == 404


# ------------------------------------------------- concurrency regressions

def test_pending_result_completion_is_single_shot():
    """_complete/_fail race by design (expiry vs. resolution vs. shutdown);
    exactly one transition wins and the rest are no-ops."""
    p = RequestQueue().submit(GenerateRequest(prompt=[1], max_new_tokens=1))
    assert p._fail(DeadlineExceeded("expired")) is True
    assert p._complete("late value") is False       # rival lost
    assert p._fail(RuntimeError("also late")) is False
    with pytest.raises(DeadlineExceeded):           # first transition stuck
        p.result(0)


def test_claim_arbitrates_expiry_vs_admission_under_contention():
    """Regression for the check-then-act window between take() and slot
    occupancy: an engine-like thread claims each request at the moment it
    would take a slot, while deadlines straddle the claim point.  Every
    request must end in EXACTLY one of {claimed-and-completed, expired} —
    never both, never neither."""
    q = RequestQueue(max_depth=256, max_batch_delay_ms=0.0)
    completed, stop = [], threading.Event()

    def engine_like():
        while not stop.is_set():
            for p in q.take(4, block_s=0.01):
                time.sleep(0.002)        # widen the take->claim window
                if q.claim(p):
                    assert p._complete(f"ok-{p.request.id}")
                    completed.append(p)

    t = threading.Thread(target=engine_like)
    t.start()
    handles = []
    now = time.monotonic
    for i in range(60):
        # deadlines scattered tightly around the claim point, some already
        # dead, some comfortably alive
        dl = now() + (i % 3 - 1) * 0.004
        handles.append(q.submit(GenerateRequest(
            prompt=[1], max_new_tokens=1, deadline_s=dl)))
    deadline = time.monotonic() + 30.0
    while not all(h.done() for h in handles):
        assert time.monotonic() < deadline, "requests stranded"
        time.sleep(0.005)
    stop.set()
    t.join(10.0)
    assert not t.is_alive()

    outcomes = {"completed": 0, "expired": 0}
    for h in handles:
        try:
            val = h.result(0)
            assert val == f"ok-{h.request.id}"
            outcomes["completed"] += 1
        except DeadlineExceeded:
            outcomes["expired"] += 1
    assert sum(outcomes.values()) == len(handles)
    counters = METRICS.snapshot()["counters"]
    assert counters.get("serving.deadline_dropped", 0) == outcomes["expired"]
    assert len(completed) == outcomes["completed"]


def test_claim_refuses_already_failed_request():
    q = RequestQueue()
    p = q.submit(GenerateRequest(prompt=[1], max_new_tokens=1))
    p._fail(RuntimeError("shutdown"))
    assert q.claim(p) is False          # never resurrect a dead request


def test_stats_and_stop_race_free_during_traffic(lm):
    """Callers hammer stats() from several threads while requests flow and
    the engine shuts down mid-read — slot bookkeeping is lock-consistent:
    no snapshot ever shows more slots than exist (a slot may be in
    transit between free and active while its prefill runs, so the sum
    can briefly undershoot, never overshoot) and nothing throws."""
    model, params = lm
    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=2, resolve_every=2))
    errors, stop = [], threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                s = engine.stats()
                assert 0 <= s["active"] + s["free"] <= s["slots"]
            except Exception as e:  # noqa: BLE001 - collected for the assert
                errors.append(e)

    ts = [threading.Thread(target=hammer) for _ in range(3)]
    for t in ts:
        t.start()
    try:
        # cold start: 3-token prompts touch only the 8 bucket, and the
        # race under test is stats()-vs-serve, not warmup
        with engine.start(warmup=False):
            outs = [engine.submit([1, 2, 3], 2, seed=i) for i in range(6)]
            for h in outs:
                h.result(60.0)
    finally:
        stop.set()
        for t in ts:
            t.join(10.0)
    assert errors == []
    assert engine.stats()["completed"] == 6


# ---------------------------------------------------------- request tracing

def test_request_trace_chain_over_http(lm):
    """PR 10 acceptance: a traced client call propagates its W3C
    traceparent over HTTP, and the engine's queue_wait -> prefill ->
    decode -> emit spans all share the CLIENT's trace id, parented under
    one serving.request root."""
    from deeplearning4j_tpu.observability import TRACER, trace

    model, params = lm
    TRACER.clear()
    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=2, resolve_every=2))
    with engine, ModelServer(engine=engine) as server:
        client = ServingClient(port=server.port)
        with trace.span("client.generate") as sp:
            out = client.generate([5, 1, 4], max_new_tokens=6)
        client_trace = sp.trace_id
        client_span = sp.span_id
    assert len(out["tokens"]) == 6

    events = [e for e in TRACER.to_chrome_trace()["traceEvents"]
              if (e["args"].get("trace_id") == client_trace
                  and e["name"].startswith("serving."))]
    names = {e["name"] for e in events}
    assert {"serving.request", "serving.queue_wait", "serving.prefill",
            "serving.decode.segment", "serving.emit"} <= names

    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (root,) = by_name["serving.request"]
    # the server-side root is a CHILD of the client span (joined, not minted)
    assert root["args"]["parent_span_id"] == client_span
    for name in ("serving.queue_wait", "serving.prefill",
                 "serving.decode.segment", "serving.emit"):
        for e in by_name[name]:
            assert e["args"]["parent_span_id"] == root["args"]["span_id"]
    # phases sit inside the root on the timeline (small tolerance: span
    # ends are stamped on the serve thread after the phase boundary)
    t0, t1 = root["ts"], root["ts"] + root["dur"]
    for name in ("serving.queue_wait", "serving.prefill", "serving.emit"):
        for e in by_name[name]:
            assert e["ts"] >= t0 - 1e3
            assert e["ts"] + e["dur"] <= t1 + 1e3


def test_untraced_request_mints_trace_and_decode_mfu_lands(lm, cpu_peak_row):
    """Without a caller span the engine mints a fresh trace id at
    admission; the decode loop publishes serving.decode_mfu either way."""
    from deeplearning4j_tpu.observability import METRICS, TRACER

    model, params = lm
    TRACER.clear()
    engine = InferenceEngine(model, params=params,
                             cfg=ServingConfig(slots=2, resolve_every=2))
    with engine:
        engine.generate([3, 1, 4], max_new_tokens=5)
    roots = [e for e in TRACER.to_chrome_trace()["traceEvents"]
             if e["name"] == "serving.request"]
    assert len(roots) == 1
    tid = roots[0]["args"]["trace_id"]
    assert tid and len(tid) == 32 and int(tid, 16) != 0
    gauges = METRICS.snapshot()["gauges"]
    assert gauges["serving.decode_mfu"] > 0
    assert np.isfinite(gauges["serving.decode_mfu"])


def test_disabled_observability_serves_without_spans(lm):
    """DL4J_TPU_OBS=0 contract: with the layer disabled the engine still
    serves, and records no spans, no cost capture, no MFU gauges."""
    from deeplearning4j_tpu import observability as obs
    from deeplearning4j_tpu.observability import METRICS, TRACER

    model, params = lm
    TRACER.clear()
    METRICS.reset()
    obs.disable()
    try:
        engine = InferenceEngine(model, params=params,
                                 cfg=ServingConfig(slots=2, resolve_every=2))
        with engine:
            out = engine.generate([5, 1, 4], max_new_tokens=4)
    finally:
        obs.enable()
    assert len(out.tokens) == 4
    assert TRACER.to_chrome_trace()["traceEvents"] == []
    assert "serving.decode_mfu" not in METRICS.snapshot()["gauges"]
    assert engine._decode_cost is None
