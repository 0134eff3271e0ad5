"""Paged-KV / prefix-sharing / speculative-decoding tests (DESIGN.md §17).

The contract under test is the PR-9 tentpole's acceptance bar: with
``paged=True`` (any page size, including sizes that do NOT divide
``max_len``), with ``prefix_cache=True``, with ``speculative=True``, and
with all three together, the engine's served tokens stay BITWISE
identical to ``Transformer.sample(..., kv_cache=True)`` — paging,
aliasing and draft-verify are memory/throughput techniques, never a
semantics change.  Alongside parity: page refcount hygiene (nothing
leaks, nothing aliased is ever wiped or reused), pool-exhaustion
backpressure (429, not a crash), and the chaos site for it.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.models.transformer import (TransformerLM, decode_step,
                                                   decode_step_paged,
                                                   decode_window,
                                                   init_decode_cache,
                                                   init_paged_cache)
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.parallel.checkpoint import CheckpointManager
from deeplearning4j_tpu.resilience import FaultSpec, inject_faults
from deeplearning4j_tpu.serving import (InferenceEngine, PagePool,
                                        PagePoolExhausted, ServingConfig)
from test_transformer import tiny_cfg


@pytest.fixture(scope="module")
def lm():
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    return model, model.init(jax.random.key(7))


PLANS = [([5, 1, 4], 6, 0.0, 0),
         ([2, 8, 2, 8, 2, 8, 2, 8, 2], 4, 0.8, 123),
         ([7], 5, 0.0, 3),
         ([3, 2, 1, 0, 5], 6, 1.0, 9)]


def _expected(model, params, prompt, n, temp, seed):
    return model.sample(params, prompt, n, temperature=temp,
                        key=jax.random.key(seed),
                        kv_cache=True)[len(prompt):]


def _serve_plans(model, params, scfg, plans=PLANS, **engine_kw):
    """Run ``plans`` through a fresh engine; returns the token lists.
    Starts WITHOUT warmup: the plans touch only the 8/16 buckets, so the
    cold-start ladder would compile graphs these tests never dispatch —
    admission compiles the buckets it actually needs, tokens identical."""
    engine = InferenceEngine(model, params=params, cfg=scfg, **engine_kw)
    handles = [engine.submit(p, n, temperature=t, seed=s)
               for p, n, t, s in plans]
    with engine.start(warmup=False):
        return engine, [h.result(120.0).tokens for h in handles]


# ------------------------------------------------------------------ paging
@pytest.mark.parametrize("page_size", [3, 5])
def test_paged_parity_at_odd_page_sizes(lm, page_size):
    """Bitwise token parity with page sizes that do not divide max_len —
    the partial last page and mid-page position math get no slack."""
    model, params = lm
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in PLANS]
    _, got = _serve_plans(model, params,
                          ServingConfig(slots=3, resolve_every=2, paged=True,
                                        page_size=page_size))
    assert got == want


def test_paged_pool_drains_after_traffic(lm):
    """Every page acquired for a request is back on the free list after
    the request completes — the no-leak invariant PG01 lints for."""
    model, params = lm
    engine, got = _serve_plans(
        model, params,
        ServingConfig(slots=2, resolve_every=2, paged=True, page_size=4))
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in PLANS]
    assert got == want
    assert engine._pool.free_count() == engine._pool.num_pages
    stats = engine.stats()
    assert stats["kv_pages_in_use"] == 0
    assert stats["kv_pages"] == engine._pool.num_pages


def test_decode_window_bitwise_vs_sequential_steps(lm):
    """The speculative verify primitive: one (B, W) window dispatch must
    leave logits AND cache bytes identical to W sequential decode_step
    calls — including at the max_len boundary, where out-of-range window
    positions must be dropped, not clamped onto the last live row."""
    model, params = lm
    cfg = model.cfg
    B, W = 2, 4
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, W)), jnp.int32)
    for start in (6, cfg.max_len - 2):        # mid-stream and boundary
        pos = jnp.full((B,), start, jnp.int32)
        cache_a = init_decode_cache(cfg, B)
        cache_b = init_decode_cache(cfg, B)
        # warm both caches identically so attention sees a real prefix
        for i in range(start):
            tok = jnp.full((B,), (i * 7) % cfg.vocab_size, jnp.int32)
            la, cache_a = decode_step(params, cache_a, tok,
                                      jnp.full((B,), i, jnp.int32), cfg)
            _, cache_b = decode_step(params, cache_b, tok,
                                     jnp.full((B,), i, jnp.int32), cfg)
        win_logits, cache_a = decode_window(params, cache_a, toks, pos, cfg)
        seq_logits = []
        for w in range(W):
            p = pos + w
            ok = p < cfg.max_len
            lw, cache_new = decode_step(
                params, cache_b, toks[:, w], jnp.minimum(p, cfg.max_len - 1),
                cfg)
            # emulate the window path's OOB-drop: rows past max_len keep
            # their cache untouched
            cache_b = jax.tree_util.tree_map(
                lambda a, b: jnp.where(
                    ok.reshape((B,) + (1,) * (a.ndim - 1)), a, b),
                cache_new, cache_b)
            seq_logits.append(lw)
        for w in range(W):
            valid = np.asarray(pos + w < cfg.max_len)
            np.testing.assert_array_equal(
                np.asarray(win_logits[:, w][valid]),
                np.asarray(seq_logits[w][valid]))
        for ca, cb in zip(cache_a, cache_b):
            np.testing.assert_array_equal(np.asarray(ca["k"]),
                                          np.asarray(cb["k"]))
            np.testing.assert_array_equal(np.asarray(ca["v"]),
                                          np.asarray(cb["v"]))


def test_decode_step_paged_matches_dense(lm):
    """Unit check under the engine: the paged single-position step is
    bitwise the dense step at an odd page size."""
    model, params = lm
    cfg = model.cfg
    B, ps = 3, 5
    n_pages = -(-cfg.max_len // ps)
    n_phys = B * n_pages + 1
    rng = np.random.default_rng(1)
    bt = jnp.asarray(rng.permutation(n_phys - 1)[:B * n_pages]
                     .reshape(B, n_pages), jnp.int32)
    dense = init_decode_cache(cfg, B)
    pages = init_paged_cache(cfg, n_phys, ps)
    for i in range(10):
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
        pos = jnp.full((B,), i, jnp.int32)
        ld, dense = decode_step(params, dense, tok, pos, cfg)
        lp, pages = decode_step_paged(params, pages, bt, tok, pos, cfg)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))


# ------------------------------------------------------------ prefix cache
@pytest.mark.lockguard
def test_prefix_refcounts_never_free_aliased_pages():
    """PagePool hygiene, run with instrumented locks: an aliased page is
    freed (and thus wipeable/reusable) only when its LAST reader lets
    go — cache eviction drops the pin, never the page."""
    pool = PagePool(num_pages=8, page_size=2)
    prompt = [1, 2, 3, 4, 5]             # 2 full pages usable (len-1 == 4)
    a = pool.alloc(3)                    # slot A's pages
    pool.insert_prefix(prompt, a, usable=4)   # pins a[0], a[1]
    assert pool.prefix_entries() == 2    # chains of length 1 and 2
    # slot B aliases the cached chain
    shared, cached = pool.lookup_prefix(prompt, usable=4)
    assert shared == a[:2] and cached == 4
    assert pool.refcount(a[0]) == 4      # A + both chain pins + B
    # slot A finishes: nothing it shares may be freed
    assert pool.decref(a) == [a[2]]      # only the unshared tail page
    grabbed = pool.alloc(6)              # exactly the free pages — no evict
    assert pool.prefix_entries() == 2
    assert not set(shared) & set(grabbed), "aliased page handed out twice"
    # allocation pressure evicts both chains (pins drop) but B's pages
    # survive the eviction, so the request STILL cannot be satisfied
    with pytest.raises(PagePoolExhausted):
        pool.alloc(1)
    assert pool.prefix_entries() == 0
    assert pool.refcount(a[0]) == 1      # B still reading, page intact
    # last reader lets go -> NOW the pages free
    assert sorted(pool.decref(shared)) == sorted(shared)
    pool.decref(grabbed)
    assert pool.free_count() == pool.num_pages


def test_prefix_exhaustion_evicts_lru_then_429s():
    pool = PagePool(num_pages=4, page_size=2)
    pages = pool.alloc(2)
    pool.insert_prefix([1, 2, 3, 4, 5], pages, usable=4)
    pool.decref(pages)                   # only the cache pins remain
    assert pool.free_count() == 2
    pool.alloc(4)                        # evicts the cache to make room
    with pytest.raises(PagePoolExhausted) as ei:
        pool.alloc(1)
    assert ei.value.status == 429


@pytest.mark.lockguard
def test_prefix_sharing_engine_parity_and_hit_rate(lm):
    """Shared system prompt across requests: bitwise parity AND a
    positive prefix hit rate, with no page leaked after the drain."""
    model, params = lm
    sys_prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1, 0, 11, 12]   # 3 pages at ps=4
    plans = [(sys_prompt + [t], 5, temp, seed)
             for t, temp, seed in ((1, 0.0, 5), (2, 0.9, 17), (3, 0.0, 23),
                                   (4, 0.7, 41))]
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in plans]
    engine, got = _serve_plans(
        model, params,
        ServingConfig(slots=2, resolve_every=2, paged=True, page_size=4,
                      prefix_cache=True),
        plans=plans)
    assert got == want
    stats = engine.stats()
    assert stats["prefix_hit_rate"] > 0.0
    assert stats["prefix_entries"] > 0
    # drained: every non-free page is held by a cache pin, none by slots
    pinned = engine._pool.in_use()
    assert engine._pool.free_count() == engine._pool.num_pages - pinned
    assert METRICS.snapshot()["counters"].get("serving.prefix_hits", 0) > 0


# ------------------------------------------------------------- speculative
def test_speculative_parity_good_and_bad_draft(lm):
    """Token parity must not depend on draft quality: a self-draft
    (agrees always — max accept length) and a garbage draft (random
    init — near-zero accept) serve identical tokens; only
    ``serving.spec_accept_len`` may differ."""
    model, params = lm
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in PLANS]
    draft, dparams = zoo.draft_lm(model.cfg, seed=99)
    for name, dm, dp in (("self", model, params), ("garbage", draft, dparams)):
        _, got = _serve_plans(
            model, params,
            ServingConfig(slots=3, resolve_every=2, speculative=True,
                          spec_k=3),
            draft_model=dm, draft_params=dp)
        assert got == want, f"{name} draft broke parity"
        hist = METRICS.snapshot()["timers"].get("serving.spec_accept_len")
        assert hist is not None and hist["count"] > 0
        METRICS.reset()


def test_speculative_draft_divergence_chaos(lm):
    """Chaos site ``serving.draft``: garbling the draft's proposals for a
    window degrades accept length only — the served tokens still match
    the offline sampler bitwise."""
    model, params = lm
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in PLANS]
    with inject_faults(FaultSpec("serving.draft", probability=1.0,
                                 max_fires=4), seed=3):
        _, got = _serve_plans(
            model, params,
            ServingConfig(slots=3, resolve_every=2, speculative=True,
                          spec_k=2),
            draft_model=model, draft_params=params)
    assert got == want
    assert METRICS.snapshot()["counters"].get("serving.draft.faults", 0) > 0


def test_combined_paged_prefix_speculative_parity(lm):
    model, params = lm
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in PLANS]
    draft, dparams = zoo.draft_lm(model.cfg, seed=1)
    _, got = _serve_plans(
        model, params,
        ServingConfig(slots=3, resolve_every=2, paged=True, page_size=5,
                      prefix_cache=True, speculative=True, spec_k=2),
        draft_model=draft, draft_params=dparams)
    assert got == want


# ------------------------------------------------------------ backpressure
def test_page_pool_exhaustion_rejects_with_429_and_recovers(lm):
    """A pool too small for two concurrent sequences 429s the second
    request (admission backpressure, slot goes back, nothing leaks) and
    serves it fine once submitted after the first drains."""
    model, params = lm
    scfg = ServingConfig(slots=2, resolve_every=2, paged=True, page_size=4,
                         num_pages=9)          # warmup needs 8; 2 reqs don't fit
    prompt, n_new = [1] * 20, 8                # need 7 pages each
    want = [int(t) for t in _expected(model, params, prompt, n_new, 0.0, 7)]
    engine = InferenceEngine(model, params=params, cfg=scfg)
    h1 = engine.submit(prompt, n_new, seed=7)
    h2 = engine.submit(prompt, n_new, seed=7)
    with engine:
        ok = h1.result(120.0)
        with pytest.raises(PagePoolExhausted) as ei:
            h2.result(120.0)
        assert ei.value.status == 429
        assert ok.tokens == want
        # rejected admission leaked nothing; a later submit succeeds
        assert engine._pool.free_count() == engine._pool.num_pages
        assert engine.generate(prompt, n_new, seed=7).tokens == want
    counters = METRICS.snapshot()["counters"]
    assert counters["serving.page_pool_exhausted"] == 1
    assert counters.get("serving.engine.errors", 0) == 0


def test_page_pool_chaos_site_fixed_seed(lm):
    """Fixed-seed chaos plan for ``serving.page_pool``: the injected
    exhaustion 429s exactly one admission, leaks nothing, and later
    requests serve token-identically."""
    model, params = lm
    want = [int(t) for t in _expected(model, params, [4, 5, 6], 5, 0.0, 13)]
    engine = InferenceEngine(
        model, params=params,
        cfg=ServingConfig(slots=2, resolve_every=2, paged=True, page_size=4))
    with inject_faults(FaultSpec("serving.page_pool", probability=1.0,
                                 max_fires=1), seed=42):
        h1 = engine.submit([4, 5, 6], 5, seed=13)
        with engine:
            with pytest.raises(PagePoolExhausted):
                h1.result(120.0)
            assert engine.generate([4, 5, 6], 5, seed=13).tokens == want
            assert engine._pool.free_count() == engine._pool.num_pages
    assert METRICS.snapshot()["counters"]["serving.page_pool_exhausted"] == 1


# -------------------------------------------------------------- hot reload
def test_reload_invalidates_prefix_cache(lm, tmp_path):
    """Hot-swap must drop every cached prefix chain: the entries hold
    K/V computed under the OLD weights, and a request admitted after the
    reload that aliased them would emit tokens matching neither model.
    Post-reload shared-prefix traffic must be bitwise the NEW params'
    offline sample, and the cache re-learns under the new weights."""
    model, params_old = lm
    params_new = model.init(jax.random.key(1234))
    mgr = CheckpointManager(tmp_path / "ck", keep=3)
    mgr.save(1, params_old)
    sys_prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1]           # 2 full pages at ps=4
    plans = [(sys_prompt + [t], 4, 0.0, 11 + t) for t in (1, 2)]
    engine = InferenceEngine(
        model, checkpoint=str(tmp_path / "ck"),
        cfg=ServingConfig(slots=2, resolve_every=2, paged=True, page_size=4,
                          prefix_cache=True))
    with engine:
        got = [engine.generate(p, n, temperature=t, seed=s, timeout=120.0)
               .tokens for p, n, t, s in plans]
        assert got == [_expected(model, params_old, p, n, t, s)
                       for p, n, t, s in plans]
        assert engine.stats()["prefix_entries"] > 0
        mgr.save(2, params_new)
        assert engine.reload() == 2
        assert engine.stats()["prefix_entries"] == 0   # old-weight chains gone
        got = [engine.generate(p, n, temperature=t, seed=s, timeout=120.0)
               .tokens for p, n, t, s in plans]
        assert got == [_expected(model, params_new, p, n, t, s)
                       for p, n, t, s in plans]
        assert engine.stats()["prefix_entries"] > 0    # re-learned, new weights
    # nothing leaked: every non-free page is a (new-weights) cache pin
    pinned = engine._pool.in_use()
    assert engine._pool.free_count() == engine._pool.num_pages - pinned


@pytest.mark.lockguard
def test_clear_prefix_quarantines_until_requeue():
    """Pool-level reload invalidation: clear_prefix unpins every chain.
    A page a live slot still aliases survives untouched; a page whose
    cache pin was the last reference is quarantined — NOT reallocatable
    — until the caller wipes it and hands it back with requeue."""
    pool = PagePool(num_pages=4, page_size=2)
    a = pool.alloc(2)
    pool.insert_prefix([1, 2, 3, 4, 5], a, usable=4)
    shared, cached = pool.lookup_prefix([1, 2, 3, 4, 5], usable=4)
    assert shared == a and cached == 4
    pool.decref(a)                       # original slot done; alias remains
    assert pool.clear_prefix() == [] and pool.prefix_entries() == 0
    assert pool.refcount(a[0]) == 1      # the alias keeps the page alive
    pool.decref(shared)                  # last reader: frees normally
    assert pool.free_count() == pool.num_pages
    # no alias left: the cleared pages quarantine until requeued
    b = pool.alloc(2)
    pool.insert_prefix([7, 7, 7, 7, 7], b, usable=4)
    pool.decref(b)                       # only the cache pins remain
    dead = pool.clear_prefix()
    assert sorted(dead) == sorted(b)
    assert pool.free_count() == 2        # quarantined pages NOT handed out
    with pytest.raises(PagePoolExhausted):
        pool.alloc(3)
    pool.requeue(dead)
    assert pool.free_count() == pool.num_pages


# ------------------------------------------------------------ stop/restart
def test_stop_with_inflight_then_restart_serves_clean(lm):
    """stop() with a request mid-decode must fail that caller AND fully
    reset the bookkeeping: after start() the engine has its whole slot
    range back, and the dead request's block-table row — which the
    decode step writes through whether the row is active or not — is
    parked on the trash page, never on pages reallocated to new traffic
    (served tokens stay bitwise the offline sample's)."""
    model, params = lm
    engine = InferenceEngine(
        model, params=params,
        cfg=ServingConfig(slots=2, resolve_every=2, paged=True, page_size=4))
    inflight = engine.submit([5, 1, 4], 25, seed=3)    # too long to finish
    engine._serve_once()     # admit + one 2-step segment: mid-decode
    assert engine._slots
    engine.stop()
    with pytest.raises(RuntimeError, match="request in flight"):
        inflight.result(0)
    assert engine._pool.free_count() == engine._pool.num_pages
    with engine._lock:
        assert sorted(engine._free) == [0, 1]          # full slot range back
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in PLANS]
    handles = [engine.submit(p, n, temperature=t, seed=s)
               for p, n, t, s in PLANS]
    with engine:
        got = [h.result(120.0).tokens for h in handles]
    assert got == want
    assert engine._pool.free_count() == engine._pool.num_pages


# --------------------------------------------------------------- small pool
def test_warmup_and_serving_with_pool_smaller_than_max_len(lm):
    """A pool sized below pages_per_slot (legal: short-prompt traffic on
    a tight memory budget) must not wedge start(): warmup warms with the
    pages it has, short requests serve with bitwise parity, and an
    oversized request 429s at admission instead."""
    model, params = lm
    scfg = ServingConfig(slots=1, resolve_every=2, paged=True, page_size=4,
                         num_pages=3)   # 12 positions; pages_per_slot is 8
    want = [int(t) for t in _expected(model, params, [3, 1, 4], 5, 0.0, 2)]
    engine = InferenceEngine(model, params=params, cfg=scfg)
    with engine:             # start() warms up: must not exhaust the pool
        assert engine.generate([3, 1, 4], 5, seed=2,
                               timeout=120.0).tokens == want
        big = engine.submit([1] * 10, 8, seed=0)       # needs 5 pages > 3
        with pytest.raises(PagePoolExhausted) as ei:
            big.result(120.0)
        assert ei.value.status == 429
    assert engine._pool.free_count() == engine._pool.num_pages


# ---------------------------------------------------------------- kv quant
QPLANS = [([5, 1, 4], 6, 0.0, 0),            # greedy-only: the agreement
          ([7], 5, 0.0, 3),                  # floor is a top-1 statistic
          ([3, 2, 1, 0, 5], 6, 0.0, 9),
          ([3, 1, 4, 1], 8, 0.0, 1)]


def _agreement(got, want):
    hits = total = 0
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            total += 1
            hits += int(a == b)
    return total, hits / max(total, 1)


def _train_decisive(cfg, period, seed=0, steps=60):
    """A briefly-trained model: decisive top-2 logit margins so the 0.999
    agreement floor measures QUANTIZATION error, not argmax coin flips on
    a random init's near-flat logits."""
    from deeplearning4j_tpu.optimize import transforms as T
    stream = np.array(period * 32, np.int32) % cfg.vocab_size
    span = cfg.max_len + 1
    n = len(stream) // span
    blocks = stream[:n * span].reshape(n, span)
    model = TransformerLM(cfg)
    tx = T.adamw(0.01)
    params = model.init(jax.random.key(seed))
    opt = model.init_opt(params, tx)
    step = model.build_train_step(tx)
    toks, tgts = jnp.asarray(blocks[:, :-1]), jnp.asarray(blocks[:, 1:])
    for _ in range(steps):
        params, opt, _ = step(params, opt, toks, tgts)
    return model, params


@pytest.fixture(scope="module")
def sharp_lm():
    return _train_decisive(tiny_cfg(vocab_size=16), [3, 1, 4, 1, 5, 9, 2, 6])


def test_kv_quant_none_stays_bitwise(lm):
    """``kv_quant=None`` is the default AND the exact path: the float
    pool serves bitwise-identical tokens — quantization is strictly
    opt-in, never a silent precision change."""
    model, params = lm
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in PLANS]
    _, got = _serve_plans(
        model, params,
        ServingConfig(slots=3, resolve_every=2, paged=True, page_size=5,
                      prefix_cache=True, kv_quant=None))
    assert got == want


def test_kv_quant_requires_paged(lm):
    """Scales live beside the page pool; a dense cache has nowhere to put
    them, so the engine refuses the combination at construction."""
    model, params = lm
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(model, params=params,
                        cfg=ServingConfig(slots=2, kv_quant="int8"))
    with pytest.raises(ValueError, match="kv_quant"):
        InferenceEngine(model, params=params,
                        cfg=ServingConfig(slots=2, paged=True, page_size=4,
                                          kv_quant="int4"))


def test_int8_kv_greedy_agreement_meets_floor(sharp_lm):
    """The tentpole's serving bar: int8 KV pages keep served-token top-1
    agreement >= 0.999 against the full-precision offline sample."""
    model, params = sharp_lm
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in QPLANS]
    engine, got = _serve_plans(
        model, params,
        ServingConfig(slots=3, resolve_every=2, paged=True, page_size=5,
                      prefix_cache=True, kv_quant="int8"),
        plans=QPLANS)
    total, agree = _agreement(got, want)
    assert total >= 20
    assert agree >= 0.999, f"top-1 agreement {agree} under the floor"
    assert engine.stats()["kv_quant"] == "int8"
    # the quantized pool drains like the float pool: no page leaked
    pinned = engine._pool.in_use()
    assert engine._pool.free_count() == engine._pool.num_pages - pinned


def test_int8_kv_speculative_agreement_meets_floor(sharp_lm):
    """Draft-verify windows run over the SAME quantized pool (the window
    gather dequantizes, the scatter requantizes): the combined
    paged+prefix+speculative int8 stack holds the agreement floor."""
    model, params = sharp_lm
    want = [_expected(model, params, p, n, t, s) for p, n, t, s in QPLANS]
    _, got = _serve_plans(
        model, params,
        ServingConfig(slots=3, resolve_every=2, paged=True, page_size=5,
                      prefix_cache=True, speculative=True, spec_k=2,
                      kv_quant="int8"),
        plans=QPLANS, draft_model=model, draft_params=params)
    total, agree = _agreement(got, want)
    assert total >= 20
    assert agree >= 0.999, f"top-1 agreement {agree} under the floor"


def test_int8_kv_decode_tracks_dense_within_quant_band():
    """Unit-level combo check (GQA x int8): the quantized paged step's
    logits track the dense float step within the absmax quantization
    band at every position — error stays bounded, it does not compound
    across incremental writes."""
    from deeplearning4j_tpu.models.transformer import decode_step_paged
    from deeplearning4j_tpu.ops.pallas.kv_quant import \
        init_quantized_paged_cache
    cfg = tiny_cfg(n_kv_heads=2)
    params = TransformerLM(cfg).init(jax.random.key(0))
    B, ps = 2, 5
    n_pages = -(-cfg.max_len // ps)
    n_phys = B * n_pages + 1
    rng = np.random.default_rng(4)
    bt = jnp.asarray(rng.permutation(n_phys - 1)[:B * n_pages]
                     .reshape(B, n_pages), jnp.int32)
    dense = init_decode_cache(cfg, B)
    pages = init_quantized_paged_cache(cfg, n_phys, ps, "int8")
    assert pages[0]["k"].dtype == jnp.int8
    assert pages[0]["k"].shape[2] == 2            # GQA-sized pool
    for i in range(12):
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
        pos = jnp.full((B,), i, jnp.int32)
        ld, dense = decode_step(params, dense, tok, pos, cfg)
        lp, pages = decode_step_paged(params, pages, bt, tok, pos, cfg)
        err = float(jnp.max(jnp.abs(ld - lp)))
        assert err < 0.05, f"step {i}: logit error {err} out of quant band"


def test_reload_invalidates_quantized_prefix_pages(tmp_path):
    """Hot-swap with a quantized pool: cached prefix chains hold int8
    pages AND their scale rows computed under the OLD weights — reload
    must drop every chain (entries -> 0) and post-reload shared-prefix
    traffic must track the NEW params, re-learning the cache."""
    model, params_old = _train_decisive(tiny_cfg(vocab_size=16),
                                        [3, 1, 4, 1, 5, 9, 2, 6])
    _, params_new = _train_decisive(tiny_cfg(vocab_size=16),
                                    [2, 7, 1, 8, 2, 8, 1, 8], seed=11)
    mgr = CheckpointManager(tmp_path / "ck", keep=3)
    mgr.save(1, params_old)
    sys_prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1]
    plans = [(sys_prompt + [t], 4, 0.0, 11 + t) for t in (1, 2)]
    engine = InferenceEngine(
        model, checkpoint=str(tmp_path / "ck"),
        cfg=ServingConfig(slots=2, resolve_every=2, paged=True, page_size=4,
                          prefix_cache=True, kv_quant="int8"))
    with engine:
        got = [engine.generate(p, n, temperature=t, seed=s, timeout=120.0)
               .tokens for p, n, t, s in plans]
        want = [_expected(model, params_old, p, n, t, s)
                for p, n, t, s in plans]
        assert _agreement(got, want)[1] >= 0.999
        assert engine.stats()["prefix_entries"] > 0
        mgr.save(2, params_new)
        assert engine.reload() == 2
        assert engine.stats()["prefix_entries"] == 0   # old-weight chains gone
        got = [engine.generate(p, n, temperature=t, seed=s, timeout=120.0)
               .tokens for p, n, t, s in plans]
        want = [_expected(model, params_new, p, n, t, s)
                for p, n, t, s in plans]
        assert _agreement(got, want)[1] >= 0.999, \
            "post-reload tokens do not track the NEW weights"
        assert engine.stats()["prefix_entries"] > 0    # re-learned
    pinned = engine._pool.in_use()
    assert engine._pool.free_count() == engine._pool.num_pages - pinned


def test_int8_pages_stretch_the_byte_budget(lm):
    """The capacity claim, engine-level: under a FIXED device-byte budget
    the int8 pool admits the concurrent request the float pool 429s —
    and the per-page byte accounting shows >= 1.9x pages (<= 0.53x bytes
    per slot) for the same geometry."""
    from deeplearning4j_tpu.serving.engine import kv_page_bytes
    model, params = lm
    cfg = model.cfg
    ps = 4
    float_page = kv_page_bytes(cfg, ps, None)
    int8_page = kv_page_bytes(cfg, ps, "int8")
    assert float_page / int8_page >= 1.9
    assert int8_page / float_page <= 0.53
    budget = 9 * float_page                    # 9 float pages: 2 long
    #                                            requests do NOT fit (the
    #                                            429 test above proves it)
    pages_int8 = budget // int8_page
    assert pages_int8 >= 1.9 * 9
    prompt, n_new = [1] * 20, 8                # 7 pages each
    exhausted_before = METRICS.snapshot()["counters"].get(
        "serving.page_pool_exhausted", 0)
    engine = InferenceEngine(
        model, params=params,
        cfg=ServingConfig(slots=2, resolve_every=2, paged=True, page_size=ps,
                          num_pages=int(pages_int8), kv_quant="int8"))
    h1 = engine.submit(prompt, n_new, seed=7)
    h2 = engine.submit(prompt, n_new, seed=7)
    with engine:
        r1 = h1.result(120.0)                  # neither request 429s: the
        r2 = h2.result(120.0)                  # budget now holds both
    assert len(r1.tokens) == n_new and len(r2.tokens) == n_new
    assert r1.tokens == r2.tokens              # same seed, same stream
    assert engine._pool.free_count() == engine._pool.num_pages
    assert METRICS.snapshot()["counters"].get(
        "serving.page_pool_exhausted", 0) == exhausted_before


# ------------------------------------------------------------------ wakeup
def test_cv_wakeup_beats_idle_poll(lm):
    """The batcher's condition-variable wakeup: with a pathological
    ``idle_wait_s`` the engine still admits (submit notifies) and stops
    (wake breaks the wait) in far less than the poll period."""
    model, params = lm
    engine = InferenceEngine(
        model, params=params,
        cfg=ServingConfig(slots=1, resolve_every=2, idle_wait_s=30.0))
    with engine:
        t0 = time.monotonic()
        got = engine.generate([3, 1, 4], 3, seed=2, timeout=60.0)
        admit_latency = time.monotonic() - t0
        assert got.tokens == [int(t) for t in
                              _expected(model, params, [3, 1, 4], 3, 0.0, 2)]
        assert admit_latency < 15.0      # notify hop, not the 30s poll
        t0 = time.monotonic()
    assert time.monotonic() - t0 < 15.0  # stop() woke the idle wait
