"""Grouped-query / multi-query attention parity battery (DESIGN.md §20).

GQA (``n_kv_heads < n_heads``) is a CACHE-bytes technique, never a
semantics change beyond the weight tying it declares: a GQA model must
compute exactly what a full-heads model computes when that model's K/V
projections are tied group-wise.  The battery pins that down at every
layer the heads flow through: init tree compatibility, training
loss/grad vs the repeat-heads reference, dense-vs-paged decode at odd
page sizes, the windowed verify primitive, and prefix-sharing admission
in the serving engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM,
                                                   decode_step,
                                                   decode_step_paged,
                                                   decode_window,
                                                   forward_local,
                                                   init_decode_cache,
                                                   init_paged_cache,
                                                   init_params,
                                                   lm_loss_local)
from deeplearning4j_tpu.serving import InferenceEngine, ServingConfig


def gqa_cfg(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("d_model", 48)
    kw.setdefault("n_heads", 6)
    kw.setdefault("n_layers", 2)
    kw.setdefault("d_ff", 64)
    kw.setdefault("max_len", 32)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("remat", False)
    return TransformerConfig(**kw)


def _expand_to_full_heads(params, cfg):
    """Tie a full-heads tree to a GQA tree: query head ``h`` gets K/V
    projection ``h // g`` — the weight-space statement of
    ``repeat_kv_heads``.  The expanded model must match bitwise-ish."""
    g = cfg.n_heads // cfg.kv_heads
    layers = []
    for lp in params["layers"]:
        lp2 = {k: v for k, v in lp.items() if k not in ("wq", "wkv")}
        wk = jnp.repeat(lp["wkv"][:, 0], g, axis=1)     # (D, H, Dh)
        wv = jnp.repeat(lp["wkv"][:, 1], g, axis=1)
        lp2["wqkv"] = jnp.stack([lp["wq"], wk, wv], axis=1)
        layers.append(lp2)
    return dict(params, layers=layers)


# ------------------------------------------------------------------- trees
def test_default_kv_heads_tree_is_bitwise_pre_gqa():
    """``n_kv_heads=None`` and ``=n_heads`` draw the SAME RNG stream into
    the SAME packed ``wqkv`` tree — every pre-GQA checkpoint stays
    loadable and every existing test keeps its exact numbers."""
    cfg_none = gqa_cfg()
    cfg_full = gqa_cfg(n_kv_heads=6)
    p_none = init_params(jax.random.key(3), cfg_none)
    p_full = init_params(jax.random.key(3), cfg_full)
    la, lb = jax.tree.leaves(p_none), jax.tree.leaves(p_full)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "wqkv" in p_none["layers"][0]


def test_kv_heads_must_divide_n_heads():
    with pytest.raises(AssertionError, match="must divide"):
        gqa_cfg(n_kv_heads=4).kv_heads


#: compiled once a configuration: the full-heads model's serves every case
forward = jax.jit(forward_local, static_argnums=2)
loss_and_grads = jax.jit(jax.value_and_grad(lm_loss_local), static_argnums=3)


@pytest.mark.parametrize("n_kv", [1, 2, 6])
def test_gqa_loss_matches_repeat_heads_reference(n_kv):
    """Forward + loss + grads of the GQA tree match the full-heads model
    whose K/V projections are tied group-wise (``n_kv == n_heads``
    exercises the packed-tree path through the same assertion)."""
    cfg = gqa_cfg(n_kv_heads=n_kv)
    cfg_full = gqa_cfg()
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    tgts = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)

    if n_kv == cfg.n_heads:
        full = params                    # same packed tree by construction
    else:
        full = _expand_to_full_heads(params, cfg)
    np.testing.assert_allclose(np.asarray(forward(params, toks, cfg)),
                               np.asarray(forward(full, toks, cfg_full)),
                               atol=1e-5, rtol=1e-5)

    loss, grads = loss_and_grads(params, toks, tgts, cfg)
    loss_f, grads_f = loss_and_grads(full, toks, tgts, cfg_full)
    np.testing.assert_allclose(float(loss), float(loss_f), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grads["tok_embed"]),
                               np.asarray(grads_f["tok_embed"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["layers"][0]["w1"]),
                               np.asarray(grads_f["layers"][0]["w1"]),
                               atol=1e-5)
    if n_kv != cfg.n_heads:
        # chain rule across the tying: d/dwq is slice 0 of d/dwqkv, and
        # each shared K/V head accumulates its whole query group
        g = cfg.n_heads // n_kv
        gq = grads_f["layers"][0]["wqkv"]
        np.testing.assert_allclose(np.asarray(grads["layers"][0]["wq"]),
                                   np.asarray(gq[:, 0]), atol=1e-5)
        for s in (0, 1):
            got = np.asarray(grads["layers"][0]["wkv"][:, s])
            want = np.asarray(gq[:, s + 1].reshape(
                gq.shape[0], n_kv, g, -1).sum(axis=2))
            np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("n_kv", [1, 2])
def test_gqa_training_reduces_loss(n_kv):
    cfg = gqa_cfg(n_kv_heads=n_kv)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    opt = model.init_opt(params, lr=0.05)
    toks = jax.random.randint(jax.random.key(2), (8, 16), 0, cfg.vocab_size)
    tgts = jnp.roll(toks, -1, axis=1)
    step = model.build_train_step(lr=0.05)
    loss0 = None
    for _ in range(30):
        params, opt, loss = step(params, opt, toks, tgts)
        if loss0 is None:
            loss0 = float(loss)
    assert float(loss) < loss0 * 0.7


# ------------------------------------------------------------------ decode
@pytest.mark.parametrize("n_kv,page_size", [(1, 3), (2, 5), (3, 5)])
def test_gqa_decode_step_paged_matches_dense(n_kv, page_size):
    """Dense-vs-paged single-position decode stays bitwise under GQA at
    page sizes that do not divide max_len — the K/V pools carry
    ``n_kv_heads`` heads, the broadcast happens at read time in both."""
    cfg = gqa_cfg(n_kv_heads=n_kv)
    params = init_params(jax.random.key(0), cfg)
    B = 3
    n_pages = -(-cfg.max_len // page_size)
    n_phys = B * n_pages + 1
    rng = np.random.default_rng(1)
    bt = jnp.asarray(rng.permutation(n_phys - 1)[:B * n_pages]
                     .reshape(B, n_pages), jnp.int32)
    dense = init_decode_cache(cfg, B)
    pages = init_paged_cache(cfg, n_phys, page_size)
    assert pages[0]["k"].shape[2] == n_kv     # pool bytes scale with Kv
    for i in range(10):
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
        pos = jnp.full((B,), i, jnp.int32)
        ld, dense = decode_step(params, dense, tok, pos, cfg)
        lp, pages = decode_step_paged(params, pages, bt, tok, pos, cfg)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))


def test_gqa_decode_window_matches_sequential_steps():
    """The speculative verify primitive under GQA: a (B, W) window equals
    W sequential steps — logits and cache bytes."""
    cfg = gqa_cfg(n_kv_heads=2)
    params = init_params(jax.random.key(0), cfg)
    B, W, start = 2, 4, 6
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, W)), jnp.int32)
    pos = jnp.full((B,), start, jnp.int32)
    cache_a = init_decode_cache(cfg, B)
    cache_b = init_decode_cache(cfg, B)
    for i in range(start):
        tok = jnp.full((B,), (i * 7) % cfg.vocab_size, jnp.int32)
        _, cache_a = decode_step(params, cache_a, tok,
                                 jnp.full((B,), i, jnp.int32), cfg)
        _, cache_b = decode_step(params, cache_b, tok,
                                 jnp.full((B,), i, jnp.int32), cfg)
    win_logits, cache_a = decode_window(params, cache_a, toks, pos, cfg)
    for w in range(W):
        lw, cache_b = decode_step(params, cache_b, toks[:, w], pos + w, cfg)
        np.testing.assert_array_equal(np.asarray(win_logits[:, w]),
                                      np.asarray(lw))
    for ca, cb in zip(cache_a, cache_b):
        assert ca["k"].shape[2] == 2
        np.testing.assert_array_equal(np.asarray(ca["k"]), np.asarray(cb["k"]))
        np.testing.assert_array_equal(np.asarray(ca["v"]), np.asarray(cb["v"]))


def test_gqa_sample_kv_cache_matches_recompute():
    cfg = gqa_cfg(n_kv_heads=3)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    a = model.sample(params, [5, 1, 4], 8, temperature=0.0)
    b = model.sample(params, [5, 1, 4], 8, temperature=0.0, kv_cache=True)
    assert a == b


# ----------------------------------------------------------------- serving
def test_gqa_prefix_sharing_admission_unchanged():
    """Prefix admission keys on token content, not head geometry: a GQA
    engine serves shared-prefix traffic with the same bitwise parity and
    a positive hit rate — the cached pages simply hold fewer bytes."""
    cfg = gqa_cfg(n_kv_heads=2)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(7))
    sys_prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1, 0, 11, 12]
    plans = [(sys_prompt + [t], 5, temp, seed)
             for t, temp, seed in ((1, 0.0, 5), (2, 0.9, 17), (3, 0.0, 23))]
    want = [model.sample(params, p, n, temperature=t, key=jax.random.key(s),
                         kv_cache=True)[len(p):] for p, n, t, s in plans]
    engine = InferenceEngine(
        model, params=params,
        cfg=ServingConfig(slots=2, resolve_every=2, paged=True, page_size=4,
                          prefix_cache=True))
    handles = [engine.submit(p, n, temperature=t, seed=s)
               for p, n, t, s in plans]
    # cold start: 13-token prompts touch only the 16 bucket, so the
    # warmup ladder would compile graphs this test never dispatches
    with engine.start(warmup=False):
        got = [h.result(120.0).tokens for h in handles]
    assert got == want
    stats = engine.stats()
    assert stats["prefix_hit_rate"] > 0.0
    assert stats["prefix_entries"] > 0
    pinned = engine._pool.in_use()
    assert engine._pool.free_count() == engine._pool.num_pages - pinned
