"""Flagship transformer tests: single-device math, training, sampling and
decoding.  The sharding correctness checks (the explicit-SPMD (dp, sp, tp)
step, ring attention and ZeRO-1 against the single-device step) are
``test_transformer_sharded.py``."""

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    forward_local,
    init_params,
    lm_loss_local,
)


def tiny_cfg(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_layers", 2)
    kw.setdefault("d_ff", 64)
    kw.setdefault("max_len", 32)
    kw.setdefault("dtype", jnp.float32)  # exact math for comparisons
    kw.setdefault("remat", False)
    return TransformerConfig(**kw)


#: the forward pass compiled once a configuration, for every test that reads it
forward = jax.jit(forward_local, static_argnums=2)


PERIOD = [3, 1, 4, 1, 5, 9, 2, 6]


def cycle(cfg):
    """``(tokens, targets)``: a strictly periodic stream cut into rows."""
    span = cfg.max_len + 1
    stream = np.array(PERIOD * 32, np.int32)
    blocks = stream[:len(stream) // span * span].reshape(-1, span)
    return jnp.asarray(blocks[:, :-1]), jnp.asarray(blocks[:, 1:])


def data(cfg, batch=8, seq=16, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    tokens = jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size)
    targets = jax.random.randint(k2, (batch, seq), 0, cfg.vocab_size)
    return tokens, targets


def test_forward_shapes():
    cfg = tiny_cfg()
    params = init_params(jax.random.key(0), cfg)
    tokens, _ = data(cfg)
    logits = forward(params, tokens, cfg)
    assert logits.shape == (8, 16, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_causal_masking():
    """Changing future tokens must not change past logits (causal)."""
    cfg = tiny_cfg(causal=True)
    params = init_params(jax.random.key(0), cfg)
    tokens, _ = data(cfg)
    logits1 = forward(params, tokens, cfg)
    tokens2 = tokens.at[:, 10:].set((tokens[:, 10:] + 7) % cfg.vocab_size)
    logits2 = forward(params, tokens2, cfg)
    np.testing.assert_allclose(np.asarray(logits1[:, :10]),
                               np.asarray(logits2[:, :10]), atol=1e-5)
    assert not np.allclose(np.asarray(logits1[:, 10:]), np.asarray(logits2[:, 10:]))


def test_bidirectional_mode():
    cfg = tiny_cfg(causal=False)
    params = init_params(jax.random.key(0), cfg)
    tokens, _ = data(cfg)
    logits1 = forward(params, tokens, cfg)
    tokens2 = tokens.at[:, -1].set((tokens[:, -1] + 3) % cfg.vocab_size)
    logits2 = forward(params, tokens2, cfg)
    # bidirectional: even position 0 sees the change
    assert not np.allclose(np.asarray(logits1[:, 0]), np.asarray(logits2[:, 0]))


def test_single_device_training_reduces_loss():
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    opt = model.init_opt(params, lr=0.05)
    tokens, _ = data(cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    step = model.build_train_step(lr=0.05)
    loss0 = None
    for i in range(30):
        params, opt, loss = step(params, opt, tokens, targets)
        if loss0 is None:
            loss0 = float(loss)
    assert float(loss) < loss0 * 0.7


def test_single_device_adamw_training_reduces_loss():
    """Flagship trains through the GradientTransform chain (VERDICT weak #2):
    AdamW + warmup-cosine schedule on the LM objective."""
    from deeplearning4j_tpu.optimize import transforms as T

    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    tx = T.adamw(T.warmup_cosine(5e-3, 5, 100), weight_decay=0.01)
    params = model.init(jax.random.key(0))
    opt = model.init_opt(params, tx)
    tokens, _ = data(cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    step = model.build_train_step(tx)
    loss0 = None
    for i in range(30):
        params, opt, loss = step(params, opt, tokens, targets)
        if loss0 is None:
            loss0 = float(loss)
    assert int(opt[0]) == 30
    assert float(loss) < loss0 * 0.6


def test_finetune_classifier_converges():
    """BERT-class fine-tune loop (VERDICT next #2): classifier head on the
    encoder, AdamW + warmup-linear, loss curve must drop and accuracy must
    beat chance on a synthetic token-signal task."""
    from deeplearning4j_tpu.optimize import transforms as T

    cfg = tiny_cfg(causal=False)
    model = TransformerLM(cfg)
    n_classes = 2
    tree = model.init_finetune(jax.random.key(0), n_classes)

    # synthetic task: label = whether token id 7 appears in the sequence
    k = jax.random.key(3)
    tokens = jax.random.randint(k, (32, 16), 0, cfg.vocab_size)
    labels = jnp.any(tokens == 7, axis=1).astype(jnp.int32)

    tx = T.adamw(T.warmup_linear(3e-3, 5, 200), weight_decay=0.01)
    opt = model.init_opt(tree, tx)
    step = model.build_finetune_step(tx)
    losses = []
    for i in range(60):
        tree, opt, loss = step(tree, opt, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5

    from deeplearning4j_tpu.models.transformer import encode_local
    x = encode_local(tree["backbone"], tokens, cfg)
    pooled = x.astype(jnp.float32).mean(axis=1)
    logits = pooled @ tree["head"]["w_cls"] + tree["head"]["b_cls"]
    acc = float(jnp.mean((jnp.argmax(logits, -1) == labels)))
    assert acc >= 0.8


def test_remat_matches_no_remat():
    cfg = tiny_cfg(remat=False)
    cfg_r = tiny_cfg(remat=True)
    params = init_params(jax.random.key(0), cfg)
    tokens, _ = data(cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    g1 = jax.grad(lambda p: lm_loss_local(p, tokens, targets, cfg))(params)
    g2 = jax.grad(lambda p: lm_loss_local(p, tokens, targets, cfg_r))(params)
    np.testing.assert_allclose(np.asarray(g1["layers"][0]["w1"]),
                               np.asarray(g2["layers"][0]["w1"]), rtol=1e-4)


def test_chunked_xent_matches_unchunked():
    """The streaming LM loss (xent_chunk scan + per-chunk remat) is an
    implementation detail: loss AND grads must match the full-logits path
    bit-for-bit-ish in f32."""
    import dataclasses

    from deeplearning4j_tpu.models.transformer import lm_head_loss

    cfg = tiny_cfg(vocab_size=128, xent_chunk=16)
    cfg0 = dataclasses.replace(cfg, xent_chunk=0)
    params = init_params(jax.random.key(0), cfg)
    h = jax.random.normal(jax.random.key(1), (4, 32, 32))
    targets = jax.random.randint(jax.random.key(2), (4, 32), 0, 128)

    loss = jax.jit(jax.value_and_grad(lm_head_loss), static_argnums=3)
    l_chunk, g_chunk = loss(params, h, targets, cfg)
    l_full, g_full = loss(params, h, targets, cfg0)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-6)

    for a, b in zip(jax.tree.leaves(g_chunk), jax.tree.leaves(g_full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_chunked_xent_pads_awkward_token_counts():
    """A near-prime token count must NOT collapse the divisor search into
    tiny chunks or fall back to full (B*T, V) logits: the stream is padded
    with zero-weight tokens to a multiple of the configured chunk, and
    loss AND grads still match the unchunked path (pad rows contribute
    exactly 0 to the sum and 0 cotangent to every param)."""
    import dataclasses

    from deeplearning4j_tpu.models.transformer import lm_head_loss

    cfg = tiny_cfg(vocab_size=128, max_len=64, xent_chunk=16)
    cfg0 = dataclasses.replace(cfg, xent_chunk=0)
    params = init_params(jax.random.key(0), cfg)
    # B*T = 61 (prime): largest divisor <= 16 is 1, so the pad path runs
    h = jax.random.normal(jax.random.key(1), (1, 61, 32))
    targets = jax.random.randint(jax.random.key(2), (1, 61), 0, 128)

    loss = jax.jit(jax.value_and_grad(lm_head_loss), static_argnums=3)
    l_chunk, g_chunk = loss(params, h, targets, cfg)
    l_full, g_full = loss(params, h, targets, cfg0)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-6)

    for a, b in zip(jax.tree.leaves(g_chunk), jax.tree.leaves(g_full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_sample_continues_a_learned_cycle():
    """Generation parity with the LSTM's sampling seam: train the causal
    flagship on a strictly periodic token stream, then greedy sample must
    continue the cycle; temperature sampling is deterministic per key."""
    cfg = tiny_cfg(vocab_size=16, causal=True)
    tokens, targets = cycle(cfg)

    from deeplearning4j_tpu.optimize import transforms as T
    model = TransformerLM(cfg)
    tx = T.adamw(0.01)
    params = model.init(jax.random.key(0))
    opt = model.init_opt(params, tx)
    step = model.build_train_step(tx)
    for _ in range(60):
        params, opt, loss = step(params, opt, tokens, targets)

    prime = PERIOD[:4]                     # 3 1 4 1 -> 5 9 2 6 3 ...
    out = model.sample(params, prime, length=8, temperature=0.0)
    want = (PERIOD * 3)[:len(out)]
    assert out == want, (out, want)

    # same key -> same continuation; different keys may differ
    a = model.sample(params, prime, 8, temperature=0.8, key=jax.random.key(1))
    b = model.sample(params, prime, 8, temperature=0.8, key=jax.random.key(1))
    assert a == b
    assert a[:4] == prime


def test_kv_cached_decode_matches_full_forward():
    """decode_step's incremental logits match the full forward position for
    position, and greedy kv-cached sampling reproduces the full-recompute
    path exactly."""
    from deeplearning4j_tpu.models.transformer import (decode_step,
                                                       init_decode_cache)

    cfg = tiny_cfg(vocab_size=32, causal=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, cfg.max_len), 0, 32)

    full = forward(params, toks, cfg)                       # (1, T, V)
    cache = init_decode_cache(cfg, 1)
    one = jax.jit(decode_step, static_argnums=4)        # compiled once, run T times
    for pos in range(cfg.max_len):
        step_logits, cache = one(params, cache, toks[:, pos], jnp.int32(pos), cfg)
        np.testing.assert_allclose(np.asarray(step_logits[0]),
                                   np.asarray(full[0, pos]),
                                   rtol=1e-4, atol=1e-4)

    # end-to-end: greedy continuation identical through both paths (train
    # briefly so the argmax is confident, not a numerical coin flip)
    from deeplearning4j_tpu.optimize import transforms as T
    tx = T.adamw(0.01)
    opt = model.init_opt(params, tx)
    step = model.build_train_step(tx)
    tr_t, tr_y = cycle(cfg)
    for _ in range(40):
        params, opt, _ = step(params, opt, tr_t, tr_y)

    prime = PERIOD[:3]
    a = model.sample(params, prime, 9, temperature=0.0)
    b = model.sample(params, prime, 9, temperature=0.0, kv_cache=True)
    assert a == b, (a, b)
    # the cached path draws the SAME RNG stream (key advances only on
    # generation steps), so temperature sampling agrees across paths too
    c0 = model.sample(params, prime, 9, temperature=0.8,
                      key=jax.random.key(4))
    c1 = model.sample(params, prime, 9, temperature=0.8,
                      key=jax.random.key(4), kv_cache=True)
    assert c0 == c1, (c0, c1)


def test_beam_search_on_flagship():
    """Beam search (LSTM.java BeamSearch seam on the flagship): width-1
    equals greedy decode; wider beams never score worse; the trained cycle
    is recovered with a finite log prob."""
    from deeplearning4j_tpu.optimize import transforms as T

    cfg = tiny_cfg(vocab_size=16, causal=True)
    model = TransformerLM(cfg)
    tx = T.adamw(0.01)
    params = model.init(jax.random.key(0))
    opt = model.init_opt(params, tx)
    step = model.build_train_step(tx)
    tr_t, tr_y = cycle(cfg)
    for _ in range(50):
        params, opt, _ = step(params, opt, tr_t, tr_y)

    prime = PERIOD[:3]
    greedy = model.sample(params, prime, 9, temperature=0.0)
    b1, s1 = model.beam_search(params, prime, 9, beam_width=1)
    assert b1 == greedy, (b1, greedy)

    b5, s5 = model.beam_search(params, prime, 9, beam_width=5)
    assert np.isfinite(s5) and s5 <= 0.0
    assert s5 >= s1 - 1e-5          # wider beam can't score worse
    assert b5 == (PERIOD * 3)[:len(b5)], b5


def test_score_matches_train_step_loss():
    """score() (the reference Model.score seam) reports the same mean token
    cross entropy the train step computes at the current params."""
    cfg = tiny_cfg(causal=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    tokens, _ = data(cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    s = model.score(params, tokens, targets)
    copy = jax.tree_util.tree_map(jnp.array, params)
    opt = model.init_opt(copy, lr=0.0)
    _, _, loss = model.build_train_step(lr=0.0)(copy, opt, tokens, targets)
    np.testing.assert_allclose(s, float(loss), rtol=1e-6)
    assert abs(s - np.log(cfg.vocab_size)) < 0.5     # untrained ~ uniform
