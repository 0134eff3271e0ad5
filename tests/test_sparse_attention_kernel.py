"""``ops/pallas/sparse_attention.py`` (the kernels that take a selection of
keys) interpreted on the CPU: the three passes against plain jnp at shapes
with several key blocks, grouped heads, rows that select nothing in a tile
and a frontier that skips blocks; the indexer's score kernels against
``models/hybrid.index_scores`` and its ``jax.vjp`` (the keys' gradient added
to a held accumulator); then ``models/hybrid``'s sparse mixer with the
kernel forced by name against its own XLA path (output, index loss, the
gradients of q, k, v and of the indexer's parameters), under ties in the
index scores and where a first chunk selects every causal key, with the
index scores in XLA and in their kernels; who chooses
(``attention_candidate``); and the other families' training steps, which
lower to the parent's text byte for byte.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models import transformer as tf
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.ops.pallas import attention as pallas_attention
from deeplearning4j_tpu.ops.pallas import sparse_attention as kernel

C, L, H, G, D = 32, 384, 4, 2, 16          # 384 keys: three blocks of 128


def chunk(dtype=jnp.float32, start=300, seed=0):
    """One chunk of queries at ``start`` over ``L`` keys with a selection
    that leaves some rows of the first key block with no key at all."""
    rng = np.random.default_rng(seed)
    q, do = (jnp.asarray(rng.standard_normal((C, H, D)), dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((L, G, D)), dtype) for _ in range(2))
    causal = np.arange(L)[None, :] <= (start + np.arange(C))[:, None]
    chosen = causal & (rng.random((C, L)) < 0.3)
    chosen[::3, :128] = False                      # empty rows in block 0
    chosen[:, 130] = True                          # no row is empty overall
    return q, k, v, do, jnp.asarray(chosen), start + C


def flat(x):
    return x.reshape(x.shape[0], -1)


def plain(q, k, v, chosen):
    """``(out, lse (G, C, R), mean of the heads' probabilities)`` in jnp."""
    s = jnp.einsum("tgrd,sgd->gtrs", q.reshape(C, G, H // G, D), k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    s = jnp.where(chosen[None, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return (kernel.reference_selected_attention(q, k, v, chosen),
            jax.nn.logsumexp(s, axis=-1), p.sum(axis=(0, 2)) / H)


def close(a, b, dtype):
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               atol=tol * max(1.0, float(jnp.abs(b).max())))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("skip", [False, True], ids=["whole", "frontier"])
def test_forward_and_heads_mean_match_plain_attention(dtype, skip):
    """``start = 300`` puts the frontier inside the third block; at 100 the
    last two blocks are skipped and the heads' mean is zero there."""
    q, k, v, _, chosen, frontier = chunk(dtype, start=100 if skip else 300)
    want, want_lse, want_mean = plain(q, k, v, chosen)
    out, lse = kernel.forward(flat(q), flat(k), flat(v), chosen, frontier, kv_heads=G)
    close(out.reshape(q.shape), want, dtype)
    close(lse, want_lse, jnp.float32 if dtype == jnp.float32 else dtype)
    mean = kernel.head_mean(flat(q), flat(k), chosen, lse, frontier, kv_heads=G)
    assert mean.shape == (C, L) and mean.dtype == jnp.float32
    close(mean, want_mean, dtype)
    assert not np.asarray(mean)[~np.asarray(chosen)].any()
    np.testing.assert_allclose(mean.sum(axis=1), 1.0, atol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_backward_adds_to_its_accumulators_below_the_frontier(dtype):
    q, k, v, do, chosen, frontier = chunk(dtype, start=100)
    out, lse = kernel.forward(flat(q), flat(k), flat(v), chosen, frontier, kv_heads=G)
    _, pull = jax.vjp(lambda q, k, v: kernel.reference_selected_attention(
        q, k, v, chosen), q, k, v)
    want = pull(do)
    held = jnp.full((L, G * D), 0.5, jnp.float32)
    dq, dk, dv = kernel.backward(flat(q), flat(k), flat(v), chosen, out, lse,
                                 flat(do), held, 2 * held, frontier, kv_heads=G)
    assert dk.dtype == dv.dtype == jnp.float32 and dk.shape == dv.shape == (L, G * D)
    close(dq.reshape(q.shape), want[0], dtype)
    close(dk.reshape(k.shape) - 0.5, want[1], dtype)
    close(dv.reshape(v.shape) - 1.0, want[2], dtype)
    # blocks past the frontier were neither read nor written
    assert (np.asarray(dk)[256:] == 0.5).all() and (np.asarray(dv)[256:] == 1.0).all()


def test_the_candidate_differentiates_through_its_own_backward():
    q, k, v, do, chosen, _ = chunk()
    got = jax.grad(lambda *a: jnp.sum(kernel.selected_attention(*a, chosen) * do),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(
        kernel.reference_selected_attention(*a, chosen) * do), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        close(a, b, jnp.float32)


@pytest.mark.parametrize("n_keys,largest,want", [
    (16384, 512, 512), (16384, 1024, 1024), (4096, 1024, 1024), (12288, 1024, 1024),
    (384, 512, 128), (768, 1024, 256), (96, 512, 96)])
def test_key_blocks_tile_the_keys(n_keys, largest, want):
    assert kernel._block_k(n_keys, largest) == want


# ------------------------------------------------------------ the indexer's scores

#: (C, L, J, D, frontier): a chunk of queries over its keys, the index heads
INDEX_SHAPES = {
    "frontier-in-the-second-of-three-blocks": (128, 384, 4, 64, 228),
    "cell-like-16-heads": (256, 512, 16, 64, 512),
    "one-key-block-128-wide": (128, 128, 2, 128, 128),
    "blocks-of-512-the-second-skipped": (256, 1024, 4, 64, 256),
}


def index_case(shape, dtype=jnp.float32, seed=0):
    c, n_keys, heads, dim, frontier = shape
    rng = np.random.default_rng(seed)
    qi = jnp.asarray(rng.standard_normal((c, heads, dim)), dtype)
    ki = jnp.asarray(rng.standard_normal((n_keys, dim)), dtype)
    w = jnp.asarray(rng.standard_normal((c, heads)), jnp.float32)
    block = kernel._block_k(n_keys)
    computed = -(-frontier // block) * block       # keys of the blocks it reads
    d_scores = jnp.asarray(rng.standard_normal((c, n_keys)), jnp.float32)
    return qi, ki, w, d_scores.at[:, computed:].set(0.0), frontier, computed


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", list(INDEX_SHAPES.values()), ids=list(INDEX_SHAPES))
def test_index_forward_is_the_xla_index_scores(shape, dtype):
    """The products of bf16 operands are exact in f32: both dtypes agree to
    f32 rounding; the key blocks from the frontier's on are zeros."""
    qi, ki, w, _, frontier, computed = index_case(shape, dtype)
    with jax.default_matmul_precision("highest"):
        want = hybrid.index_scores(qi, ki, w)
    got = kernel.index_forward(qi, ki, w, frontier)
    assert got.shape == want.shape and got.dtype == jnp.float32
    close(got[:, :computed], want[:, :computed], jnp.float32)
    assert not np.asarray(got[:, computed:]).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", list(INDEX_SHAPES.values()), ids=list(INDEX_SHAPES))
def test_index_backward_adds_to_a_held_accumulator(shape, dtype):
    """``d qi``, ``d w`` and ``d ki`` against ``jax.vjp`` of the XLA path;
    ``d ki`` ADDS to a non-zero accumulator, whose key blocks from the
    frontier's on are neither read nor written."""
    qi, ki, w, d_scores, frontier, computed = index_case(shape, dtype)
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(hybrid.index_scores, qi, ki, w)
        want_qi, want_ki, want_w = pull(d_scores)
    held = jnp.asarray(np.random.default_rng(1).standard_normal(ki.shape[::-1]),
                       jnp.float32)
    d_qi, dki_t, d_w = kernel.index_backward(qi, ki, w, d_scores, held, frontier)
    assert (d_qi.shape, d_qi.dtype) == (qi.shape, qi.dtype)
    assert dki_t.dtype == d_w.dtype == jnp.float32 and d_w.shape == w.shape
    close(d_qi, want_qi, dtype)
    close((dki_t - held).T, want_ki, dtype)
    close(d_w, want_w, jnp.float32 if dtype == jnp.float32 else dtype)
    assert (np.asarray(dki_t)[:, computed:] == np.asarray(held)[:, computed:]).all()


def test_index_scores_differentiate_through_their_own_backward():
    qi, ki, w, d_scores, frontier, _ = index_case(INDEX_SHAPES[
        "frontier-in-the-second-of-three-blocks"])

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * d_scores), argnums=(0, 1, 2))(
            qi, ki, w)

    with jax.default_matmul_precision("highest"):
        want = grads(hybrid.index_scores)
    for a, b in zip(grads(lambda *a: kernel.index_scores(*a, frontier)), want):
        close(a, b, jnp.float32)


@pytest.mark.parametrize("c,n_keys,dim,want", [
    (256, 16384, 64, True), (128, 128, 128, True), (512, 4096, 64, True),
    (256, 16384, 32, False), (16, 64, 64, False), (256, 640, 64, True),
    (256, 200, 64, False), (256, 32768, 64, False)],
    ids=["cell", "one-block", "chunk-of-512", "width-32", "chunk-of-16",
         "five-blocks", "partial-block", "a-second-chips"])
def test_index_kernels_take_the_attention_kernels_chunks(c, n_keys, dim, want):
    assert kernel.index_takes(c, n_keys, dim) is want


# ------------------------------------------------------------ the mixer's two paths

E, SEQ, BATCH, J, DI = 64, 64, 2, 4, 8


def mixer(**kw):
    return dataclasses.replace(hybrid.SparseAttention(
        H, G, D, 1e7, True, J, DI, top_k=24, q_chunk=8, kv_chunk=8, rows=16), **kw)


def mixer_case(spec, seed=3, tied=False):
    p = spec.init(jax.random.key(seed), E, jnp.float32)
    if tied:        # no head weighs anything: every index score is 0.0
        p = dict(p, index=dict(p["index"], ww=jnp.zeros_like(p["index"]["ww"])))
    return p, jax.random.normal(jax.random.key(seed + 1), (BATCH, SEQ, E))


def both_paths(monkeypatch, fn, *args, index="xla"):
    """``fn(*args)`` with the mixer on its XLA path and on the kernel, whose
    index scores take the ``index`` path."""
    out = []
    for asked in ("ring", "selected"):
        monkeypatch.setattr(hybrid, "sparse_attend", functools.partial(
            _sparse_attend, asked=asked))
        jax.clear_caches()               # a checkpointed block's trace is cached
        METRICS.reset()
        with jax.default_matmul_precision("highest"):
            out.append(fn(*args))
        c = METRICS.snapshot()["counters"]
        on = asked == "selected"
        assert c.get("attention.path.kernel" if on else "attention.path.xla", 0) >= 1
        want = f"dsa.index_path.{index if on else 'xla'}"
        assert c.get(want, 0) == c["dsa.layers"] >= 1, (asked, c)
    monkeypatch.undo()
    jax.clear_caches()
    return out


_sparse_attend = hybrid.sparse_attend


@pytest.mark.parametrize("case", ["selection-bites", "tied-scores", "top-k-above-a-chunk",
                                  "one-chunk", "one-kv-head"])
def test_mixer_on_the_kernel_is_the_mixer_on_the_xla_path(monkeypatch, case):
    """Output, index loss, and the gradients of every parameter of the mixer
    (the indexer's too: they come from the index loss alone, through the
    heads' mean the kernel writes) and of its input.  ``top_k = 24`` over
    chunks of 16: the first chunk selects every causal key, the second some
    rows all and some rows 24, the rest 24 of up to 64."""
    spec = {"selection-bites": mixer(), "tied-scores": mixer(),
            "top-k-above-a-chunk": mixer(top_k=40),
            "one-chunk": mixer(rows=24),              # 24 does not divide 64
            "one-kv-head": mixer(n_kv_heads=1)}[case]
    p, u = mixer_case(spec, tied=case == "tied-scores")

    def run(p, u):
        def f(p, u):
            out, loss = hybrid.sparse_attention_mixer(spec, p, u, jnp.float32)
            return jnp.sum(jnp.sin(out)) + 2.0 * jnp.sum(loss), (out, loss)
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, u)

    ((_, (want, want_loss)), want_g), ((_, (got, got_loss)), got_g) = both_paths(
        monkeypatch, run, p, u)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-5)
    assert float(jnp.abs(want_g[0]["index"]["wq" if case != "tied-scores" else "ww"]
                         ).max()) > 1e-6        # the indexer has a gradient
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_g)[0],
                            jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())), err_msg=str(path))


def index_mixer(**kw):
    """A mixer whose chunks the index kernels take: 128 queries at a time,
    index heads of 64."""
    return dataclasses.replace(hybrid.SparseAttention(
        H, G, D, 1e7, True, J, 64, top_k=48, q_chunk=128, kv_chunk=128, rows=128),
        **kw)


@pytest.mark.parametrize("case", ["two-chunks", "one-chunk-of-256", "tied-scores"])
def test_mixer_on_the_index_kernels_is_the_mixer_on_the_xla_path(monkeypatch, case):
    """As above, with the index scores (and, backward, the indexer's
    gradients) in the index kernels: 256 positions, ``top_k = 48``."""
    spec = index_mixer(rows=256) if case == "one-chunk-of-256" else index_mixer()
    p = spec.init(jax.random.key(5), E, jnp.float32)
    if case == "tied-scores":
        p = dict(p, index=dict(p["index"], ww=jnp.zeros_like(p["index"]["ww"])))
    u = jax.random.normal(jax.random.key(6), (1, 256, E))

    def run(p, u):
        def f(p, u):
            out, loss = hybrid.sparse_attention_mixer(spec, p, u, jnp.float32)
            return jnp.sum(jnp.sin(out)) + 2.0 * jnp.sum(loss), (out, loss)
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, u)

    ((_, (want, want_loss)), want_g), ((_, (got, got_loss)), got_g) = both_paths(
        monkeypatch, run, p, u, index="kernel")
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-5)
    assert float(jnp.abs(want_g[0]["index"]["wq" if case != "tied-scores" else "ww"]
                         ).max()) > 1e-6
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_g)[0],
                            jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * max(1.0, float(jnp.abs(b).max())), err_msg=str(path))


def test_a_checkpointed_model_on_the_kernel_is_the_model_on_the_xla_path(monkeypatch):
    """Two layers under ``remat``, bf16 compute: the objective's two parts and
    every gradient leaf; the kernel's layers count ``attention.path.kernel``."""
    base = TransformerConfig(
        vocab_size=256, d_model=E, n_heads=H, n_kv_heads=G, n_layers=2, d_ff=32,
        max_len=SEQ, causal=True, tie_embeddings=False, dtype=jnp.bfloat16,
        param_dtype=jnp.float32, remat=True, xent_chunk=32)
    cfg = hybrid.HybridConfig(base=base, norm_eps=1e-6, layers=((
        mixer(), hybrid.MoE(8, (0, 4), 0, 32, top_k=2, renormalize=True)),) * 2)
    params = hybrid.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(7), (BATCH, SEQ), 0, 256)

    def run(params):
        def f(p):
            lm, own = hybrid.loss_parts(p, toks, jnp.roll(toks, -1, axis=1), cfg)
            return (lm + own).mean(), (lm, own)
        return jax.jit(jax.value_and_grad(f, has_aux=True))(params)

    ((_, (lm, own)), want), ((_, (lm_k, own_k)), got) = both_paths(
        monkeypatch, run, params)
    np.testing.assert_allclose(lm_k, lm, rtol=2e-3)
    np.testing.assert_allclose(own_k, own, rtol=2e-2)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        # bf16 near-ties in the index scores may select another key: a few
        # entries move, the leaf as a whole does not
        err = float(jnp.linalg.norm(a - b) / max(float(jnp.linalg.norm(b)), 1e-12))
        assert err < 0.05, (path, err)


def test_a_checkpointed_block_runs_the_forward_kernel_once(monkeypatch):
    """``out`` and ``lse`` are kept by name: the compiled gradient of a
    checkpointed layer holds the forward pass once a span of chunks, the
    backward once, and the heads' mean twice (forward, and again where the
    index loss is differentiated)."""
    monkeypatch.setattr(hybrid, "sparse_attend", functools.partial(
        _sparse_attend, asked="selected"))
    jax.clear_caches()
    base = TransformerConfig(
        vocab_size=256, d_model=E, n_heads=H, n_kv_heads=G, n_layers=1, d_ff=32,
        max_len=SEQ, causal=True, tie_embeddings=False, remat=True, xent_chunk=32)
    cfg = hybrid.HybridConfig(base=base, norm_eps=1e-6, layers=((
        mixer(), hybrid.GatedMLP(32)),))
    params = hybrid.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((1, SEQ), jnp.int32)
    text = jax.jit(jax.value_and_grad(lambda p: hybrid.lm_loss(p, toks, toks, cfg))
                   ).lower(params).as_text()
    jax.clear_caches()
    spans = len(hybrid._key_spans(mixer(), SEQ)[1])
    calls = {name: len(re.findall(rf"call @{name}(_\d+)?\(", text)) for name in (
        "_sparse_fwd", "_sparse_bwd", "_sparse_headsum")}
    assert calls == {"_sparse_fwd": spans, "_sparse_bwd": spans,
                     "_sparse_headsum": 2 * spans}, calls


def test_a_checkpointed_block_scores_the_index_twice(monkeypatch):
    """On the index kernels the compiled gradient of a checkpointed layer
    holds the index forward twice a span of chunks (the forward pass, and
    the backward's selection made again) and the index backward once: the
    block's recomputed forward makes no index score."""
    monkeypatch.setattr(hybrid, "sparse_attend", functools.partial(
        _sparse_attend, asked="selected"))
    jax.clear_caches()
    base = TransformerConfig(
        vocab_size=256, d_model=E, n_heads=H, n_kv_heads=G, n_layers=1, d_ff=32,
        max_len=256, causal=True, tie_embeddings=False, remat=True, xent_chunk=32)
    cfg = hybrid.HybridConfig(base=base, norm_eps=1e-6, layers=((
        index_mixer(), hybrid.GatedMLP(32)),))
    params = hybrid.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((1, 256), jnp.int32)
    text = jax.jit(jax.value_and_grad(lambda p: hybrid.lm_loss(p, toks, toks, cfg))
                   ).lower(params).as_text()
    jax.clear_caches()
    spans = len(hybrid._key_spans(index_mixer(), 256)[1])
    calls = {name: len(re.findall(rf"call @{name}(_\d+)?\(", text)) for name in (
        "_index_fwd", "_index_bwd", "_sparse_fwd")}
    assert calls == {"_index_fwd": 2 * spans, "_index_bwd": spans,
                     "_sparse_fwd": spans}, calls


# ------------------------------------------------------------------- who chooses

@pytest.mark.parametrize("backend,asked,shape,want", [
    ("tpu", "auto", (16384, 32, 128, (4, 256)), "selected"),
    ("tpu", "auto", (4096, 32, 128, (4, 256)), "selected"),
    ("cpu", "auto", (16384, 32, 128, (4, 256)), None),
    ("tpu", "auto", (16384, 32, 64, (4, 256)), None),       # head width
    ("tpu", "auto", (16384, 30, 128, (4, 256)), None),      # broken groups
    ("tpu", "auto", (16384, 32, 128, (4, 192)), None),      # chunks of 1.5 blocks
    ("tpu", "auto", (16640, 32, 128, (4, 16640)), None),    # no whole chunks
    ("tpu", "auto", (1920, 32, 128, (4, 1920)), None),      # one chunk, too tall
    ("tpu", "auto", (32768, 32, 128, (4, 256)), None),      # a second chip's
    ("tpu", "ring", (16384, 32, 128, (4, 256)), None),
    ("cpu", "selected", (64, 4, 16, (2, 16)), "selected"),  # a parity test's
], ids=["cell", "4096", "cpu", "width-64", "30-heads", "rows-192", "ragged", "1920",
        "32768", "forced-ring", "forced-kernel"])
def test_a_mixer_with_a_selection_gets_the_kernel_where_it_compiles(
        monkeypatch, backend, asked, shape, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    METRICS.reset()
    t, h, d, selection = shape
    assert pallas_attention.attention_candidate(
        t, h, d, asked=asked, selection=selection) == want
    c = METRICS.snapshot()["counters"]
    assert (c.get("attention.path.kernel", 0), c.get("attention.path.xla", 0)) == (
        (1, 0) if want else (0, 1))


def test_without_a_selection_the_answers_are_what_they_were(monkeypatch):
    for backend in ("tpu", "cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        for t, h, d in ((512, 12, 64), (4096, 8, 128), (8192, 8, 128), (448, 12, 64),
                        (16384, 32, 128)):
            for asked in ("auto", "ring", "fused"):
                for n_sp in (1, 2):
                    assert pallas_attention.attention_candidate(
                        t, h, d, n_sp=n_sp, asked=asked) == parents_attention_candidate(
                            t, h, d, n_sp=n_sp, asked=asked)


# ------------------------------------------- the other families' steps are the parent's

def parents_attention_candidate(t, h, d, *, n_sp=1, asked="auto"):
    """``attention_candidate`` as the parent commit (e64259f) had it."""
    if asked == "ring" or n_sp != 1:
        name = None
    elif asked == "auto":
        on = jax.default_backend() == "tpu" and pallas_attention.kernel_takes(t, h, d)
        name = "fused" if on else None
    else:
        name = asked if t % 128 == 0 else None
    METRICS.increment(
        "attention.path.kernel" if name else "attention.path.xla")
    return name


def _tiny(**kw):
    return TransformerConfig(**{**dict(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128, max_len=128,
        causal=True, remat=True, xent_chunk=64), **kw})


def _dense():
    cfg = _tiny(causal=False, remat=False)
    return cfg, tf.init_params, lambda p, x, y: tf.lm_loss_local(p, x, y, cfg)


def _zaya_like():
    cfg = hybrid.HybridConfig(base=_tiny(n_kv_heads=2), layers=((
        hybrid.CCA(4, 2, 16), hybrid.MoE(8, (0, 4), 32, 48)),) * 2)
    return cfg, hybrid.init_params, lambda p, x, y: hybrid.lm_loss_per_example(
        p, x, y, cfg).mean()


def _looped():
    cfg = hybrid.HybridConfig(
        base=_tiny(tie_embeddings=False), norm_eps=1e-6, n_loops=3, exit_beta=0.1,
        layers=((hybrid.Attention(4, 4, 16), hybrid.GatedMLP(96)),) * 2)
    return cfg, hybrid.init_params, lambda p, x, y: hybrid.looped_lm_loss_per_example(
        p, x, y, cfg).mean()


@pytest.mark.parametrize("family", [_dense, _zaya_like, _looped],
                         ids=["dense", "zaya-like", "looped"])
def test_other_families_training_steps_lower_to_the_parents_text(monkeypatch, family):
    """What this PR changed on the code these families run: the one predicate
    (a new keyword, a new branch) and the names a checkpointed block keeps.
    With the parent's of both put back, an SGD step's lowered text is the
    same, byte for byte: nothing else they trace was touched."""
    cfg, init, loss = family()
    params = init(jax.random.key(0), cfg)
    toks = jnp.zeros((2, 128), jnp.int32)

    def step(p, x, y):
        value, grads = jax.value_and_grad(loss)(p, x, y)
        return value, jax.tree.map(lambda a, g: a - 0.1 * g.astype(a.dtype), p, grads)

    def lowered():
        jax.clear_caches()
        return jax.jit(step).lower(params, toks, toks).as_text()

    mine = lowered()
    monkeypatch.setattr(pallas_attention, "attention_candidate",
                        parents_attention_candidate)
    monkeypatch.setattr(hybrid, "KEPT", ("dsa.out", "dsa.loss"))
    parents = lowered()
    monkeypatch.undo()
    jax.clear_caches()
    assert len(mine) > 10_000 and mine == parents
