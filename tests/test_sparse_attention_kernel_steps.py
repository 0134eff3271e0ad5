"""The steps that run the sparse mixer (``test_sparse_attention_kernel.py``'s
sizes): a checkpointed model and block on the kernel, who chooses the path
(``attention_candidate``), and the other families' steps as the parent's.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.ops.pallas import attention as pallas_attention
from hybrid_common import dense_family, looped_family, zaya_like_family
from test_sparse_attention_kernel import (BATCH, D, E, G, H, SEQ, _sparse_attend,
                                          both_paths, index_mixer, mixer, programs)


def test_a_checkpointed_model_on_the_kernel_is_the_model_on_the_xla_path(programs):
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
        return jax.value_and_grad(f, has_aux=True)(params)

    ((_, (lm, own)), want), ((_, (lm_k, own_k)), got) = both_paths(
        programs, cfg, run, params)
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
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
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
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
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


@pytest.mark.parametrize("family", [dense_family, zaya_like_family, looped_family],
                         ids=["dense", "zaya-like", "looped"])
def test_other_families_training_steps_lower_to_the_parents_text(monkeypatch, family):
    """What this PR changed on the code these families run: the one predicate
    (a new keyword, a new branch) and the names a checkpointed block keeps.
    With the parent's of both put back, an SGD step's lowered text is the
    same, byte for byte: nothing else they trace was touched."""
    cfg, init, loss, _ = family()
    params = jax.eval_shape(lambda: init(jax.random.key(0), cfg))
    toks = jnp.zeros((2, 128), jnp.int32)

    def step(p, x, y):
        value, grads = jax.value_and_grad(lambda p: jnp.mean(loss(p, x, y)))(p)
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
