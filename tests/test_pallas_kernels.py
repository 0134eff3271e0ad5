"""Gradient-parity suite for the ops/pallas kernel tier.

Every kernel candidate must match its pure-jnp reference forward AND
backward, in Pallas interpret mode on CPU (the same code compiles to
Mosaic on TPU), at odd/near-prime shapes and in both f32 and bf16 — plus
unit coverage of the candidate registry, and every Pallas candidate held
to the tolerances it declares against its reference.
"""

import ast
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.pallas import registry
from deeplearning4j_tpu.ops.pallas.attention import (fused_attention,
                                                     reference_attention)
from deeplearning4j_tpu.ops.pallas.layernorm import (
    fused_residual_layernorm, reference_residual_layernorm)
from deeplearning4j_tpu.ops.pallas.matmul_int8 import (
    dequantize, int8_matmul, quantize, quantize_params_for_decode,
    reference_int8_matmul, top1_agreement)
from deeplearning4j_tpu.ops.pallas.xent import (blocked_cross_entropy,
                                                reference_xent_sum)

F32_TOL = dict(atol=2e-5, rtol=3e-5)
# bf16 inputs: reference and kernel round differently mid-pipeline
BF16_TOL = dict(atol=3e-2, rtol=3e-2)


def _tol(dtype):
    return F32_TOL if dtype == jnp.float32 else BF16_TOL


def _close(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **_tol(dtype))


# ------------------------------------------------------------------ registry

def test_registry_kinds_and_candidates_complete():
    assert registry.kinds() == ["attention", "int8_matmul",
                                "layernorm_residual", "paged_attention",
                                "paged_attention_int8", "sparse_attention",
                                "xent"]
    assert [c.name for c in registry.candidates("attention")] == [
        "fused", "ring"]
    # every pallas candidate ships a reference and documented tolerances
    for kind in registry.kinds():
        for c in registry.candidates(kind):
            assert c.reference is not None, (kind, c.name)
            if c.source == "pallas":
                assert c.tolerances, (kind, c.name)
                # and is held to them below
                assert (kind, c.name) in _CHECKS, (kind, c.name)


def test_registry_get_unknown_lists_registered():
    with pytest.raises(KeyError, match="fused"):
        registry.get("attention", "nope")


def test_registry_reregistration_same_fn_is_noop_different_fn_raises():
    cand = registry.get("attention", "fused")
    registry.register(cand)                       # idempotent
    clash = dataclasses.replace(cand, fn=lambda *a, **k: None)
    with pytest.raises(ValueError, match="already registered"):
        registry.register(clash)


def _package_sources():
    import deeplearning4j_tpu
    root = Path(deeplearning4j_tpu.__file__).parent
    return {f: f.read_text() for f in sorted(root.rglob("*.py"))}


def test_registry_imports_without_pallas():
    """``registry.py`` names no jax module at its top level: the kernels,
    and ``jax.experimental.pallas`` with them, load at the first lookup."""
    tree = ast.parse(Path(registry.__file__).read_text())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    named = [a.name for n in top if isinstance(n, ast.Import)
             for a in n.names] + [n.module or "" for n in top
                                  if isinstance(n, ast.ImportFrom)]
    assert not [m for m in named if m.split(".")[0] == "jax"], named


def test_kernels_take_vmem_spec_from_inside_the_tier():
    tier = Path(registry.__file__).parent
    users = {f.name: [l for l in text.splitlines()
                      if "import" in l and "vmem_spec" in l]
             for f, text in _package_sources().items() if "vmem_spec" in text}
    assert set(users) == {"attention.py", "layernorm.py", "matmul_int8.py",
                          "paged_attention.py", "xent.py", "vmem.py"}
    assert (tier / "vmem.py").is_file()
    for name, lines in users.items():
        if name != "vmem.py":
            assert lines == ["from .vmem import vmem_spec"], (name, lines)


def test_one_function_joins_the_backend_with_the_shape():
    """The choice of attention kernel lives in one function of the package:
    nothing else asks for the TPU backend and ``kernel_takes`` together."""
    joins = []
    for f, text in _package_sources().items():
        for node in ast.walk(ast.parse(text)):
            if isinstance(node, ast.FunctionDef):
                src = ast.get_source_segment(text, node)
                if ('default_backend() == "tpu"' in src
                        and "kernel_takes(" in src):
                    joins.append(f"{f.name}:{node.name}")
    assert joins == ["attention.py:attention_candidate"]


# ------------------------------------------------------ declared tolerances

def _max_abs(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def _grad_err(fn, ref, *args, argnums=0):
    """Largest difference between the gradients of ``sum(out ** 2) / 2``
    through ``fn`` and through ``ref`` (a tuple's last member is the
    output that counts)."""
    def loss(f):
        def l(*a):
            out = f(*a)
            out = out[-1] if isinstance(out, tuple) else out
            return (out.astype(jnp.float32) ** 2).sum() / 2
        return l
    ga = jax.grad(loss(fn), argnums)(*args)
    gb = jax.grad(loss(ref), argnums)(*args)
    return max(_max_abs(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)))


def _attention_check(cand):
    rng = np.random.default_rng(0)
    qkv = tuple(jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
                for _ in range(3))
    return {"max_err": _max_abs(cand.fn(*qkv), cand.reference(*qkv)),
            "grad_err": _grad_err(cand.fn, cand.reference, *qkv,
                                  argnums=(0, 1, 2))}


def _sparse_attention_check(cand):
    """One chunk of 64 queries at position 128 over 192 keys, 4 heads over 2
    KV heads, a random third of the causal keys selected."""
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((64, 4, 32)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((192, 2, 32)), jnp.float32)
            for _ in range(2))
    causal = np.arange(192)[None, :] <= 128 + np.arange(64)[:, None]
    chosen = causal & (rng.random((64, 192)) < 0.33)
    chosen[:, 0] = True
    chosen = jnp.asarray(chosen)
    fn, ref = (lambda *a, f=f: f(*a, chosen) for f in (cand.fn, cand.reference))
    return {"max_err": _max_abs(fn(q, k, v), ref(q, k, v)),
            "grad_err": _grad_err(fn, ref, q, k, v, argnums=(0, 1, 2))}


def _ln_check(cand):
    rng = np.random.default_rng(1)
    x, r = (jnp.asarray(rng.standard_normal((101, 64)), jnp.float32)
            for _ in range(2))
    scale = jnp.asarray(rng.standard_normal(64), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(64), jnp.float32)
    y, h = cand.fn(x, r, scale, bias)
    yr, hr = cand.reference(x, r, scale, bias)
    return {"max_err": max(_max_abs(y, yr), _max_abs(h, hr)),
            "grad_err": _grad_err(cand.fn, cand.reference, x, r, scale, bias,
                                  argnums=(0, 1, 2, 3))}


def _xent_check(cand):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((101, 64)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((64, 77)) * 0.05, jnp.float32)
    tgt = jnp.asarray(rng.integers(0, 77, 101), jnp.int32)
    a, b = float(cand.fn(x, head, tgt)), float(cand.reference(x, head, tgt))
    return {"max_err": abs(a - b) / max(abs(b), 1e-9),
            "grad_err": _grad_err(
                lambda x_, h_: jnp.sqrt(cand.fn(x_, h_, tgt)),
                lambda x_, h_: jnp.sqrt(cand.reference(x_, h_, tgt)),
                x, head, argnums=(0, 1))}


def _int8_check(cand):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((101, 64)), jnp.float32)
    qw = quantize(jnp.asarray(rng.standard_normal((64, 77)) * 0.05))
    out, ref = cand.fn(x, qw), cand.reference(x, qw)
    return {"max_err": _max_abs(out, ref),
            "top1_agree": float(top1_agreement(out, ref))}


def _paged_check(cand):
    _, _, args = _paged_case(jnp.float32)
    return {"max_err": _max_abs(cand.fn(*args), cand.reference(*args))}


def _paged_int8_check(cand):
    _, _, args = _paged_int8_case()
    out, ref = cand.fn(*args), cand.reference(*args)
    return {"max_err": _max_abs(out, ref),
            "top1_agree": float(top1_agreement(out, ref))}


_CHECKS = {
    ("attention", "fused"): _attention_check,
    ("sparse_attention", "selected"): _sparse_attention_check,
    ("layernorm_residual", "fused"): _ln_check,
    ("xent", "blocked"): _xent_check,
    ("int8_matmul", "pallas_int8"): _int8_check,
    ("paged_attention", "pallas"): _paged_check,
    ("paged_attention_int8", "pallas_int8"): _paged_int8_check,
}


@pytest.mark.parametrize("kind,name", list(_CHECKS),
                         ids=[f"{k}.{n}" for k, n in _CHECKS])
def test_pallas_candidate_holds_its_declared_tolerances(kind, name):
    """The correctness half of how a kernel becomes a default, on every PR:
    the candidate's kernel (interpreted here) against its ``reference``,
    every reading under the ``max_err`` it declares and every reading named
    in ``min`` at or above its floor.  The speed half is the ledger."""
    cand = registry.get(kind, name)
    check = _CHECKS[kind, name](cand)
    floors = cand.tolerances.get("min", {})
    assert set(floors) <= set(check), "a declared floor was not measured"
    for key, val in check.items():
        if key in floors:
            assert val >= floors[key], (key, val, floors[key])
        else:
            assert val < cand.tolerances["max_err"], (
                key, val, cand.tolerances["max_err"])


# ---------------------------------------------------------- fused attention

@pytest.mark.strict_dtypes
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_attention_forward_parity(causal, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 256, 3, 16), dtype) for kk in ks)
    got = fused_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    want = reference_attention(q, k, v, causal=causal)
    _close(got, want, dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_attention_gradient_parity(causal):
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (1, 128, 2, 8), jnp.float32)
               for kk in ks)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, causal=causal)))

    g1 = jax.grad(loss(fused_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        _close(a, b, jnp.float32)


def test_fused_attention_block_sweep_and_frontier():
    """Asymmetric block configs exercise the traced frontier bound: the
    kernel must stay exact when block_q != block_k."""
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (1, 512, 1, 8), jnp.float32)
               for kk in ks)
    want = reference_attention(q, k, v, causal=True)
    for bq, bk in ((128, 128), (256, 128), (128, 256), (512, 512)):
        got = fused_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        _close(got, want, jnp.float32)


# ------------------------------------------------------- fused ln + residual

@pytest.mark.strict_dtypes
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_layernorm_forward_parity_odd_rows(dtype):
    # 101 rows: prime, forces the internal pad-and-slice path
    ks = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(ks[0], (101, 48), dtype)
    r = jax.random.normal(ks[1], (101, 48), dtype)
    scale = jax.random.normal(ks[2], (48,)) + 1.0
    bias = jax.random.normal(ks[3], (48,))
    y1, h1 = fused_residual_layernorm(x, r, scale, bias, block_rows=32)
    y2, h2 = reference_residual_layernorm(x, r, scale, bias)
    _close(y1, y2, dtype)
    _close(h1, h2, dtype)


def test_fused_layernorm_gradient_parity_with_mask():
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (67, 32), jnp.float32)
    r = jax.random.normal(ks[1], (67, 32), jnp.float32)
    scale = jax.random.normal(ks[2], (32,)) + 1.0
    bias = jax.random.normal(ks[3], (32,))
    mask = (jax.random.uniform(ks[4], (67, 1)) > 0.3).astype(jnp.float32)

    def loss(fn):
        def l(x, r, scale, bias):
            y, h = fn(x, r, scale, bias, mask=mask)
            return jnp.sum(jnp.sin(h)) + 0.1 * jnp.sum(y)
        return l

    g1 = jax.grad(loss(fused_residual_layernorm), argnums=(0, 1, 2, 3))(
        x, r, scale, bias)
    g2 = jax.grad(loss(reference_residual_layernorm), argnums=(0, 1, 2, 3))(
        x, r, scale, bias)
    for a, b in zip(g1, g2):
        _close(a, b, jnp.float32)


def test_fused_layernorm_batched_shape_roundtrip():
    x = jax.random.normal(jax.random.key(5), (2, 37, 16), jnp.float32)
    r = jnp.zeros_like(x)
    y, h = fused_residual_layernorm(x, r, jnp.ones((16,)), jnp.zeros((16,)))
    assert y.shape == h.shape == x.shape
    _close(y, x, jnp.float32)


# ------------------------------------------------------------- blocked xent

@pytest.mark.strict_dtypes
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blocked_xent_forward_parity_near_prime(dtype):
    # N=101 (prime) tokens, V=77 (odd, not a multiple of any block):
    # both pad/mask paths fire
    ks = jax.random.split(jax.random.key(6), 4)
    h = jax.random.normal(ks[0], (101, 24), dtype)
    head = (jax.random.normal(ks[1], (24, 77)) * 0.2).astype(dtype)
    t = jax.random.randint(ks[2], (101,), 0, 77)
    w = jax.random.uniform(ks[3], (101,))
    got = blocked_cross_entropy(h, head, t, w, block_t=32, block_v=16)
    want = reference_xent_sum(h, head, t, w)
    np.testing.assert_allclose(float(got), float(want),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_blocked_xent_gradient_parity():
    ks = jax.random.split(jax.random.key(7), 4)
    h = jax.random.normal(ks[0], (101, 16), jnp.float32)
    head = jax.random.normal(ks[1], (16, 53)) * 0.3
    t = jax.random.randint(ks[2], (101,), 0, 53)
    w = jax.random.uniform(ks[3], (101,))
    g1 = jax.grad(lambda h, hd, w: blocked_cross_entropy(
        h, hd, t, w, block_t=32, block_v=16), argnums=(0, 1, 2))(h, head, w)
    g2 = jax.grad(lambda h, hd, w: reference_xent_sum(h, hd, t, w),
                  argnums=(0, 1, 2))(h, head, w)
    for a, b in zip(g1, g2):
        _close(a, b, jnp.float32)


def test_blocked_xent_under_jit_and_weightless():
    h = jax.random.normal(jax.random.key(8), (64, 16), jnp.float32)
    head = jax.random.normal(jax.random.key(9), (16, 32)) * 0.3
    t = jax.random.randint(jax.random.key(10), (64,), 0, 32)
    got = jax.jit(lambda h: blocked_cross_entropy(h, head, t))(h)
    want = reference_xent_sum(h, head, t)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_losses_dispatch_table_has_blocked_entry():
    from deeplearning4j_tpu.ops import losses
    assert losses.BLOCKED_XENT_BACKEND == "pallas"
    fn = losses.get("blocked_mcxent")
    labels = jnp.eye(8)[jnp.arange(8) % 8]
    h = jax.random.normal(jax.random.key(11), (8, 16))
    head = jax.random.normal(jax.random.key(12), (16, 8)) * 0.3
    via_pair = fn(labels, (h, head))
    logits = (h @ head).astype(jnp.float32)
    via_logits = fn(labels, logits)
    np.testing.assert_allclose(float(via_pair), float(via_logits), rtol=1e-5)


def test_losses_fallback_matches_pallas_backend():
    from deeplearning4j_tpu.ops import losses
    h = jax.random.normal(jax.random.key(13), (45, 16), jnp.float32)
    head = jax.random.normal(jax.random.key(14), (16, 19)) * 0.3
    t = jax.random.randint(jax.random.key(15), (45,), 0, 19)
    a = losses.blocked_token_xent(h, head, t)
    b = losses._blocked_xent_fallback(h, head, t)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)


# ------------------------------------------------------------- int8 matmul

def test_quantize_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.key(16), (32, 24)) * 0.1
    qw = quantize(w)
    assert qw.q.dtype == jnp.int8 and qw.scale.shape == (24,)
    # symmetric absmax: per-channel error <= scale/2 (half a quant step)
    err = jnp.abs(dequantize(qw) - w)
    assert bool(jnp.all(err <= qw.scale[None, :] * 0.5 + 1e-7))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_matmul_forward_parity(dtype):
    w = jax.random.normal(jax.random.key(17), (32, 100)) * 0.05
    qw = quantize(w)
    x = jax.random.normal(jax.random.key(18), (3, 5, 32), dtype)
    got = int8_matmul(x, qw, block_n=64)
    want = reference_int8_matmul(x, qw)
    assert got.dtype == jnp.float32
    _close(got, want, dtype)


def test_int8_matmul_gradient_flows_to_activations_only():
    w = jax.random.normal(jax.random.key(19), (16, 24)) * 0.05
    qw = quantize(w)
    x = jax.random.normal(jax.random.key(20), (7, 16), jnp.float32)
    g1 = jax.grad(lambda x: jnp.sum(jnp.sin(int8_matmul(x, qw))))(x)
    g2 = jax.grad(lambda x: jnp.sum(jnp.sin(reference_int8_matmul(x, qw))))(x)
    _close(g1, g2, jnp.float32)


def test_quantized_tree_drops_f32_ffn_and_decode_agrees():
    from deeplearning4j_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=97, d_model=32, n_heads=2,
                               n_layers=2, max_len=64, dtype=jnp.float32)
    params = tf.init_params(jax.random.key(21), cfg)
    qp = quantize_params_for_decode(params, cfg)
    for lp in qp["layers"]:
        assert "w1" not in lp and "w2" not in lp
        assert lp["w1_q"].q.dtype == jnp.int8
    assert "head_q" in qp
    cache = tf.init_decode_cache(cfg, 2)
    toks = jnp.array([3, 5], jnp.int32)
    lg_f32, _ = tf.decode_step(params, cache, toks, 0, cfg)
    lg_i8, _ = tf.decode_step(qp, cache, toks, 0, cfg)
    assert float(top1_agreement(lg_f32, lg_i8)) == 1.0


# ------------------------------------------------- transformer-level parity

def _tiny_cfg(**kw):
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    return TransformerConfig(vocab_size=101, d_model=32, n_heads=2,
                             n_layers=2, d_ff=64, max_len=128, causal=True,
                             dtype=jnp.float32, **kw)


@pytest.mark.parametrize("variant", [
    {"attention": "fused"},
    {"fused_ln": True},
    {"xent_impl": "blocked", "xent_chunk": 64},
])
def test_transformer_kernel_variants_match_default(variant):
    """Each kernel opt-in computes the same loss and gradients
    as the default XLA path (vocab 101 is prime: the blocked variant runs
    the shape-independent streaming schedule, not a lucky divisor)."""
    from deeplearning4j_tpu.models.transformer import (init_params,
                                                       lm_loss_local)
    cfg = _tiny_cfg()
    params = init_params(jax.random.key(22), cfg)
    toks = jax.random.randint(jax.random.key(23), (2, 128), 0, 101)
    tgts = jnp.roll(toks, -1, axis=1)

    def run(c):
        return jax.value_and_grad(
            lambda p: lm_loss_local(p, toks, tgts, c))(params)

    l0, g0 = run(cfg)
    l1, g1 = run(_tiny_cfg(**variant))
    assert abs(float(l0) - float(l1)) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_near_prime_token_count_streams_through_blocked_xent():
    """The PR-5 zero-weight-padding fallback is gone: a near-prime token
    count now routes to the blocked kernel and still matches the
    unchunked loss exactly."""
    from deeplearning4j_tpu.models.transformer import (init_params,
                                                       lm_head_loss)
    cfg = _tiny_cfg(xent_chunk=64)
    params = init_params(jax.random.key(24), cfg)
    # B*T = 1*127 (prime): the divisor search collapses below chunk//4
    h = jax.random.normal(jax.random.key(25), (1, 127, 32), jnp.float32)
    tgts = jax.random.randint(jax.random.key(26), (1, 127), 0, 101)
    chunked = lm_head_loss(params, h, tgts, cfg)
    full = lm_head_loss(params, h, tgts, _tiny_cfg(xent_chunk=0))
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-5)


# ----------------------------------------------------------- paged attention

def _paged_case(dtype, B=3, H=4, D=16, ps=5, n_pages=4, seed=0):
    from deeplearning4j_tpu.ops.pallas.paged_attention import (
        paged_attention, reference_paged_attention)

    rng = np.random.default_rng(seed)
    n_phys = B * n_pages + 1
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((n_phys, ps, H, D)), dtype)
    v = jnp.asarray(rng.standard_normal((n_phys, ps, H, D)), dtype)
    bt = jnp.asarray(rng.permutation(n_phys - 1)[: B * n_pages]
                     .reshape(B, n_pages), jnp.int32)
    lengths = jnp.asarray([1, ps + 2, n_pages * ps], jnp.int32)[:B]
    return paged_attention, reference_paged_attention, (q, k, v, bt, lengths)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_parity_odd_page_size(dtype):
    """Interpret-mode kernel vs the jnp gather reference at an odd page
    size, including a row whose valid length is 1 (one real K/V entry,
    three fully-masked pages — the running-softmax edge case) and a row
    ending exactly on a page boundary."""
    fn, ref, args = _paged_case(dtype)
    out = fn(*args)
    want = ref(*args)
    assert out.dtype == args[0].dtype
    _close(out, want, dtype)


def test_paged_attention_reads_through_block_table():
    """Permuting the physical pages while permuting the table the same
    way must not change the result — the kernel really addresses K/V
    through the scalar-prefetched table, not by position."""
    fn, ref, (q, k, v, bt, lengths) = _paged_case(jnp.float32, seed=3)
    base = fn(q, k, v, bt, lengths)
    perm = np.random.default_rng(7).permutation(k.shape[0])
    inv = np.argsort(perm)
    k2 = k[perm]
    v2 = v[perm]
    bt2 = jnp.asarray(np.asarray(inv)[np.asarray(bt)], jnp.int32)
    again = fn(q, k2, v2, bt2, lengths)
    _close(again, base, jnp.float32)


def test_paged_attention_registered_beside_its_xla_incumbent():
    """The serving engine reaches the Pallas candidate through the
    registry, beside the jnp gather it is held to."""
    cand = registry.get("paged_attention", "pallas")
    inc = registry.get("paged_attention", "gather")
    assert inc.source == "xla" and cand.tolerances["max_err"] == 0.05
    assert cand.source == "pallas" and cand.reference is inc.fn.unscoped


# ------------------------------------------------ paged attention: GQA + int8

@pytest.mark.parametrize("n_kv", [1, 2, 4])
def test_paged_attention_gqa_parity(n_kv):
    """Kernel vs reference when pages carry fewer K/V heads than query
    heads (H=4, Kv in {1, 2, 4}): the in-register head-group broadcast
    must match the gather reference's repeat-heads path."""
    from deeplearning4j_tpu.ops.pallas.paged_attention import (
        paged_attention, reference_paged_attention)
    B, H, D, ps, n_pages = 3, 4, 16, 5, 4
    rng = np.random.default_rng(11)
    n_phys = B * n_pages + 1
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n_phys, ps, n_kv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n_phys, ps, n_kv, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(n_phys - 1)[: B * n_pages]
                     .reshape(B, n_pages), jnp.int32)
    lengths = jnp.asarray([1, ps + 2, n_pages * ps], jnp.int32)
    out = paged_attention(q, k, v, bt, lengths)
    want = reference_paged_attention(q, k, v, bt, lengths)
    _close(out, want, jnp.float32)


def _paged_int8_case(n_kv=4, B=3, H=4, D=16, ps=5, n_pages=4, seed=0):
    from deeplearning4j_tpu.ops.pallas import kv_quant
    from deeplearning4j_tpu.ops.pallas.paged_attention import (
        paged_attention_int8, reference_paged_attention_int8)
    rng = np.random.default_rng(seed)
    n_phys = B * n_pages + 1
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kf = jnp.asarray(rng.standard_normal((n_phys, ps, n_kv, D)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((n_phys, ps, n_kv, D)), jnp.float32)
    s0 = jnp.full((n_phys, n_kv), kv_quant.neutral_scale(jnp.int8))
    k, ks = kv_quant.requantize_pool(kf, s0, jnp.int8)
    v, vs = kv_quant.requantize_pool(vf, s0, jnp.int8)
    bt = jnp.asarray(rng.permutation(n_phys - 1)[: B * n_pages]
                     .reshape(B, n_pages), jnp.int32)
    lengths = jnp.asarray([1, ps + 2, n_pages * ps], jnp.int32)[:B]
    return (paged_attention_int8, reference_paged_attention_int8,
            (q, k, v, ks, vs, bt, lengths))


@pytest.mark.parametrize("n_kv", [2, 4])
def test_paged_attention_int8_kernel_matches_reference(n_kv):
    """The in-kernel per-page dequantize (interpret mode, so the real
    kernel body runs on CPU) must match the dequantize-whole-pool jnp
    reference — which IS the engine's quantized parity path."""
    fn, ref, args = _paged_int8_case(n_kv=n_kv)
    out = fn(*args)
    want = ref(*args)
    assert out.dtype == args[0].dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_paged_attention_int8_tracks_float_within_quant_band():
    """Quantize-then-attend stays inside the kind's registered numeric
    band (max_err 0.05) of full-precision attention over the ORIGINAL
    float pool content — the error budget the candidate declares."""
    from deeplearning4j_tpu.ops.pallas import kv_quant
    from deeplearning4j_tpu.ops.pallas.paged_attention import (
        reference_paged_attention, reference_paged_attention_int8)
    B, H, D, ps, n_pages = 3, 4, 16, 5, 4
    rng = np.random.default_rng(5)
    n_phys = B * n_pages + 1
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kf = jnp.asarray(rng.standard_normal((n_phys, ps, H, D)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((n_phys, ps, H, D)), jnp.float32)
    s0 = jnp.full((n_phys, H), kv_quant.neutral_scale(jnp.int8))
    k, ks = kv_quant.requantize_pool(kf, s0, jnp.int8)
    v, vs = kv_quant.requantize_pool(vf, s0, jnp.int8)
    bt = jnp.asarray(rng.permutation(n_phys - 1)[: B * n_pages]
                     .reshape(B, n_pages), jnp.int32)
    lengths = jnp.asarray([1, ps + 2, n_pages * ps], jnp.int32)
    a = reference_paged_attention_int8(q, k, v, ks, vs, bt, lengths)
    b = reference_paged_attention(q, kf, vf, bt, lengths)
    assert float(jnp.max(jnp.abs(a - b))) < 0.05


def test_paged_attention_int8_declares_the_agreement_floor():
    """The int8 KV candidate declares the top-1 agreement floor on top of
    ``max_err``: a kernel that flips tokens does not hold its tolerances."""
    cand = registry.get("paged_attention_int8", "pallas_int8")
    inc = registry.get("paged_attention_int8", "gather_int8")
    assert inc.source == "xla"
    assert cand.tolerances["min"]["top1_agree"] == 0.999
    assert cand.tolerances["max_err"] == 0.05
