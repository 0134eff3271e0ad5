"""The attention kernels: forward/backward parity with the naive attention
math (interpret mode on CPU; the same code compiles to Mosaic on TPU), and
the fused kernel's backward in the shapes the training step runs it in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.flash_attention import flash_attention
from deeplearning4j_tpu.ops.pallas.attention import (fused_attention,
                                                     reference_attention)


def _naive(q, k, v, causal):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _qkv(b=2, t=256, h=3, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_naive(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal)
    want = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_naive(causal):
    q, k, v = _qkv(b=1, t=128, h=2, d=8, seed=1)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(o))            # non-trivial cotangent

    def loss_naive(q, k, v):
        return jnp.sum(jnp.sin(_naive(q, k, v, causal)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_multiple_key_blocks_exercised():
    """t=512 with block 128 -> 4 key blocks per query block; parity must
    hold across block boundaries (running-softmax correctness)."""
    q, k, v = _qkv(b=1, t=512, h=1, d=8, seed=2)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    want = _naive(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_transformer_flash_config_matches_ring():
    """The flagship model with attention='flash' computes the same loss and
    gradients as the default path (single device, sp=1)."""
    import dataclasses

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig, lm_loss_local)

    cfg = TransformerConfig(vocab_size=128, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=128, causal=True,
                            dtype=jnp.float32, remat=False)
    from deeplearning4j_tpu.models.transformer import init_params
    params = init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 128), 0, 128)
    tgts = jnp.roll(toks, -1, axis=1)

    def loss_with(attn_impl):
        c = dataclasses.replace(cfg, attention=attn_impl)
        return jax.value_and_grad(
            lambda p: lm_loss_local(p, toks, tgts, c))(params)

    l_ring, g_ring = loss_with("ring")
    l_flash, g_flash = loss_with("flash")
    assert abs(float(l_ring) - float(l_flash)) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(g_ring),
                    jax.tree_util.tree_leaves(g_flash)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ------------------------------------------------ the fused kernel's backward

def _value_and_grads(fn, q, k, v, **kw):
    def loss(q, k, v):                        # non-trivial cotangent
        return jnp.sum(jnp.sin(fn(q, k, v, **kw).astype(jnp.float32)))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_matches_reference(got, q, k, v, causal, dtype):
    """Against ``reference_attention`` in f32 on the same (rounded) inputs:
    the kernel's MXU operands are ``dtype``, its accumulators f32."""
    want = _value_and_grads(reference_attention,
                            *(x.astype(jnp.float32) for x in (q, k, v)),
                            causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    assert abs(float(got[0]) - float(want[0])) < tol * q.shape[1]
    for a, b in zip(got[1], want[1]):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("t", [256, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True],
                         ids=["bidirectional", "causal"])
def test_fused_backward_matches_reference(causal, dtype, t, head_dim):
    """128-row tiles: 2 x 2 and 4 x 4 of them, two lane groups per row
    (two heads each at width 64, one at 128)."""
    q, k, v = (x.astype(dtype)
               for x in _qkv(b=1, t=t, h=256 // head_dim, d=head_dim, seed=3))
    got = _value_and_grads(fused_attention, q, k, v, causal=causal,
                           block_q=128, block_k=128)
    _assert_matches_reference(got, q, k, v, causal, dtype)


@pytest.mark.parametrize("block_q,block_k,dtype", [
    (256, 128, jnp.bfloat16), (128, 256, jnp.bfloat16),
    (None, None, jnp.float32)], ids=["256x128", "128x256", "from-shape"])
def test_fused_backward_uneven_tiles_and_frontier(block_q, block_k, dtype):
    """Asymmetric tiles move the causal frontier off the tile diagonal in
    both loops; ``None`` is the size the kernel picks from the shape (one
    512-row tile here, in f32: XLA's CPU backend, which interprets the
    kernel, has no bf16 matmul of that size)."""
    q, k, v = (x.astype(dtype) for x in _qkv(b=2, t=512, h=2, d=64, seed=4))
    got = _value_and_grads(fused_attention, q, k, v, causal=True,
                           block_q=block_q, block_k=block_k)
    _assert_matches_reference(got, q, k, v, True, dtype)


@pytest.mark.parametrize("causal", [False, True],
                         ids=["bidirectional", "causal"])
def test_fused_under_per_example_vmap(causal):
    """The trainer's form: ``vmap`` over examples of batch 1, forward and
    gradient — ``pallas_call`` batches by growing its grid."""
    q, k, v = (x.astype(jnp.bfloat16)[:, None]
               for x in _qkv(b=3, t=256, h=2, d=64, seed=5))

    def per_example(q, k, v, **kw):
        return jax.vmap(lambda a, b, c: fused_attention(a, b, c, **kw))(
            q, k, v)[:, 0]

    got = _value_and_grads(per_example, q, k, v, causal=causal,
                           block_q=128, block_k=128)
    flat = tuple(x[:, 0] for x in (q, k, v))
    _assert_matches_reference((got[0], [g[:, 0] for g in got[1]]),
                              *flat, causal, jnp.bfloat16)
