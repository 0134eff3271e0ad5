"""The fused attention kernel's backward against the naive attention math
(interpret mode on CPU; the same code compiles to Mosaic on TPU), in the
shapes the training step runs it in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.pallas.attention import (fused_attention,
                                                     reference_attention)


def _qkv(b=2, t=256, h=3, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def _value_and_grads(fn, q, k, v, **kw):
    def loss(q, k, v):                        # non-trivial cotangent
        return jnp.sum(jnp.sin(fn(q, k, v, **kw).astype(jnp.float32)))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)


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
