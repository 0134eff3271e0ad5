"""Fused causal attention — the block-skipping generalization of flash.

Same flash-v2 schedule as ``ops/flash_attention.py`` (one query block per
program, K/V streamed through a running softmax in VMEM) with one
structural difference that matters for causal LM training: the key loop
stops at the causal frontier instead of streaming fully-masked blocks.
For causal attention that halves the streamed K/V traffic and the MXU
work (the lower-triangular half is all that exists), which is exactly
the regime the flagship decoder trains in — so this registers as a
separate ``attention`` candidate and has to beat flash AND ring through
the bench auto-pick rather than replacing either by fiat.

The loop bound is a traced value (``fori_loop`` lowers it to a while
loop, fine under both Mosaic and interpret mode); masking inside the
frontier block stays branch-free like flash.  Backward reuses flash's
``_blockwise_bwd`` jnp recompute — O(T) memory, no second kernel to
maintain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..flash_attention import _blockwise_bwd, vmem_spec

from . import registry

_NEG_INF = -1e30


def reference_attention(q, k, v, *, causal: bool = True):
    """Naive softmax attention on (B, T, H, D) — the jnp ground truth
    every attention candidate is gated against."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                block_k: int, seq_len: int, scale: float):
    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)
    bq = q.shape[0]
    qi = pl.program_id(1)
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    n_k = seq_len // block_k
    if causal:
        # causal frontier: key blocks past the last query row of this
        # program are fully masked — skip them instead of streaming zeros
        n_k = jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, n_k)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (BQ, BK)
        if causal:
            k_pos = (j * block_k
                     + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1))
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, q.shape[1]), jnp.float32)
    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = lax.fori_loop(0, n_k, body, (acc0, m0, l0))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]


def _fused_fwd(q, k, v, causal, block_q, block_k, interpret):
    """q/k/v: (BH, T, D) -> (out (BH, T, D), lse (BH, T))."""
    bh, t, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
    scale = d ** -0.5

    kernel = functools.partial(_fwd_kernel, causal=causal, block_k=block_k,
                               seq_len=t, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, t // block_q),
        in_specs=[
            vmem_spec((1, block_q, d), lambda b, i: (b, i, 0)),
            vmem_spec((1, t, d), lambda b, i: (b, 0, 0)),
            vmem_spec((1, t, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            vmem_spec((1, block_q, d), lambda b, i: (b, i, 0)),
            # trailing singleton: same Mosaic last-two-dims constraint as
            # the flash kernel's lse output
            vmem_spec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_bhtd(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _fused_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _fused_bhtd_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _fused_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _fused_bhtd_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    return _blockwise_bwd(q, k, v, out, lse, do, causal, block_k)


_fused_bhtd.defvjp(_fused_bhtd_fwd, _fused_bhtd_bwd)


def fused_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """Block-skipping fused attention for (B, T, H, D) tensors.

    Public API mirrors :func:`ops.flash_attention.flash_attention`;
    ``interpret=None`` resolves through ``registry.resolve_interpret``.
    """
    interpret = registry.resolve_interpret(interpret)
    b, t, h, d = q.shape

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    out = _fused_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v),
                      causal, block_q, block_k, interpret)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _ring_single_shard(q, k, v, *, causal: bool = True, **_):
    """The XLA incumbent as a candidate: single-shard ring attention
    (lazy import — models must not load at registry import time)."""
    from ...models.transformer import ring_attention
    return ring_attention(q, k, v, n_sp=1, sp_axis=None, causal=causal,
                          t_local=q.shape[1])


registry.register(registry.KernelCandidate(
    kind="attention", name="fused", fn=fused_attention,
    reference=reference_attention,
    blocks=({"block_q": 128, "block_k": 128},
            {"block_q": 256, "block_k": 128},
            {"block_q": 128, "block_k": 256},
            {"block_q": 256, "block_k": 256}),
    # fwd/bwd max abs error vs reference_attention on the battery shapes
    # (f32; matches the flash_check gate bench has always applied)
    tolerances={"max_err": 0.05},
))

registry.register(registry.KernelCandidate(
    kind="attention", name="ring", fn=_ring_single_shard,
    reference=reference_attention, source="xla",
))
