"""Fused flash attention as a Pallas TPU kernel.

Perf groundwork for the flagship transformer (BASELINE.md: >=35% MFU on
BERT-base): the XLA path materializes per-layer (B, H, T, T) score tensors
in HBM; this kernel keeps the running-softmax state in VMEM and streams
K/V blocks through the MXU, so attention becomes HBM-bandwidth-light and
O(T) in memory.  Single-(shard-)chip op: under sequence parallelism the
ring layer (``models/transformer.ring_attention``) still rotates K/V
between chips and can call any per-block attention underneath.

Design (standard flash attention v2 schedule):
- grid = (batch*heads, T/BQ); each program owns one query block and loops
  over key blocks with a ``fori_loop``, carrying (acc, m, l) in registers.
- causal masking compares block-level iota offsets, so fully-masked key
  blocks still stream but contribute zeros (simple, branch-free).
- the kernel also emits the row log-sum-exp, and a ``jax.custom_vjp``
  backward recomputes per-block probabilities from (q, k, v, lse) under a
  ``lax.scan`` over key blocks — O(T) memory in the backward too, no
  hand-written backward kernel to maintain.

``interpret=None`` follows the tier's one rule
(``ops.pallas.registry.resolve_interpret``): compiled Mosaic kernel on a TPU
backend, interpret mode on the CPU backend (tests), an error anywhere else.

Status since PR 27: this candidate is the DUPLICATE.  The training default
on a TPU is ``ops/pallas/attention.py`` ("fused": bf16 MXU operands, a
Pallas backward, no transposes around the kernel), which the chip measured
at 2.5 ms a BERT-base layer forward + backward against 10.5 ms for the XLA
path (PERF.md §6).  This one still casts q, k and v to f32 in its forward
and runs ``_blockwise_bwd``, which writes its per-block scores to HBM; it is
reached only through ``TransformerConfig(attention="flash")`` and leaves
with the registry's autopick chain (ROADMAP D1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: every kernel of this tier stages its blocks through VMEM
vmem_spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                block_k: int, seq_len: int, scale: float):
    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)
    bq = q.shape[0]
    qi = pl.program_id(1)
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    n_k = seq_len // block_k

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


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
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
            # lse carries a trailing singleton: Mosaic requires the last two
            # block dims divisible by (8, 128) or equal to the array dims, so
            # a (1, block_q) block is unlowerable while (1, block_q, 1) is.
            vmem_spec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


def _blockwise_bwd(q, k, v, out, lse, do, causal, block_k):
    """O(T)-memory backward: rebuild P per key block from (q, lse) under a
    scan, accumulate dq and emit per-block dk/dv (flash attention v2
    backward math, plain JAX so autodiff/XLA handle fusion)."""
    bh, t, d = q.shape
    block_k = min(block_k, t)
    n_k = t // block_k
    scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    dof = do.astype(jnp.float32)
    # D_i = rowsum(dO * O) (the lse-gradient shortcut)
    delta = (dof * out.astype(jnp.float32)).sum(-1)            # (BH, T)
    q_pos = jnp.arange(t)[:, None]

    kb = k.reshape(bh, n_k, block_k, d).swapaxes(0, 1).astype(jnp.float32)
    vb = v.reshape(bh, n_k, block_k, d).swapaxes(0, 1).astype(jnp.float32)

    def body(dq_acc, blk):
        j, k_j, v_j = blk                                       # (BH, BK, D)
        s = jnp.einsum("btd,bkd->btk", qf, k_j)                 # (BH, T, BK)
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                         # (BH, T, BK)
        dv_j = jnp.einsum("btk,btd->bkd", p, dof)
        dp = jnp.einsum("btd,bkd->btk", dof, v_j)
        ds = p * (dp - delta[..., None])
        dq_acc = dq_acc + jnp.einsum("btk,bkd->btd", ds, k_j) * scale
        dk_j = jnp.einsum("btk,btd->bkd", ds, qf)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((bh, t, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = lax.scan(
        body, dq0, (jnp.arange(n_k), kb, vb))
    dk = dk_blocks.swapaxes(0, 1).reshape(bh, t, d)
    dv = dv_blocks.swapaxes(0, 1).reshape(bh, t, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhtd(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_bhtd_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bhtd_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    return _blockwise_bwd(q, k, v, out, lse, do, causal, block_k)


_flash_bhtd.defvjp(_flash_bhtd_fwd, _flash_bhtd_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """Fused attention for (B, T, H, D) tensors (the transformer's layout).

    ``interpret=None`` resolves through ``registry.resolve_interpret``:
    compiled on a TPU backend, interpreted on the CPU backend (tests).
    """
    interpret = _kernel_registry.resolve_interpret(interpret)
    b, t, h, d = q.shape

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    out = _flash_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v),
                      causal, block_q, block_k, interpret)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


# --------------------------------------------------------------- registration

def _naive_reference(q, k, v, *, causal: bool = True):
    # call-time import: ops.pallas.attention imports _blockwise_bwd from
    # this module, so a top-level import here would be circular
    from .pallas.attention import reference_attention
    return reference_attention(q, k, v, causal=causal)


# flash predates the ops/pallas tier but competes through the SAME
# candidate registry (one registration API — DESIGN.md §14); the public
# flash_attention signature above is unchanged.
from .pallas import registry as _kernel_registry  # noqa: E402

_kernel_registry.register(_kernel_registry.KernelCandidate(
    kind="attention", name="flash", fn=flash_attention,
    reference=_naive_reference,
    blocks=({"block_q": 128, "block_k": 128},
            {"block_q": 256, "block_k": 128}),
    # the on-chip battery's flash_check gate, unchanged: fwd/bwd max abs
    # error vs naive attention must stay under 0.05
    tolerances={"max_err": 0.05},
))
