"""Paged-attention decode kernel: K/V read through block tables.

The serving engine's paged decode (DESIGN.md §17) stores K/V as
fixed-size pages in a ``(num_pages, page_size, H, Dh)`` pool and
addresses each sequence through an ``(B, n_pages)`` block table.  The
exact-parity read path gathers a row's logical K/V into a dense
``(B, max_len, H, Dh)`` buffer and reuses the dense attention ops —
bitwise, but it materializes max_len per row per layer.  This module's
Pallas candidate streams the pages instead: one program per
(sequence, page), the block table SCALAR-PREFETCHED so each program's
K/V block is DMA'd straight from its physical page, a running softmax
in VMEM scratch across the page axis.  No (B, max_len) intermediate is
ever built.

Two axes ride this read path (DESIGN.md §20):

- **GQA/MQA**: the page pools carry ``n_kv_heads <= n_heads`` heads;
  the kernel broadcasts each K/V head across its static group of
  ``n_heads // n_kv_heads`` query heads in-register instead of
  materializing repeated heads.
- **int8 KV** (kind ``paged_attention_int8``): pages are stored int8
  (or fp8) with per-page, per-head absmax scales (``ops/pallas/
  kv_quant.py``); the kernel dequantizes each page inside the same
  streamed read — one broadcast multiply on the block it DMA'd anyway.

Both kinds are default off and unmeasured on the chip:
:func:`reference_paged_attention` /
:func:`reference_paged_attention_int8` (pure jnp, the same gather the
engine's parity path uses) are both the incumbent candidates
("gather"/"gather_int8", source="xla") and the references a tier-1
test holds the Pallas candidates' declared tolerances to — the int8
kind with a ≥0.999 token top-1-agreement floor beside its error bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .vmem import vmem_spec

_NEG_INF = -1e30


def reference_paged_attention(q, k_pages, v_pages, block_tables, lengths,
                              **_):
    """Ground truth: gather each row's pages to a dense (B, T, H, Dh)
    view and run the dense decode attention ops over it.

    ``q`` (B, H, Dh) single-position queries, ``k_pages``/``v_pages``
    (P, ps, Kv, Dh) where Kv divides H (Kv < H is GQA/MQA: each K/V
    head serves H//Kv query heads), ``block_tables`` (B, n_pages)
    physical page ids, ``lengths`` (B,) valid K/V prefix per row
    (>= 1).  Returns (B, H, Dh) in ``q``'s dtype.  These are
    byte-for-byte the engine's masked-gather attention ops (repeat-
    heads then dense attend), so this reference IS the parity path.
    """
    ps = k_pages.shape[1]
    B = q.shape[0]
    T = block_tables.shape[1] * ps
    scale = q.shape[-1] ** -0.5
    t = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    flat = jnp.take_along_axis(block_tables, t // ps, axis=1) * ps + t % ps
    k = k_pages.reshape((-1,) + k_pages.shape[2:])[flat]     # (B, T, Kv, Dh)
    v = v_pages.reshape((-1,) + v_pages.shape[2:])[flat]
    n_rep = q.shape[1] // k.shape[2]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)                     # (B, T, H, Dh)
        v = jnp.repeat(v, n_rep, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where((t < lengths[:, None])[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,bthd->bhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _accumulate_page(b, j, q, k, v, len_ref, o_ref, acc_ref, m_ref, l_ref,
                     *, page_size: int, n_pages: int):
    """Shared running-softmax body: fold one (ps, Kv, Dh) K/V page into
    the (H, Dh) accumulator for already-scaled f32 queries ``q``
    (H, Dh).  Kv < H is the GQA path: each K/V head is broadcast across
    its static group of H//Kv query heads in-register — no repeated-
    head buffer is ever built."""
    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    H = q.shape[0]
    Kv = k.shape[1]
    if Kv == H:
        s = jnp.sum(q[None, :, :] * k, axis=-1).T            # (H, ps)
    else:
        g = H // Kv
        qg = q.reshape(Kv, g, q.shape[-1])                   # (Kv, g, Dh)
        kt = k.transpose(1, 0, 2)                            # (Kv, ps, Dh)
        s = jnp.sum(qg[:, :, None, :] * kt[:, None, :, :],
                    axis=-1).reshape(H, page_size)           # (H, ps)
    pos = j * page_size + lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                        # (1, ps)
    mask = pos < len_ref[b]
    s = jnp.where(mask, s, _NEG_INF)
    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # a fully-masked page leaves m_new at -inf; zero its weights
    # explicitly so exp(-inf - -inf) == 1 cannot leak into the sum
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)    # (H, ps)
    corr = jnp.exp(m_prev - m_new)
    l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=1)
    if Kv == H:
        pv = jnp.sum(p.T[:, :, None] * v, axis=0)            # (H, Dh)
    else:
        g = H // Kv
        pg = p.reshape(Kv, g, page_size)                     # (Kv, g, ps)
        vt = v.transpose(1, 0, 2)                            # (Kv, ps, Dh)
        pv = jnp.sum(pg[:, :, :, None] * vt[:, None, :, :],
                     axis=2).reshape(H, v.shape[-1])         # (H, Dh)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[:, 0] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size: int, n_pages: int,
                  scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                 # (H, Dh)
    k = k_ref[0].astype(jnp.float32)                         # (ps, Kv, Dh)
    v = v_ref[0].astype(jnp.float32)
    _accumulate_page(b, j, q, k, v, len_ref, o_ref, acc_ref, m_ref, l_ref,
                     page_size=page_size, n_pages=n_pages)


def _paged_int8_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                       o_ref, acc_ref, m_ref, l_ref, *, page_size: int,
                       n_pages: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                 # (H, Dh)
    # dequantize THIS page inside the streamed read: one broadcast
    # multiply by its (Kv, 1) per-head scale column, DMA'd beside the page
    k = k_ref[0].astype(jnp.float32) * ks_ref[0][None]
    v = v_ref[0].astype(jnp.float32) * vs_ref[0][None]
    _accumulate_page(b, j, q, k, v, len_ref, o_ref, acc_ref, m_ref, l_ref,
                     page_size=page_size, n_pages=n_pages)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    interpret: bool | None = None):
    """Pallas paged decode attention; same signature/result contract as
    :func:`reference_paged_attention` (within the registered tolerance —
    running softmax reassociates the reduction, so NOT bitwise).
    ``interpret=None`` resolves through ``registry.resolve_interpret``."""
    interpret = registry.resolve_interpret(interpret)
    B, H, Dh = q.shape
    ps = k_pages.shape[1]
    Kv = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    scale = Dh ** -0.5
    kernel = functools.partial(_paged_kernel, page_size=ps, n_pages=n_pages,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            vmem_spec((1, H, Dh), lambda b, j, bt, ln: (b, 0, 0)),
            # the paged read itself: this program's K/V block is whatever
            # physical page the scalar-prefetched table names
            vmem_spec((1, ps, Kv, Dh),
                      lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
            vmem_spec((1, ps, Kv, Dh),
                      lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=vmem_spec((1, H, Dh), lambda b, j, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, Dh), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)


def reference_paged_attention_int8(q, k_pages, v_pages, k_scale, v_scale,
                                   block_tables, lengths, **_):
    """Ground truth for the quantized kind: dequantize the whole pool
    via the same :mod:`kv_quant` helpers the engine's parity gather
    uses, then delegate to :func:`reference_paged_attention`.  This IS
    the engine's jnp path when ``kv_quant`` is on, so candidate-vs-
    reference agreement is exactly served-vs-offline agreement."""
    from . import kv_quant
    kf = kv_quant.dequantize_pool(k_pages, k_scale, q.dtype)
    vf = kv_quant.dequantize_pool(v_pages, v_scale, q.dtype)
    return reference_paged_attention(q, kf, vf, block_tables, lengths)


def paged_attention_int8(q, k_pages, v_pages, k_scale, v_scale,
                         block_tables, lengths, *,
                         interpret: bool | None = None):
    """Pallas paged decode attention over int8/fp8 pages with
    per-(page, kv_head) f32 scales; same contract as
    :func:`reference_paged_attention_int8` within the registered
    tolerance.  The dequantize happens in-kernel on each streamed
    page block — the full-precision pool is never materialized."""
    interpret = registry.resolve_interpret(interpret)
    B, H, Dh = q.shape
    ps = k_pages.shape[1]
    Kv = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    scale = Dh ** -0.5
    kernel = functools.partial(_paged_int8_kernel, page_size=ps,
                               n_pages=n_pages, scale=scale)
    page_spec = vmem_spec((1, ps, Kv, Dh),
                          lambda b, j, bt, ln: (bt[b, j], 0, 0, 0))
    # each page's per-head scales ride the same block-table index as the
    # page they scale.  They enter as (P, Kv, 1): a (1, Kv) block of a
    # (P, Kv) array breaks Mosaic's rule that the last two block dims be
    # (8, 128)-divisible or equal to the array's, a (1, Kv, 1) block of
    # (P, Kv, 1) meets it — and Kv lands on sublanes, where the page's
    # own (Kv, Dh) tiles have it, so the multiply is a lane broadcast.
    scale_spec = vmem_spec((1, Kv, 1),
                           lambda b, j, bt, ln: (bt[b, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            vmem_spec((1, H, Dh), lambda b, j, bt, ln: (b, 0, 0)),
            page_spec, page_spec, scale_spec, scale_spec,
        ],
        out_specs=vmem_spec((1, H, Dh), lambda b, j, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, Dh), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages, k_scale.astype(jnp.float32)[..., None],
      v_scale.astype(jnp.float32)[..., None])


registry.register(registry.KernelCandidate(
    kind="paged_attention", name="pallas", fn=paged_attention,
    reference=reference_paged_attention,
    tolerances={"max_err": 0.05},
))

registry.register(registry.KernelCandidate(
    kind="paged_attention", name="gather", fn=reference_paged_attention,
    reference=reference_paged_attention, source="xla",
))

registry.register(registry.KernelCandidate(
    kind="paged_attention_int8", name="pallas_int8", fn=paged_attention_int8,
    reference=reference_paged_attention_int8,
    # same numeric band as the float kind, PLUS the served-token
    # agreement floor the int8 weight path declares: a cache precision
    # may not flip more than 1/1000 greedy tokens
    tolerances={"max_err": 0.05, "min": {"top1_agree": 0.999}},
))

registry.register(registry.KernelCandidate(
    kind="paged_attention_int8", name="gather_int8",
    fn=reference_paged_attention_int8,
    reference=reference_paged_attention_int8, source="xla",
))
