"""Fused attention, forward and backward, with the scores kept in VMEM.

The training default on a TPU (``attention_candidate`` gives it to
``models/transformer._block`` and ``models/hybrid.cca_mixer`` for every
shape ``kernel_takes`` accepts): the XLA path writes the
``(B, H, T, T)`` f32 scores of every layer to HBM and reads them back a
dozen times over forward and backward; these kernels hold one
``(block_q, block_k)`` tile of them at a time and write no ``(…, T, T)``
array at all.

Layout.  The kernels read and write ``(B, H*D, T)``: features major,
positions on the lanes.  That is the layout XLA itself picks for q, k, v and
their gradients around the projections' matmuls (a 64-wide minor dimension
would be padded to 128), so it hands the operands over and takes the results
back without a copy; a kernel on ``(B, T, H*D)`` paid eight transposing
copies of 50 MB a layer at BERT-base size.  One program owns one batch row
and one group of ``lanes = lcm(D, 128)`` features, that is ``lanes // D``
heads (two at head width 64), with the whole sequence of that group
resident in VMEM; it transposes its operands once into ``(T, lanes)``
scratch, which costs the otherwise idle transpose unit a few hundred
cycles.  A head is picked out of its group by zeroing the other heads'
features in ONE matmul operand (``q`` for the scores, ``dO`` for ``dP``):
the MXU contracts 128 deep whatever the head width, so the zeros cost
nothing that a 64-deep contraction would not, and every load, store and
accumulator stays 128 wide.

Forward: the flash-v2 schedule.  Per query block a loop over key blocks
carries ``(acc, m, l)`` per head; with ``causal=True`` the loop stops at the
causal frontier.  ``q, k, v`` enter the MXU in their own dtype (bf16 in
training) with f32 accumulation; ``m``, ``l``, ``lse`` and ``acc`` are f32;
``p`` is cast to ``v.dtype`` for the PV product, as the XLA path does.

Backward: one kernel for dq, dk and dv.  It works on the TRANSPOSED tile
``s^T = k q^T`` (keys on sublanes, queries on lanes) so that ``lse`` and
``delta = rowsum(dO * O)`` are rows that broadcast down the sublanes, and
``dv = p^T dO``, ``dk = ds^T q`` are plain matmuls.  ``dq`` accumulates as
``dq^T = k^T ds^T`` in a ``(lanes, T)`` f32 scratch, which is already the
layout it leaves in.  ``p`` is recomputed per tile from ``(q, k, lse)``;
residuals are ``(q, k, v, out, lse)``.

``interpret=None`` follows the tier's one rule
(``registry.resolve_interpret``): compiled on a TPU backend, interpreted on
the CPU backend (tests).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability import METRICS
from . import registry
from .vmem import vmem_spec

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))        # a @ b.T, contracting the lane axes

#: the whole sequence of one head group sits in VMEM (q, k, v, O, dO and the
#: three gradients double-buffered, their transposed copies, the f32 dq^T
#: scratch): 2048 rows compile inside the 16 MiB a v5e kernel may use by
#: default, 4096 rows (about 30 MiB in the backward) inside the limit
#: ``_vmem_limit`` asks for (tests/test_chip_compile.py)
MAX_T = 4096


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


def kernel_takes(t: int, h: int, d: int) -> bool:
    """Shapes the compiled kernel is built and measured for: whole 128-row
    blocks, a sequence that fits in VMEM, heads that tile groups of 128
    features."""
    return (t % 128 == 0 and t <= MAX_T and d in (64, 128)
            and (h * d) % 128 == 0)


def attention_candidate(t: int, h: int, d: int, *, n_sp: int = 1,
                        asked: str = "auto",
                        selection: tuple[int, int] | None = None,
                        d_v: int | None = None, count: bool = True) -> str | None:
    """The registered attention candidate a block runs on ``(B, t, h, d)``
    queries, or ``None`` for the XLA path (``ring_attention``) — the one
    place that decides, for every block family.  By default the fused
    kernel runs where it compiles (a TPU backend) on the shapes it is built
    for.  ``asked="ring"`` is always the XLA path; a registered name is that
    kernel wherever whole 128-row blocks allow it, interpreted on the CPU:
    how a parity test forces a side.  The sp ring is the collective and
    never a candidate.

    A mixer that restricts each query to SELECTED keys says so with
    ``selection=(KV heads, queries it selects for at a time)``.  Its
    candidates are the ``sparse_attention`` kind's (``"selected"``, the
    kernels of ``ops/pallas/sparse_attention.py``), by default where they
    compile on the shapes they are built for, by name wherever the sequence
    is whole chunks; ``None`` is the mixer's own XLA path.

    ``d_v`` is the values' width where it is not the queries' and keys'
    ``d`` (latent attention's heads): no kernel here takes unequal widths, so
    the answer is ``None``, the caller's own XLA path.

    Counts its answer as ``attention.path.kernel`` / ``.xla``: callers ask
    once per block while tracing, so the counters tell a step on the kernel
    from one that fell back.  ``count=False`` is for a caller outside any
    block that must follow the blocks' answer
    (``models/hybrid.sparse_selection``)."""
    if asked == "ring" or n_sp != 1 or d_v not in (None, d):
        name = None
    elif selection is not None:
        from . import sparse_attention

        if asked == "auto":
            on = (jax.default_backend() == "tpu"
                  and sparse_attention.kernel_takes(t, h, d, *selection))
            name = "selected" if on else None
        else:
            name = asked if t % selection[1] == 0 else None
    elif asked == "auto":
        on = jax.default_backend() == "tpu" and kernel_takes(t, h, d)
        name = "fused" if on else None
    else:
        name = asked if t % 128 == 0 else None
    if count:
        METRICS.increment(
            "attention.path.kernel" if name else "attention.path.xla")
    return name


def _block_size(t: int) -> int:
    """Rows of queries and of keys in one tile, from the sequence length.
    Measured on a v5e at head width 64, forward + backward per layer: at
    64 x 512 non-causal 128/256/512 took 7.86 / 4.20 / 2.53 ms, at 8 x 1024
    causal 3.02 / 1.82 / 1.64 ms (PERF.md §6, PR 27) — the largest tile
    wins even where the causal frontier skips less; past 512 rows the f32
    tiles crowd VMEM."""
    if t <= 512:
        return t
    return next(b for b in (512, 256, 128) if t % b == 0)


def _group_lanes(h: int, d: int) -> int:
    """Lanes one program owns: whole heads, 128-aligned where the widths
    allow it, else every head at once (a block as wide as the array)."""
    lanes = math.lcm(d, 128)
    return lanes if (h * d) % lanes == 0 else h * d


def _head_masks(lanes: int, d: int, axis: int):
    """One boolean mask per head of the group along ``axis`` of a
    ``(1, lanes)`` / ``(lanes, 1)`` iota; ``None`` for a group of one."""
    g = lanes // d
    if g == 1:
        return [None]
    shape = (1, lanes) if axis == 1 else (lanes, 1)
    pos = lax.broadcasted_iota(jnp.int32, shape, axis)
    return [(pos >= i * d) & (pos < (i + 1) * d) for i in range(g)]


def _only(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _scaled(x, scale: float):
    """``x * scale`` in ``x``'s dtype where that is exact (a power of two,
    as at head widths 64 and 16), else ``None``: the caller scales the f32
    scores instead, as the XLA path does."""
    return x * scale if math.frexp(scale)[0] == 0.5 else None


def _load_transposed(pairs):
    """Fill ``(T, lanes)`` VMEM scratch from ``(1, lanes, T)`` blocks: the
    tiles below want positions on sublanes and features on lanes."""
    for src, dst in pairs:
        dst[...] = src[0].T


def _as_row(col):
    """A ``(rows, 1)`` f32 column as a ``(1, rows)`` row: the forward's
    statistics are columns, the backward broadcasts them down the sublanes."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


def _fwd_kernel(qt_ref, kt_ref, vt_ref, ot_ref, lse_ref, q_ref, k_ref, v_ref,
                *, causal: bool, block_q: int, block_k: int, head_dim: int):
    t, lanes = q_ref.shape
    scale = head_dim ** -0.5
    masks = _head_masks(lanes, head_dim, axis=1)
    n_k = t // block_k
    _load_transposed(((qt_ref, q_ref), (kt_ref, k_ref), (vt_ref, v_ref)))

    def q_block(i, _):
        q0 = pl.multiple_of(i * block_q, block_q)
        q = q_ref[pl.ds(q0, block_q), :]
        qs = _scaled(q, scale)
        q_heads = [_only(mk, q if qs is None else qs) for mk in masks]
        q_pos = q0 + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

        def k_block(j, carry):
            k0 = pl.multiple_of(j * block_k, block_k)
            k = k_ref[pl.ds(k0, block_k), :]
            v = v_ref[pl.ds(k0, block_k), :]
            if causal:
                keep = q_pos >= k0 + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
            out = []
            for q_h, (acc, m, l) in zip(q_heads, carry):
                s = lax.dot_general(q_h, k, _NT,
                                    preferred_element_type=jnp.float32)
                if qs is None:
                    s = s * scale
                if causal:
                    s = jnp.where(keep, s, _NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(axis=1, keepdims=True)
                acc_new = acc * corr + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                out.append((acc_new, m_new, l_new))
            return tuple(out)

        init = tuple((jnp.zeros((block_q, lanes), jnp.float32),
                      jnp.full((block_q, 1), _NEG_INF, jnp.float32),
                      jnp.zeros((block_q, 1), jnp.float32)) for _ in masks)
        # causal frontier: key blocks past this query block's last row are
        # fully masked — skip them instead of streaming zeros
        stop = (jnp.minimum((q0 + block_q + block_k - 1) // block_k, n_k)
                if causal else n_k)
        carry = lax.fori_loop(0, stop, k_block, init)

        out = None
        for idx, (mk, (acc, m, l)) in enumerate(zip(masks, carry)):
            l = jnp.maximum(l, 1e-30)
            o_h = acc / l                   # the other heads' lanes: garbage
            out = o_h if out is None else jnp.where(mk, o_h, out)
            lse_ref[0, 0, pl.ds(idx, 1), pl.ds(q0, block_q)] = _as_row(
                m + jnp.log(l))
        ot_ref[0, :, pl.ds(q0, block_q)] = out.astype(ot_ref.dtype).T
        return 0

    lax.fori_loop(0, t // block_q, q_block, 0)


def _bwd_kernel(qt_ref, kt_ref, vt_ref, ot_ref, dot_ref, lse_ref,
                dqt_ref, dkt_ref, dvt_ref,
                q_ref, k_ref, v_ref, do_ref, dq_t_ref, delta_ref,
                *, causal: bool, block_q: int, block_k: int, head_dim: int):
    t, lanes = q_ref.shape
    scale = head_dim ** -0.5
    lane_masks = _head_masks(lanes, head_dim, axis=1)
    row_masks = _head_masks(lanes, head_dim, axis=0)
    n_q = t // block_q
    _load_transposed(((qt_ref, q_ref), (kt_ref, k_ref), (vt_ref, v_ref),
                      (dot_ref, do_ref)))
    dq_t_ref[...] = jnp.zeros_like(dq_t_ref)
    # D_i = rowsum(dO * O) per head, the lse-gradient shortcut: here a sum
    # down the head's sublanes, which leaves the row the tiles want
    prod_t = dot_ref[0].astype(jnp.float32) * ot_ref[0].astype(jnp.float32)
    for idx, mk in enumerate(row_masks):
        delta_ref[pl.ds(idx, 1), :] = _only(mk, prod_t).sum(
            axis=0, keepdims=True)

    def k_block(j, _):
        k0 = pl.multiple_of(j * block_k, block_k)
        k = k_ref[pl.ds(k0, block_k), :]
        v = v_ref[pl.ds(k0, block_k), :]
        k_t = kt_ref[0, :, pl.ds(k0, block_k)]
        k_t_heads = [_only(mk, k_t) for mk in row_masks]
        k_pos = k0 + lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)

        def q_block(i, carry):
            dk, dv = carry
            q0 = pl.multiple_of(i * block_q, block_q)
            q = q_ref[pl.ds(q0, block_q), :]
            do = do_ref[pl.ds(q0, block_q), :]
            qs = _scaled(q, scale)
            if causal:
                keep = k_pos <= q0 + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
            dq_t = None
            for idx, (mk, k_t_h) in enumerate(zip(lane_masks, k_t_heads)):
                lse = lse_ref[0, 0, pl.ds(idx, 1), pl.ds(q0, block_q)]
                delta = delta_ref[pl.ds(idx, 1), pl.ds(q0, block_q)]
                q_h, do_h = _only(mk, q), _only(mk, do)
                qs_h = q_h if qs is None else _only(mk, qs)
                s_t = lax.dot_general(k, qs_h, _NT,
                                      preferred_element_type=jnp.float32)
                if qs is None:
                    s_t = s_t * scale
                if causal:
                    s_t = jnp.where(keep, s_t, _NEG_INF)
                p_t = jnp.exp(s_t - lse)                    # (BK, BQ)
                dp_t = lax.dot_general(v, do_h, _NT,
                                       preferred_element_type=jnp.float32)
                ds_t = (p_t * (dp_t - delta)).astype(q.dtype)
                dv = dv + jnp.dot(p_t.astype(do.dtype), do_h,
                                  preferred_element_type=jnp.float32)
                dk = dk + jnp.dot(ds_t, q_h,
                                  preferred_element_type=jnp.float32)
                part = jnp.dot(k_t_h, ds_t,
                               preferred_element_type=jnp.float32)
                dq_t = part if dq_t is None else dq_t + part
            dq_t_ref[:, pl.ds(q0, block_q)] += dq_t
            return dk, dv

        zero = jnp.zeros((block_k, lanes), jnp.float32)
        # causal: query blocks that end before this key block see none of it
        start = k0 // block_q if causal else 0
        dk, dv = lax.fori_loop(start, n_q, q_block, (zero, zero))
        dkt_ref[0, :, pl.ds(k0, block_k)] = (
            dk * scale).astype(dkt_ref.dtype).T
        dvt_ref[0, :, pl.ds(k0, block_k)] = dv.astype(dvt_ref.dtype).T
        return 0

    lax.fori_loop(0, t // block_k, k_block, 0)
    dqt_ref[0] = (dq_t_ref[...] * scale).astype(dqt_ref.dtype)


def _vmem_limit(t: int):
    """Mosaic parameters for a sequence past what the default VMEM budget
    holds (of the 128 MiB a v5e core has); ``None`` up to 2048 rows, so those
    kernels compile as they always did."""
    if t <= 2048:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _layout(x, head_dim: int):
    """How both kernels walk ``(B, H*D, T)`` operands: one program per batch
    row and head group.  Returns the grid, the block spec of an operand
    (``(1, lanes, T)``), the block spec and the shape of a row statistic
    (``(B, groups, g, T)`` f32), and ``(lanes, g)``."""
    b, hd, t = x.shape
    lanes = _group_lanes(hd // head_dim, head_dim)
    g = lanes // head_dim
    wide = vmem_spec((1, lanes, t), lambda bi, gi: (bi, gi, 0))
    rows = vmem_spec((1, 1, g, t), lambda bi, gi: (bi, gi, 0, 0))
    stats = jax.ShapeDtypeStruct((b, hd // lanes, g, t), jnp.float32)
    return (b, hd // lanes), wide, rows, stats, (lanes, g)


# jitted, so that the blocks of a model trace and lower each kernel once:
# twelve copies of the pair cost the BERT-base step 0.85 s a lowering
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fused_fwd(q, k, v, causal, head_dim, block_q, block_k, interpret):
    """q/k/v: (B, H*D, T) -> (out (B, H*D, T), lse (B, groups, g, T))."""
    t = q.shape[2]
    assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
    grid, wide, rows, stats, (lanes, _) = _layout(q, head_dim)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, head_dim=head_dim),
        grid=grid,
        in_specs=[wide] * 3,
        out_specs=[wide, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), stats],
        scratch_shapes=[pltpu.VMEM((t, lanes), q.dtype)] * 3,
        compiler_params=_vmem_limit(t),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _fused_bwd(q, k, v, out, lse, do, causal, head_dim, block_q, block_k,
               interpret):
    """-> (dq, dk, dv), all (B, H*D, T)."""
    t = q.shape[2]
    grid, wide, rows, _, (lanes, g) = _layout(q, head_dim)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, head_dim=head_dim),
        grid=grid,
        in_specs=[wide] * 5 + [rows],
        out_specs=[wide] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((t, lanes), q.dtype)] * 4 + [
            pltpu.VMEM((lanes, t), jnp.float32),        # dq^T
            pltpu.VMEM((g, t), jnp.float32)],           # delta
        compiler_params=_vmem_limit(t),
        interpret=interpret,
    )(q, k, v, out, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused(q, k, v, causal, head_dim, block_q, block_k, interpret):
    return _fused_fwd(q, k, v, causal, head_dim, block_q, block_k,
                      interpret)[0]


def _fused_vjp_fwd(q, k, v, causal, head_dim, block_q, block_k, interpret):
    out, lse = _fused_fwd(q, k, v, causal, head_dim, block_q, block_k,
                          interpret)
    return out, (q, k, v, out, lse)


def _fused_vjp_bwd(causal, head_dim, block_q, block_k, interpret, res, do):
    return tuple(_fused_bwd(*res, do, causal, head_dim, block_q, block_k,
                            interpret))


_fused.defvjp(_fused_vjp_fwd, _fused_vjp_bwd)


def fused_attention(q, k, v, *, causal: bool = True,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
    """Fused attention for (B, T, H, D) tensors (the transformer's layout).

    Block sizes follow the shape unless given (the tests sweep them);
    ``interpret=None`` resolves through ``registry.resolve_interpret``.
    """
    interpret = registry.resolve_interpret(interpret)
    b, t, h, d = q.shape
    block_q = min(block_q or _block_size(t), t)
    block_k = min(block_k or _block_size(t), t)

    def feature_major(x):       # a layout XLA hands over without a copy
        return x.reshape(b, t, h * d).transpose(0, 2, 1)

    out = _fused(feature_major(q), feature_major(k), feature_major(v),
                 causal, d, block_q, block_k, interpret)
    return out.transpose(0, 2, 1).reshape(b, t, h, d)


def _ring_single_shard(q, k, v, *, causal: bool = True, **_):
    """The XLA incumbent as a candidate: single-shard ring attention
    (lazy import — models must not load at registry import time)."""
    from ...models.transformer import ring_attention
    return ring_attention(q, k, v, n_sp=1, sp_axis=None, causal=causal,
                          t_local=q.shape[1])


registry.register(registry.KernelCandidate(
    kind="attention", name="fused", fn=fused_attention,
    reference=reference_attention,
    # fwd/bwd max abs error vs reference_attention
    tolerances={"max_err": 0.05},
))

registry.register(registry.KernelCandidate(
    kind="attention", name="ring", fn=_ring_single_shard,
    reference=reference_attention, source="xla",
))
