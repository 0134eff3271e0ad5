"""Attention over a SELECTION of keys, forward and backward, with the
per-head scores kept in VMEM.

What ``models/hybrid.sparse_attention_mixer`` runs on a TPU
(``attention.attention_candidate`` answers ``"selected"`` for the shapes
``kernel_takes`` accepts): grouped-query attention of one chunk of ``C``
queries over the ``L`` keys so far, each query restricted to the keys a
``(C, L)`` mask names.  The XLA path writes every (query, key) score of all
heads in f32 and reads it back three or four times forward and again
backward, selected or not; these kernels hold one ``(C, block_k)`` tile of
one head's scores at a time and write no array with a ``heads x queries x
keys`` extent at all.

Three passes, each one ``pallas_call`` over ``(KV head, key block)``:

- ``forward``: ``(out, lse)``, the flash schedule.  A program owns the ``R``
  query heads of one KV head: they share the K / V tile and the mask tile,
  so K and V are read once a group and never repeated in HBM.  Running
  ``(acc, m, l)`` per head live in VMEM scratch across the key blocks.
- ``backward``: ``dq``, ``dk``, ``dv`` in one kernel that recomputes ``p``
  per tile from ``(q, k, lse)``.  It works on the TRANSPOSED tile ``s^T = k
  q^T`` (keys on sublanes, queries on lanes), as ``attention._bwd_kernel``
  does and for its reasons: ``lse`` and ``delta`` are rows, ``dv = p^T dO``
  and ``dk = ds^T q`` are plain matmuls.  The mask tile is transposed in the
  kernel, once for the group's heads (a transposed copy made by XLA cost a
  third of a millisecond a chunk).
  ``dk`` and ``dv`` ADD to f32 accumulators handed in and aliased to the
  outputs: a sequence's chunks of queries go through the kernel one after
  the other and only the key blocks a chunk can see are read and written.
- ``head_mean``: ``mean_h softmax_h[t, s]`` as ONE ``(C, L)`` f32 array, the
  target of the selection's own loss; forward only.

Layout.  ``q, out, dO, dq``: ``(C, H*d)``; ``k, v`` and the accumulators:
``(L, G*d)``, flat on both sides of the call (a ``(L, G, d)`` f32 array pads
``G`` to 8 sublanes, and reshaping it is a copy); a head is
a 128-lane slice of its group's block (``d = 128`` when compiled; any width
interpreted).  Row statistics are ``(G, C, R)`` f32 (columns that broadcast
along the lanes) and, for the backward, ``(G, R, C)`` (rows that broadcast
down the sublanes).  K and V stream from HBM a key block at a time: nothing
here is as long as the sequence.

``frontier`` (a traced scalar, prefetched) is the number of leading keys any
of the chunk's queries may select: key blocks from there on are skipped, not
fetched, and their share of ``head_mean`` written as zeros.

Precision is the XLA path's: ``q, k, v`` enter the MXU in their own dtype
(bf16 in training) with f32 accumulation; ``m``, ``l``, ``lse``, ``acc`` and
the accumulators are f32; ``p`` is cast to ``v.dtype`` for the value product.

The indexer's scores that make the selection, ``I[t, s] = sum_j w[t, j]
relu(qi[t, j] . ki[s])``, are a pair of kernels of their own over the key
blocks (``index_forward``, ``index_backward``; ``index_scores`` is the pair
as one differentiable function): a program holds the chunk's index queries
and head weights and one key tile, and no ``heads x queries x keys`` array
of pre-activations, relu mask or products ever reaches HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

_NEG_INF = -1e30                      # a row's running maximum starts here
_MASKED = -2e30                       # under it: exp(_MASKED - m) is 0 even
#                                       for a row with no selected key so far
_NT = (((1,), (1,)), ((), ()))        # a @ b.T, contracting the lane axes

#: keys a sequence may have on one chip (ROADMAP M8 is the second chip)
MAX_KEYS = 16384
#: queries in a chunk: one to four whole 128-row blocks
CHUNK_ROWS = (128, 256, 384, 512)


def kernel_takes(t: int, h: int, d: int, kv_heads: int, rows: int) -> bool:
    """Shapes the compiled kernels are built for: chunks of one to four
    whole 128-row blocks (a chunk's queries are ONE tile: measured at 256,
    compiled at 128 and 512) that tile the sequence, heads of 128 lanes,
    whole groups of query heads a KV head."""
    return (d == 128 and h % kv_heads == 0 and rows in CHUNK_ROWS
            and t % rows == 0 and t <= MAX_KEYS)


def index_takes(c: int, n_keys: int, dim: int) -> bool:
    """Shapes the index kernels are built for: a chunk of ``CHUNK_ROWS``
    queries over whole 128-row blocks of keys, index heads of 64 or 128
    lanes."""
    return (dim in (64, 128) and c in CHUNK_ROWS and n_keys % 128 == 0
            and n_keys <= MAX_KEYS)


def _block_k(n_keys: int, largest: int = 512) -> int:
    """Keys in one tile: the largest of ``largest``, ... 256, 128 that tiles
    the keys (one block where none does: the interpreted tests' small
    shapes).  Measured on a v5e, one chunk of 256 queries x 32 heads over
    16,384 keys: the forward pass 1.40 / 1.01 / 0.70 ms at 256 / 512 / 1024
    (its row maxima and sums cross the lanes once a tile), the backward 1.50
    / 1.41 / 1.39 ms and the heads' mean 0.37 / 0.35 / 0.35 ms (neither
    reduces anything; PERF.md section 6, PR 35)."""
    sizes = (b for b in (1024, 512, 256, 128) if b <= largest)
    return next((b for b in sizes if n_keys % b == 0), n_keys)


def _needed(frontier_ref, block_k: int):
    """Key blocks that hold a key below the frontier."""
    return (frontier_ref[0] + block_k - 1) // block_k


def _keep(mask_ref, transposed: bool = False):
    """The int8 mask tile as booleans in the scores' own (32-bit) layout,
    keys on the sublanes if ``transposed``."""
    if transposed:
        return mask_ref[...].astype(jnp.float32).T != 0
    return mask_ref[...].astype(jnp.int32) != 0


def _fwd_kernel(frontier_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, heads: int, head_dim: int):
    j, n_k = pl.program_id(1), pl.num_programs(1)
    block_k = k_ref.shape[0]
    scale = head_dim ** -0.5

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < _needed(frontier_ref, block_k))
    def _():
        k, v = k_ref[...], v_ref[...]
        keep = _keep(mask_ref)
        for h in range(heads):
            q = q_ref[:, h * head_dim:(h + 1) * head_dim]
            s = lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, _MASKED)
            m = m_ref[h]
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_k - 1)
    def _():
        for h in range(heads):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[:, h * head_dim:(h + 1) * head_dim] = (
                acc_ref[h] / l).astype(o_ref.dtype)
            lse_ref[:, h:h + 1] = m_ref[h] + jnp.log(l)


def _bwd_kernel(frontier_ref, q_ref, k_ref, v_ref, mask_ref, do_ref,
                lse_ref, delta_ref, dk_in_ref, dv_in_ref,
                dq_ref, dk_ref, dv_ref, dq_t_ref,
                *, heads: int, head_dim: int):
    j, n_k = pl.program_id(1), pl.num_programs(1)
    block_k = k_ref.shape[0]
    scale = head_dim ** -0.5

    @pl.when(j == 0)
    def _():
        dq_t_ref[...] = jnp.zeros_like(dq_t_ref)

    @pl.when(j < _needed(frontier_ref, block_k))
    def _():
        k, v = k_ref[...], v_ref[...]
        k_t = k.T
        keep = _keep(mask_ref, transposed=True)              # (BK, C)
        dk = jnp.zeros(k.shape, jnp.float32)
        dv = jnp.zeros(v.shape, jnp.float32)
        for h in range(heads):
            q = q_ref[:, h * head_dim:(h + 1) * head_dim]
            do = do_ref[:, h * head_dim:(h + 1) * head_dim]
            s_t = lax.dot_general(k, q, _NT,
                                  preferred_element_type=jnp.float32) * scale
            s_t = jnp.where(keep, s_t, _MASKED)
            p_t = jnp.exp(s_t - lse_ref[h:h + 1, :])
            dp_t = lax.dot_general(v, do, _NT,
                                   preferred_element_type=jnp.float32)
            ds_t = (p_t * (dp_t - delta_ref[h:h + 1, :])).astype(q.dtype)
            dv = dv + jnp.dot(p_t.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
            dk = dk + jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
            dq_t_ref[h] += jnp.dot(k_t, ds_t,
                                   preferred_element_type=jnp.float32)
        dk_ref[...] = dk_in_ref[...] + dk * scale
        dv_ref[...] = dv_in_ref[...] + dv

    @pl.when(j == n_k - 1)
    def _():
        for h in range(heads):
            dq_ref[:, h * head_dim:(h + 1) * head_dim] = (
                dq_t_ref[h] * scale).T.astype(dq_ref.dtype)


def _mean_kernel(frontier_ref, q_ref, k_ref, mask_ref, lse_ref, t_ref,
                 *, heads: int, head_dim: int, total_heads: int):
    j, g = pl.program_id(0), pl.program_id(1)
    block_k = k_ref.shape[0]
    scale = head_dim ** -0.5

    @pl.when(g == 0)
    def _():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(j < _needed(frontier_ref, block_k))
    def _():
        k = k_ref[...]
        keep = _keep(mask_ref)
        total = None
        for h in range(heads):
            q = q_ref[:, h * head_dim:(h + 1) * head_dim]
            s = lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(jnp.where(keep, s, _MASKED) - lse_ref[:, h:h + 1])
            total = p if total is None else total + p
        t_ref[...] += total * (1.0 / total_heads)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                               vmem_limit_bytes=64 * 1024 * 1024)


def _last_needed(j, frontier_ref, block_k: int):
    """Block ``j``, or the last block below the frontier: a skipped step
    names the block its predecessor did, so nothing is fetched for it."""
    return jnp.minimum(j, jnp.maximum(_needed(frontier_ref, block_k), 1) - 1)


def _layout(q, k, kv_heads: int, head_dim: int, *, largest: int = 512,
            keys_outer: bool = False):
    """How the three kernels walk a chunk's operands: ``(grid, R, block
    specs by name)``, the grid over ``(KV head, key block)`` or, if
    ``keys_outer``, the other way round.  A step's key block is
    ``_last_needed``'s, so that skipped steps fetch nothing."""
    block_k = _block_k(k.shape[0], largest)
    n_k = k.shape[0] // block_k

    def spec(shape, at):
        def index(a, b, f):
            g, j = (b, a) if keys_outer else (a, b)
            return at(g, j, _last_needed(j, f, block_k))
        return pl.BlockSpec(shape, index)

    r = q.shape[1] // (kv_heads * head_dim)
    return (((n_k, kv_heads) if keys_outer else (kv_heads, n_k)), r,
            {"chunk": spec((q.shape[0], r * head_dim), lambda g, j, last: (0, g)),
             "keys": spec((block_k, head_dim), lambda g, j, last: (last, g)),
             "mask": spec((q.shape[0], block_k), lambda g, j, last: (0, last)),
             "columns": spec((None, q.shape[0], r), lambda g, j, last: (g, 0, 0)),
             "rows": spec((None, r, q.shape[0]), lambda g, j, last: (g, 0, 0)),
             "target": spec((q.shape[0], block_k), lambda g, j, last: (0, j))})


# jitted, so that a sequence's spans of chunks trace and lower each kernel
# once a key length, and so that the custom calls carry these names
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _sparse_fwd(q, k, v, mask, frontier, kv_heads, head_dim, interpret):
    """q (C, H*d), k / v (L, G*d), mask (C, L) int8, frontier (1,) int32 ->
    (out (C, H*d), lse (G, C, R) f32)."""
    grid, r, at = _layout(q, k, kv_heads, head_dim, largest=1024)
    c = q.shape[0]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=r, head_dim=head_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[at["chunk"], at["keys"], at["keys"], at["mask"]],
            out_specs=[at["chunk"], at["columns"]],
            scratch_shapes=[pltpu.VMEM((r, c, head_dim), jnp.float32),
                            pltpu.VMEM((r, c, 1), jnp.float32),
                            pltpu.VMEM((r, c, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((kv_heads, c, r), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(frontier, q, k, v, mask)


@functools.partial(jax.jit, static_argnums=(10, 11, 12))
def _sparse_bwd(q, k, v, mask, do, lse_t, delta_t, frontier, dk_acc, dv_acc,
                kv_heads, head_dim, interpret):
    """mask (C, L) int8, lse_t / delta_t (G, R, C) f32, dk_acc / dv_acc
    (L, G*d) f32 -> (dq (C, H*d), dk_acc + dk, dv_acc + dv)."""
    grid, r, at = _layout(q, k, kv_heads, head_dim)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=r, head_dim=head_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[at["chunk"], at["keys"], at["keys"], at["mask"],
                      at["chunk"], at["rows"], at["rows"], at["keys"],
                      at["keys"]],
            out_specs=[at["chunk"], at["keys"], at["keys"]],
            scratch_shapes=[
                pltpu.VMEM((r, head_dim, q.shape[0]), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(dk_acc.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dv_acc.shape, jnp.float32)],
        # operand indices count the prefetched scalar
        input_output_aliases={8: 1, 9: 2},
        compiler_params=_PARAMS,
        interpret=interpret,
    )(frontier, q, k, v, mask, do, lse_t, delta_t, dk_acc, dv_acc)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _sparse_headsum(q, k, mask, lse, frontier, kv_heads, head_dim, interpret):
    """-> (C, L) f32: the heads' probabilities, averaged.  Key blocks outside,
    KV heads inside: a tile of the result adds the groups up in VMEM."""
    grid, r, at = _layout(q, k, kv_heads, head_dim, keys_outer=True)
    return pl.pallas_call(
        functools.partial(_mean_kernel, heads=r, head_dim=head_dim,
                          total_heads=kv_heads * r),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[at["chunk"], at["keys"], at["mask"], at["columns"]],
            out_specs=at["target"]),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], k.shape[0]), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(frontier, q, k, mask, lse)


def _index_fwd_kernel(frontier_ref, qi_ref, k_ref, w_ref, s_ref):
    needed = _needed(frontier_ref, k_ref.shape[0])

    @pl.when(pl.program_id(0) < needed)
    def _():
        k, w = k_ref[...], w_ref[...]
        total = None
        for j in range(qi_ref.shape[0]):
            pre = lax.dot_general(qi_ref[j], k, _NT,
                                  preferred_element_type=jnp.float32)
            part = w[:, j:j + 1] * jnp.maximum(pre, 0.0)
            total = part if total is None else total + part
        s_ref[...] = total

    @pl.when(pl.program_id(0) >= needed)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)


def _index_bwd_kernel(frontier_ref, qi_ref, qi_t_ref, k_ref, w_ref, ds_ref,
                      dk_in_ref, dqi_ref, dk_ref, dw_ref, dqi_acc, dw_acc):
    i, n_k = pl.program_id(0), pl.num_programs(0)
    block_k = k_ref.shape[0]

    @pl.when(i == 0)
    def _():
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(i < _needed(frontier_ref, block_k))
    def _():
        k, w, ds = k_ref[...], w_ref[...], ds_ref[...]
        dk = None
        for j in range(qi_ref.shape[0]):
            pre = lax.dot_general(qi_ref[j], k, _NT,
                                  preferred_element_type=jnp.float32)
            g = jnp.where(pre > 0, ds, 0.0)          # d relu(pre) . d scores
            d_pre = (g * w[:, j:j + 1]).astype(k.dtype)
            # d w: relu(pre) x d scores summed over the keys, folded to one
            # vreg column a row here and across the lanes once, at the end
            prod = pre * g
            fold = prod[:, :128]
            for a in range(128, block_k, 128):
                fold = fold + prod[:, a:a + 128]
            dw_acc[j] += fold
            dqi_acc[j] += jnp.dot(d_pre, k, preferred_element_type=jnp.float32)
            part = jnp.dot(qi_t_ref[j], d_pre,
                           preferred_element_type=jnp.float32)   # (D, BK)
            dk = part if dk is None else dk + part
        dk_ref[...] = dk_in_ref[...] + dk

    @pl.when(i == n_k - 1)
    def _():
        dqi_ref[...] = dqi_acc[...].astype(dqi_ref.dtype)
        for j in range(qi_ref.shape[0]):
            dw_ref[:, j:j + 1] = dw_acc[j].sum(axis=1, keepdims=True)


def _index_layout(qi_h, k):
    """How the two index kernels walk a chunk: ``(grid, block specs by
    name)`` over the key blocks, a skipped step naming the last block below
    the frontier so that nothing is fetched for it."""
    heads, c, dim = qi_h.shape
    block_k = _block_k(k.shape[0])

    def spec(shape, at):
        return pl.BlockSpec(shape, lambda i, f: at(_last_needed(i, f, block_k)))

    return (k.shape[0] // block_k,), {
        "queries": spec((heads, c, dim), lambda last: (0, 0, 0)),
        "queries_t": spec((heads, dim, c), lambda last: (0, 0, 0)),
        "weights": spec((c, heads), lambda last: (0, 0)),
        "keys": spec((block_k, dim), lambda last: (last, 0)),
        "keys_t": spec((dim, block_k), lambda last: (0, last)),
        "scores_in": spec((c, block_k), lambda last: (0, last)),
        "scores": pl.BlockSpec((c, block_k), lambda i, f: (0, i))}


@functools.partial(jax.jit, static_argnums=(4,))
def _index_fwd(qi_h, k, w, frontier, interpret):
    """qi_h (J, C, D), k (L, D), w (C, J) f32, frontier (1,) int32 -> the
    scores (C, L) f32, zero from the frontier's key block on."""
    grid, at = _index_layout(qi_h, k)
    return pl.pallas_call(
        _index_fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[at["queries"], at["keys"], at["weights"]],
            out_specs=at["scores"]),
        out_shape=jax.ShapeDtypeStruct((qi_h.shape[1], k.shape[0]),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(frontier, qi_h, k, w)


@functools.partial(jax.jit, static_argnums=(7,))
def _index_bwd(qi_h, qi_t, k, w, ds, dk_acc, frontier, interpret):
    """qi_h (J, C, D), qi_t (J, D, C), ds (C, L) f32, dk_acc (D, L) f32 ->
    (d qi (J, C, D), dk_acc + d k^T, d w (C, J) f32)."""
    grid, at = _index_layout(qi_h, k)
    heads, c, dim = qi_h.shape
    return pl.pallas_call(
        _index_bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[at["queries"], at["queries_t"], at["keys"],
                      at["weights"], at["scores_in"], at["keys_t"]],
            out_specs=[at["queries"], at["keys_t"], at["weights"]],
            scratch_shapes=[pltpu.VMEM((heads, c, dim), jnp.float32),
                            pltpu.VMEM((heads, c, 128), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(qi_h.shape, qi_h.dtype),
                   jax.ShapeDtypeStruct(dk_acc.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        # operand indices count the prefetched scalar
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(frontier, qi_h, qi_t, k, w, ds, dk_acc)


def _frontier(frontier, n_keys: int):
    return jnp.full((1,), n_keys if frontier is None else frontier, jnp.int32)


@jax.named_scope("dsa.select")
def _mask(chosen):
    """The selection as the kernels read it, int8: the selection's last
    operation in a trace (XLA fuses what made ``chosen`` into it), so it
    carries the selection's name; the three entry points below name their
    kernel's pass (``dsa.attend``, ``dsa.index_loss``) themselves, side by
    side with it and never around it."""
    return chosen.astype(jnp.int8)


def forward(q, k, v, chosen, frontier=None, *, kv_heads: int,
            interpret: bool | None = None):
    """One chunk of queries ``q (C, H*d)`` over ``k, v (L, G*d)`` under
    ``chosen`` (C, L) bool: ``(out (C, H*d), lse (G, C, R) f32)``.  No key
    at or past ``frontier`` is chosen (all may be, by default)."""
    mask = _mask(chosen)
    with jax.named_scope("dsa.attend"):
        return _sparse_fwd(q, k, v, mask, _frontier(frontier, k.shape[0]),
                           kv_heads, k.shape[1] // kv_heads,
                           registry.resolve_interpret(interpret))


def backward(q, k, v, chosen, out, lse, do, dk_acc, dv_acc, frontier=None, *,
             kv_heads: int, interpret: bool | None = None):
    """``(dq (C, H*d), dk_acc + dk, dv_acc + dv)`` for the cotangent ``do``
    of ``forward``'s ``out``; the accumulators are ``(L, G*d)`` f32."""
    c = q.shape[0]
    d = k.shape[1] // kv_heads
    with jax.named_scope("dsa.attend"):
        delta = jnp.sum((do.astype(jnp.float32) * out.astype(jnp.float32)
                         ).reshape(c, kv_heads, -1, d), axis=-1)  # (C, G, R)
    mask = _mask(chosen)
    with jax.named_scope("dsa.attend"):
        return _sparse_bwd(
            q, k, v, mask, do, lse.transpose(0, 2, 1),
            delta.transpose(1, 2, 0), _frontier(frontier, k.shape[0]),
            dk_acc, dv_acc, kv_heads, d, registry.resolve_interpret(interpret))


def head_mean(q, k, chosen, lse, frontier=None, *, kv_heads: int,
              interpret: bool | None = None):
    """``mean_h softmax_h[t, s]`` over the chosen keys, ``(C, L)`` f32, from
    ``forward``'s ``lse``; zero where nothing is chosen."""
    mask = _mask(chosen)
    with jax.named_scope("dsa.index_loss"):
        return _sparse_headsum(q, k, mask, lse,
                               _frontier(frontier, k.shape[0]), kv_heads,
                               k.shape[1] // kv_heads,
                               registry.resolve_interpret(interpret))


def index_forward(qi, ki, w, frontier=None, *, interpret: bool | None = None):
    """The index scores ``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])``,
    ``(C, L)`` f32, of ``qi (C, J, D)``, ``ki (L, D)`` and ``w (C, J)`` f32,
    zero from the key block that holds ``frontier`` on.  ``qi . ki`` enters
    the MXU in the operands' dtype (bf16 in training) and accumulates in
    f32; ``w``, the pre-activations and the sum over the heads (in head
    order) are f32, as on the XLA path (``models/hybrid.index_scores``)."""
    with jax.named_scope("dsa.index_scores"):
        return _index_fwd(qi.transpose(1, 0, 2), ki, w,
                          _frontier(frontier, ki.shape[0]),
                          registry.resolve_interpret(interpret))


def index_backward(qi, ki, w, d_scores, dki_t, frontier=None, *,
                   interpret: bool | None = None):
    """``(d qi (C, J, D) in qi's dtype, dki_t + d ki^T, d w (C, J) f32)`` for
    the cotangent ``d_scores (C, L)`` f32 of ``index_forward``; the index
    keys' gradient ADDS to ``dki_t (D, L)`` f32, keys on the lanes (an
    ``(L, 64)`` f32 array would pad its lanes to 128).

    Each key tile's pre-activations are made again in VMEM, ``d pre = d
    scores x w[:, j] x (pre > 0)``.  The products take what XLA:TPU's
    compiled ``jax.vjp`` of the XLA path takes (read off its optimised HLO
    for a v5e): the f32 ``d pre`` at DEFAULT precision, one bf16 pass, so
    ``d pre`` rounded to bf16, against the bf16 ``qi`` / ``ki``, accumulated
    in f32.  That path rounds each chunk's ``d ki`` to bf16 before its f32
    sum over the chunks; here it adds to the f32 accumulator unrounded."""
    with jax.named_scope("dsa.index_scores"):
        d_qi, dki_t, d_w = _index_bwd(
            qi.transpose(1, 0, 2), qi.transpose(1, 2, 0), ki, w, d_scores,
            dki_t, _frontier(frontier, ki.shape[0]),
            registry.resolve_interpret(interpret))
        return d_qi.transpose(1, 0, 2), dki_t, d_w


@jax.custom_vjp
def index_scores(qi, ki, w, frontier=None):
    """``index_forward`` with ``index_backward`` as its gradient: what
    ``models/hybrid.index_scores`` computes, in the kernels."""
    return index_forward(qi, ki, w, frontier)


def _index_scores_fwd(qi, ki, w, frontier=None):
    return index_forward(qi, ki, w, frontier), (qi, ki, w, frontier)


def _index_scores_bwd(res, d_scores):
    qi, ki, w, frontier = res
    zero = jnp.zeros(ki.shape[::-1], jnp.float32)
    d_qi, dki_t, d_w = index_backward(qi, ki, w, d_scores, zero, frontier)
    return d_qi, dki_t.T.astype(ki.dtype), d_w.astype(w.dtype), None


index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


def _flat(x):
    return x.reshape(x.shape[0], -1)


@jax.custom_vjp
def selected_attention(q, k, v, chosen):
    """``q (C, H, d)`` over the ``chosen`` (C, L) of ``k, v (L, G, d)``:
    ``forward``'s ``out`` with ``backward`` as its gradient.  The registered
    candidate, what the tolerance test holds to
    ``reference_selected_attention``."""
    return _selected_fwd(q, k, v, chosen)[0]


def _selected_fwd(q, k, v, chosen):
    out, lse = forward(_flat(q), _flat(k), _flat(v), chosen,
                       kv_heads=k.shape[1])
    return out.reshape(q.shape), (q, k, v, chosen, out, lse)


def _selected_bwd(res, do):
    q, k, v, chosen, out, lse = res
    zero = jnp.zeros(_flat(k).shape, jnp.float32)
    dq, dk, dv = backward(_flat(q), _flat(k), _flat(v), chosen, out, lse,
                          _flat(do), zero, zero, kv_heads=k.shape[1])
    return (dq.reshape(q.shape), dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype), None)


selected_attention.defvjp(_selected_fwd, _selected_bwd)


def reference_selected_attention(q, k, v, chosen):
    """Naive softmax attention of ``q (C, H, d)`` over the ``chosen`` (C, L)
    of ``k, v (L, G, d)``: the jnp ground truth."""
    c, h, d = q.shape
    g = k.shape[1]
    s = jnp.einsum("tgrd,sgd->gtrs", q.reshape(c, g, h // g, d), k,
                   preferred_element_type=jnp.float32) * d ** -0.5
    p = jax.nn.softmax(jnp.where(chosen[None, :, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("gtrs,sgd->tgrd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).reshape(c, h, d).astype(q.dtype)


registry.register(registry.KernelCandidate(
    kind="sparse_attention", name="selected", fn=selected_attention,
    reference=reference_selected_attention,
    # fwd/bwd max abs error vs reference_selected_attention
    tolerances={"max_err": 0.05},
))
