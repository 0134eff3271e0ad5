"""Blocked cross-entropy: stream (N, V) logits tile-by-tile, never
materializing them.

The LM loss is the single biggest HBM tensor in big-vocab training (the
full f32 logits are 4.3 GB at batch 64 / seq 512 / 32k vocab).  The
chunked-scan path in ``models/transformer.lm_head_loss`` already streams
token chunks, but its chunk size must DIVIDE the token count — a
near-prime count used to force a zero-weight padding workaround (PR 5).
This kernel replaces that fallback with a shape-independent schedule:

- grid = (token tiles, vocab tiles); the vocab axis is the inner
  (sequential) dimension, so each token tile keeps a running softmax
  (max, denominator) and its gold-logit gather in VMEM while (D, BV)
  head tiles stream through the MXU;
- any token count works (rows pad internally with zero-weight tokens),
  any vocab works (the tail tile is masked in-kernel by the real V, so
  odd vocabularies never pad the head matrix);
- per-token ``w * (logsumexp - gold)`` and the lse come out; the sum is
  the same quantity ``token_xent`` computes today.

Backward is a jnp ``lax.scan`` over token tiles under a custom_vjp: it
recomputes each tile's logits from the saved lse (flash-attention-style
recompute — O(tile × V) transient, nothing stored), emits dh/dhead/dw,
and a ``float0`` cotangent for the integer targets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from . import registry
from .vmem import vmem_spec

_NEG_INF = -1e30


def reference_xent_sum(h, head, targets, weights=None):
    """Naive ground truth: full (N, V) logits, f32, weighted sum of
    per-token (lse - gold)."""
    logits = jnp.dot(h, head,
                     preferred_element_type=jnp.float32).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    w = jnp.ones_like(lse) if weights is None else weights.astype(jnp.float32)
    return ((lse - gold) * w).sum()


def _kernel(h_ref, hd_ref, t_ref, wt_ref,
            m_ref, l_ref, g_ref, loss_ref, lse_ref, *,
            block_v: int, v_real: int):
    j = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        g_ref[...] = jnp.zeros(g_ref.shape, jnp.float32)

    logits = jnp.dot(h_ref[...], hd_ref[...],
                     preferred_element_type=jnp.float32)   # (BT, BV)
    cols = j * block_v + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    # the tail vocab tile may run past the real V (uneven split — the
    # whole point is never padding the head): mask phantom columns so
    # they contribute exp(-inf)=0 to the denominator and 0 to gold
    logits = jnp.where(cols < v_real, logits, _NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    l_new = (l_prev * jnp.exp(m_prev - m_new)
             + jnp.exp(logits - m_new).sum(-1, keepdims=True))
    m_ref[...] = m_new
    l_ref[...] = l_new
    hit = cols == t_ref[...]                              # (BT, BV)
    g_ref[...] = g_ref[...] + jnp.where(hit, logits, 0.0).sum(
        -1, keepdims=True)

    @pl.when(j == n_v - 1)
    def _finish():
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[...] = lse
        loss_ref[...] = wt_ref[...] * (lse - g_ref[...])


def _xent_fwd(h2, head, t2, w2, block_t, block_v, interpret):
    """h2 (N, D) with N % block_t == 0; returns (wloss (N,), lse (N,))."""
    n, d = h2.shape
    v = head.shape[1]
    n_v = -(-v // block_v)
    kernel = functools.partial(_kernel, block_v=block_v, v_real=v)
    outs = pl.pallas_call(
        kernel,
        grid=(n // block_t, n_v),
        in_specs=[
            vmem_spec((block_t, d), lambda i, j: (i, 0)),
            vmem_spec((d, block_v), lambda i, j: (0, j)),
            vmem_spec((block_t, 1), lambda i, j: (i, 0)),
            vmem_spec((block_t, 1), lambda i, j: (i, 0)),
        ],
        # running (max, denom, gold) live in revisited output blocks —
        # the same accumulate-across-the-inner-grid-axis pattern as a
        # blocked matmul; loss/lse are written on the final vocab tile
        out_specs=[vmem_spec((block_t, 1), lambda i, j: (i, 0))
                   for _ in range(5)],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32)
                   for _ in range(5)],
        interpret=interpret,
    )(h2, head, t2.reshape(n, 1), w2.reshape(n, 1))
    _, _, _, wloss, lse = outs
    return wloss[:, 0], lse[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _blocked(h2, head, t2, w2, block_t, block_v, interpret):
    wloss, _ = _xent_fwd(h2, head, t2, w2, block_t, block_v, interpret)
    return wloss.sum()


def _blocked_fwd(h2, head, t2, w2, block_t, block_v, interpret):
    wloss, lse = _xent_fwd(h2, head, t2, w2, block_t, block_v, interpret)
    return wloss.sum(), (h2, head, t2, w2, lse)


def _blocked_bwd(block_t, block_v, interpret, res, gct):
    h2, head, t2, w2, lse = res
    n, d = h2.shape
    head32 = head.astype(jnp.float32)
    n_t = n // block_t
    tiles = (h2.reshape(n_t, block_t, d), t2.reshape(n_t, block_t),
             w2.reshape(n_t, block_t), lse.reshape(n_t, block_t))

    def tile(dhead, xs):
        h_t, t_t, w_t, lse_t = xs
        logits = jnp.dot(h_t, head,
                         preferred_element_type=jnp.float32)  # (BT, V)
        p = jnp.exp(logits.astype(jnp.float32) - lse_t[:, None])
        gw = (gct * w_t)[:, None]                             # (BT, 1) f32
        # dL/dlogits = g*w * (softmax - onehot), applied without ever
        # building the onehot: matmul with p, then scatter the gold term
        dh_t = (jnp.dot(gw * p, head32.T)
                - gw * head32.T[t_t]).astype(h2.dtype)
        h32 = h_t.astype(jnp.float32)
        dhead = dhead + jnp.dot(h32.T, gw * p)
        dhead = dhead.at[:, t_t].add(-(gw * h32).T)
        gold = jnp.take_along_axis(logits, t_t[:, None], axis=1)[:, 0]
        dw_t = gct * (lse_t - gold.astype(jnp.float32))
        return dhead, (dh_t, dw_t)

    dhead, (dhs, dws) = lax.scan(
        tile, jnp.zeros(head.shape, jnp.float32), tiles)
    dh = dhs.reshape(n, d)
    dw = dws.reshape(n).astype(w2.dtype)
    # integer targets take a float0 cotangent (JAX's convention for
    # non-differentiable integer primal inputs)
    dt = np.zeros(t2.shape, jax.dtypes.float0)
    return dh, dhead.astype(head.dtype), dt, dw


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def blocked_cross_entropy(h, head, targets, weights=None, *,
                          block_t: int = 256, block_v: int = 512,
                          interpret: bool | None = None):
    """Weighted token cross-entropy SUM of (N, D) hiddens against a
    (D, V) head, streamed so (N, V) logits never exist.

    Mirrors ``lm_head_loss``'s ``token_xent`` contract (the caller
    divides by the real token count).  Any N and V work: N pads
    internally with zero-weight rows, the tail V tile is masked
    in-kernel.  ``interpret=None`` resolves through
    ``registry.resolve_interpret``.
    """
    interpret = registry.resolve_interpret(interpret)
    n, d = h.shape
    block_t = min(block_t, n)
    block_v = min(block_v, head.shape[1])
    t2 = targets.astype(jnp.int32)
    w2 = (jnp.ones((n,), jnp.float32) if weights is None
          else weights.astype(jnp.float32))
    pad = -n % block_t
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)])
        t2 = jnp.concatenate([t2, jnp.zeros((pad,), t2.dtype)])
        w2 = jnp.concatenate([w2, jnp.zeros((pad,), w2.dtype)])
    return _blocked(h, head, t2, w2, block_t, block_v, interpret)


def _scan_xent_sum(h, head, targets, weights=None, *, block_t: int = 256,
                   **_):
    """The XLA incumbent (and pallas-unavailable fallback): a remat'd
    ``lax.scan`` over zero-weight-padded token tiles — the PR-5 schedule,
    generalized to any N.  Still O(tile × V) transient memory, but each
    tile's logits DO materialize."""
    n, d = h.shape
    block_t = min(block_t, n)
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    t = targets
    pad = -n % block_t
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)])
        t = jnp.concatenate([t, jnp.zeros((pad,), t.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])

    @jax.checkpoint
    def tile(h_t, t_t, w_t):
        return reference_xent_sum(h_t, head, t_t, w_t)

    def body(tot, xs):
        return tot + tile(*xs), None

    total, _ = lax.scan(
        body, jnp.zeros((), jnp.float32),
        (h.reshape(-1, block_t, d), t.reshape(-1, block_t),
         w.reshape(-1, block_t)))
    return total


registry.register(registry.KernelCandidate(
    kind="xent", name="blocked", fn=blocked_cross_entropy,
    reference=reference_xent_sum,
    # fwd relative loss error + bwd max grad error vs reference (f32)
    tolerances={"max_err": 1e-3},
))

registry.register(registry.KernelCandidate(
    kind="xent", name="scan", fn=_scan_xent_sum,
    reference=reference_xent_sum, source="xla",
))
