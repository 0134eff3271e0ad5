"""Fused residual-add + LayerNorm (+ optional dropout mask) kernel.

The transformer block's mid-sublayer seam is

    x = x + proj            # residual write to HBM
    h = layernorm(x)        # read x back, write h

— two full-activation HBM round-trips that XLA does not reliably fuse
across (the LN reduction materializes its input).  This kernel computes
both outputs in one VMEM pass per row block: ``y = x + r * mask`` and
``h = LN(y) * scale + bias``, reading x/r once and writing y/h once.

Shape-independent: rows flatten to (N, D), N pads internally to the row
block (pad rows are discarded on the way out), D rides whole (a block
equal to the array dim satisfies Mosaic's last-two-dims constraint).
Backward is the standard LN gradient in plain jnp under a custom_vjp —
cheap relative to the matmuls around it, no second kernel to maintain.

Default off, unmeasured on the chip (ROADMAP D1b): opt-in via
``TransformerConfig(fused_ln=True)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import registry
from .vmem import vmem_spec


def reference_residual_layernorm(x, r, scale, bias, *, mask=None,
                                 eps: float = 1e-5):
    """Pure-jnp ground truth: f32 compute, outputs cast to x.dtype."""
    x32 = x.astype(jnp.float32)
    r32 = r.astype(jnp.float32)
    if mask is not None:
        r32 = r32 * mask.astype(jnp.float32)
    y = x32 + r32
    mu = y.mean(-1, keepdims=True)
    var = y.var(-1, keepdims=True)
    h = (y - mu) * lax.rsqrt(var + eps)
    h = h * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype), h.astype(x.dtype)


def _kernel(x_ref, r_ref, m_ref, s_ref, b_ref, y_ref, h_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                       # (BR, D)
    y = x + r_ref[...].astype(jnp.float32) * m_ref[...]
    mu = y.mean(-1, keepdims=True)
    var = ((y - mu) ** 2).mean(-1, keepdims=True)
    h = (y - mu) * lax.rsqrt(var + eps)
    h = h * s_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    h_ref[...] = h.astype(h_ref.dtype)


def _fused_call(x2, r2, m2, scale, bias, eps, block_rows, interpret):
    """x2/r2: (N, D), m2: (N, 1) f32 keep-mask, scale/bias: (1, D)."""
    n, d = x2.shape
    br = min(block_rows, n)
    pad = -n % br
    if pad:
        # zero pad rows: LN of zeros is finite (rsqrt(eps)), rows sliced off
        x2 = jnp.concatenate([x2, jnp.zeros((pad, d), x2.dtype)])
        r2 = jnp.concatenate([r2, jnp.zeros((pad, d), r2.dtype)])
        m2 = jnp.concatenate([m2, jnp.zeros((pad, 1), m2.dtype)])
    y, h = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=((n + pad) // br,),
        in_specs=[
            vmem_spec((br, d), lambda i: (i, 0)),
            vmem_spec((br, d), lambda i: (i, 0)),
            vmem_spec((br, 1), lambda i: (i, 0)),
            vmem_spec((1, d), lambda i: (0, 0)),
            vmem_spec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            vmem_spec((br, d), lambda i: (i, 0)),
            vmem_spec((br, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n + pad, d), x2.dtype),
            jax.ShapeDtypeStruct((n + pad, d), x2.dtype),
        ],
        interpret=interpret,
    )(x2, r2, m2, scale.reshape(1, d), bias.reshape(1, d))
    if pad:
        y, h = y[:n], h[:n]
    return y, h


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(x2, r2, m2, scale, bias, eps, block_rows, interpret):
    return _fused_call(x2, r2, m2, scale, bias, eps, block_rows, interpret)


def _fused_fwd(x2, r2, m2, scale, bias, eps, block_rows, interpret):
    y, h = _fused_call(x2, r2, m2, scale, bias, eps, block_rows, interpret)
    return (y, h), (y, r2, m2, scale)


def _fused_bwd(eps, block_rows, interpret, res, cts):
    y, r2, m2, scale = res
    dy, dh = cts
    y32 = y.astype(jnp.float32)
    mu = y32.mean(-1, keepdims=True)
    var = y32.var(-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    yhat = (y32 - mu) * rstd
    dh32 = dh.astype(jnp.float32)
    dscale = (dh32 * yhat).sum(0).astype(scale.dtype)
    dbias = dh32.sum(0).astype(scale.dtype)
    dyhat = dh32 * scale.astype(jnp.float32)
    g_ln = rstd * (dyhat - dyhat.mean(-1, keepdims=True)
                   - yhat * (dyhat * yhat).mean(-1, keepdims=True))
    g = dy.astype(jnp.float32) + g_ln
    dx = g.astype(y.dtype)
    dr = (g * m2).astype(r2.dtype)
    dm = (g * r2.astype(jnp.float32)).sum(-1, keepdims=True)
    return dx, dr, dm, dscale, dbias


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_residual_layernorm(x, r, scale, bias, *, mask=None,
                             eps: float = 1e-5, block_rows: int = 256,
                             interpret: bool | None = None):
    """Fused ``y = x + r*mask; h = LN(y)`` on (..., D) activations.

    Returns ``(y, h)`` in x.dtype.  ``mask`` (broadcastable to x's row
    shape) is a dropout keep-mask (pre-scaled, e.g. bernoulli/keep_prob);
    None means no masking.  ``interpret=None`` resolves through
    ``registry.resolve_interpret``.
    """
    interpret = registry.resolve_interpret(interpret)
    *lead, d = x.shape
    n = 1
    for s in lead:
        n *= s
    x2 = x.reshape(n, d)
    r2 = r.reshape(n, d)
    if mask is None:
        m2 = jnp.ones((n, 1), jnp.float32)
    else:
        m2 = jnp.broadcast_to(
            mask.astype(jnp.float32).reshape(n, -1)[:, :1], (n, 1))
    y, h = _fused(x2, r2, m2, scale, bias, eps, block_rows, interpret)
    return y.reshape(x.shape), h.reshape(x.shape)


def _unfused(x, r, scale, bias, *, mask=None, eps: float = 1e-5, **_):
    """The XLA incumbent: exactly the transformer's existing two-op seam
    (residual add in x.dtype, then the f32 LN)."""
    r = r * mask.astype(r.dtype) if mask is not None else r
    y = x + r.astype(x.dtype)
    y32 = y.astype(jnp.float32)
    mu = y32.mean(-1, keepdims=True)
    var = y32.var(-1, keepdims=True)
    h = (y32 - mu) * lax.rsqrt(var + eps)
    h = h * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y, h.astype(x.dtype)


registry.register(registry.KernelCandidate(
    kind="layernorm_residual", name="fused", fn=fused_residual_layernorm,
    reference=reference_residual_layernorm,
    # fwd/bwd max abs error vs the f32 reference (f32)
    tolerances={"max_err": 1e-3},
))

registry.register(registry.KernelCandidate(
    kind="layernorm_residual", name="unfused", fn=_unfused,
    reference=reference_residual_layernorm, source="xla",
))
