"""Weight-quantized int8 matmul for the serving decode path.

Decode is HBM-bandwidth-bound: each token streams every weight matrix
once for a handful of rows of activations.  Storing the big matrices
(FFN w1/w2 and the LM head — the bulk of the bytes) as int8 with
per-output-channel scales cuts that traffic 4x; the kernel dequantizes
tiles in VMEM on the way to the MXU, so full-precision weights never
exist on the wire.

Quantization is symmetric absmax per output channel: ``scale[n] =
max(|w[:, n]|) / 127``, ``q = round(w / scale)``.  The activation side
stays in the model's compute dtype (weight-only quantization — no
calibration data needed, and the error is a fixed, testable function of
the weights).

Opt-in behind ``ServingConfig(int8_decode=True)``, default off and
unmeasured on the chip; the candidate declares a token-level top-1
agreement floor (``tolerances["min"]["top1_agree"]``) beside its error
bound.  Differentiable wrt the activations only (the
quantized weights are frozen serving artifacts) — the custom_vjp hands
the int8 leaf a float0 cotangent.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import registry
from .vmem import vmem_spec


class QuantizedLinear(NamedTuple):
    """Per-output-channel int8 weight: ``w ≈ q * scale``.  NamedTuple =
    automatic pytree, so it rides inside param dicts through jit."""

    q: jax.Array       # (K, N) int8
    scale: jax.Array   # (N,) f32


def quantize(w) -> QuantizedLinear:
    """Symmetric absmax quantization of a (K, N) matrix, per column."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=0)
    scale = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantizedLinear(q=q, scale=scale)


def dequantize(qw: QuantizedLinear) -> jax.Array:
    return qw.q.astype(jnp.float32) * qw.scale


def reference_int8_matmul(x, qw: QuantizedLinear):
    """jnp ground truth: dequantize then matmul, f32 out."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    out = jnp.dot(x2, dequantize(qw), preferred_element_type=jnp.float32)
    return out.reshape(*lead, qw.q.shape[1])


def _kernel(x_ref, q_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                    # (M, K)
    w = q_ref[...].astype(jnp.float32)                    # (K, BN) dequant
    o_ref[...] = jnp.dot(x, w,
                         preferred_element_type=jnp.float32) * s_ref[...]


def _largest_divisor(n: int, cap: int) -> int:
    d = min(cap, n)
    while n % d:
        d -= 1
    return d


def _int8_call(x2, q, s2, block_n, interpret):
    m, k = x2.shape
    n = q.shape[1]
    bn = _largest_divisor(n, block_n)
    return pl.pallas_call(
        _kernel,
        grid=(n // bn,),
        in_specs=[
            vmem_spec((m, k), lambda j: (0, 0)),
            vmem_spec((k, bn), lambda j: (0, j)),
            vmem_spec((1, bn), lambda j: (0, j)),
        ],
        out_specs=vmem_spec((m, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x2, q, s2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _int8_mm(x2, q, s2, block_n, interpret):
    return _int8_call(x2, q, s2, block_n, interpret)


def _int8_mm_fwd(x2, q, s2, block_n, interpret):
    return _int8_call(x2, q, s2, block_n, interpret), (x2, q, s2)


def _int8_mm_bwd(block_n, interpret, res, g):
    x2, q, s2 = res
    g32 = g.astype(jnp.float32) * s2                      # fold scale in
    dx = jnp.dot(g32, q.astype(jnp.float32).T).astype(x2.dtype)
    dq = np.zeros(q.shape, jax.dtypes.float0)             # frozen weights
    ds = jnp.zeros_like(s2)
    return dx, dq, ds


_int8_mm.defvjp(_int8_mm_fwd, _int8_mm_bwd)


def int8_matmul(x, qw: QuantizedLinear, *, block_n: int = 512,
                interpret: bool | None = None):
    """``x @ (q * scale)`` on (..., K) activations, f32 out (callers cast
    — the decode head wants f32 logits, the FFN re-casts to the compute
    dtype).  ``interpret=None`` resolves through
    ``registry.resolve_interpret``."""
    interpret = registry.resolve_interpret(interpret)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = _int8_mm(x2, qw.q, qw.scale.reshape(1, -1), block_n, interpret)
    return out.reshape(*lead, qw.q.shape[1])


def quantize_params_for_decode(params: dict, cfg) -> dict:
    """Serving-side tree transform: add int8 copies of the decode path's
    bandwidth-heavy matrices (FFN w1/w2 per layer + the LM head), drop
    the f32 FFN originals from the copy so the decode step streams 4x
    fewer bytes.  ``decode_step``/``_ffn`` take the int8 path purely on
    key presence, so training trees (no ``*_q`` keys) are untouched."""
    layers = []
    for lp in params["layers"]:
        lp2 = {k: v for k, v in lp.items() if k not in ("w1", "w2")}
        lp2["w1_q"] = quantize(lp["w1"])
        lp2["w2_q"] = quantize(lp["w2"])
        layers.append(lp2)
    head = (params["tok_embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    return dict(params, layers=layers, head_q=quantize(head))


def top1_agreement(logits_a, logits_b) -> jax.Array:
    """Fraction of rows whose argmax agrees: the statistic the int8
    candidates' ``top1_agree`` floor is held to."""
    return jnp.mean((jnp.argmax(logits_a, axis=-1)
                     == jnp.argmax(logits_b, axis=-1)).astype(jnp.float32))


def _f32_matmul(x, qw: QuantizedLinear, **_):
    """The incumbent: plain matmul against the dequantized (i.e. full
    precision, as served today) weights."""
    return reference_int8_matmul(x, qw)


registry.register(registry.KernelCandidate(
    kind="int8_matmul", name="pallas_int8", fn=int8_matmul,
    reference=reference_int8_matmul,
    # vs the int8 reference the kernel must be near-exact, and its
    # greedy tokens must agree with the reference's
    tolerances={"max_err": 1e-3, "min": {"top1_agree": 0.999}},
))

registry.register(registry.KernelCandidate(
    kind="int8_matmul", name="f32", fn=_f32_matmul,
    reference=reference_int8_matmul, source="xla",
))
