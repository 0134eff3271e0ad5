"""KV-cache quantization helpers: per-page, per-head absmax scales.

The paged serving tier (DESIGN.md §17) stores K/V as fixed-size pages in
a shared device pool; this module extends the ``matmul_int8`` symmetric
absmax machinery from weight precision to CACHE precision (DESIGN.md
§20).  Storage is int8 (or, gated off by default, fp8) with one f32
scale per (page, kv_head): ``scale[p, h] = max(|page[p, :, h, :]|) /
qmax``, so dequantization inside the paged-attention read is one
broadcast multiply per page — the shape the streamed Pallas kernel DMAs
anyway.

Write discipline (the part that makes incremental decode sound): scales
are MONOTONE per page — ``requantize_pool`` takes ``max(old_scale,
amax/qmax)`` — so a page whose content did not change requantizes to
byte-identical storage (``round(q * s / s) == q``), and repeated
single-token writes can never drift the untouched remainder of the
pool.  A freed page's scale resets to :func:`neutral_scale` (wipe
hygiene in ``reset_cache_pages``), so a previous occupant's large scale
cannot poison the next sequence's precision.

Every raw precision cast lives HERE (``cast_to``): graftlint QT01 keeps
``serving/`` and ``models/`` free of ad-hoc ``.astype(jnp.int8)`` /
``.astype(jnp.float8_*)`` so scale handling stays centralized.
"""

from __future__ import annotations

import jax.numpy as jnp

#: fp8 storage rides the same seam as int8 but only exists when the
#: installed jax exposes float8_e4m3fn — and is gated off by default
#: either way (unmeasured on the chip)
_FP8 = getattr(jnp, "float8_e4m3fn", None)

#: kv_quant modes ServingConfig accepts on this build
KV_QUANT_MODES = ("int8",) + (("fp8",) if _FP8 is not None else ())


def storage_dtype(mode: str):
    """The on-device dtype of a quantized KV page for ``mode``."""
    if mode == "int8":
        return jnp.int8
    if mode == "fp8":
        if _FP8 is None:
            raise ValueError(
                "kv_quant='fp8' needs a jax build with float8_e4m3fn")
        return _FP8
    raise ValueError(
        f"unknown kv_quant mode {mode!r} (supported: {KV_QUANT_MODES})")


def qmax(dtype) -> float:
    """Largest magnitude the absmax scale maps onto for ``dtype``."""
    d = jnp.dtype(dtype)
    if d == jnp.dtype(jnp.int8):
        return 127.0
    if _FP8 is not None and d == jnp.dtype(_FP8):
        return 448.0  # float8_e4m3fn finite max
    raise ValueError(f"not a KV storage dtype: {dtype!r}")


def neutral_scale(dtype) -> float:
    """Scale of an all-zero (freshly wiped) page: positive so dequant is
    division-safe, and MINIMAL so the monotone per-page running max only
    grows from real content, never from a stale previous occupant."""
    return 1.0 / qmax(dtype)


def cast_to(x, dtype):
    """Saturating cast of already-scaled f32 values into the storage
    dtype — the one place a raw KV precision cast is allowed (QT01)."""
    m = qmax(dtype)
    x = jnp.clip(x, -m, m)
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        x = jnp.round(x)
    return x.astype(dtype)


def init_quantized_paged_cache(cfg, num_pages: int, page_size: int,
                               mode: str) -> list:
    """Quantized twin of ``transformer.init_paged_cache``: per-layer
    int8/fp8 K/V pools ``(num_pages, page_size, n_kv_heads, Dh)`` plus
    ``(num_pages, n_kv_heads)`` f32 per-page per-head scales for each of
    k and v.  Key presence (``k_scale``) is how every consumer detects a
    quantized pool — the same static-dispatch idiom as ``w1_q``."""
    dt = storage_dtype(mode)
    kvh = cfg.kv_heads
    shape = (num_pages, page_size, kvh, cfg.head_dim)

    def s0():
        # fresh array per leaf: the engine DONATES its decode state, and
        # XLA rejects the same buffer appearing at two donated positions
        return jnp.full((num_pages, kvh), neutral_scale(dt), jnp.float32)

    return [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
             "k_scale": s0(), "v_scale": s0()}
            for _ in range(cfg.n_layers)]


def dequantize_pool(q, scale, dtype=jnp.float32):
    """``(P, ps, K, Dh)`` storage × ``(P, K)`` scales → ``dtype`` pool."""
    return (q.astype(jnp.float32) * scale[:, None, :, None]).astype(dtype)


def requantize_pool(f, scale, dtype):
    """Quantize a float pool back into storage against monotone per-page
    per-head absmax scales.  ``scale`` is the pool's CURRENT scale tree;
    the new scale is ``max(scale, amax/qmax)``, so pages whose content
    did not change round-trip byte-identically (see module docstring).
    Returns ``(storage pool, new scales)``."""
    f32 = f.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f32), axis=(1, 3))
    s = jnp.maximum(scale, amax / qmax(dtype))
    return cast_to(f32 / s[:, None, :, None], dtype), s


def kv_itemsize(mode: str | None, model_dtype) -> int:
    """Bytes per stored K/V element under ``mode`` (None = full
    precision at the model's dtype) — the gauge layer's accounting."""
    if mode is None:
        return jnp.dtype(model_dtype).itemsize
    return jnp.dtype(storage_dtype(mode)).itemsize
