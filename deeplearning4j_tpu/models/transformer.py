"""Transformer LM/encoder — the flagship model, explicit-SPMD edition.

Role in the framework (BASELINE.json north star: BERT-base fine-tune at
≥35% MFU on v5e): the reference has no attention models (SURVEY.md §5.7 —
sequence handling tops out at LSTM BPTT), but its *capability obligation* at
modern scale is long-sequence training sharded over a pod.  This module is
the TPU-first design for that: ONE train step, manually sharded with
``shard_map`` over a (dp, sp, tp) mesh, every collective explicit:

- **dp** data parallel: batch sharded; gradient `pmean` after backward.
- **tp** tensor parallel (Megatron-style): attention heads and FFN hidden
  sharded; one `psum` after the attention output projection and one after
  FFN's second matmul (forward); autodiff transposes them into the matching
  backward collectives.
- **sp** sequence/context parallel: sequence sharded; attention runs as
  **ring attention** — K/V blocks rotate around the ``sp`` ring via
  `ppermute` with a flash-style running-softmax (log-sum-exp) accumulator,
  so no device ever materializes the full (T, T) score matrix and sequence
  length scales with the ring size.

Compute is bfloat16 on the MXU with float32 params/accumulators (the
softmax statistics and loss reductions stay f32).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import METRICS
from ..parallel.mesh import DP, SP, TP

Params = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_len: int = 2048
    causal: bool = True              # False = BERT-style bidirectional
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16        # MXU compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True               # jax.checkpoint each block (HBM for FLOPs)
    attention: str = "auto"          # "auto": the fused Pallas kernel on a
    #                                  TPU for the shapes it takes, the XLA
    #                                  ring path otherwise (ops/pallas/
    #                                  attention.attention_candidate) |
    #                                  "ring": always the XLA path |
    #                                  "fused": that kernel, single-shard
    #                                  only.  No production caller sets it:
    #                                  it is how a parity test forces a side
    fused_ln: bool = False           # fuse the mid-block residual+LN seam
    #                                  through ops/pallas/layernorm — one
    #                                  VMEM pass instead of two HBM
    #                                  round-trips; default off, unmeasured
    #                                  on the chip; ROADMAP D1b
    xent_impl: str = "scan"          # "scan" (chunked lax.scan, default) |
    #                                  "blocked" (ops/pallas/xent streaming
    #                                  kernel for ALL chunked cases; the
    #                                  near-prime fallback always streams
    #                                  through the blocked kernel); default
    #                                  off, unmeasured on the chip; ROADMAP
    #                                  D1b
    xent_chunk: int = 2048           # LM-loss token-chunk size; 0 disables.
    #                                  Full (B*T, V) f32 logits are the
    #                                  biggest HBM tensor in training (4.3 GB
    #                                  at batch 64/seq 512/32k vocab);
    #                                  chunking + per-chunk remat streams
    #                                  them through VMEM-sized pieces instead
    n_kv_heads: int | None = None    # GQA/MQA: K/V heads shared by groups of
    #                                  n_heads // n_kv_heads query heads.
    #                                  None (or == n_heads) keeps today's
    #                                  full-attention layout byte-identical;
    #                                  1 is MQA.  Cache shapes (dense rows
    #                                  and page pools) are sized by this, so
    #                                  it divides serving.kv_bytes_per_slot
    #                                  directly (DESIGN.md §20)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        """Effective K/V head count (== n_heads without GQA)."""
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        assert 1 <= kv <= self.n_heads and self.n_heads % kv == 0, (
            f"n_kv_heads={kv} must divide n_heads={self.n_heads}")
        return kv

    def flops_per_token(self) -> float:
        """Approximate training FLOPs per token (fwd+bwd ≈ 6*N params +
        attention term; used for MFU accounting)."""
        n_params = (self.vocab_size * self.d_model
                    + self.n_layers * (4 * self.d_model * self.d_model
                                       + 2 * self.d_model * self.d_ff)
                    + self.max_len * self.d_model)
        attn = self.n_layers * 2 * self.max_len * self.d_model  # per-token qk+av
        return 6.0 * (n_params + attn)


# --------------------------------------------------------------------------- params

def init_params(key, cfg: TransformerConfig) -> Params:
    """Scaled-normal init; qkv packed (D, 3, H, Dh), out proj (H, Dh, D).

    Under GQA (``cfg.kv_heads < n_heads``) the packed ``wqkv`` splits into
    ``wq`` (D, H, Dh) and ``wkv`` (D, 2, Kv, Dh) — a DIFFERENT tree, so
    key-presence dispatch in the forward paths is static at trace time;
    the equal-heads tree (and its RNG draws) stays byte-identical to
    every pre-GQA checkpoint."""
    pd = cfg.param_dtype
    d, h, dh, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    kv = cfg.kv_heads
    keys = jax.random.split(key, cfg.n_layers + 3)

    def norm(k, shape, scale):
        return (scale * jax.random.normal(k, shape)).astype(pd)

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 4)
        if kv == h:
            qkv_leaves = {"wqkv": norm(lk[0], (d, 3, h, dh), d ** -0.5)}
        else:
            qk, kk = jax.random.split(lk[0])
            qkv_leaves = {"wq": norm(qk, (d, h, dh), d ** -0.5),
                          "wkv": norm(kk, (d, 2, kv, dh), d ** -0.5)}
        layers.append({
            "ln1_scale": jnp.ones((d,), pd), "ln1_bias": jnp.zeros((d,), pd),
            **qkv_leaves,
            "wo": norm(lk[1], (h, dh, d), (h * dh) ** -0.5),
            "ln2_scale": jnp.ones((d,), pd), "ln2_bias": jnp.zeros((d,), pd),
            "w1": norm(lk[2], (d, f), d ** -0.5),
            "b1": jnp.zeros((f,), pd),
            "w2": norm(lk[3], (f, d), f ** -0.5),
            "b2": jnp.zeros((d,), pd),
        })
    params = {
        "tok_embed": norm(keys[-3], (cfg.vocab_size, d), 0.02),
        "pos_embed": norm(keys[-2], (cfg.max_len, d), 0.02),
        "final_ln_scale": jnp.ones((d,), pd),
        "final_ln_bias": jnp.zeros((d,), pd),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(keys[-1], (d, cfg.vocab_size), d ** -0.5)
    return params


def param_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpecs per leaf: heads/ffn-hidden sharded over tp, the rest
    replicated (sharded-embedding variants come with the ep axis later)."""
    layer = {
        "ln1_scale": P(), "ln1_bias": P(),
        # GQA trees stay replicated: the shard-offset-aware head-group map
        # tp would need is not implemented (asserted in _block), and GQA's
        # payoff is serving-side cache bytes, not training-side tp
        **({"wqkv": P(None, None, TP, None)} if cfg.kv_heads == cfg.n_heads
           else {"wq": P(), "wkv": P()}),
        "wo": P(TP, None, None),
        "ln2_scale": P(), "ln2_bias": P(),
        "w1": P(None, TP), "b1": P(TP),
        "w2": P(TP, None), "b2": P(),
    }
    specs = {
        "tok_embed": P(), "pos_embed": P(),
        "final_ln_scale": P(), "final_ln_bias": P(),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P()
    return specs


# --------------------------------------------------------------------------- tp boundary ops

# Megatron-style f/g pair: explicit AD-correct boundaries for the tensor-
# parallel branch.  Under ``shard_map(check_vma=False)`` plain `psum` has an
# ambiguous transpose (replicated vs partial cotangents), so each tp branch
# is entered through ``copy_to_tp`` (identity fwd / psum bwd — collects the
# per-head/per-ffn-shard cotangent contributions exactly once) and exited
# through ``reduce_from_tp`` (psum fwd / identity bwd).  With these, local
# `jax.grad` produces full, replica-identical gradients for replicated
# params and correct shard-local gradients for tp-sharded params.

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tp(x, axis):
    return x


def _copy_fwd(x, axis):
    return x, None


def _copy_bwd(axis, _, ct):
    return (lax.psum(ct, axis),)


copy_to_tp.defvjp(_copy_fwd, _copy_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tp(x, axis):
    return lax.psum(x, axis)


def _reduce_fwd(x, axis):
    return lax.psum(x, axis), None


def _reduce_bwd(axis, _, ct):
    return (ct,)


reduce_from_tp.defvjp(_reduce_fwd, _reduce_bwd)


# --------------------------------------------------------------------------- math
#
# ``jax.named_scope`` names the sublayers in the functions training and
# decode share, so a profiler trace names device time by part of the model
# (DESIGN.md §9): embed, layernorm, qkv_proj, attention, attn_out, ffn,
# lm_head_loss (lm_head where only logits are made), kv_gather, kv_scatter.
# ``residual`` names the block's two adds to the stream INSIDE ``attn_out``
# and ``ffn``, where they have always been counted (models/hybrid.py's block
# adds outside every sublayer, so there the name stands alone).
# Scopes are HLO metadata: the compiled program does not change.

@jax.named_scope("layernorm")
def _layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _attend_block(q, k, v, q_pos, k_pos, causal, acc, m, l):
    """One flash-style block update.

    q: (B, Tq, Hl, Dh); k/v: (B, Tk, Hl, Dh); acc: (B, Tq, Hl, Dh) f32;
    m/l: (B, Tq, Hl) running max / denominator (f32).
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        mask = q_pos[None, :, None, None] >= k_pos[None, None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard rows with no valid keys yet (all -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bqhk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def ring_attention(q, k, v, *, n_sp: int, sp_axis: str | None, causal: bool,
                   t_local: int):
    """Blockwise ring attention over the sp axis (Liu et al. style).

    Inside ``shard_map``: each device holds local Q/K/V of t_local tokens;
    K/V rotate ``n_sp`` times via ``ppermute`` while a running-softmax
    accumulates — peak memory O(T_local^2) scores, full-sequence semantics.
    With n_sp == 1 this degenerates to single-block flash attention.
    """
    B, Tq, Hl, Dh = q.shape
    my = lax.axis_index(sp_axis) if sp_axis else 0
    q_pos = my * t_local + jnp.arange(t_local)

    acc = jnp.zeros((B, Tq, Hl, Dh), jnp.float32)
    m = jnp.full((B, Tq, Hl), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, Tq, Hl), jnp.float32)

    def body(i, carry):
        k_blk, v_blk, acc, m, l = carry
        src = (my - i) % n_sp
        k_pos = src * t_local + jnp.arange(t_local)
        acc, m, l = _attend_block(q, k_blk, v_blk, q_pos, k_pos, causal, acc, m, l)
        if n_sp > 1:
            perm = [(j, (j + 1) % n_sp) for j in range(n_sp)]
            k_blk = lax.ppermute(k_blk, sp_axis, perm)
            v_blk = lax.ppermute(v_blk, sp_axis, perm)
        return (k_blk, v_blk, acc, m, l)

    if n_sp > 1:
        # rotate n_sp-1 times; unrolled python loop keeps ppermute count static
        carry = (k, v, acc, m, l)
        for i in range(n_sp):
            carry = body(i, carry)
        _, _, acc, m, l = carry
    else:
        _, _, acc, m, l = body(0, (k, v, acc, m, l))

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


@jax.named_scope("qkv_proj")
def _qkv_proj(lp, h, dt, merged: bool = False):
    """Project normed activations ``h`` (..., D) to ``(q, k, v)`` heads.

    Classic trees carry the packed ``wqkv`` and run the exact einsum the
    pre-GQA code always did (the bitwise-parity path); GQA trees carry
    ``wq``/``wkv`` and produce k/v with ``n_kv_heads`` heads.  The key
    check is static at trace time (same idiom as ``w1_q`` in ``_ffn``).

    ``merged`` (classic trees) runs one matmul each for q, k and v against
    the weight with its head axes merged, and splits the heads afterwards:
    the same numbers, for the fused attention kernel, which merges them
    again.  XLA cancels the two reshapes and lays the matmul's result out
    the way the kernel reads it; a ``(..., H, Dh)`` result of its own it
    lays out otherwise (a 64-wide minor dimension pads to 128) and pays a
    transposing copy per operand, eight a block with the gradients."""
    if merged and "wkv" not in lp:
        w = lp["wqkv"].astype(dt)
        return tuple(
            jnp.einsum("...d,df->...f", h.astype(dt),
                       w[:, i].reshape(w.shape[0], -1)
                       ).reshape(*h.shape[:-1], *w.shape[2:])
            for i in range(3))
    if "wkv" in lp:
        q = jnp.einsum("...d,dhe->...he", h.astype(dt), lp["wq"].astype(dt))
        kv = jnp.einsum("...d,dshe->...she", h.astype(dt),
                        lp["wkv"].astype(dt))
        return q, kv[..., 0, :, :], kv[..., 1, :, :]
    qkv = jnp.einsum("...d,dshe->...she", h.astype(dt), lp["wqkv"].astype(dt))
    return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]


def repeat_kv_heads(x, n_rep: int):
    """Head-group broadcast for GQA: repeat the K/V head axis (always
    axis -2, for both (..., T, K, Dh) caches and (..., K, Dh) tokens) so
    query head ``h`` reads shared head ``h // n_rep``.  ``n_rep == 1``
    returns ``x`` untouched — the bitwise-parity guarantee for classic
    trees."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


@jax.named_scope("ffn")
def _ffn(lp, h, dt):
    """The FFN sublayer body on (..., D) activations — shared verbatim by
    the training ``_block`` and the incremental ``decode_step`` so the two
    paths cannot silently diverge (tp boundaries stay with the caller).

    A serving tree quantized by ``quantize_params_for_decode`` carries
    ``w1_q``/``w2_q`` (int8 + per-channel scales) instead of w1/w2; the
    key check is static at trace time, so training trees compile exactly
    the code they always did."""
    if "w1_q" in lp:
        from ..ops.pallas.matmul_int8 import int8_matmul
        u = int8_matmul(h.astype(dt), lp["w1_q"]).astype(dt)
        u = jax.nn.gelu(u + lp["b1"].astype(dt))
        return int8_matmul(u, lp["w2_q"]).astype(dt)
    u = jnp.einsum("...d,df->...f", h.astype(dt), lp["w1"].astype(dt))
    u = jax.nn.gelu(u + lp["b1"].astype(dt))
    return jnp.einsum("...f,fd->...d", u, lp["w2"].astype(dt))


def _attention_candidate(cfg: TransformerConfig, n_sp: int, t_local: int,
                         n_heads_local: int) -> str | None:
    """The registered ops/pallas attention candidate ``_block`` runs, or
    ``None`` for ``ring_attention``: what ``attention_candidate`` answers
    for this block's shapes.  Dense GQA keeps the XLA path by default (no
    cell has measured it on the kernel)."""
    from ..ops.pallas.attention import attention_candidate
    asked = cfg.attention
    if asked == "auto" and cfg.kv_heads != cfg.n_heads:
        asked = "ring"
    return attention_candidate(t_local, n_heads_local, cfg.head_dim,
                               n_sp=n_sp, asked=asked)


def _block(params, x, cfg: TransformerConfig, n_sp, sp_axis, tp_axis, t_local):
    """One transformer block, tp/sp-aware (runs inside shard_map)."""
    dt = cfg.dtype
    h = _layernorm(x, params["ln1_scale"], params["ln1_bias"])
    if tp_axis:
        h = copy_to_tp(h, tp_axis)
    name = _attention_candidate(cfg, n_sp, t_local, params["wo"].shape[0])
    merged = name is not None       # a kernel reads the heads merged
    q, k, v = _qkv_proj(params, h, dt, merged=merged)
    with jax.named_scope("attention"):
        if k.shape[-2] != q.shape[-2]:
            # GQA head-group broadcast before attention; under tp the local
            # query heads would need a shard-offset-aware group map — not
            # implemented, train GQA models without a tp axis
            assert tp_axis is None, (
                "GQA (n_kv_heads < n_heads) does not shard over tp")
            k = repeat_kv_heads(k, q.shape[-2] // k.shape[-2])
            v = repeat_kv_heads(v, q.shape[-2] // v.shape[-2])
        if name:
            from ..ops.pallas import registry as kernel_registry
            attn = kernel_registry.get("attention", name).fn(
                q, k, v, causal=cfg.causal)
        else:
            attn = ring_attention(q, k, v, n_sp=n_sp, sp_axis=sp_axis,
                                  causal=cfg.causal, t_local=t_local)
    # one VMEM pass for the mid-block residual-add + LayerNorm seam
    # (default off, unmeasured; under tp the unfused path keeps the copy_to_tp
    # placement below untouched)
    fuse_ln = cfg.fused_ln and not tp_axis
    with jax.named_scope("attn_out"):
        wo = params["wo"].astype(dt)
        if merged:  # on both sides of the kernel, as in _qkv_proj
            proj = jnp.einsum("btf,fd->btd",
                              attn.astype(dt).reshape(*attn.shape[:2], -1),
                              wo.reshape(-1, wo.shape[-1]))
        else:
            proj = jnp.einsum("bthe,hed->btd", attn.astype(dt), wo)
        if tp_axis:
            proj = reduce_from_tp(proj, tp_axis)  # partial sums over local heads
        if not fuse_ln:
            with jax.named_scope("residual"):
                x = x + proj.astype(x.dtype)
    if fuse_ln:
        from ..ops.pallas.layernorm import fused_residual_layernorm
        with jax.named_scope("layernorm"):
            x, h2 = fused_residual_layernorm(
                x, proj.astype(x.dtype), params["ln2_scale"],
                params["ln2_bias"])
    else:
        h2 = _layernorm(x, params["ln2_scale"], params["ln2_bias"])
    if tp_axis:
        h2 = copy_to_tp(h2, tp_axis)
    down = _ffn(params, h2, dt)
    if tp_axis:
        down = reduce_from_tp(down, tp_axis)
    with jax.named_scope("ffn"):
        down = down + params["b2"].astype(dt)
        with jax.named_scope("residual"):
            return x + down.astype(x.dtype)


@jax.named_scope("embed")
def embed_local(params, tokens, cfg: TransformerConfig,
                sp_axis: str | None = None) -> jnp.ndarray:
    """Token + position embedding for the local (sp-offset) token shard —
    shared by the plain and pipelined forward paths."""
    B, T = tokens.shape
    my_sp = lax.axis_index(sp_axis) if sp_axis else 0
    pos0 = my_sp * T
    x = jnp.take(params["tok_embed"], tokens, axis=0)
    pos = lax.dynamic_slice_in_dim(params["pos_embed"], pos0, T, axis=0)
    return (x + pos[None]).astype(cfg.dtype)


def _token_xent(h_c, t_c, hd):
    """The tokens' losses, f32, of ``h_c`` (n, D) against the cast head ``hd``
    (D, V)."""
    logits = (h_c.astype(hd.dtype) @ hd).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]


def _recomputed_xent(h3, t2, head, dt):
    """The tokens' losses ``(chunks, chunk)`` f32, chunk by chunk, each
    chunk's logits recomputed when differentiated (``jax.checkpoint``)."""
    hd = head.astype(dt)
    body = jax.checkpoint(lambda h_c, t_c: _token_xent(h_c, t_c, hd))
    return lax.scan(lambda c, inp: (c, body(*inp)), None, (h3, t2))[1]


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_xent(h3, t2, head, w2, dt):
    """``_recomputed_xent`` whose gradients are made while each chunk's
    logits exist: differentiated, the forward scan also computes, under a
    unit cotangent, ``dh`` (a residual in ``dt``) and the head's ``dW``
    (one f32 buffer of the head's shape), and the backward scales them by the
    tokens' cotangent ``g``.  ``dh`` takes any ``g``, token by token; ``dW``
    only one that is the same number for every token, which a mean over full
    batches gives.  Any other ``g`` (a padded batch) recomputes the chunks and
    pulls ``g`` back through them, as ``_recomputed_xent`` differentiated.

    With the tokens' weights ``w2`` (``(chunks, chunk)`` f32; ``None`` is the
    program above, operation for operation) the result is ``(w2 * xent,
    xent)`` and the scan folds the weights into what it stores: ``dW`` sums
    ``h^T (w (softmax - onehot))``, weighted in f32 before the cast to ``dt``,
    and ``dh``'s rows are scaled in f32 as they leave their product.  Both are
    the weighted losses' own, so weights that differ token by token (an exit
    distribution) still leave ``g`` one number and ``dW`` made once.  The
    weights' gradient is ``g * xent``; the second result carries none."""
    xent = _recomputed_xent(h3, t2, head, dt)
    return xent if w2 is None else (w2 * xent, xent)


def _fused_xent_fwd(h3, t2, head, w2, dt):
    hd = head.astype(dt)

    def body(dw, inp):
        h_c, t_c, *w_c = inp
        h_c = h_c.astype(dt)
        # _token_xent's numbers, with the targets' logits gathered BEFORE the
        # cast (the same values): the product then leaves no f32 copy of the
        # chunk's logits behind for the gather alone
        logits = h_c @ hd
        gold = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        # d(losses)/d(logits): rounded where the recompute's backward rounds it
        p = jnp.exp(logits - lse[:, None])
        p = p - (lax.broadcasted_iota(jnp.int32, p.shape, 1) == t_c[:, None])
        if not w_c:
            p = p.astype(dt)
            dw = dw + jnp.dot(h_c.T, p, preferred_element_type=jnp.float32)
            return dw, (lse - gold.astype(jnp.float32), p @ hd.T)
        # dW needs the weights inside its sum over tokens; dh takes them row
        # by row on its way out of the product (chunk x D numbers, not
        # chunk x V)
        w = w_c[0][:, None]
        dw = dw + jnp.dot(h_c.T, (w * p).astype(dt),
                          preferred_element_type=jnp.float32)
        dh_c = w * jnp.dot(p.astype(dt), hd.T, preferred_element_type=jnp.float32)
        return dw, (lse - gold.astype(jnp.float32), dh_c.astype(dt))

    with jax.named_scope("lm_head.fused"):
        dw, (xent, dh) = lax.scan(
            body, jnp.zeros(head.shape, jnp.float32),
            (h3, t2) if w2 is None else (h3, t2, w2))
    if w2 is None:
        return xent, (h3, t2, head, None, dh, dw)
    return (w2 * xent, xent), (h3, t2, head, (w2, xent), dh, dw)


def _fused_xent_bwd(dt, res, g):
    h3, t2, head, weighted, dh, dw = res
    d_w = None
    if weighted is not None:
        w2, xent = weighted
        g = g[0]                    # the unweighted losses carry no gradient
        d_w = g * xent
    g0 = g[0, 0]

    def scaled():
        with jax.named_scope("lm_head.fused"):
            return (g0 * dw).astype(head.dtype)

    def recomputed():
        g_xent = g if weighted is None else g * w2
        with jax.named_scope("lm_head.recompute"):
            return jax.vjp(lambda w: _recomputed_xent(h3, t2, w, dt),
                           head)[1](g_xent)[0]

    with jax.named_scope("lm_head.fused"):
        d_h = (dh * g[..., None]).astype(h3.dtype)
    return d_h, None, lax.cond(jnp.all(g == g0), scaled, recomputed), d_w


_fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


def _per_example_xent(h_flat, t_flat, head, cfg: TransformerConfig,
                      w_flat=None):
    """Per-token cross entropy ``(N,)`` f32 of ``h_flat`` (N, D) against the
    head ``head`` (D, V, cast to ``cfg.dtype`` here), ``cfg.xent_chunk``
    tokens at a time (the largest divisor of N under it) through
    ``_fused_xent``; N tokens or fewer than a chunk at once.  With the tokens'
    weights ``w_flat`` (N,) f32: ``(w_flat * xent, xent)``, the second without
    a gradient."""
    n_tok, d = h_flat.shape
    chunk = cfg.xent_chunk
    if w_flat is not None:
        METRICS.increment("lm_head_loss.path.weighted")
    if not chunk or n_tok <= chunk:
        METRICS.increment("lm_head_loss.path.plain")
        xent = _token_xent(h_flat, t_flat, head.astype(cfg.dtype))
        if w_flat is None:
            return xent
        return w_flat * xent, lax.stop_gradient(xent)
    while n_tok % chunk:
        chunk -= 1
    # counted while tracing, as attention.path.*: the chunked loss makes its
    # gradients in the forward pass
    METRICS.increment("lm_head_loss.path.fused")
    out = _fused_xent(
        h_flat.reshape(-1, chunk, d), t_flat.reshape(-1, chunk), head,
        None if w_flat is None else w_flat.reshape(-1, chunk), cfg.dtype)
    return jax.tree_util.tree_map(lambda a: a.reshape(n_tok), out)


def lm_head_token_loss(params, h, targets, cfg: TransformerConfig,
                       weights=None):
    """Every token's cross entropy ``(B, T)`` f32 of hidden states ``h``
    (B, T, D): what ``lm_head_loss(per_example=True)`` takes its rows' means
    of.  A caller that weights the tokens hands ``weights`` (B, T) f32 in and
    gets ``(weights * xent, xent)``: the first differentiable in ``h``, the
    head and ``weights``, with the weights already inside the gradients the
    chunked loss stores (``_fused_xent``); the second a value beside it that
    carries NO gradient.  The caller names the scope (``lm_head_loss``)."""
    head = (params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"])
    B, T, D = h.shape
    out = _per_example_xent(
        h.reshape(B * T, D), targets.reshape(B * T), head, cfg,
        None if weights is None else weights.reshape(B * T))
    return jax.tree_util.tree_map(lambda a: a.reshape(B, T), out)


@jax.named_scope("lm_head_loss")
def lm_head_loss(params, h, targets, cfg: TransformerConfig,
                 per_example: bool = False) -> jnp.ndarray:
    """Mean token cross entropy of final hidden states against targets
    (tied or separate head) — shared by the plain and pipelined paths.

    When ``cfg.xent_chunk`` divides the local token count, the loss is
    computed as a ``lax.scan`` over token chunks with the chunk body under
    ``jax.checkpoint``: only per-chunk logits (chunk × V) ever exist, and
    the backward recomputes them instead of reading a stored (B·T, V)
    tensor back from HBM.  One extra head matmul (~7% step FLOPs at
    BERT-base shapes) buys an order of magnitude less loss-layer HBM
    traffic — the dominant bandwidth cost of big-vocab training.

    ``per_example=True`` returns each row's mean, ``(B,)``, from the same
    chunks over the WHOLE batch's tokens: what a trainer built with
    ``per_example_loss=True`` takes, so that chunking engages at the batch's
    token count and not at one example's.  Differentiated, that path does
    NOT recompute (``_fused_xent``): the forward scan makes, while a chunk's
    logits exist, the chunk's ``dh`` and the head's ``dW`` under a unit
    cotangent, and stores them (``dh`` (B*T, D) in ``cfg.dtype``, ``dW`` one
    f32 buffer of the head's shape) in place of the cast head; the backward
    scales them.  ``dW`` can be scaled only by one number, so a ``lax.cond``
    takes the stored one when the rows' cotangents are all equal (a mean over
    a full batch) and otherwise recomputes chunk by chunk as the mean path
    does (a padded batch).  A caller whose weights differ token by token
    hands them to ``lm_head_token_loss(weights=...)``: they go into the
    stored gradients, and the cotangent stays one number.  "The backward
    recomputes them" above holds for the mean path and for that fallback
    only."""
    head = (params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"])
    hd = head.astype(cfg.dtype)
    B, T, D = h.shape
    n_tok = B * T
    chunk = cfg.xent_chunk
    if per_example:
        return lm_head_token_loss(params, h, targets, cfg).mean(axis=1)

    def token_xent(h_flat, t_flat, w_flat):
        logits = (h_flat.astype(cfg.dtype) @ hd).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_flat[:, None], axis=-1)[:, 0]
        return ((lse - gold) * w_flat).sum()

    h_flat = h.reshape(n_tok, D)
    t_flat = targets.reshape(n_tok)
    w_flat = jnp.ones((n_tok,), jnp.float32)
    if chunk and n_tok > chunk:
        # largest divisor of n_tok <= chunk, so odd token counts still
        # stream instead of silently falling back to full (B*T, V) logits
        div = chunk
        while n_tok % div:
            div -= 1
        if div >= cfg.xent_chunk // 4 and cfg.xent_impl != "blocked":
            chunk = div
        else:
            # Two ways here: a near-prime token count drives the divisor
            # search down to a tiny chunk (thousands of sequential
            # (chunk, V) matmuls), or ``cfg.xent_impl="blocked"`` opted
            # the whole chunked path in.  Either way the blocked-xent
            # tier streams (N, V) tile-by-tile with internal zero-weight
            # row padding — shape-independent, and on the pallas backend
            # the logits never materialize at all.
            from ..ops import losses
            return losses.blocked_token_xent(
                h_flat.astype(cfg.dtype), hd, t_flat) / n_tok

    if chunk and 1 < chunk < n_tok:
        body_fn = jax.checkpoint(token_xent)

        def body(carry, inp):
            h_c, t_c, w_c = inp
            return carry + body_fn(h_c, t_c, w_c), None

        total, _ = lax.scan(
            body, jnp.zeros((), jnp.float32),
            (h_flat.reshape(-1, chunk, D), t_flat.reshape(-1, chunk),
             w_flat.reshape(-1, chunk)))
        return total / n_tok
    return token_xent(h_flat, t_flat, w_flat) / n_tok


# --------------------------------------------------------------------------- KV-cached decode

def init_decode_cache(cfg: TransformerConfig, batch: int = 1) -> list:
    """Per-layer K/V buffers for incremental decoding: each layer caches
    ``(B, max_len, Kv, Dh)`` keys and values (``Kv = cfg.kv_heads``, ==
    n_heads without GQA); positions beyond the current one stay zero and
    are masked out of the softmax."""
    shape = (batch, cfg.max_len, cfg.kv_heads, cfg.head_dim)
    return [{"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.n_layers)]


@jax.named_scope("embed")
def _embed_at(params, tokens, pos, dt):
    """Token + position embedding of ``tokens`` (N,) at positions ``pos``
    (N,) — the decode paths' twin of :func:`embed_local`."""
    return (jnp.take(params["tok_embed"], tokens, axis=0)
            + jnp.take(params["pos_embed"], pos, axis=0)).astype(dt)


@jax.named_scope("lm_head")
def _lm_head_logits(params, h, cfg: TransformerConfig):
    """f32 vocabulary logits of final hidden states ``h`` (..., D)."""
    dt = cfg.dtype
    if "head_q" in params:
        # int8-quantized serving tree (quantize_params_for_decode): the
        # LM head streams as int8 + per-channel scales, logits f32
        from ..ops.pallas.matmul_int8 import int8_matmul
        return int8_matmul(h.astype(dt), params["head_q"])
    head = (params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"])
    return (h.astype(dt) @ head.astype(dt)).astype(jnp.float32)


def _decode_attend(params, x, valid, write_kv, cfg: TransformerConfig,
                   attend=None):
    """Shared per-row decode arithmetic over already-embedded queries
    ``x`` (N, D): every einsum/softmax below is byte-for-byte the op the
    single-step decode path has always run, only at a different leading
    batch size — the bitwise-parity anchor for the paged and windowed
    variants (DESIGN.md §17).  ``write_kv(layer_idx, k, v) -> (ck, cv)``
    commits the new K/V wherever the caller keeps it (dense row, page
    pool) and returns the ``(N, T, H, Dh)`` view attention reads.
    ``attend(layer_idx, q)`` optionally replaces the gather-read
    attention (the paged-attention kernel hook); numerics then carry that
    candidate's tolerance instead of bitwise parity."""
    dt = cfg.dtype
    scale = cfg.head_dim ** -0.5
    n_rep = cfg.n_heads // cfg.kv_heads
    for li, lp in enumerate(params["layers"]):
        h = _layernorm(x, lp["ln1_scale"], lp["ln1_bias"])
        q, k, v = _qkv_proj(lp, h, dt)                          # (N, H|Kv, Dh)
        ck, cv = write_kv(li, k, v)
        with jax.named_scope("attention"):
            if attend is not None:
                att = attend(li, q)
            else:
                # GQA: broadcast the cached heads up to the query heads at
                # the READ — the cache (and its bytes) stay at n_kv_heads
                ck, cv = repeat_kv_heads(ck, n_rep), repeat_kv_heads(cv, n_rep)
                s = jnp.einsum("bhd,bthd->bht", q, ck,
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid[:, None, :], s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1)
                att = jnp.einsum("bht,bthd->bhd", p.astype(dt), cv,
                                 preferred_element_type=jnp.float32).astype(dt)
        with jax.named_scope("attn_out"):
            proj = jnp.einsum("bhe,hed->bd", att, lp["wo"].astype(dt))
            x = x + proj.astype(x.dtype)
        h2 = _layernorm(x, lp["ln2_scale"], lp["ln2_bias"])
        down = _ffn(lp, h2, dt)
        with jax.named_scope("ffn"):
            down = down + lp["b2"].astype(dt)
            x = x + down.astype(x.dtype)
    h = _layernorm(x, params["final_ln_scale"], params["final_ln_bias"])
    return _lm_head_logits(params, h, cfg)


def decode_step(params, cache, tokens, pos, cfg: TransformerConfig):
    """One incremental decode step: ``tokens`` (B,) are the ids at
    position ``pos`` — a traced scalar (every row at the same depth: the
    ``sample``/``beam_search`` path) or a ``(B,)`` vector of PER-ROW
    positions (the serving slot pool, where every slot decodes at its own
    depth).  Returns ``(logits (B, V) f32, new_cache)``.  O(T·D) per
    token — each layer attends the single new query against its cached
    K/V instead of recomputing the full T×T attention.  Single-device
    path (the tp/sp sharded model trains; decode serves), numerics mirror
    ``_block``: bf16 matmuls, f32 softmax/LN.  The vector-pos path runs
    the same per-row arithmetic as the scalar path (broadcast + vmapped
    row updates), so the two cannot diverge numerically."""
    dt = cfg.dtype
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tokens.shape)  # (B,)
    x = _embed_at(params, tokens, pos_b, dt)                         # (B, D)
    valid = jnp.arange(cfg.max_len)[None, :] <= pos_b[:, None]       # (B, T)
    # per-row cache write: row b's K/V lands at its OWN position pos_b[b]
    upd = jax.vmap(
        lambda c, kv, p: lax.dynamic_update_slice_in_dim(c, kv[None], p, axis=0))
    new_cache: list = []

    def write_kv(li, k, v):
        with jax.named_scope("kv_scatter"):
            ck = upd(cache[li]["k"], k, pos_b)
            cv = upd(cache[li]["v"], v, pos_b)
        new_cache.append({"k": ck, "v": cv})
        return ck, cv

    logits = _decode_attend(params, x, valid, write_kv, cfg)
    return logits, new_cache


def reset_cache_slots(cache, slot_mask) -> list:
    """Zero the K/V rows named by ``slot_mask`` (B,) bool — the serving
    slot pool's eviction hygiene.  A newly admitted sequence's prefill
    rewrites its row before any read, so this is defense-in-depth against
    a stale-KV read ever influencing a later occupant (and makes cache
    state inspectable in tests: an evicted slot is all-zeros)."""
    def wipe(c):
        return jnp.where(slot_mask[:, None, None, None], jnp.zeros_like(c), c)
    return [{"k": wipe(c["k"]), "v": wipe(c["v"])} for c in cache]


# --------------------------------------------------------------------------- paged KV decode

def init_paged_cache(cfg: TransformerConfig, num_pages: int,
                     page_size: int) -> list:
    """Per-layer paged K/V pools: ``(num_pages, page_size, Kv, Dh)`` keys
    and values shared by ALL serving slots, addressed through per-slot
    block tables instead of a dense per-slot row (DESIGN.md §17).  The
    caller typically sizes ``num_pages`` with one extra trash page whose
    index is parked in the block-table rows of inactive slots.  For the
    int8/fp8 storage twin see ``ops.pallas.kv_quant
    .init_quantized_paged_cache`` (DESIGN.md §20)."""
    shape = (num_pages, page_size, cfg.kv_heads, cfg.head_dim)
    return [{"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.n_layers)]


def reset_cache_pages(pages, page_mask) -> list:
    """Zero the physical pages named by ``page_mask`` (P,) bool — the
    paged twin of :func:`reset_cache_slots`: eviction hygiene for pages
    whose refcount just reached zero (never for aliased pages).  A
    quantized pool (``k_scale`` present) additionally resets the wiped
    pages' absmax scales to neutral, so the monotone per-page running max
    restarts from real content for the next occupant."""
    def wipe(c):
        return jnp.where(page_mask[:, None, None, None], jnp.zeros_like(c), c)

    out = []
    for c in pages:
        d = {"k": wipe(c["k"]), "v": wipe(c["v"])}
        if "k_scale" in c:
            from ..ops.pallas import kv_quant
            s0 = jnp.float32(kv_quant.neutral_scale(c["k"].dtype))
            for sk in ("k_scale", "v_scale"):
                d[sk] = jnp.where(page_mask[:, None], s0, c[sk])
        out.append(d)
    return out


def paged_flat_index(block_table, positions, page_size: int):
    """Flatten logical positions to indices into a ``(P*page_size, ...)``
    view of the page pool: ``block_table`` (B, n_pages), ``positions``
    (B, W) → ``bt[b, t // ps] * ps + t % ps`` (B, W).  Page lookups are
    clamped to the table; callers mask out-of-range positions themselves
    (scatters use ``mode="drop"`` sentinels)."""
    n_pages = block_table.shape[1]
    page = jnp.minimum(positions // page_size, n_pages - 1)
    return (jnp.take_along_axis(block_table, page, axis=1) * page_size
            + positions % page_size)


def gather_paged_kv(c, block_table, max_len: int):
    """Materialize one logical ``(B, max_len, H, Dh)`` K/V view from the
    page pool ``c`` (P, ps, H, Dh) through ``block_table`` (B, n_pages).
    The gathered buffer has EXACTLY the dense cache's shape, so running
    ``decode_step``'s attention over it is bitwise the dense computation
    whenever the gathered content matches (the §17 parity argument —
    garbage beyond ``pos`` is masked to -inf and contributes exactly 0)."""
    ps = c.shape[1]
    B = block_table.shape[0]
    t = jnp.broadcast_to(jnp.arange(max_len, dtype=jnp.int32)[None, :],
                         (B, max_len))
    flat = paged_flat_index(block_table, t, ps)
    return c.reshape((-1,) + c.shape[2:])[flat]


@jax.named_scope("kv_gather")
def gather_paged_layer(c, block_table, max_len: int, dtype):
    """Logical ``(B, max_len, Kv, Dh)`` k and v views of ONE layer's page
    pool dict ``c`` — quant-transparent: a float pool gathers exactly as
    :func:`gather_paged_kv` always did (the §17 bitwise path), a
    quantized pool (``k_scale`` present) dequantizes through its per-page
    per-head absmax scales first.  Returns ``(k, v)`` in ``dtype``."""
    if "k_scale" in c:
        from ..ops.pallas import kv_quant
        kf = kv_quant.dequantize_pool(c["k"], c["k_scale"], dtype)
        vf = kv_quant.dequantize_pool(c["v"], c["v_scale"], dtype)
    else:
        kf, vf = c["k"], c["v"]
    return (gather_paged_kv(kf, block_table, max_len),
            gather_paged_kv(vf, block_table, max_len))


@jax.named_scope("kv_scatter")
def scatter_paged_layer(c, flat, k, v) -> dict:
    """Commit token K/V rows ``k``/``v`` (N, Kv, Dh) at flat pool indices
    ``flat`` (N,) into one layer's pool dict ``c`` (out-of-range indices
    drop — the window paths' OOB sentinel).  Float pools scatter exactly
    as before; quantized pools quantize AT THE WRITE (DESIGN.md §20):
    dequantize → scatter → requantize against monotone per-page per-head
    absmax scales, so untouched pages round-trip byte-identically and
    only the written page can re-round.  This jnp path is the parity
    REFERENCE; the streamed ``paged_attention_int8`` kernel is the perf
    path, default off, unmeasured."""
    if "k_scale" not in c:
        return {
            key: c[key].reshape((-1,) + c[key].shape[2:]).at[flat].set(
                val, mode="drop").reshape(c[key].shape)
            for key, val in (("k", k), ("v", v))}
    from ..ops.pallas import kv_quant
    out = {}
    for key, val in (("k", k), ("v", v)):
        skey = key + "_scale"
        f = kv_quant.dequantize_pool(c[key], c[skey], jnp.float32)
        f = f.reshape((-1,) + f.shape[2:]).at[flat].set(
            val.astype(jnp.float32), mode="drop").reshape(f.shape)
        out[key], out[skey] = kv_quant.requantize_pool(
            f, c[skey], c[key].dtype)
    return out


def decode_step_paged(params, pages, block_tables, tokens, pos,
                      cfg: TransformerConfig, attn_fn=None):
    """Paged twin of :func:`decode_step`: K/V live in the shared page
    pool and each row reads/writes through its block-table row.  The new
    K/V is scattered to page ``bt[b, pos // ps]`` BEFORE attending (same
    write-then-read order as the dense path), then attention runs over a
    gather of the row's logical ``[0, max_len)`` K/V — an exactly
    ``(B, max_len)`` buffer through :func:`_decode_attend`, so logits are
    bitwise ``decode_step``'s given equal cache content.  Quantized pools
    (``k_scale`` present) quantize-at-write and dequantize-at-read
    through :func:`scatter_paged_layer`/:func:`gather_paged_layer`;
    numerics then carry the int8-KV agreement tolerance instead of
    bitwise parity.  ``attn_fn`` optionally swaps the gather+softmax read
    for a registry candidate ``(q, k_pages, v_pages, block_tables,
    lengths) -> (B, H, Dh)`` (``(q, k_pages, v_pages, k_scale, v_scale,
    block_tables, lengths)`` for quantized pools — default off,
    unmeasured).  Returns ``(logits (B, V) f32, new_pages)``."""
    dt = cfg.dtype
    ps = pages[0]["k"].shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tokens.shape)  # (B,)
    x = _embed_at(params, tokens, pos_b, dt)
    valid = jnp.arange(cfg.max_len)[None, :] <= pos_b[:, None]
    flat = paged_flat_index(block_tables, pos_b[:, None], ps)[:, 0]      # (B,)
    new_pages: list = []

    def write_kv(li, k, v):
        c2 = scatter_paged_layer(pages[li], flat, k, v)
        new_pages.append(c2)
        if attn_fn is not None:
            return None, None  # the attend hook reads new_pages directly
        return gather_paged_layer(c2, block_tables, cfg.max_len, dt)

    attend = None
    if attn_fn is not None:
        def attend(li, q):
            c2 = new_pages[li]
            if "k_scale" in c2:
                return attn_fn(q, c2["k"], c2["v"], c2["k_scale"],
                               c2["v_scale"], block_tables,
                               pos_b + 1).astype(dt)
            return attn_fn(q, c2["k"], c2["v"], block_tables,
                           pos_b + 1).astype(dt)

    logits = _decode_attend(params, x, valid, write_kv, cfg, attend=attend)
    return logits, new_pages


def decode_window(params, cache, tokens, pos, cfg: TransformerConfig):
    """Speculative verify window: process ``tokens`` (B, W) at positions
    ``pos[b] .. pos[b]+W-1`` in ONE dispatch, returning logits for every
    window position.  Per row this is bitwise identical to W sequential
    ``decode_step`` calls: the window folds into the leading batch dim
    (N = B*W) so every matmul/softmax is the same op the single-step path
    runs (batch-size independence of those ops is what the engine's
    B=1-offline vs B=S parity already rests on), and window position w's
    validity mask admits exactly the K/V a sequential step at ``pos+w``
    would see — all W writes land before any of them is read, and a
    write at position p is masked out of every query with ``pos+w < p``.
    Positions past ``max_len-1`` become dropped scatters (never clamped
    onto a live row).  Returns ``(logits (B, W, V) f32, new_cache)``."""
    dt = cfg.dtype
    B, W = tokens.shape
    T = cfg.max_len
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    wpos = pos_b[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]   # (B, W)
    ok = wpos < T
    pos2 = jnp.minimum(wpos, T - 1).reshape(B * W)
    tok2 = tokens.reshape(B * W)
    x = _embed_at(params, tok2, pos2, dt)
    valid = jnp.arange(T)[None, :] <= pos2[:, None]                   # (N, T)
    row = jnp.arange(B, dtype=jnp.int32)[:, None]
    flat = jnp.where(ok, row * T + wpos, B * T).reshape(B * W)        # drop OOB
    new_cache: list = []

    def write_kv(li, k, v):
        c = cache[li]
        ck = c["k"].reshape((B * T,) + c["k"].shape[2:]).at[flat].set(
            k, mode="drop").reshape(c["k"].shape)
        cv = c["v"].reshape((B * T,) + c["v"].shape[2:]).at[flat].set(
            v, mode="drop").reshape(c["v"].shape)
        new_cache.append({"k": ck, "v": cv})
        ck2 = jnp.broadcast_to(ck[:, None], (B, W) + ck.shape[1:]).reshape(
            (B * W,) + ck.shape[1:])
        cv2 = jnp.broadcast_to(cv[:, None], (B, W) + cv.shape[1:]).reshape(
            (B * W,) + cv.shape[1:])
        return ck2, cv2

    logits = _decode_attend(params, x, valid, write_kv, cfg)
    return logits.reshape(B, W, -1), new_cache


def decode_window_paged(params, pages, block_tables, tokens, pos,
                        cfg: TransformerConfig):
    """Paged twin of :func:`decode_window`: the W window writes scatter
    into the page pool through the block table (out-of-range window
    positions become dropped sentinel scatters), then each window query
    attends a gather of its row's logical K/V — same shapes, same ops,
    same masks as the dense window, so the §17 parity argument carries
    over unchanged.  Returns ``(logits (B, W, V) f32, new_pages)``."""
    dt = cfg.dtype
    B, W = tokens.shape
    T = cfg.max_len
    ps = pages[0]["k"].shape[1]
    n_phys = pages[0]["k"].shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    wpos = pos_b[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]   # (B, W)
    ok = wpos < T
    pos2 = jnp.minimum(wpos, T - 1).reshape(B * W)
    tok2 = tokens.reshape(B * W)
    x = _embed_at(params, tok2, pos2, dt)
    valid = jnp.arange(T)[None, :] <= pos2[:, None]
    flat = jnp.where(ok, paged_flat_index(block_tables, wpos, ps),
                     n_phys * ps).reshape(B * W)                      # drop OOB
    new_pages: list = []

    def write_kv(li, k, v):
        c2 = scatter_paged_layer(pages[li], flat, k, v)
        new_pages.append(c2)
        ck, cv = gather_paged_layer(c2, block_tables, T, dt)
        ck2 = jnp.broadcast_to(ck[:, None], (B, W) + ck.shape[1:]).reshape(
            (B * W,) + ck.shape[1:])
        cv2 = jnp.broadcast_to(cv[:, None], (B, W) + cv.shape[1:]).reshape(
            (B * W,) + cv.shape[1:])
        return ck2, cv2

    logits = _decode_attend(params, x, valid, write_kv, cfg)
    return logits.reshape(B, W, -1), new_pages


def encode_local(params, tokens, cfg: TransformerConfig, *,
                 n_sp: int = 1, sp_axis: str | None = None,
                 tp_axis: str | None = None) -> jnp.ndarray:
    """Final hidden states (B_loc, T_loc, D) for the local token shard —
    runs inside shard_map (or standalone when all axes are trivial)."""
    T = tokens.shape[1]
    x = embed_local(params, tokens, cfg, sp_axis)

    block = _block
    if cfg.remat:
        block = jax.checkpoint(_block, static_argnums=(2, 3, 4, 5, 6))
    for lp in params["layers"]:
        x = block(lp, x, cfg, n_sp, sp_axis, tp_axis, T)

    return _layernorm(x, params["final_ln_scale"], params["final_ln_bias"])


def forward_local(params, tokens, cfg: TransformerConfig, *,
                  n_sp: int = 1, sp_axis: str | None = None,
                  tp_axis: str | None = None) -> jnp.ndarray:
    """Vocabulary logits for the local token shard."""
    x = encode_local(params, tokens, cfg, n_sp=n_sp, sp_axis=sp_axis,
                     tp_axis=tp_axis)
    head = (params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"])
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("btd,dv->btv", x.astype(cfg.dtype),
                            head.astype(cfg.dtype))
        return logits.astype(jnp.float32)


def lm_loss_local(params, tokens, targets, cfg: TransformerConfig, **axes):
    """Mean next-token (or MLM-style given targets) cross entropy on the
    local shard; caller pmean's across dp/sp."""
    h = encode_local(params, tokens, cfg, **axes)
    return lm_head_loss(params, h, targets, cfg)


def init_cls_head(key, cfg: TransformerConfig, n_classes: int):
    """Sequence-classification head (the BERT fine-tune north star): mean
    pooling → dense.  Mean pooling (not [CLS]) so the pooled vector is an
    sp-pmean away from correct under sequence parallelism."""
    w = (cfg.d_model ** -0.5 * jax.random.normal(
        key, (cfg.d_model, n_classes))).astype(cfg.param_dtype)
    return {"w_cls": w, "b_cls": jnp.zeros((n_classes,), cfg.param_dtype)}


def cls_head_specs():
    return {"w_cls": P(), "b_cls": P()}


def cls_loss_local(params, head, tokens, labels, cfg: TransformerConfig, *,
                   n_sp: int = 1, sp_axis: str | None = None,
                   tp_axis: str | None = None):
    """Softmax cross entropy of the pooled classifier on the local shard.

    Pooling: local mean over T_loc, then pmean over sp — equal shard sizes
    make that the exact global sequence mean."""
    x = encode_local(params, tokens, cfg, n_sp=n_sp, sp_axis=sp_axis,
                     tp_axis=tp_axis)
    pooled = x.astype(jnp.float32).mean(axis=1)
    if sp_axis:
        pooled = lax.pmean(pooled, sp_axis)
    logits = pooled @ head["w_cls"].astype(jnp.float32) + head["b_cls"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# --------------------------------------------------------------------------- model facade

class TransformerLM:
    """Flagship trainer: explicit-SPMD train step over a (dp, sp, tp) mesh."""

    def __init__(self, cfg: TransformerConfig, mesh: Mesh | None = None):
        self.cfg = cfg
        self.mesh = mesh
        self._train_step = None
        self._fwd = None
        self._score_fn = None
        self._sample_cache: dict = {}

    # -- single-device --------------------------------------------------
    def init(self, key=None) -> Params:
        return init_params(key if key is not None else jax.random.key(0), self.cfg)

    def forward(self, params, tokens) -> jnp.ndarray:
        if self._fwd is None:
            self._fwd = jax.jit(partial(forward_local, cfg=self.cfg))
        return self._fwd(params, tokens)

    def sample(self, params, prime, length: int, temperature: float = 1.0,
               key=None, kv_cache: bool = False) -> list:
        """Temperature-sampled continuation of ``prime`` (greedy when
        ``temperature <= 0``) — the transformer counterpart of
        ``LSTMNet.sample`` (reference ``LSTM.java`` sampling seam).

        TPU-idiomatic decode: the whole loop is ONE compiled
        ``lax.fori_loop`` over a fixed ``(1, max_len)`` token buffer (no
        per-token dispatch); causality makes the unwritten suffix inert.
        Prime/generation lengths are traced int arguments, so every call
        shares one executable per (mode, kv_cache) pair.

        Two decode paths: the default recomputes the full forward per
        token (O(T²) attention — simplest, exercises the training
        graph); ``kv_cache=True`` decodes incrementally through
        :func:`decode_step` — O(T·D) per token, same numerics class (bf16
        matmuls, f32 softmax), parity-tested against the full path, and
        drawing the SAME RNG stream (the key advances only on generation
        steps, so a given key yields the same continuation either way).

        ``key=None`` defaults to ``jax.random.key(0)`` — DETERMINISTIC,
        like ``LSTMNet.sample``'s ``seed=0`` default; pass distinct keys
        to collect diverse samples."""
        cfg = self.cfg
        assert cfg.causal, "sampling needs a causal LM (cfg.causal=True)"
        P = len(prime)
        assert 1 <= P and P + length <= cfg.max_len, (P, length, cfg.max_len)
        if key is None:
            key = jax.random.key(0)
        greedy = temperature <= 0.0
        fn = self._sample_cache.get((greedy, kv_cache))
        if fn is None:
            def pick(logits, sub, temp):
                if greedy:
                    return jnp.argmax(logits).astype(jnp.int32)
                return jax.random.categorical(sub, logits / temp).astype(
                    jnp.int32)

            if kv_cache:
                def run(params, toks, key, temp, p0, n):
                    cache = init_decode_cache(cfg, 1)

                    def body(i, carry):
                        toks, cache, key = carry
                        logits, cache = decode_step(
                            params, cache, toks[:, i], i, cfg)
                        new_key, sub = jax.random.split(key)
                        # advance the RNG only on GENERATION steps, so the
                        # draw sequence matches the non-cached path (which
                        # never splits during prime prefill)
                        gen = i + 1 >= p0
                        key = jax.random.wrap_key_data(jnp.where(
                            gen, jax.random.key_data(new_key),
                            jax.random.key_data(key)))
                        nxt = pick(logits[0], sub, temp)
                        cur = toks[0, i + 1]
                        toks = toks.at[0, i + 1].set(
                            jnp.where(gen, nxt, cur))
                        return toks, cache, key

                    toks, _, _ = lax.fori_loop(0, p0 + n - 1, body,
                                               (toks, cache, key))
                    return toks
            else:
                def run(params, toks, key, temp, p0, n):
                    def body(i, carry):
                        toks, key = carry
                        pos = p0 - 1 + i
                        logits = forward_local(params, toks, cfg)[0, pos]
                        key, sub = jax.random.split(key)
                        nxt = pick(logits, sub, temp)
                        return toks.at[0, pos + 1].set(nxt), key
                    toks, _ = lax.fori_loop(0, n, body, (toks, key))
                    return toks
            fn = jax.jit(run)
            self._sample_cache[(greedy, kv_cache)] = fn
        toks0 = jnp.zeros((1, cfg.max_len), jnp.int32)
        toks0 = toks0.at[0, :P].set(jnp.asarray(prime, jnp.int32))
        toks = fn(params, toks0, key,
                  jnp.float32(temperature if not greedy else 1.0),
                  jnp.int32(P), jnp.int32(length))
        # sample() returns host tokens by contract; this is the one
        # deliberate end-of-generation pull  # graftlint: disable=HS01
        return [int(t) for t in np.asarray(toks[0, :P + length])]

    def score(self, params, tokens, targets) -> float:
        """Mean token cross entropy (model ``score`` seam, reference
        ``MultiLayerNetwork.score``); ``exp(score)`` is perplexity."""
        if self._score_fn is None:
            cfg = self.cfg
            self._score_fn = jax.jit(
                lambda p, t, y: lm_loss_local(p, t, y, cfg))
        return float(self._score_fn(params, jnp.asarray(tokens),
                                    jnp.asarray(targets)))

    def beam_search(self, params, prime, length: int, beam_width: int = 5
                    ) -> tuple[list, float]:
        """Highest-log-likelihood continuation of ``prime`` — the
        ``LSTM.java`` BeamSearch seam on the flagship.  Returns
        ``(token sequence, total log prob)``.

        The device does the O(W·T·D) work through the KV-cached
        :func:`decode_step` with the beam as the batch axis; the tiny
        top-k bookkeeping (sort W·V scores, reorder W cache rows) runs on
        host per step — beam decode is a quality tool, not a throughput
        path."""
        cfg = self.cfg
        assert cfg.causal, "beam search needs a causal LM (cfg.causal=True)"
        # more beams than vocabulary entries cannot all be distinct
        P, W = len(prime), min(beam_width, cfg.vocab_size)
        assert 1 <= P and P + length <= cfg.max_len, (P, length, cfg.max_len)
        fn = self._sample_cache.get(("beam_step", W))
        if fn is None:
            fn = jax.jit(lambda p, c, t, i: decode_step(p, c, t, i, cfg))
            self._sample_cache[("beam_step", W)] = fn

        toks = jnp.zeros((W, cfg.max_len), jnp.int32)
        toks = toks.at[:, :P].set(jnp.asarray(prime, jnp.int32)[None])
        cache = init_decode_cache(cfg, W)
        for i in range(P - 1):                       # prefill
            _, cache = fn(params, cache, toks[:, i], jnp.int32(i))

        scores = np.zeros(W)
        for i in range(P - 1, P - 1 + length):
            logits, cache = fn(params, cache, toks[:, i], jnp.int32(i))
            logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))  # (W, V)
            if i == P - 1:
                # all beams are identical clones of the prime: branch the
                # top-W tokens from ONE row (else W duplicate beams)
                top = np.argsort(-logp[0])[:W]
                beam_idx, next_toks, scores = np.zeros(W, int), top, logp[0][top]
            else:
                flat = (scores[:, None] + logp).reshape(-1)
                top = np.argsort(-flat)[:W]
                beam_idx, next_toks = np.divmod(top, logp.shape[1])
                scores = flat[top]
            sel = jnp.asarray(beam_idx)
            toks = jnp.take(toks, sel, axis=0).at[:, i + 1].set(
                jnp.asarray(next_toks, jnp.int32))
            cache = jax.tree_util.tree_map(
                lambda c: jnp.take(c, sel, axis=0), cache)

        best = int(np.argmax(scores))
        return ([int(t) for t in np.asarray(toks[best, :P + length])],
                float(scores[best]))

    # -- sharded train step --------------------------------------------
    def _axes(self):
        if self.mesh is None:
            return 1, 1, 1
        s = self.mesh.shape
        return s.get(DP, 1), s.get(SP, 1), s.get(TP, 1)

    @staticmethod
    def _default_tx(lr: float):
        """SGD-with-momentum, the reference's finetune default
        (``BaseOptimizer.java:68-118`` momentum seam)."""
        from ..optimize import transforms as T
        return T.chain(T.momentum(0.9), T.sgd_lr(lr))

    def _is_finetune_tree(self, tree):
        return isinstance(tree, dict) and set(tree.keys()) == {"backbone", "head"}

    def _decay_mask(self, tree):
        """Bool pytree naming the weight-class (decayed) leaves of ``tree``.
        None = the transforms' ndim >= 2 default, which is correct for this
        class's canonical layout; layout-changing subclasses override."""
        return None

    def _specs(self):
        """Param-tree PartitionSpecs for this model's layer layout
        (subclasses with a different layout — the stacked pp pipeline —
        override, and every spec consumer routes through here)."""
        return param_specs(self.cfg)

    def init_opt(self, params, tx=None, lr: float = 1e-3, specs=None):
        """Optimizer state for ``build_train_step``/``build_finetune_step``:
        ``(step_count, tx_state)``, placed onto the mesh with tx-declared
        PartitionSpecs.  Works for both the plain param tree and the
        ``{"backbone", "head"}`` finetune tree (specs inferred; pass
        ``specs`` explicitly for custom trees)."""
        tx = tx if tx is not None else self._default_tx(lr)
        state = (jnp.zeros((), jnp.int32), tx.init(params))
        if self.mesh is None:
            return state
        if specs is None:
            specs = (self.finetune_specs() if self._is_finetune_tree(params)
                     else self._specs())
        return self.place(state, self.opt_specs(tx, specs))

    def opt_specs(self, tx, params_specs=None):
        ps = params_specs if params_specs is not None else self._specs()
        spec_fn = tx.state_spec or (lambda _: ())
        return (P(), spec_fn(ps))

    def _loss_reduce(self, loss, sp_axis):
        """Cross-replica reduction of the reported loss (subclasses with
        extra axes — e.g. the pp pipeline — extend this)."""
        loss = lax.pmean(loss, DP)
        return lax.pmean(loss, SP) if sp_axis else loss

    def _grad_sync(self, specs, sp_axis, tp_axis, include_dp: bool = True):
        """Cross-replica gradient pmean over every axis a param is
        REPLICATED on (dp+sp always; tp for tp-replicated leaves).
        ``include_dp=False`` leaves dp to the caller (the ZeRO-1 path
        reduce-scatters over dp instead)."""

        def sync(g, spec):
            if include_dp:
                g = lax.pmean(g, DP)
            if sp_axis:
                g = lax.pmean(g, SP)
            sharded_on_tp = any(ax == TP for ax in spec if ax is not None)
            if tp_axis and not sharded_on_tp:
                g = lax.pmean(g, TP)
            return g

        return lambda grads: jax.tree_util.tree_map(
            sync, grads, specs, is_leaf=lambda x: isinstance(x, P))

    # -- ZeRO-1 weight-update sharding over dp --------------------------
    #
    # Instead of pmean-ing full gradients and updating replicated optimizer
    # state on every dp rank, each rank owns 1/n_dp of every (tp-local)
    # parameter: gradients reduce-scatter over dp, the transform updates
    # only the local chunk (optimizer memory / n_dp — the XLA
    # weight-update-sharding / ZeRO-1 design), and updated params
    # all-gather back.  State leaves are encoded globally as
    # (T, n_dp * chunk) with spec P(TP|None, DP): T = n_tp for tp-sharded
    # params (their chunks differ per tp rank), else 1.

    @staticmethod
    def _z1_chunk(size: int, n_dp: int) -> int:
        return -(-size // n_dp)

    def _z1_leaf_is_tp_sharded(self, spec) -> bool:
        return any(ax == TP for ax in spec if ax is not None)

    def _z1_template_and_specs(self, params, specs):
        """(zeros template for tx.init, matching PartitionSpecs)."""
        n_dp, _, n_tp = self._axes()

        def template(p, spec):
            tp_sharded = self._z1_leaf_is_tp_sharded(spec) and n_tp > 1
            local_size = int(np.prod(p.shape))
            if tp_sharded:
                local_size //= n_tp
            k = self._z1_chunk(local_size, n_dp)
            return jnp.zeros((n_tp if tp_sharded else 1, n_dp * k), p.dtype)

        def spec_of(p, spec):
            tp_sharded = self._z1_leaf_is_tp_sharded(spec) and n_tp > 1
            return P(TP if tp_sharded else None, DP)

        is_p = lambda x: isinstance(x, P)
        tmpl = jax.tree_util.tree_map(template, params, specs, is_leaf=is_p)
        tspec = jax.tree_util.tree_map(spec_of, params, specs, is_leaf=is_p)
        return tmpl, tspec

    def init_opt_zero1(self, params, tx, specs=None):
        """Optimizer state with ZeRO-1 layout for
        ``build_train_step(..., zero1=True)``: every stateful-transform
        leaf holds only this dp-rank's parameter chunk."""
        assert self.mesh is not None, "zero1 requires a mesh"
        if specs is None:
            specs = (self.finetune_specs() if self._is_finetune_tree(params)
                     else self._specs())
        tmpl, _ = self._z1_template_and_specs(params, specs)
        state = (jnp.zeros((), jnp.int32), tx.init(tmpl))
        return self.place(state, self.opt_specs_zero1(tx, specs))

    def opt_specs_zero1(self, tx, params_specs=None, params=None):
        """Placement specs for a ZeRO-1 ``(count, tx_state)`` tree — the
        checkpoint-restore counterpart of ``init_opt_zero1`` (restore host
        arrays, then ``place(opt, model.opt_specs_zero1(tx))``).  For a
        finetune run pass the restored ``{"backbone", "head"}`` ``params``
        (or explicit ``params_specs``) so the spec tree matches."""
        if params_specs is None:
            params_specs = (self.finetune_specs()
                            if params is not None
                            and self._is_finetune_tree(params)
                            else self._specs())
        spec_fn = tx.state_spec or (lambda _: ())
        return (P(), spec_fn(self._z1_state_specs(params_specs)))

    def _z1_state_specs(self, specs):
        """ZeRO-1 state PartitionSpecs derivable from param specs alone
        (the step builder has no params in hand)."""
        n_tp = self._axes()[2]

        def spec_of(spec):
            tp_sharded = self._z1_leaf_is_tp_sharded(spec) and n_tp > 1
            return P(TP if tp_sharded else None, DP)

        return jax.tree_util.tree_map(
            spec_of, specs, is_leaf=lambda x: isinstance(x, P))

    def _z1_scatter_gather(self):
        """(scatter grads -> local chunks, slice params -> local chunks,
        gather updated chunks -> full params) closures for local_step."""
        n_dp = self._axes()[0]

        def scatter(g):
            flat = g.reshape(-1).astype(jnp.float32)
            k = self._z1_chunk(flat.size, n_dp)
            flat = jnp.pad(flat, (0, n_dp * k - flat.size))
            return lax.psum_scatter(flat, DP, scatter_dimension=0,
                                    tiled=True) / n_dp

        def pslice(p):
            flat = p.reshape(-1)
            k = self._z1_chunk(flat.size, n_dp)
            flat = jnp.pad(flat, (0, n_dp * k - flat.size))
            my = lax.axis_index(DP)
            return lax.dynamic_slice(flat, (my * k,), (k,))

        def gather(chunk, p):
            full = lax.all_gather(chunk, DP, tiled=True)
            return full[:int(np.prod(p.shape))].reshape(p.shape).astype(p.dtype)

        return scatter, pslice, gather

    def _build_step(self, tx, loss_of, specs, data_specs, zero1: bool = False):
        """Shared step builder: ``loss_of(tree, *data, axes)`` differs per
        objective; everything else (grad, cross-replica sync, transform
        chain, shard_map wrapper) is identical.  Replaces the reference's
        ``Solver``→``BaseOptimizer.optimize`` dispatch for the flagship."""
        from ..optimize import transforms as Tmod
        from ..optimize.transforms import apply_updates
        n_dp, n_sp, n_tp = self._axes()

        if self.mesh is None:
            assert not zero1, "zero1 requires a mesh with a dp axis"
            def simple(tree, opt, *data):
                count, tx_state = opt
                loss, g = jax.value_and_grad(
                    lambda t: loss_of(t, *data, axes={}))(tree)
                with Tmod.decay_mask_override(self._decay_mask(tree)):
                    updates, tx_state = tx.update(g, tx_state, tree, count)
                tree = apply_updates(tree, updates)
                return tree, (count + 1, tx_state), loss
            return jax.jit(simple, donate_argnums=(0, 1))

        sp_axis = SP if n_sp > 1 else None
        tp_axis = TP if n_tp > 1 else None
        axes = dict(n_sp=n_sp, sp_axis=sp_axis, tp_axis=tp_axis)

        if zero1:
            assert n_dp > 1, "zero1 needs a dp axis to shard state over"
            spec_fn = tx.state_spec or (lambda _: ())
            opt_spec = (P(), spec_fn(self._z1_state_specs(specs)))
            # dp is handled by reduce-scatter below; only the replication
            # axes (sp, and tp for tp-replicated leaves) pmean here
            sync = self._grad_sync(specs, sp_axis, tp_axis, include_dp=False)
            scatter, pslice, gather = self._z1_scatter_gather()
            tmap = jax.tree_util.tree_map

            def local_step(tree, opt, *data):
                count, tx_state = opt
                loss, grads = jax.value_and_grad(
                    lambda t: loss_of(t, *data, axes=axes))(tree)
                loss = self._loss_reduce(loss, sp_axis)
                grads = sync(grads)
                gch = tmap(scatter, grads)
                pch = tmap(pslice, tree)
                st = tmap(lambda s: s[0], tx_state)     # (1, k) -> (k,)
                # chunking flattened every param to 1-D, so the ndim >= 2
                # decay default would silently drop weight decay — name the
                # weight-class leaves from the UNchunked tree instead
                mask = self._decay_mask(tree)
                if mask is None:
                    mask = Tmod.decay_leaf_mask(tree)
                with Tmod.decay_mask_override(mask):
                    updates, st = tx.update(gch, st, pch, count)
                tx_state = tmap(lambda s: s[None], st)
                pch = apply_updates(pch, updates)
                tree = tmap(gather, pch, tree)
                return tree, (count + 1, tx_state), loss
        else:
            opt_spec = self.opt_specs(tx, specs)
            sync = self._grad_sync(specs, sp_axis, tp_axis)

            def local_step(tree, opt, *data):
                count, tx_state = opt
                loss, grads = jax.value_and_grad(
                    lambda t: loss_of(t, *data, axes=axes))(tree)
                loss = self._loss_reduce(loss, sp_axis)
                grads = sync(grads)
                with Tmod.decay_mask_override(self._decay_mask(tree)):
                    updates, tx_state = tx.update(grads, tx_state, tree, count)
                tree = apply_updates(tree, updates)
                return tree, (count + 1, tx_state), loss

        smapped = shard_map(
            local_step, mesh=self.mesh,
            in_specs=(specs, opt_spec) + data_specs,
            out_specs=(specs, opt_spec, P()),
            check_vma=False,
        )
        return jax.jit(smapped, donate_argnums=(0, 1))

    def build_train_step(self, tx=None, lr: float = 1e-3, zero1: bool = False):
        """LM train step over any ``GradientTransform`` (default: the
        reference's SGD+momentum).  Returns
        ``step(params, opt, tokens, targets) -> (params, opt, loss)`` where
        ``opt = (step_count, tx_state)``.  ``zero1=True`` shards optimizer
        state over dp (pair with ``init_opt_zero1``)."""
        cfg = self.cfg
        tx = tx if tx is not None else self._default_tx(lr)

        def loss_of(params, tokens, targets, axes):
            return lm_loss_local(params, tokens, targets, cfg, **axes)

        return self._build_step(tx, loss_of, self._specs(),
                                (P(DP, SP), P(DP, SP)), zero1=zero1)

    # -- BERT-style sequence-classification fine-tune -------------------
    def init_finetune(self, key, n_classes: int, params=None):
        """(backbone, head) combined tree for ``build_finetune_step``."""
        backbone = params if params is not None else self.init(key)
        head = init_cls_head(jax.random.fold_in(key, 1), self.cfg, n_classes)
        tree = {"backbone": backbone, "head": head}
        return self.place(tree, self.finetune_specs()) if self.mesh else tree

    def finetune_specs(self):
        return {"backbone": self._specs(), "head": cls_head_specs()}

    def build_finetune_step(self, tx=None, lr: float = 2e-5,
                            zero1: bool = False):
        """Classifier fine-tune step (north star: BERT-base fine-tune).
        ``step(tree, opt, tokens, labels) -> (tree, opt, loss)`` with
        ``tree = {"backbone": ..., "head": ...}``.  ``zero1=True`` shards
        optimizer state over dp (pair with ``init_opt_zero1``)."""
        cfg = self.cfg
        tx = tx if tx is not None else self._default_tx(lr)

        def loss_of(tree, tokens, labels, axes):
            return cls_loss_local(tree["backbone"], tree["head"], tokens,
                                  labels, cfg, **axes)

        return self._build_step(tx, loss_of, self.finetune_specs(),
                                (P(DP, SP), P(DP)), zero1=zero1)

    def fit(self, params, opt, batches, *, tx=None, lr: float = 1e-3,
            epochs: int = 1, finetune: bool = False,
            checkpoint_manager=None, checkpoint_every: int = 0,
            resume: bool = True):
        """Convenience training loop with auto-checkpoint/resume.

        ``batches``: list of (tokens, targets|labels) pairs.  Runs to
        ``epochs * len(batches)`` total steps counted by the optimizer's
        step counter, so a restored state continues where it left off.
        Checkpoints carry params + full transform state + data cursor
        (exceeds the reference's bare-params ``ModelSavingActor.java:75-79``).
        """
        tx = tx if tx is not None else self._default_tx(lr)
        step_fn = (self.build_finetune_step(tx) if finetune
                   else self.build_train_step(tx))
        specs = self.finetune_specs() if finetune else self._specs()

        if (checkpoint_manager is not None and resume
                and checkpoint_manager.latest_step() is not None):
            r = checkpoint_manager.restore(params, tstate_template=opt)
            params, opt = r["params"], r["tstate"]
            if self.mesh is not None:
                params = self.place(params, specs)
                opt = self.place(opt, self.opt_specs(tx, specs))

        def save():
            checkpoint_manager.save(int(opt[0]), params, tstate=opt,
                                    data_cursor=int(opt[0]))

        losses = []
        start = int(opt[0])
        total = epochs * len(batches)
        # double-buffered host->device staging: the device_put of batch k+1
        # overlaps the step on batch k (async transfers), resuming from the
        # checkpointed cursor
        from ..datasets.iterator import prefetch_to_device
        feed = (batches[k % len(batches)] for k in range(start, total))
        done = 0  # host-side mirror of opt[0]: reading it back would sync
        for a, b in prefetch_to_device(feed, size=2):
            params, opt, loss = step_fn(params, opt, a, b)
            losses.append(loss)  # stays on device; resolved once below
            done += 1
            if (checkpoint_manager is not None and checkpoint_every > 0
                    and (start + done) % checkpoint_every == 0):
                save()  # CheckpointManager.save fences params/opt itself
        losses = [float(l) for l in jax.block_until_ready(losses)]
        if checkpoint_manager is not None and losses:
            save()
        return params, opt, losses

    def place(self, tree, specs=None):
        """Device-put a pytree onto the mesh per param_specs."""
        if self.mesh is None:
            return tree
        specs = specs if specs is not None else self._specs()
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))

    def init_opt_momentum(self, params, lr: float = 1e-3):
        """Convenience: opt state for the default SGD+momentum transform."""
        return self.init_opt(params, self._default_tx(lr))
