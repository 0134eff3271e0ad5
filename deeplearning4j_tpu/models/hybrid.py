"""Models whose layers are (mixer, ffn) pairs chosen per layer (DESIGN D5),
run once or several times over the same weights.

``models/transformer.py`` hard-codes one block (learned positions, biased
LayerNorm, GELU FFN, equal heads).  Here a layer is ``x + mixer(norm(x))``
then ``h + ffn(norm(h))`` with RMSNorm, no biases and no position table, and
the two halves are specs looked up in ``MIXERS`` / ``FFNS``: a spec is a
frozen dataclass that knows how to make its parameters, under which key of
the layer they live (``key``), whether its output is normed once more before
it joins the residual (``post_norm``: the sandwich layer), and how to run.
Any mixer goes with any ffn.  The trunk (embedding, ``lm_head_loss`` with its
chunked cross entropy, dtypes, ``remat``) is the dense model's:
``HybridConfig.base`` is a ``TransformerConfig`` and the head's code is
shared, so a change to the head or to the ``attention`` registry's kernels
moves every family.  The head is the embedding transposed or, untied,
``params["lm_head"]``.

Four families are here, each with a plain reference whose parameter tree is
the one below (``models/reference/zaya.py``, ``models/reference/ouro.py``,
``models/reference/keye.py``, ``models/reference/joyai.py``).

The ZAYA1 layer (``CCA`` mixer, arXiv:2510.04476; ``MoE`` ffn,
arXiv:2511.17127):

- ``CCA``: attention in a compressed latent — ``H`` query heads and ``G`` KV
  heads of width ``d`` projected straight from the hidden size, two causal
  convolutions over time on q and k, a q-k mean, L2-normed q and k with a
  learned temperature per KV head, rotary positions on part of each head,
  half of the value taken from the previous token.  The scores run through
  the ``attention`` registry's kernel where it takes the shape.
- ``MoE``: a router MLP over ALL ``n_experts``, top-1, no dropped tokens, a
  grouped matmul (``lax.ragged_dot`` over tokens sorted by expert) over the
  ``held`` experts this chip owns.  A token routed to an expert that lives
  elsewhere gets zero from this chip: on one chip the layer runs without its
  exchange, and nothing stands in for the absent chips.

The sparse-attention layer (a learned top-k selection of keys, DeepSeek sparse
attention's "lightning indexer", DeepSeek-V3.2-Exp report; top-k experts
behind a linear router, the Qwen3-MoE family's):

- ``SparseAttention``: grouped-query attention with per-head RMSNorm on q and
  k, restricted query by query to the ``top_k`` earlier keys that a small
  scoring network ranks highest: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`` over ``index_heads`` heads of ``index_dim``, all read from the
  normed hidden state behind a ``stop_gradient``.  The selection is exact
  (the k-th largest score by bisection on the scores' bits, ties to the lower
  position) and a constant of the backward pass.  Both paths compute ``rows``
  queries at a time against the keys so far (at most four key lengths a
  sequence, ``_key_spans``), and on both the selection is XLA's, ``rows x
  keys`` at a time.  On a TPU, for the shapes they take, the kernels of
  ``ops/pallas/sparse_attention.py`` make each chunk's index scores a key
  tile at a time (forward, and backward with the indexer's gradients), are
  handed the chunk's selection and do the scores, the masked softmax, the
  value product and the heads' mean probabilities with one tile of one
  head's scores in VMEM at a time (``_kernel_attend``, a ``custom_vjp``
  whose backward makes the index scores, the selection and the indexer's
  target again, and the attention's forward pass not).  Elsewhere (the CPU,
  other shapes) ``_sparse_chunk`` is the XLA path: a chunk's scores of
  every head, and of every index head, in HBM, each chunk checkpointed.
  Beside its output the mixer yields the indexer's own loss: the
  divergence of the head-summed attention probabilities from the softmax of
  the index scores over the selected keys, which reaches the indexer's
  parameters alone, as the language-model loss reaches everything else alone.
- ``MoE`` with ``top_k > 1`` and ``router_hidden = 0``: a linear softmax
  router, the ``top_k`` largest probabilities renormalised; the (token,
  choice) pairs are sorted ONCE by held expert and the pairs that have an
  expert here run in blocks of sorted rows, the blocks past the last local
  pair skipped (``_pair_blocks``), so that time follows the local share and
  memory stays what one block needs.

The looped layer (Ouro, arXiv:2510.25741):

- ``Attention``: plain causal attention, ``H`` query heads over ``G`` KV
  heads of width ``d``, rotary positions on ``rotary_factor`` of each head
  (the whole head by default), through the same kernel.
- ``GatedMLP``: ``W_down(silu(W_gate u) * (W_up u))``, dense.
- Both with ``post_norm`` (a field of each spec, on by default): ``x +
  N2(mixer(N1(x)))``, ``x + N4(ffn(N3(x)))``.

The latent-attention layer (DeepSeek-V3, arXiv:2412.19437, whose keys
JoyAI-LLM-Flash's config carries):

- ``MLA``: queries out of one normed low-rank latent and keys and values out
  of another (``mla_qkv``); a head's query and key are a part without position
  beside a rotary part (interleaved pairs), the rotary key ONE head that all
  heads share; values of a width of their own.  No kernel takes queries wider
  than values (``attention_candidate(d_v=...)`` answers ``None`` and counts
  ``attention.path.xla``), so the scores run on XLA (``chunked_attend``): one
  example and ``rows`` queries at a time against the keys so far, the
  example and each of its chunks checkpointed, the heads' outputs kept
  across a checkpointed block.
- ``GatedMLP(post_norm=False)`` in the leading layer, then ``MoE`` with
  ``scoring="sigmoid"``, ``bias_rate``, ``scale`` and ``shared_ff``: each
  expert's own sigmoid; the ``top_k`` experts with the largest score PLUS a
  bias per expert chosen, the scores themselves renormalised and scaled their
  weights; one shared expert added to the routed sum (``_with_shared``); the
  choices kept across a checkpointed block by name (``moe.chosen``), so that
  its backward pass reads the forward pass's decisions.  No
  gradient reaches the bias and the optimizer leaves it alone: the loss
  ``lm_loss_and_moves`` hands the trainer, beside the rows' losses, what the
  step adds to it (``bias_moves``: ``bias_rate * sign(mean(count) - count)``
  from the step's own counts; DESIGN.md section 28).
- A prediction module behind the trunk (``HybridConfig.mtp``,
  ``mtp_hidden``): the next token's embedding and the trunk's last state,
  each normed, merged by ``eh_proj``, one more block (index ``len(layers)``
  of ``layer_specs``), a norm, the MAIN model's head.  Its cross entropy of
  the token after the next joins the objective at ``mtp_weight``; both head
  passes are one weighted call of the chunked head (``objective_parts``).

The loop (``HybridConfig.n_loops``, ``exit_beta``): ``encode_steps`` runs the
SAME layers ``n_loops`` times as one traced body (``lax.scan`` over loop steps
around the checkpointed blocks: a step compiles each layer once), the final
norm closing every step, so that the normed state is that step's output and
the next step's input, and hands back every step's state.  With an exit gate
(``exit_beta`` set: one Linear E -> 1 on each step's state)
``looped_lm_loss_per_example`` sends all steps' states through the head's
chunked loss in ONE call, turns the gates into a distribution over the steps
token by token, and weights the steps' cross entropies by it, less
``exit_beta`` times its entropy.  The weights go into the head's call
(``lm_head_token_loss(weights=...)``), so its forward scan makes the weighted
``dh`` and ``dW`` once.  ``n_loops == 1`` without a gate is one plain
pass.

Sublayer names in a trace: ``layernorm``, ``qkv_proj`` (with ``cca.mix``,
``dsa.index_proj`` or ``mla.down``, ``mla.up``, ``mla.rope`` inside),
``attention`` (with ``dsa.index_scores``, ``dsa.select``, ``dsa.index_loss``
or ``mla.attend`` inside), ``attn_out``, ``ffn`` (with ``moe.router``,
``moe.dispatch``, ``moe.experts``, ``moe.shared`` inside), ``embed`` (with
``mtp.merge`` inside), ``optimizer`` (with ``moe.bias_update`` inside: the
counts and the sign rule), ``lm_head_loss`` (with ``loop.exit`` inside: gate,
exit distribution, objective).  Since PR 36 nothing a step traces here is under no name:
``residual`` (the blocks' adds to the stream, a step's state handed on),
``loss_reduce`` (the layers' own losses added to the rows'), ``dsa.attend``
in ``attention`` (the attention over the selection; the kernels' entry
points name it, beside the mask they take, which is ``dsa.select``'s) and
``moe.combine`` in ``ffn`` (the expert layer's shaping, casts and the zeros
its sums start from).  A ``dsa.*``, ``mla.*`` or ``moe.*`` name never
encloses another of its family:
the readers take the first they meet on a path, so that a sublayer's parts
add up.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..observability import METRICS, trace
from .transformer import (TransformerConfig, lm_head_loss,
                          lm_head_token_loss)

Params = Any


def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


# --------------------------------------------------------------------------- mixer

@dataclasses.dataclass(frozen=True)
class CCA:
    """Compressed convolutional attention."""
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    conv_kernels: tuple[int, int] = (2, 2)   # depthwise, then grouped by head
    rope_theta: float = 5_000_000.0
    rotary_factor: float = 0.5
    key = "cca"            # where a layer keeps these parameters
    post_norm = False      # the output joins the residual as it is
    aux_loss = False       # the mixer returns its output alone

    def init(self, key, d_model: int, dtype) -> Params:
        h, g, d = self.n_heads, self.n_kv_heads, self.head_dim
        assert g == 2 and h % g == 0, "the value halves are KV heads 0 and 1"
        k0, k1 = self.conv_kernels
        ks = jax.random.split(key, 7)
        return {
            "wq": _normal(ks[0], (d_model, h * d), d_model ** -0.5, dtype),
            "wk": _normal(ks[1], (d_model, g * d), d_model ** -0.5, dtype),
            "wv1": _normal(ks[2], (d_model, d), d_model ** -0.5, dtype),
            "wv2": _normal(ks[3], (d_model, d), d_model ** -0.5, dtype),
            "conv0": _normal(ks[4], (k0, (h + g) * d), k0 ** -0.5, dtype),
            "conv1": _normal(ks[5], (k1, h + g, d, d), (k1 * d) ** -0.5, dtype),
            "temp": jnp.ones((g,), dtype),
            "wo": _normal(ks[6], (h * d, d_model), (h * d) ** -0.5, dtype),
        }


def _shift(x, n: int):
    """``x`` (B, T, ...) moved ``n`` positions later in time, zeros first."""
    if n == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (n, 0)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def _rope(x, theta: float, rotary: int, interleaved: bool = False):
    """``x`` (B, T, heads, d) f32: rotate the first ``rotary`` features of
    each head by position, feature ``i`` paired with ``i + rotary // 2`` or,
    ``interleaved``, feature ``2i`` with ``2i + 1`` (pair ``i`` turns at the
    same rate either way: the two differ by a permutation of the features)."""
    half = rotary // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-2.0 * math.log(theta) / rotary))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if interleaved:
        pairs = x[..., :rotary].reshape(*x.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return jnp.concatenate(
            [turned.reshape(*x.shape[:-1], rotary), x[..., rotary:]], axis=-1)
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], axis=-1)


def _depthwise_conv(z, w):
    """Causal convolution over time of ``z`` (B, T, C) with one filter
    ``w`` (k, C) a channel; tap ``k - 1`` is the current position."""
    n = w.shape[0]
    return sum(_shift(z, n - 1 - j) * w[j] for j in range(n))


def _grouped_conv(z, w):
    """Causal convolution over time of ``z`` (B, T, G, i) with ``w``
    (k, G, i, o): each group's channels mix among themselves.  f32 out (the
    CPU backend has no batched bf16 x bf16 -> f32 product, so each tap
    leaves in ``z``'s dtype and the taps add up in f32)."""
    n = w.shape[0]
    return sum(jnp.einsum("btgi,gio->btgo", _shift(z, n - 1 - j),
                          w[j]).astype(jnp.float32) for j in range(n))


def _unit(x, scale):
    return x * (scale * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                  + 1e-12))


@jax.named_scope("qkv_proj")
def cca_qkv(spec: CCA, p, u, dt, *, convs=True, qk_mean=True, value_shift=True,
            rotary=True):
    """Normed activations ``u`` (B, T, E) -> ``q (B, T, H, d)``,
    ``k, v (B, T, G, d)`` in ``dt``.  The four switches exist for the tests
    that show each part moves the output; a model runs with all of them on."""
    b, t, _ = u.shape
    h, g, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    u = u.astype(dt)

    def proj(x, w):
        return jnp.einsum("btd,df->btf", x, w.astype(dt),
                          preferred_element_type=jnp.float32)

    q0, k0 = proj(u, p["wq"]), proj(u, p["wk"])               # f32 (B, T, H*d)
    v = jnp.stack([proj(u, p["wv1"]),
                   proj(_shift(u, 1) if value_shift else u, p["wv2"])], axis=2)
    with jax.named_scope("cca.mix"):
        z = jnp.concatenate([q0, k0], axis=-1)                # (B, T, C)
        if convs:
            z = _depthwise_conv(z, p["conv0"].astype(jnp.float32))
            z = _grouped_conv(z.astype(dt).reshape(b, t, h + g, d),
                              p["conv1"].astype(dt)).reshape(b, t, (h + g) * d)
        q = z[..., :h * d].reshape(b, t, h, d)
        k = z[..., h * d:].reshape(b, t, g, d)
        if qk_mean:
            m = (q0.reshape(b, t, h, d)
                 + jnp.repeat(k0.reshape(b, t, g, d), h // g, axis=2)) * 0.5
            q = q + m
            k = k + m.reshape(b, t, g, h // g, d).mean(axis=3)
        q = _unit(q, math.sqrt(d))
        k = _unit(k, math.sqrt(d)) * p["temp"].astype(jnp.float32)[:, None]
        if rotary:
            r = int(spec.rotary_factor * d)
            q, k = _rope(q, spec.rope_theta, r), _rope(k, spec.rope_theta, r)
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _attend(q, k, v):
    """Causal attention of ``q (B, T, H, d)`` over ``k, v (B, T, G, d)``:
    the candidate ``attention_candidate`` answers for the shape (the fused
    kernel on a TPU for the shapes it takes), the XLA path otherwise."""
    from ..ops.pallas import registry as kernel_registry
    from ..ops.pallas.attention import attention_candidate
    from .transformer import repeat_kv_heads, ring_attention

    t, h, d = q.shape[1:]
    k, v = repeat_kv_heads(k, h // k.shape[2]), repeat_kv_heads(v, h // v.shape[2])
    name = attention_candidate(t, h, d)
    if name:
        return kernel_registry.get("attention", name).fn(q, k, v, causal=True)
    return ring_attention(q, k, v, n_sp=1, sp_axis=None, causal=True, t_local=t)


def _attend_and_project(q, k, v, wo, dt):
    """What every mixer ends with: the scores, then the heads through ``wo``."""
    with jax.named_scope("attention"):
        out = _attend(q, k, v)
    with jax.named_scope("attn_out"):
        return jnp.einsum("btf,fd->btd",
                          out.astype(dt).reshape(*out.shape[:2], -1),
                          wo.astype(dt))


def cca_mixer(spec: CCA, p, u, dt, **parts):
    q, k, v = cca_qkv(spec, p, u, dt, **parts)
    return _attend_and_project(q, k, v, p["wo"], dt)


@dataclasses.dataclass(frozen=True)
class Attention:
    """Plain causal attention with rotary positions."""
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1_000_000.0
    rotary_factor: float = 1.0
    post_norm: bool = True  # normed once more before the residual (sandwich)
    key = "attn"
    aux_loss = False

    def init(self, key, d_model: int, dtype) -> Params:
        h, g, d = self.n_heads, self.n_kv_heads, self.head_dim
        assert h % g == 0, "whole groups of query heads a KV head"
        ks = jax.random.split(key, 4)
        return {
            "wq": _normal(ks[0], (d_model, h * d), d_model ** -0.5, dtype),
            "wk": _normal(ks[1], (d_model, g * d), d_model ** -0.5, dtype),
            "wv": _normal(ks[2], (d_model, g * d), d_model ** -0.5, dtype),
            "wo": _normal(ks[3], (h * d, d_model), (h * d) ** -0.5, dtype),
        }


def attention_mixer(spec: Attention, p, u, dt):
    """Normed activations ``u`` (B, T, E) -> the mixer's output (B, T, E)."""
    b, t, _ = u.shape
    with jax.named_scope("qkv_proj"):
        u = u.astype(dt)

        def proj(w, heads):
            return jnp.einsum("btd,df->btf", u, w.astype(dt),
                              preferred_element_type=jnp.float32
                              ).reshape(b, t, heads, spec.head_dim)

        q, k = proj(p["wq"], spec.n_heads), proj(p["wk"], spec.n_kv_heads)
        v = proj(p["wv"], spec.n_kv_heads)
        r = int(spec.rotary_factor * spec.head_dim)
        q, k = _rope(q, spec.rope_theta, r), _rope(k, spec.rope_theta, r)
        q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    return _attend_and_project(q, k, v, p["wo"], dt)


@dataclasses.dataclass(frozen=True)
class SparseAttention:
    """Causal grouped-query attention over the ``top_k`` earlier keys a
    learned indexer ranks highest for each query."""
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10_000_000.0
    qk_norm: bool = True             # RMSNorm on each head of q and k
    index_heads: int = 16
    index_dim: int = 64
    top_k: int = 2048
    q_chunk: int = 512               # with kv_chunk, the tile of dsa.tiles_*
    kv_chunk: int = 512
    rows: int = 256                  # queries either path selects for and
    #                                  attends from at a time (the kernels'
    #                                  tile of queries; the XLA path's chunk)
    norm_eps: float = 1e-6           # of the head norms and the key LayerNorm
    key = "dsa"
    post_norm = False
    aux_loss = True        # the mixer returns (output, its own loss (B,))

    def init(self, key, d_model: int, dtype) -> Params:
        h, g, d = self.n_heads, self.n_kv_heads, self.head_dim
        hi, di = self.index_heads, self.index_dim
        assert h % g == 0, "whole groups of query heads a KV head"
        ks = jax.random.split(key, 7)
        p = {
            "wq": _normal(ks[0], (d_model, h * d), d_model ** -0.5, dtype),
            "wk": _normal(ks[1], (d_model, g * d), d_model ** -0.5, dtype),
            "wv": _normal(ks[2], (d_model, g * d), d_model ** -0.5, dtype),
            "wo": _normal(ks[3], (h * d, d_model), (h * d) ** -0.5, dtype),
            "index": {
                "wq": _normal(ks[4], (d_model, hi * di), d_model ** -0.5, dtype),
                "wk": _normal(ks[5], (d_model, di), d_model ** -0.5, dtype),
                "ww": _normal(ks[6], (d_model, hi), d_model ** -0.5, dtype),
                "k_norm_w": jnp.ones((di,), dtype),
                "k_norm_b": jnp.zeros((di,), dtype),
            },
        }
        if self.qk_norm:
            p["q_norm"], p["k_norm"] = jnp.ones((d,), dtype), jnp.ones((d,), dtype)
        return p


#: what a checkpointed block keeps of a sparse mixer besides its input: the
#: heads' outputs (B, T, H, d), the index loss (B,) and, on the kernel's path,
#: the rows' log-sum-exp (B, chunks, G, rows, H / G) its backward reads; of a
#: latent-attention mixer the heads' outputs (B, T, H, d_v); and of a router
#: that selects on a biased score its choices (N, top_k)
KEPT = ("dsa.out", "dsa.loss", "dsa.lse", "mla.out", "moe.chosen")
#: stands for "not selected" in a row of scores: finite, so that 0 x it is 0
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
#: key lengths a sequence's query chunks are computed at, on both paths:
#: chunk ``c`` needs the keys up to its own end, and a static shape serves a
#: run of chunks (inside a run the kernels skip the key blocks past a chunk's
#: last query; the index scores and the XLA path compute them masked)
_KEY_SPANS = 4


def _project(u, w, dt):
    return jnp.einsum("btd,df->btf", u, w.astype(dt),
                      preferred_element_type=jnp.float32)


@jax.named_scope("dsa.index_proj")
def index_inputs(spec: SparseAttention, p, u, dt):
    """Normed activations ``u`` (B, T, E) -> the indexer's queries ``(B, T,
    index_heads, index_dim)`` and keys ``(B, T, index_dim)`` in ``dt``, and
    its head weights ``(B, T, index_heads)`` f32, all behind a
    ``stop_gradient``: the indexer learns from its own loss alone."""
    b, t, _ = u.shape
    hi, di = spec.index_heads, spec.index_dim
    u = lax.stop_gradient(u).astype(dt)
    qi = _rope(_project(u, p["wq"], dt).reshape(b, t, hi, di),
               spec.rope_theta, di)
    ki = _project(u, p["wk"], dt)
    ki = ki - ki.mean(axis=-1, keepdims=True)
    ki = (ki * lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                         + spec.norm_eps) * p["k_norm_w"] + p["k_norm_b"])
    ki = _rope(ki[:, :, None, :], spec.rope_theta, di)[:, :, 0, :]
    w = _project(u, p["ww"], dt) * (hi ** -0.5 * di ** -0.5)
    return qi.astype(dt), ki.astype(dt), w


@jax.named_scope("dsa.index_scores")
def index_scores(qi, ki, w):
    """``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])``: ``(C, L)`` f32 from
    ``qi (C, J, D)``, ``ki (L, D)`` and ``w (C, J)``; the products take their
    operands as they come and accumulate in float32.  The XLA path's, and
    the kernels' path's where the index kernels do not take the shape: it
    writes every head's ``(C, L)`` pre-activations to HBM, forward and again
    in its ``jax.vjp``.  Where they do (``_chunk_index_scores``), the same
    mathematics runs in ``ops/pallas/sparse_attention.index_scores``."""
    pre = jnp.einsum("tjd,sd->tjs", qi, ki, preferred_element_type=jnp.float32)
    return jnp.sum(w[:, :, None] * jax.nn.relu(pre), axis=1)


def _chunk_index_scores(qi, ki, w, frontier):
    """One chunk's index scores on the attention kernels' path: the index
    kernels where they take the chunk's shape (``index_takes``), zero from
    the key block of ``frontier`` on, else ``index_scores``.  The step's
    forward pass and ``sparse_selection`` come here and its backward pass
    (``_kernel_chunk_grads``) decides alike, so that the three run one
    kernel and select alike, bit for bit."""
    from ..ops.pallas import sparse_attention as kernel

    if kernel.index_takes(qi.shape[0], ki.shape[0], qi.shape[2]):
        return kernel.index_scores(qi, ki, w, frontier)
    return index_scores(qi, ki, w)


@jax.named_scope("dsa.select")
def select_top_k(scores, allowed, count):
    """The ``count[t]`` positions of row ``t`` with the largest ``scores``
    ``(C, L)`` f32 among those ``allowed`` ``(C, L)``, ties to the lower
    position: bool ``(C, L)`` with exactly ``count[t]`` set in row ``t``
    (``1 <= count[t] <=`` the row's allowed positions).  No sort: a float's
    bits, the sign bit flipped (all of them for a negative number), order as
    the floats do, so the ``count``-th largest is found bit by bit, 32 counts
    over the row; among the scores equal to it the first ones make up the
    number."""
    scores = jnp.where(scores == 0, 0.0, scores)      # -0.0 is 0.0's equal
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    keys = jnp.where(allowed, keys, 0)                # under every number's

    def narrow(i, kth):
        trial = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(keys >= trial[:, None], axis=1) >= count
        return jnp.where(enough, trial, kth)

    kth = lax.fori_loop(0, 32, narrow, jnp.zeros(keys.shape[:1], jnp.uint32))
    above, tied = keys > kth[:, None], keys == kth[:, None]
    short = count - jnp.sum(above, axis=1)
    return above | (tied & (jnp.cumsum(tied, axis=1) <= short[:, None]))


@jax.named_scope("dsa.select")
def _chunk_rows(spec: SparseAttention, start, c: int, n_keys: int):
    """For the ``c`` queries from ``start`` on against keys ``0..n_keys``:
    which keys are not later than the query ``(c, n_keys)``, and how many
    each query selects ``(c,)``."""
    tq = start + jnp.arange(c, dtype=jnp.int32)
    causal = jnp.arange(n_keys, dtype=jnp.int32)[None, :] <= tq[:, None]
    return causal, jnp.minimum(tq + 1, spec.top_k)


def _sparse_chunk(spec: SparseAttention, k, v, ki, start, q, qi, w):
    """One chunk of queries ``q (C, H, d)`` (the first at position ``start``)
    against the keys so far ``k, v (L, G, d)``: ``(output (C, H, d), the
    chunk's sum of index losses)``."""
    c, h, d = q.shape
    n_keys, g = k.shape[:2]
    causal, count = _chunk_rows(spec, start, c, n_keys)
    scores = index_scores(qi, ki, w)
    chosen = select_top_k(lax.stop_gradient(scores), causal, count)
    with jax.named_scope("dsa.attend"):
        # (KV head, query, query head of the group, key): the product's own order
        s = jnp.einsum("tgrd,sgd->gtrs", q.reshape(c, g, h // g, d), k,
                       preferred_element_type=jnp.float32) * d ** -0.5
        s = jnp.where(chosen[None, :, None, :], s, _MASKED)
        # exp once, in the values' dtype for the product; the rows' sums divide
        # the product's output (C x H x d numbers, not C x H x L probabilities)
        e = jnp.exp(s - lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
        inv = 1.0 / jnp.sum(e, axis=-1)                       # (G, C, R)
        e = e.astype(v.dtype)
        out = jnp.einsum("gtrs,sgd->tgrd", e, v,
                         preferred_element_type=jnp.float32
                         ) * inv.transpose(1, 0, 2)[..., None]
    with jax.named_scope("dsa.index_loss"):
        # what the heads attend to, summed: the indexer's target, a constant
        target = lax.stop_gradient(jnp.einsum(
            "gtrs,gtr->ts", e, inv, preferred_element_type=jnp.float32) / h)
        loss = _index_loss(scores, chosen, target)
    with jax.named_scope("dsa.attend"):
        return out.reshape(c, h, d).astype(q.dtype), loss


def _index_loss(scores, chosen, target):
    """A chunk's sum over its queries of the divergence of ``target`` (C, L)
    from the softmax of the index ``scores`` over the ``chosen`` keys."""
    logp = jax.nn.log_softmax(jnp.where(chosen, scores, _MASKED), axis=-1)
    return jnp.sum(jnp.where(
        chosen, jax.scipy.special.xlogy(target, target) - target * logp, 0.0))


def _key_spans(spec, t: int):
    """``(chunk rows, [(first chunk, chunks, keys)])``: the sequence cut into
    chunks of ``spec.rows`` queries (one chunk where it does not divide), in
    at most ``_KEY_SPANS`` runs of chunks that share a key length."""
    c = spec.rows if t % spec.rows == 0 else t
    n = t // c
    per = -(-n // min(_KEY_SPANS, n))
    return c, [(a, min(per, n - a), (min(a + per, n)) * c)
               for a in range(0, n, per)]


def _over_chunks(spec, t: int, fn, chunked, whole, scope="dsa.attend"):
    """``fn(*[a[:keys] for a in whole], start, *[a's chunk for a in chunked])``
    for every chunk of queries of one example, in order, one at a time; its
    results stacked over the chunks.  ``scope`` names the cutting and the
    stacking: the part of the mixer they are made for."""
    c, spans = _key_spans(spec, t)
    outs = []
    for first, n, keys in spans:
        rows = slice(first * c, (first + n) * c)
        with jax.named_scope(scope):          # cutting the sequence, and below
            xs = (jnp.arange(first, first + n, dtype=jnp.int32) * c,
                  *(a[rows].reshape(n, c, *a.shape[1:]) for a in chunked))
            held = [a[:keys] for a in whole]
        outs.append(lax.map(lambda x: fn(*held, *x), xs))
    with jax.named_scope(scope):              # putting its chunks together
        return jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *outs)


def sparse_attend(spec: SparseAttention, q, k, v, qi, ki, w, asked="auto"):
    """``q (B, T, H, d)`` over ``k, v (B, T, G, d)`` under the selection the
    indexer's ``qi, ki, w`` make: ``(output (B, T, H, d), index loss (B,))``,
    the loss a mean over the example's positions.  One example and one chunk
    of queries at a time, on the path ``attention_candidate`` answers (counted
    as ``attention.path.*``; ``asked`` as there): the Pallas kernels that take
    the selection, where no score leaves VMEM, or the XLA path, each chunk
    recomputed in the backward pass so that no more than ``rows x T`` scores
    a head are ever held.  Which path the index scores took is counted too,
    per layer per trace: ``dsa.index_path.kernel`` where the index kernels
    take them (``_chunk_index_scores``), ``.xla`` elsewhere."""
    from ..ops.pallas import sparse_attention as kernel
    from ..ops.pallas.attention import attention_candidate

    t, h, d = q.shape[1:]
    c = _key_spans(spec, t)[0]
    on = attention_candidate(t, h, d, asked=asked,
                             selection=(spec.n_kv_heads, c))
    METRICS.increment(
        "dsa.index_path.kernel"
        if on and kernel.index_takes(c, t, spec.index_dim)
        else "dsa.index_path.xla")
    if on:
        return _kernel_attend(spec, q, k, v, qi, ki, w)
    chunk = jax.checkpoint(functools.partial(_sparse_chunk, spec))

    def example(args):
        q, k, v, qi, ki, w = args
        out, loss = _over_chunks(spec, t, chunk, (q, qi, w), (k, v, ki))
        with jax.named_scope("dsa.attend"):
            out = out.reshape(q.shape)
        with jax.named_scope("dsa.index_loss"):
            return out, jnp.sum(loss) / t

    return lax.map(example, (q, k, v, qi, ki, w))


def _kernel_chunk(spec: SparseAttention, k, v, ki, start, q, qi, w):
    """``_sparse_chunk`` on the kernels that take the selection, heads merged
    (``q (C, H*d)``, ``k, v (L, G*d)``): ``(output (C, H*d), the rows'
    log-sum-exp, the chunk's sum of index losses)``."""
    from ..ops.pallas import sparse_attention as kernel

    c, g = q.shape[0], spec.n_kv_heads
    causal, count = _chunk_rows(spec, start, c, k.shape[0])
    scores = _chunk_index_scores(qi, ki, w, start + c)
    chosen = select_top_k(scores, causal, count)
    # the kernels' entry points name their own passes (dsa.index_scores,
    # dsa.attend, dsa.index_loss) beside the mask they take (dsa.select)
    out, lse = kernel.forward(q, k, v, chosen, start + c, kv_heads=g)
    target = kernel.head_mean(q, k, chosen, lse, start + c, kv_heads=g)
    with jax.named_scope("dsa.index_loss"):
        loss = _index_loss(scores, chosen, target)
    return out, lse, loss


def _kernel_chunk_grads(spec: SparseAttention, k, v, ki, carry, x):
    """One chunk's part of the backward pass: ``carry`` holds the f32 sums of
    ``dk, dv (L, G*d)`` and of the index keys' gradient ``dki_t (D, L)``
    (keys on the lanes) over the chunks so far, ``x`` the chunk's ``(start,
    q, qi, w, out, lse, d out, d loss)``; the index scores, the selection and
    the indexer's target are made again, the attention kernel's forward pass
    is not.  Where the index kernels take the chunk (``_chunk_index_scores``)
    their backward adds the chunk's ``d ki`` to ``dki_t`` itself, in f32;
    elsewhere ``jax.vjp`` of ``index_scores`` gives it in ``ki``'s dtype and
    it is added here."""
    from ..ops.pallas import sparse_attention as kernel

    dk, dv, dki_t = carry
    start, q, qi, w, out, lse, d_out, d_loss = x
    c, g = q.shape[0], spec.n_kv_heads
    causal, count = _chunk_rows(spec, start, c, k.shape[0])
    on_kernel = kernel.index_takes(c, k.shape[0], qi.shape[2])
    if on_kernel:
        scores = kernel.index_scores(qi, ki, w, start + c)
    else:
        scores, index_grads = jax.vjp(index_scores, qi, ki, w)
    chosen = select_top_k(scores, causal, count)
    target = kernel.head_mean(q, k, chosen, lse, start + c, kv_heads=g)
    with jax.named_scope("dsa.index_loss"):
        d_scores = d_loss * jax.grad(_index_loss)(scores, chosen, target)
    if on_kernel:
        d_qi, dki_t, d_w = kernel.index_backward(qi, ki, w, d_scores, dki_t,
                                                 start + c)
    else:
        d_qi, d_ki, d_w = index_grads(d_scores)
    d_q, dk, dv = kernel.backward(q, k, v, chosen, out, lse, d_out, dk, dv,
                                  start + c, kv_heads=g)
    if not on_kernel:
        with jax.named_scope("dsa.index_scores"):
            dki_t = dki_t + d_ki.T.astype(jnp.float32)
    return (dk, dv, dki_t), (d_q, d_qi, d_w)


def _merged(x):
    """``(T, heads, d)`` as ``(T, heads * d)``: how the kernels read it."""
    return x.reshape(x.shape[0], -1)


def _kernel_example(spec: SparseAttention, args):
    """One example through ``_kernel_chunk``: ``(output (T, H, d), log-sum-exp
    (chunks, G, rows, H / G), index loss)``."""
    q, k, v, qi, ki, w = args
    t = q.shape[0]
    with jax.named_scope("dsa.attend"):
        q2, k2, v2 = _merged(q), _merged(k), _merged(v)
    out, lse, loss = _over_chunks(
        spec, t, functools.partial(_kernel_chunk, spec), (q2, qi, w), (k2, v2, ki))
    with jax.named_scope("dsa.attend"):
        out = out.reshape(q.shape)
    with jax.named_scope("dsa.index_loss"):
        return out, lse, jnp.sum(loss) / t


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel_attend(spec: SparseAttention, q, k, v, qi, ki, w):
    """``sparse_attend`` on the kernels.  Its backward pass is written out:
    the forward kernel's ``(out, lse)`` are what it keeps (by name, so that a
    checkpointed block keeps them too and recomputes no kernel), and the sums
    over a sequence's chunks of ``dk``, ``dv`` and the index keys' gradient
    are carried in float32 through the kernel's own accumulators."""
    return _kernel_attend_fwd(spec, q, k, v, qi, ki, w)[0]


def _kernel_attend_fwd(spec, q, k, v, qi, ki, w):
    out, lse, loss = lax.map(functools.partial(_kernel_example, spec),
                             (q, k, v, qi, ki, w))
    with jax.named_scope("dsa.attend"):
        out, lse = checkpoint_name(out, KEPT[0]), checkpoint_name(lse, KEPT[2])
    return (out, loss), (q, k, v, qi, ki, w, out, lse)


def _kernel_attend_bwd(spec, kept, cotangents):
    t = kept[0].shape[1]
    c, spans = _key_spans(spec, t)

    def example(args):
        # what is cut, kept and put together here carries the name of the
        # part it is made for: q, k, v, out and their gradients the
        # attention's (dsa.attend), the indexer's the index scores'
        *inputs, out, lse, d_out, d_loss = args
        q, k, v, qi, ki, w = inputs
        with jax.named_scope("dsa.attend"):
            q2, k2, v2, out2, d_out2 = (_merged(a) for a in (q, k, v, out, d_out))
            dk, dv = (jnp.zeros(a.shape, jnp.float32) for a in (k2, v2))
        with jax.named_scope("dsa.index_scores"):
            dki = jnp.zeros(ki.shape[::-1], jnp.float32)       # (D, T)
        per_chunk = []
        for first, n, keys in spans:
            rows = slice(first * c, (first + n) * c)

            def cut(a):
                return a[rows].reshape(n, c, *a.shape[1:])

            with jax.named_scope("dsa.attend"):
                starts = jnp.arange(first, first + n, dtype=jnp.int32) * c
                q_c = cut(q2)
            with jax.named_scope("dsa.index_scores"):
                qi_c, w_c = cut(qi), cut(w)
            with jax.named_scope("dsa.attend"):
                out_c, lse_c = cut(out2), lse[first:first + n]
                d_out_c = d_out2[rows].reshape(n, c, -1)
            with jax.named_scope("dsa.index_loss"):
                d_loss_c = jnp.full((n,), d_loss / t)
            with jax.named_scope("dsa.attend"):
                k_s, v_s = k2[:keys], v2[:keys]
            with jax.named_scope("dsa.index_scores"):
                ki_s = ki[:keys]
            with jax.named_scope("dsa.attend"):
                dk_s, dv_s = dk[:keys], dv[:keys]
            with jax.named_scope("dsa.index_scores"):
                dki_s = dki[:, :keys]
            part, grads = lax.scan(
                functools.partial(_kernel_chunk_grads, spec, k_s, v_s, ki_s),
                (dk_s, dv_s, dki_s),
                (starts, q_c, qi_c, w_c, out_c, lse_c, d_out_c, d_loss_c))
            with jax.named_scope("dsa.attend"):
                dk, dv = dk.at[:keys].set(part[0]), dv.at[:keys].set(part[1])
            with jax.named_scope("dsa.index_scores"):
                dki = dki.at[:, :keys].set(part[2])
            per_chunk.append(grads)
        d_q, d_qi, d_w = zip(*per_chunk)

        def like(a, b):
            return a.reshape(b.shape).astype(b.dtype)

        with jax.named_scope("dsa.attend"):
            d_q = jnp.concatenate(d_q)
        with jax.named_scope("dsa.index_scores"):
            d_qi, d_w = jnp.concatenate(d_qi), jnp.concatenate(d_w)
        with jax.named_scope("dsa.attend"):
            d_q, dk, dv = like(d_q, q), like(dk, k), like(dv, v)
        with jax.named_scope("dsa.index_scores"):
            return d_q, dk, dv, like(d_qi, qi), like(dki.T, ki), like(d_w, w)

    return lax.map(example, (*kept, *cotangents))


_kernel_attend.defvjp(_kernel_attend_fwd, _kernel_attend_bwd)


def sparse_attention_mixer(spec: SparseAttention, p, u, dt):
    """Normed activations ``u`` (B, T, E) -> ``(the mixer's output (B, T, E),
    the indexer's loss (B,))``."""
    b, t, _ = u.shape
    h, g, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    METRICS.increment("dsa.layers")      # per layer per trace, as attention.path
    with jax.named_scope("qkv_proj"):
        x = u.astype(dt)
        q = _project(x, p["wq"], dt).reshape(b, t, h, d)
        k = _project(x, p["wk"], dt).reshape(b, t, g, d)
        v = _project(x, p["wv"], dt).reshape(b, t, g, d)
        if spec.qk_norm:
            q = rms_norm(q, p["q_norm"], spec.norm_eps)      # over each head
            k = rms_norm(k, p["k_norm"], spec.norm_eps)
        q, k = _rope(q, spec.rope_theta, d), _rope(k, spec.rope_theta, d)
        q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
        qi, ki, w = index_inputs(spec, p["index"], u, dt)
    with jax.named_scope("attention"):
        out, loss = sparse_attend(spec, q, k, v, qi, ki, w)
        # kept across a checkpointed block (run_layers' policy): the block's
        # recomputed forward then stops at the chunks' inputs.  On the XLA
        # path each chunk is computed twice (forward, and once more in its own
        # backward); on the kernel's the selection and the indexer's target
        # are, and the attention's forward pass runs once
        with jax.named_scope("dsa.attend"):
            out = checkpoint_name(out, KEPT[0])
        with jax.named_scope("dsa.index_loss"):
            loss = checkpoint_name(loss, KEPT[1])
    with jax.named_scope("attn_out"):
        return jnp.einsum("btf,fd->btd", out.reshape(b, t, h * d),
                          p["wo"].astype(dt)), loss


def sparse_selection(spec: SparseAttention, p, u, dt, reduce=None):
    """What the mixer selects for normed activations ``u`` (B, T, E): bool
    ``(B, T, T)``, row ``t`` its query's keys; or, with ``reduce(chosen (C,
    L), causal (C, L), start)``, that function's results stacked ``(B,
    chunks, ...)`` with no ``T x T`` array made.  The indexer's half of the
    mixer alone, outside any step: for comparisons and counters.  The index
    scores take the path the mixer's would (``attention_candidate``'s
    answer, not counted)."""
    from ..ops.pallas.attention import attention_candidate

    t = u.shape[1]
    on_kernel = attention_candidate(
        t, spec.n_heads, spec.head_dim,
        selection=(spec.n_kv_heads, _key_spans(spec, t)[0]), count=False)
    qi, ki, w = index_inputs(spec, p["index"], u, dt)
    if reduce is not None:            # counted over whole tiles of queries
        spec = dataclasses.replace(spec, rows=spec.q_chunk)

    def chunk(ki, start, qi, w):
        c = qi.shape[0]
        causal, count = _chunk_rows(spec, start, c, ki.shape[0])
        scores = (_chunk_index_scores(qi, ki, w, start + c) if on_kernel
                  else index_scores(qi, ki, w))
        chosen = select_top_k(scores, causal, count)
        if reduce is not None:
            return reduce(chosen, causal, start)
        return jnp.pad(chosen, ((0, 0), (0, t - ki.shape[0])))

    out = lax.map(lambda a: _over_chunks(spec, t, chunk, (a[0], a[2]), (a[1],)),
                  (qi, ki, w))
    return out if reduce is not None else out.reshape(u.shape[0], t, t)


@dataclasses.dataclass(frozen=True)
class MLA:
    """Multi-head latent attention: queries and, together, keys and values
    come up out of normed low-rank latents; a head's query and key are a part
    that carries no position beside a rotary part, the rotary key ONE head
    that every query head shares; the values have a width of their own."""
    n_heads: int = 32
    q_rank: int = 1536               # the queries' latent
    kv_rank: int = 512               # the keys' and values' latent
    nope_dim: int = 128              # of each head's q and k, without position
    rope_dim: int = 64               # of each head's q, and the shared key
    v_dim: int = 128
    rope_theta: float = 10_000.0
    rope_interleave: bool = True     # pairs (2i, 2i + 1), not (i, i + d / 2)
    rows: int = 512                  # queries the XLA path attends from at a time
    norm_eps: float = 1e-6           # of the two latents' norms
    key = "mla"
    post_norm = False
    aux_loss = False

    def init(self, key, d_model: int, dtype) -> Params:
        h, rq, rkv = self.n_heads, self.q_rank, self.kv_rank
        dn, dr, dv = self.nope_dim, self.rope_dim, self.v_dim
        ks = jax.random.split(key, 5)
        return {
            "wqa": _normal(ks[0], (d_model, rq), d_model ** -0.5, dtype),
            "q_norm": jnp.ones((rq,), dtype),
            "wqb": _normal(ks[1], (rq, h * (dn + dr)), rq ** -0.5, dtype),
            "wkva": _normal(ks[2], (d_model, rkv + dr), d_model ** -0.5, dtype),
            "kv_norm": jnp.ones((rkv,), dtype),
            "wkvb": _normal(ks[3], (rkv, h * (dn + dv)), rkv ** -0.5, dtype),
            "wo": _normal(ks[4], (h * dv, d_model), (h * dv) ** -0.5, dtype),
        }


def mla_qkv(spec: MLA, p, u, dt):
    """Normed activations ``u`` (B, T, E) -> the heads' parts in ``dt``:
    ``(q_nope (B, T, H, nope), q_rope (B, T, H, rope), k_nope (B, T, H, nope),
    k_rope (B, T, rope), v (B, T, H, v_dim))``.  The training form: every
    head's keys and values are up-projected, nothing is absorbed into the
    queries.  Each part comes out of its own columns of the up-projection, so
    that a part's gradient goes back into its own product and no cotangent of
    the joined width is ever made."""
    b, t, _ = u.shape
    h, dn = spec.n_heads, spec.nope_dim
    with jax.named_scope("mla.down"):
        x = u.astype(dt)
        cq = rms_norm(_project(x, p["wqa"], dt), p["q_norm"], spec.norm_eps)
        down = _project(x, p["wkva"], dt)                # f32 (B, T, rank + rope)
        ckv = rms_norm(down[..., :spec.kv_rank], p["kv_norm"], spec.norm_eps)
        cq, ckv = cq.astype(dt), ckv.astype(dt)
    with jax.named_scope("mla.up"):
        def up(c, w, columns):
            w = w.reshape(w.shape[0], h, -1)[..., columns].astype(dt)
            return jnp.einsum("btr,rhd->bthd", c, w,
                              preferred_element_type=jnp.float32)

        q_nope, q_rope = (up(cq, p["wqb"], c)
                          for c in (slice(None, dn), slice(dn, None)))
        k_nope, v = (up(ckv, p["wkvb"], c)
                     for c in (slice(None, dn), slice(dn, None)))
    with jax.named_scope("mla.rope"):
        turn = functools.partial(_rope, theta=spec.rope_theta,
                                 rotary=spec.rope_dim,
                                 interleaved=spec.rope_interleave)
        k_rope = turn(down[:, :, None, spec.kv_rank:])[:, :, 0]
        return tuple(a.astype(dt)
                     for a in (q_nope, turn(q_rope), k_nope, k_rope, v))


def mla_heads(q_nope, q_rope, k_nope, k_rope, v):
    """``mla_qkv``'s parts (any leading axes before ``(T, ...)``) joined into
    ``q, k (..., T, H, nope + rope)`` and ``v``: the one rotary key handed to
    every head."""
    k_rope = jnp.broadcast_to(k_rope[..., None, :],
                              (*k_nope.shape[:-1], k_rope.shape[-1]))
    return (jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([k_nope, k_rope], axis=-1), v)


def _causal_chunk(k, v, start, q):
    """One chunk of queries ``q (C, H, d)`` (the first at position ``start``)
    against the keys so far ``k (L, H, d)``, ``v (L, H, d_v)``: ``(C, H,
    d_v)``.  As ``_sparse_chunk``: one exponential, in the values' dtype for
    the product, and the rows' sums divide the product's output."""
    tq = start + jnp.arange(q.shape[0], dtype=jnp.int32)
    causal = jnp.arange(k.shape[0], dtype=jnp.int32)[None, :] <= tq[:, None]
    s = jnp.einsum("thd,shd->hts", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    s = jnp.where(causal[None], s, _MASKED)
    e = jnp.exp(s - lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    inv = 1.0 / jnp.sum(e, axis=-1)                           # (H, C)
    out = jnp.einsum("hts,shd->thd", e.astype(v.dtype), v,
                     preferred_element_type=jnp.float32) * inv.T[..., None]
    return out.astype(q.dtype)


def chunked_attend(spec, operands, scope: str, join=lambda *a: a):
    """Causal attention of ``q (B, T, H, d)`` over ``k (B, T, H, d)`` and
    ``v (B, T, H, d_v)`` on XLA for the shapes no kernel takes (``d_v`` other
    than ``d``, more rows than a kernel holds): one example and ``spec.rows``
    queries at a time against the keys so far (``_key_spans``), each chunk
    recomputed in the backward pass, so that no more than ``rows x T`` scores
    a head are ever held.  ``operands`` are ``(q, k, v)`` or, with ``join``,
    what ``join`` makes one example's ``(q, k, v)`` of (a mixer whose heads
    share a part joins them an example at a time)."""
    chunk = jax.checkpoint(_causal_chunk)

    def example(parts):
        with jax.named_scope(scope):
            q, k, v = join(*parts)
        out = _over_chunks(spec, q.shape[0], chunk, (q,), (k, v), scope=scope)
        with jax.named_scope(scope):
            return out.reshape(q.shape[0], *out.shape[-2:])

    # an example's joined heads and its spans' slices of them are made again
    # in its backward pass, not kept for every example of the batch
    return lax.map(jax.checkpoint(example), tuple(operands))


def mla_mixer(spec: MLA, p, u, dt):
    """Normed activations ``u`` (B, T, E) -> the mixer's output (B, T, E)."""
    from ..ops.pallas.attention import attention_candidate

    b, t, _ = u.shape
    METRICS.increment("mla.layers")      # per layer per trace, as attention.path
    with jax.named_scope("qkv_proj"):
        parts = mla_qkv(spec, p, u, dt)
    with jax.named_scope("attention"), jax.named_scope("mla.attend"):
        # asked for its count (attention.path.xla): no kernel takes queries
        # and keys wider than the values
        if attention_candidate(t, spec.n_heads, spec.nope_dim + spec.rope_dim,
                               d_v=spec.v_dim):
            raise NotImplementedError("latent attention has no kernel path")
        out = chunked_attend(spec, parts, "mla.attend", join=mla_heads)
        # kept across a checkpointed block, as the sparse mixer's: the block's
        # recomputed forward stops at the chunks' inputs
        out = checkpoint_name(out, KEPT[3])
    with jax.named_scope("attn_out"):
        return jnp.einsum("btf,fd->btd", out.reshape(b, t, -1),
                          p["wo"].astype(dt))


# --------------------------------------------------------------------------- ffn

@dataclasses.dataclass(frozen=True)
class MoE:
    """Top-k mixture of gated-SiLU experts behind a router MLP (or, with
    ``router_hidden = 0``, one linear layer).  ``top_k`` decides the dispatch:
    one sort of the tokens at top-1 (``_one_choice``), one sort of the (token,
    choice) pairs run in blocks above it (``_pair_blocks``).  The scores are a
    softmax over the experts or (``scoring="sigmoid"``) each expert's own
    sigmoid; with a ``bias_rate`` the experts are CHOSEN by score plus a bias
    per expert that no gradient reaches (``router["bias"]``, moved by
    ``bias_moves`` at ``bias_rate`` a step; 0: a bias that stays where it is)
    and WEIGHTED by the score alone.
    ``shared_ff`` is the width of one more expert that every token takes."""
    n_experts: int = 16              # the router's width, as published
    held: tuple[int, int] = (0, 8)   # (first, count): this chip's experts
    router_hidden: int = 256
    d_ff: int = 2048
    top_k: int = 1                   # experts a token
    renormalize: bool = False        # the chosen weights divided by their sum
    scoring: str = "softmax"         # or "sigmoid"
    bias_rate: float | None = None   # None: no selection bias; what a step moves it by
    scale: float = 1.0               # the chosen weights times this
    shared_ff: int = 0
    key = "moe"
    post_norm = False

    @property
    def select_bias(self) -> bool:
        return self.bias_rate is not None

    @property
    def router_columns(self) -> tuple[str, ...]:
        """The router's leaves whose LAST axis is the experts: what a
        placement of the experts reorders together."""
        return (("w3" if self.router_hidden else "w"),
                *(("bias",) if self.select_bias else ()))

    def init(self, key, d_model: int, dtype) -> Params:
        r, f, n = self.router_hidden, self.d_ff, self.held[1]
        assert self.top_k > 1 or not self.renormalize, "one weight is its own sum"
        assert self.scoring in ("softmax", "sigmoid")
        ks = jax.random.split(key, 7)
        router = {
            "wd": _normal(ks[0], (d_model, r), d_model ** -0.5, dtype),
            "w1": _normal(ks[1], (r, r), r ** -0.5, dtype),
            "b1": jnp.zeros((r,), dtype),
            "w2": _normal(ks[2], (r, r), r ** -0.5, dtype),
            "b2": jnp.zeros((r,), dtype),
            "w3": _normal(ks[3], (r, self.n_experts), r ** -0.5, dtype),
        } if r else {
            "w": _normal(ks[0], (d_model, self.n_experts), d_model ** -0.5, dtype)}
        if self.select_bias:
            router["bias"] = jnp.zeros((self.n_experts,), dtype)
        p = {
            "router": router,
            "wg": _normal(ks[4], (n, d_model, f), d_model ** -0.5, dtype),
            "wu": _normal(ks[5], (n, d_model, f), d_model ** -0.5, dtype),
            "wdn": _normal(ks[6], (n, f, d_model), f ** -0.5, dtype),
        }
        if self.shared_ff:
            # a stream of its own off the layer's key: an eighth split would
            # redraw every model that has no shared expert
            p["shared"] = GatedMLP(self.shared_ff).init(
                jax.random.fold_in(key, 7),  # graftlint: disable=RNG01
                d_model, dtype)
        return p


@jax.named_scope("moe.router")
def route(spec: MoE, r, u):
    """``u`` (N, E) -> ``(gate f32, e int32)``, each ``(N,)`` for a top-1
    layer and ``(top_k, N)``, one row a choice, above it: the router in
    float32 whatever the compute dtype, so that a near-tie is decided by the
    activations and not by the router's own rounding."""
    hi = lax.Precision.HIGHEST
    u = u.astype(jnp.float32)
    if spec.router_hidden:
        p = jnp.dot(u, r["wd"].astype(jnp.float32), precision=hi)
        a = jax.nn.gelu(jnp.dot(p, r["w1"].astype(jnp.float32), precision=hi)
                        + r["b1"])
        b = jax.nn.gelu(jnp.dot(a, r["w2"].astype(jnp.float32), precision=hi)
                        + r["b2"])
        logits = jnp.dot(b, r["w3"].astype(jnp.float32), precision=hi)
    else:
        logits = jnp.dot(u, r["w"].astype(jnp.float32), precision=hi)
    pi = (jax.nn.sigmoid(logits) if spec.scoring == "sigmoid"
          else jax.nn.softmax(logits, axis=-1))
    # chosen by the biased score, weighted by the score itself
    chosen_by = (pi + lax.stop_gradient(r["bias"].astype(jnp.float32))
                 if spec.select_bias else pi)
    if spec.top_k == 1:
        e = jnp.argmax(chosen_by, axis=-1).astype(jnp.int32)
        gate = jnp.take_along_axis(pi, e[:, None], axis=-1)[:, 0]
        return (gate if spec.scale == 1.0 else gate * spec.scale), e
    if spec.select_bias:
        # kept across a checkpointed block by name: the backward pass reads
        # the choices the forward pass made, and does not decide the near-ties
        # a second time on activations it has made again
        e = checkpoint_name(lax.top_k(chosen_by, spec.top_k)[1], KEPT[4])
        gate = jnp.take_along_axis(pi, e, axis=-1)
    else:
        gate, e = lax.top_k(pi, spec.top_k)
    if spec.renormalize:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    if spec.scale != 1.0:
        gate = gate * spec.scale
    return gate.T, e.T.astype(jnp.int32)


def expert_counts(spec: MoE, e):
    """(Token, choice) pairs per expert over ALL ``n_experts``, from the
    choices ``e``."""
    return jnp.zeros((spec.n_experts,), jnp.int32).at[e.reshape(-1)].add(1)


def _one_choice(spec: MoE, p, u, gate, e, dt):
    """This chip's experts' outputs ``(N, E)`` f32 for tokens ``u (N, E)``
    each sent to ONE expert ``e (N,)`` with weight ``gate (N,)``: tokens are
    sorted by the held expert they chose (those routed elsewhere last), three
    grouped matmuls run over the sorted rows, and the rows go back to their
    places weighted.  No capacity, no dropped token, whatever the imbalance.
    The top-1 layer's whole dispatch; above top-1 ``_pair_blocks`` sorts the
    pairs of all choices at once."""
    n = u.shape[0]
    first, count = spec.held
    with jax.named_scope("moe.dispatch"):
        local = (e >= first) & (e < first + count)
        slot = jnp.where(local, e - first, count)      # elsewhere: sorted last
        order = jnp.argsort(slot, stable=True)
        back = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        sizes = jnp.zeros((count + 1,), jnp.int32).at[slot].add(1)[:count]
        xs = u[order]
    with jax.named_scope("moe.experts"):
        # Rows past the last held group belong to no expert here.  The TPU's
        # ragged product leaves such rows of its result UNWRITTEN (the CPU's
        # zero-fills them), in the backward products too: masked on the way in
        # and on the way out, so that neither pass ever reads them.
        held_rows = (jnp.arange(n) < sizes.sum())[:, None]

        def grouped(x, w):
            out = lax.ragged_dot(jnp.where(held_rows, x, 0), w.astype(dt), sizes,
                                 preferred_element_type=jnp.float32)
            return jnp.where(held_rows, out, 0.0)

        hidden = (jax.nn.silu(grouped(xs, p["wg"]))
                  * grouped(xs, p["wu"])).astype(dt)
        ys = grouped(hidden, p["wdn"])
    with jax.named_scope("moe.dispatch"):
        return ys[back] * jnp.where(local, gate, 0.0)[:, None]


#: rows of sorted (token, choice) pairs that one block of a top-k layer holds.
#: A block that runs costs about a millisecond whatever its rows (its rows'
#: scatter-add, the weight-gradient sums), so few large blocks beat many small
#: ones: 8192 against 4096 and 2048 on a v5e at 16,384 x 8 pairs (PERF.md §6)
PAIR_BLOCK_ROWS = 8192


def _pair_block_shape(pairs: int) -> tuple[int, int]:
    """``(rows a block, blocks)`` for a layer of ``pairs`` (token, choice)
    pairs: whole blocks, the last one padded."""
    rows = min(pairs, PAIR_BLOCK_ROWS)
    return rows, -(-pairs // rows)


def _sorted_pairs(spec: MoE, e, n: int):
    """The (token, choice) pairs of choices ``e (top_k, N)`` sorted ONCE by
    the held expert they chose, those routed elsewhere (and the padding of the
    last block) last, a stable sort as ``_one_choice``'s: ``(pair (blocks,
    rows), token (blocks, rows), ends (count,))``.  ``pair`` indexes the
    flattened ``(top_k, N)`` arrays and ``ends[g]`` is the sorted row past
    held expert ``g``'s last pair, so ``ends[-1]`` pairs have an expert
    here."""
    first, count = spec.held
    rows, blocks = _pair_block_shape(e.size)
    e = e.reshape(-1)
    slot = jnp.where((e >= first) & (e < first + count), e - first, count)
    slot = jnp.pad(slot, (0, blocks * rows - e.size), constant_values=count)
    pair = jnp.argsort(slot, stable=True).astype(jnp.int32)
    sizes = jnp.sum(slot[:, None] == jnp.arange(count), axis=0, dtype=jnp.int32)
    # a padded row reads pair 0's token and weight; it lies past the last pair
    pair = jnp.where(pair < e.size, pair, 0).reshape(blocks, rows)
    return pair, pair % n, jnp.cumsum(sizes)


def _over_blocks(run, init, pair, token, ends):
    """``run(carry, pair (rows,), token (rows,), sizes (count,), live (rows,
    1)) -> carry`` over the blocks of sorted rows in turn: ``sizes`` a block's
    rows of each held expert's group, ``live`` which of its rows are a local
    pair.  A block that starts past the last local pair is skipped, and what
    is carried goes THROUGH the ``cond``: a skipped block touches none of it."""
    rows = pair.shape[1]

    def block(carry, x):
        pair, token, start = x

        def held(carry):
            with jax.named_scope("moe.dispatch"):
                hi = jnp.clip(ends, start, start + rows)
                lo = jnp.clip(jnp.concatenate([ends[:1] * 0, ends[:-1]]),
                              start, start + rows)
                live = (start + jnp.arange(rows) < ends[-1])[:, None]
            return run(carry, pair, token, hi - lo, live)

        return lax.cond(start < ends[-1], held, lambda carry: carry, carry), None

    with jax.named_scope("moe.dispatch"):
        starts = jnp.arange(pair.shape[0], dtype=jnp.int32) * rows
    return lax.scan(block, init, (pair, token, starts))[0]


@jax.named_scope("moe.experts")
def _block_experts(dt, sizes, live, w, xs, gate):
    """A block's rows ``xs (rows, E)`` through their experts (weights ``w``
    already in ``dt``), each weighted by its pair's ``gate (rows,)``: float32
    ``(rows, E)``.  Rows that are no local pair are masked on the way in and
    out, as ``_one_choice`` masks ``held_rows``."""
    def grouped(x, weight):
        out = lax.ragged_dot(jnp.where(live, x, 0), weight, sizes,
                             preferred_element_type=jnp.float32)
        return jnp.where(live, out, 0.0)

    hidden = (jax.nn.silu(grouped(xs, w["wg"]))
              * grouped(xs, w["wu"])).astype(dt)
    return grouped(hidden, w["wdn"]) * jnp.where(live[:, 0], gate, 0.0)[:, None]


@jax.named_scope("moe.experts")
def _in_dtype(experts, dt):
    """The experts' weights cast once a layer and pass, not once a block."""
    return {name: w.astype(dt) for name, w in experts.items()}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _pair_blocks(spec: MoE, dt, experts, u, gate, e):
    """This chip's experts' outputs ``(N, E)`` f32 for tokens ``u (N, E)``
    each sent to ``top_k`` experts ``e (top_k, N)`` with weights ``gate
    (top_k, N)``: the (token, choice) pairs are sorted once by held expert
    (``_sorted_pairs``) and the sorted rows run in blocks of
    ``PAIR_BLOCK_ROWS`` through one ``lax.scan`` (``_over_blocks``).  A block
    gathers its rows of ``u`` by token, runs the three grouped products with
    the group sizes clipped to the block and adds its weighted rows to the
    layer's f32 output at their tokens; a block that starts past the last
    local pair is skipped (``lax.cond`` on the traced count), so what runs
    follows the share of pairs that have an expert HERE.  The scan has every
    block a layer can fill: no capacity, no dropped token, whatever the
    imbalance.  The backward pass is written out as the mirror image over the
    same blocks, each block's forward recomputed in it, and keeps nothing of a
    block but the layer's inputs: ``jax``'s own transpose of the scan would
    add a skipped block's zeros to ``du`` and to the weights' gradients
    OUTSIDE the ``cond`` (2.6 times the time of the layer on a v5e)."""
    return _pair_blocks_fwd(spec, dt, experts, u, gate, e)[0]


def _pair_blocks_fwd(spec, dt, experts, u, gate, e):
    with jax.named_scope("moe.dispatch"):
        pair, token, ends = _sorted_pairs(spec, e, u.shape[0])
        flat = gate.reshape(-1)
    w = _in_dtype(experts, dt)

    def run(acc, pair, token, sizes, live):
        with jax.named_scope("moe.dispatch"):
            xs, g = u[token], flat[pair]
        ys = _block_experts(dt, sizes, live, w, xs, g)
        with jax.named_scope("moe.dispatch"):
            return acc.at[token].add(ys)

    with jax.named_scope("moe.combine"):
        zero = jnp.zeros(u.shape, jnp.float32)
    return (_over_blocks(run, zero, pair, token, ends),
            (experts, u, gate, pair, token, ends))


def _pair_blocks_bwd(spec, dt, kept, dy):
    experts, u, gate, pair, token, ends = kept
    with jax.named_scope("moe.dispatch"):
        flat = gate.reshape(-1)
    w = _in_dtype(experts, dt)

    def run(sums, pair, token, sizes, live):
        du, dgate, dw = sums
        with jax.named_scope("moe.dispatch"):
            xs, g, d_ys = u[token], flat[pair], dy[token]
        _, pull = jax.vjp(
            functools.partial(_block_experts, dt, sizes, live), w, xs, g)
        d_w, d_xs, d_g = pull(d_ys)
        with jax.named_scope("moe.dispatch"):
            du = du.at[token].add(d_xs.astype(jnp.float32))
            dgate = dgate.at[pair].add(d_g)
        with jax.named_scope("moe.experts"):
            dw = {name: dw[name] + d_w[name].astype(jnp.float32) for name in dw}
        return du, dgate, dw

    with jax.named_scope("moe.combine"):
        zeros = (jnp.zeros(u.shape, jnp.float32),
                 jnp.zeros(flat.shape, jnp.float32),
                 {name: jnp.zeros(a.shape, jnp.float32)
                  for name, a in experts.items()})
    du, dgate, dw = _over_blocks(run, zeros, pair, token, ends)
    with jax.named_scope("moe.combine"):
        return ({name: dw[name].astype(a.dtype) for name, a in experts.items()},
                du.astype(u.dtype), dgate.reshape(gate.shape).astype(gate.dtype),
                None)


_pair_blocks.defvjp(_pair_blocks_fwd, _pair_blocks_bwd)


@jax.named_scope("ffn")
def moe_ffn(spec: MoE, p, u, dt):
    """Normed activations ``u`` (B, T, E) -> ``(this chip's part of the
    layer's output (B, T, E), e (B, T) or, above top-1, (B, T, top_k))``.
    Every token of the batch is grouped at once.  A top-1 layer is the one
    choice through ``_one_choice``, with no loop around it; above top-1 the
    (token, choice) pairs are sorted once and the pairs that have an expert
    here run in blocks of sorted rows (``_pair_blocks``).  A choice that lives
    on another chip adds zero here and, above top-1, moves no row."""
    shape = u.shape
    with jax.named_scope("moe.combine"):     # the layer's own shaping and casts
        u = u.reshape(-1, shape[-1]).astype(dt)
    gate, e = route(spec, p["router"], u)
    # counted while tracing, as attention.path.*: which dispatch a layer took
    if spec.top_k == 1:
        METRICS.increment("moe.dispatch.path.choice")
        y = _with_shared(spec, p, u, _one_choice(spec, p, u, gate, e, dt), dt)
        with jax.named_scope("moe.combine"):
            return y.astype(dt).reshape(shape), e.reshape(shape[:-1])
    METRICS.increment("moe.dispatch.path.pairs")
    METRICS.increment("moe.dispatch.blocks", _pair_block_shape(e.size)[1])
    experts = {name: p[name] for name in ("wg", "wu", "wdn")}
    y = _with_shared(spec, p, u, _pair_blocks(spec, dt, experts, u, gate, e), dt)
    with jax.named_scope("moe.combine"):
        return y.astype(dt).reshape(shape), e.T.reshape(*shape[:-1], spec.top_k)


def _with_shared(spec: MoE, p, u, y, dt):
    """The routed experts' sum ``y (N, E)`` f32 plus, where the layer has
    one, the shared expert's output for every token ``u (N, E)``: the same on
    every chip that shares the layer, so counted ONCE when shares are added."""
    if not spec.shared_ff:
        return y
    with jax.named_scope("moe.shared"):
        return y + _gated_silu(p["shared"], u, dt, jnp.float32)


@dataclasses.dataclass(frozen=True)
class GatedMLP:
    """One gated-SiLU MLP for every token."""
    d_ff: int = 5632
    post_norm: bool = True           # as ``Attention.post_norm``
    key = "mlp"

    def init(self, key, d_model: int, dtype) -> Params:
        f = self.d_ff
        ks = jax.random.split(key, 3)
        return {"wg": _normal(ks[0], (d_model, f), d_model ** -0.5, dtype),
                "wu": _normal(ks[1], (d_model, f), d_model ** -0.5, dtype),
                "wdn": _normal(ks[2], (f, d_model), f ** -0.5, dtype)}


@jax.named_scope("ffn")
def gated_mlp(spec: GatedMLP, p, u, dt):
    """Normed activations ``u`` (B, T, E) -> ``(the layer's output, None)``:
    there are no expert choices to report."""
    return _gated_silu(p, u.astype(dt), dt), None


def _gated_silu(p, u, dt, out_dtype=None):
    """``W_down(silu(W_gate u) * (W_up u))`` of ``u (..., E)`` in ``dt``; the
    last product leaves in ``out_dtype`` (``dt`` by default)."""
    def up(w):
        return jnp.einsum("...d,df->...f", u, w.astype(dt),
                          preferred_element_type=jnp.float32)

    hidden = (jax.nn.silu(up(p["wg"])) * up(p["wu"])).astype(dt)
    return jnp.einsum("...f,fd->...d", hidden, p["wdn"].astype(dt),
                      preferred_element_type=out_dtype)


#: spec class -> the function that runs it
MIXERS = {CCA: cca_mixer, Attention: attention_mixer,
          SparseAttention: sparse_attention_mixer, MLA: mla_mixer}
FFNS = {MoE: moe_ffn, GatedMLP: gated_mlp}


# --------------------------------------------------------------------------- model

@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """``base`` carries what the trunk shares with the dense model
    (``vocab_size``, ``d_model``, ``dtype``, ``param_dtype``, ``remat``,
    ``xent_chunk``, ``xent_impl``, ``tie_embeddings``; heads, ``d_ff``,
    ``max_len`` and ``causal`` are not read here); ``layers`` one ``(mixer
    spec, ffn spec)`` per layer; ``n_loops`` how many times the layers run
    over the same weights; ``exit_beta`` the weight of the exit
    distribution's entropy in the looped objective, and None for a model
    without an exit gate; ``mtp`` the ``(mixer spec, ffn spec)`` of ONE more
    block behind the trunk that predicts the token after the next
    (``mtp_hidden``), its cross entropy weighted ``mtp_weight`` in the
    objective, and None for a model without it."""
    base: TransformerConfig
    layers: tuple[tuple[Any, Any], ...]
    norm_eps: float = 1e-5
    n_loops: int = 1
    exit_beta: float | None = None
    mtp: tuple[Any, Any] | None = None
    mtp_weight: float = 0.3


def layer_specs(cfg: HybridConfig) -> tuple[tuple[Any, Any], ...]:
    """Every block's ``(mixer, ffn)``: the trunk's layers and, last (index
    ``len(cfg.layers)``), the prediction module's block where there is one."""
    return cfg.layers + ((cfg.mtp,) if cfg.mtp is not None else ())


def layer_path(cfg: HybridConfig, i: int) -> tuple:
    """Where block ``i`` of ``layer_specs`` keeps its parameters."""
    return ("layers", i) if i < len(cfg.layers) else ("mtp", "block")


def layer_params(params, cfg: HybridConfig, i: int):
    a, b = layer_path(cfg, i)
    return params[a][b]


def _init_layer(key, mixer, ffn, d: int, pd) -> Params:
    km, kf = jax.random.split(key)
    lp = {"norm1": jnp.ones((d,), pd), mixer.key: mixer.init(km, d, pd),
          "norm2": jnp.ones((d,), pd), ffn.key: ffn.init(kf, d, pd)}
    for spec, name in ((mixer, "norm1_post"), (ffn, "norm2_post")):
        if spec.post_norm:
            lp[name] = jnp.ones((d,), pd)
    return lp


def init_params(key, cfg: HybridConfig) -> Params:
    pd, d = cfg.base.param_dtype, cfg.base.d_model
    keys = jax.random.split(key, len(cfg.layers) + 1)
    layers = [_init_layer(k, mixer, ffn, d, pd)
              for (mixer, ffn), k in zip(cfg.layers, keys[:-1])]
    params = {"tok_embed": _normal(keys[-1], (cfg.base.vocab_size, d), 0.02, pd),
              "final_norm": jnp.ones((d,), pd), "layers": layers}
    kh, kg = jax.random.split(jax.random.fold_in(keys[-1], 1))
    if not cfg.base.tie_embeddings:
        params["lm_head"] = _normal(kh, (d, cfg.base.vocab_size), d ** -0.5, pd)
    if cfg.exit_beta is not None:
        params["exit_gate"] = {"w": _normal(kg, (d,), d ** -0.5, pd),
                               "b": jnp.zeros((), pd)}
    if cfg.mtp is not None:
        assert cfg.n_loops == 1, "the module reads ONE pass's last state"
        kp, kb = jax.random.split(jax.random.fold_in(keys[-1], 2))
        params["mtp"] = {
            "enorm": jnp.ones((d,), pd), "hnorm": jnp.ones((d,), pd),
            "eh_proj": _normal(kp, (2 * d, d), (2 * d) ** -0.5, pd),
            "block": _init_layer(kb, *cfg.mtp, d, pd),
            "norm": jnp.ones((d,), pd)}
    return params


@jax.named_scope("layernorm")
def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def block(lp, x, cfg: HybridConfig, i: int):
    """Block ``i`` of ``layer_specs``: ``(x + mixer + ffn, the ffn's expert
    choices, the mixer's own loss (B,) or None)``, each half's output normed
    before it is added where its spec says so."""
    mixer, ffn = layer_specs(cfg)[i]
    dt, eps = cfg.base.dtype, cfg.norm_eps
    a = MIXERS[type(mixer)](mixer, lp[mixer.key], rms_norm(x, lp["norm1"], eps), dt)
    a, aux = a if mixer.aux_loss else (a, None)
    if mixer.post_norm:
        a = rms_norm(a, lp["norm1_post"], eps)
    with jax.named_scope("residual"):
        x = x + a
    y, e = FFNS[type(ffn)](ffn, lp[ffn.key], rms_norm(x, lp["norm2"], eps), dt)
    if ffn.post_norm:
        y = rms_norm(y, lp["norm2_post"], eps)
    with jax.named_scope("residual"):
        return x + y, e, aux


def _block_fn(cfg: HybridConfig):
    """``block``, checkpointed where the configuration says so."""
    if not cfg.base.remat:
        return block
    return jax.checkpoint(
        block, static_argnums=(2, 3),
        policy=jax.checkpoint_policies.save_only_these_names(*KEPT))


def run_layers(params, tokens, cfg: HybridConfig):
    """``tokens`` (B, T) -> ``(every loop step's final normed hidden
    (n_loops, B, T, E), [e per layer, each (n_loops, B, T[, top_k]) or None],
    the layers' own losses summed (B,), or None where no mixer has one)``.
    The loop is one ``lax.scan`` whose body holds each layer once;
    ``n_loops == 1`` is the layers in line, with no loop around them."""
    return _run_layers(params, tokens, cfg)[:3]


def _run_layers(params, tokens, cfg: HybridConfig):
    """``run_layers`` and, fourth, the last layer's output BEFORE the final
    norm ``(B, T, E)`` (None under a loop): what a prediction module reads."""
    with jax.named_scope("embed"):
        x = jnp.take(params["tok_embed"], tokens, axis=0).astype(cfg.base.dtype)
    fn = _block_fn(cfg)
    # counted while tracing, as attention.path.*: what one trace of the model
    # holds (layers) against what a step runs (layer applications)
    METRICS.increment("loop.steps", cfg.n_loops)
    METRICS.increment("loop.layer_applications", cfg.n_loops * len(cfg.layers))

    def step(x):
        choices, own = [], None
        for i, lp in enumerate(params["layers"]):
            x, e, aux = fn(lp, x, cfg, i)
            choices.append(e)
            if aux is not None:
                with jax.named_scope("loss_reduce"):
                    own = aux if own is None else own + aux
        return rms_norm(x, params["final_norm"], cfg.norm_eps), choices, own, x

    if cfg.n_loops == 1:
        h, choices, own, last = step(x)
        with jax.named_scope("residual"):       # one step, stacked as a loop's
            return (h[None], [None if e is None else e[None] for e in choices],
                    own, last)

    def body(x, _):
        h, choices, own, _ = step(x)
        return h, (h, choices, own)  # the normed state is the next step's input

    hs, choices, own = lax.scan(body, x, None, length=cfg.n_loops)[1]
    with jax.named_scope("loss_reduce"):
        return hs, choices, None if own is None else own.sum(axis=0), None


def encode_steps(params, tokens, cfg: HybridConfig):
    """``run_layers`` without the layers' own losses: ``(hs, choices)``."""
    return run_layers(params, tokens, cfg)[:2]


def encode(params, tokens, cfg: HybridConfig):
    """``tokens`` (B, T) -> ``(the last loop step's final normed hidden
    (B, T, E), [e per layer])``."""
    hs, choices = encode_steps(params, tokens, cfg)
    with jax.named_scope("residual"):           # the last step's, handed on
        return hs[-1], [None if e is None else e[-1] for e in choices]


def forward(params, tokens, cfg: HybridConfig):
    """f32 logits (B, T, V) over the vocabulary held, of the last loop step."""
    h, _ = encode(params, tokens, cfg)
    head = (params["tok_embed"].T if cfg.base.tie_embeddings
            else params["lm_head"])
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,dv->btv", h.astype(cfg.base.dtype),
                          head.astype(cfg.base.dtype)).astype(jnp.float32)


def mtp_hidden(params, last, targets, cfg: HybridConfig):
    """The prediction module (DeepSeek-V3 section 2.2, depth 1): ``(its normed
    state (B, T, E), its ffn's expert choices)``.  Position ``i`` merges the
    embedding of the NEXT token ``targets[i]`` with the trunk's last state
    ``last[i]`` (before the final norm), each under a norm of its own,
    through ``eh_proj`` (embedding first), runs block ``len(cfg.layers)`` of
    ``layer_specs`` and a norm of its own; the MAIN model's head then reads
    the token after the next from it.  The embedding is the main model's."""
    m, eps, dt = params["mtp"], cfg.norm_eps, cfg.base.dtype
    METRICS.increment("mtp.modules")
    with jax.named_scope("embed"):
        emb = jnp.take(params["tok_embed"], targets, axis=0).astype(dt)
        with jax.named_scope("mtp.merge"):
            both = jnp.concatenate([rms_norm(emb, m["enorm"], eps),
                                    rms_norm(last, m["hnorm"], eps)], axis=-1)
            x = jnp.einsum("btf,fd->btd", both, m["eh_proj"].astype(dt))
    x, e, _ = _block_fn(cfg)(m["block"], x, cfg, len(cfg.layers))
    return rms_norm(x, m["norm"], eps), e


def objective_parts(params, tokens, targets, cfg: HybridConfig):
    """``({"objective", "lm", "own", "mtp"}, choices)``: each example's
    objective ``(B,)``, differentiable, beside its parts (``lm`` the mean
    next-token cross entropy; ``own`` the layers' own losses summed, whose
    gradients never meet the others': a mixer's own loss reads its inputs
    behind a ``stop_gradient``; ``mtp`` the prediction module's mean cross
    entropy over the positions that have a target; None for a part the model
    lacks), and every block's expert choices, ``layer_specs``' order.

    With a prediction module both head passes are ONE call of the chunked
    head over ``2 B`` rows, the objective's weights inside it (``1 / T`` a
    main position; ``mtp_weight / (T - 1)`` a module position and 0 at the
    last one, which has no token after the next): one ``dh``, one ``dW``.
    ``lm`` and ``mtp`` are then values that carry no gradient."""
    hs, choices, own, last = _run_layers(params, tokens, cfg)
    with jax.named_scope("residual"):           # the last step's, handed on
        h = hs[-1]
    if cfg.mtp is None:
        lm = lm_head_loss(params, h, targets, cfg.base, per_example=True)
        with jax.named_scope("loss_reduce"):
            total = lm if own is None else lm + own
        return {"objective": total, "lm": lm, "own": own, "mtp": None}, choices
    h2, e2 = mtp_hidden(params, last, targets, cfg)
    b, t = targets.shape
    with jax.named_scope("lm_head_loss"):
        # the token after the next; the last column wraps and weighs nothing
        later = jnp.roll(targets, -1, axis=1)
        has_target = jnp.arange(t) < t - 1
        weights = jnp.concatenate([
            jnp.full((b, t), 1.0 / t, jnp.float32),
            jnp.broadcast_to(jnp.where(has_target, cfg.mtp_weight / (t - 1), 0.0
                                       ).astype(jnp.float32), (b, t))])
        weighted, xent = lm_head_token_loss(
            params, jnp.concatenate([h, h2]), jnp.concatenate([targets, later]),
            cfg.base, weights=weights)
        lm = xent[:b].mean(axis=1)
        mtp = jnp.sum(jnp.where(has_target, xent[b:], 0.0), axis=1) / (t - 1)
    with jax.named_scope("loss_reduce"):
        total = weighted[:b].sum(axis=1) + weighted[b:].sum(axis=1)
        if own is not None:
            total = total + own
    return ({"objective": total, "lm": lm, "own": own, "mtp": mtp},
            choices + [None if e2 is None else e2[None]])


def loss_parts(params, tokens, targets, cfg: HybridConfig):
    """``(each example's mean cross entropy (B,), the layers' own losses
    summed (B,) or None)``: two parts of the objective whose gradients never
    meet (``objective_parts`` has them all)."""
    parts = objective_parts(params, tokens, targets, cfg)[0]
    return parts["lm"], parts["own"]


def lm_loss_per_example(params, tokens, targets, cfg: HybridConfig):
    """Each example's objective, ``(B,)``: its mean cross entropy, plus the
    layers' own losses where a mixer has one, plus the prediction module's
    weighted cross entropy where there is one, with the whole batch's tokens
    grouped together in the expert layers and chunked together in the head:
    the loss a ``DataParallelTrainer(per_example_loss=True)`` takes."""
    return objective_parts(params, tokens, targets, cfg)[0]["objective"]


def bias_moves(cfg: HybridConfig, choices) -> dict:
    """What one step adds to the leaves its gradient does not reach, ``{leaf
    path: rows' validity (B,) -> array}``: for every expert layer whose
    ``bias_rate`` is not zero, from this step's counts ``c`` of the VALID
    rows' (token, choice) pairs over ALL its experts, ``bias_rate *
    sign(mean(c) - c)`` for its ``router["bias"]`` (the auxiliary-loss-free
    balancing of DeepSeek-V3 section 2.1.2: an expert that got more than its
    share is chosen a little less next step).  The trainer calls each with
    the mask it weighs the rows' losses by, so that the rows a ragged batch
    was padded with count for nothing.  ``choices`` as ``objective_parts``
    gives them: a block's ``(steps, B, T, top_k)``."""
    moves = {}
    for i, ((_, ffn), e) in enumerate(zip(layer_specs(cfg), choices)):
        if e is None or not getattr(ffn, "bias_rate", None):
            continue
        METRICS.increment("moe.bias_updates")    # per layer per trace

        def delta(valid, e=e, ffn=ffn):
            with jax.named_scope("optimizer"), jax.named_scope("moe.bias_update"):
                chose = e[..., None] == jnp.arange(ffn.n_experts)
                c = jnp.sum(chose & valid[None, :, None, None, None],
                            axis=(0, 1, 2, 3), dtype=jnp.float32)
                return ffn.bias_rate * jnp.sign(jnp.mean(c) - c)

        moves["/".join(map(str, (*layer_path(cfg, i), ffn.key, "router",
                                 "bias")))] = delta
    return moves


def lm_loss_and_moves(params, tokens, targets, cfg: HybridConfig):
    """``(lm_loss_per_example, bias_moves)`` of one pass: the loss a
    ``DataParallelTrainer(per_example_loss=True)`` takes for a model some of
    whose leaves a step moves by a rule of their own."""
    parts, choices = objective_parts(params, tokens, targets, cfg)
    return parts["objective"], bias_moves(cfg, choices)


def lm_loss(params, tokens, targets, cfg: HybridConfig):
    """Mean cross entropy over the batch."""
    per = lm_loss_per_example(params, tokens, targets, cfg)
    with jax.named_scope("loss_reduce"):
        return per.mean()


def exit_distribution(gate, hs):
    """Every loop step's state ``hs`` (n, B, T, E) -> ``log p`` (n, B, T)
    f32, the distribution over the steps at which a token leaves the loop:
    ``lambda_t = sigmoid(w . h_t + b)``, ``p_t = lambda_t prod_{s<t} (1 -
    lambda_s)`` for ``t < n`` and the rest of the mass at the last step (whose
    own gate is not read).  In logarithms, so that a gate near 0 or 1 leaves
    the entropy's ``p log p`` finite."""
    z = jnp.sum(hs.astype(jnp.float32) * gate["w"].astype(jnp.float32),
                axis=-1) + gate["b"].astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)    # log S_1..S_{n-1}
    before = jnp.concatenate([jnp.zeros_like(z[:1]), stay[:-1]])
    return jnp.concatenate([jax.nn.log_sigmoid(z[:-1]) + before, stay[-1:]])


def looped_losses(params, tokens, targets, cfg: HybridConfig):
    """``(objective (B, T), cross entropy of every loop step (n, B, T), log of
    the exit distribution (n, B, T))``, all f32, token by token: the
    objective is ``sum_t p_t xent_t - exit_beta H(p)``.  The steps' states go
    through the head's chunked loss as one call over ``n x B x T`` tokens: one
    scan, one ``dh``, one ``dW``.  The exit distribution comes from the gate
    on each step's state, so it is known before the head runs and goes INTO
    that call as the tokens' weights: the gradients the scan stores are the
    objective's own, and a mean over a full batch takes them as they are.
    The cross entropies returned beside the objective are values only (no
    gradient flows through them)."""
    hs, _ = encode_steps(params, tokens, cfg)
    n, b, t, d = hs.shape
    with jax.named_scope("lm_head_loss"):
        with jax.named_scope("loop.exit"):
            log_p = exit_distribution(params["exit_gate"], hs)
            p = jnp.exp(log_p)
        weighted, xent = lm_head_token_loss(
            params, hs.reshape(n * b, t, d), jnp.tile(targets, (n, 1)),
            cfg.base, weights=p.reshape(n * b, t))
        with jax.named_scope("loop.exit"):
            objective = jnp.sum(weighted.reshape(n, b, t)
                                + cfg.exit_beta * p * log_p, axis=0)
    return objective, xent.reshape(n, b, t), log_p


def looped_lm_loss_per_example(params, tokens, targets, cfg: HybridConfig):
    """Each example's mean looped objective, ``(B,)``: the loss a
    ``DataParallelTrainer(per_example_loss=True)`` takes for a model with an
    exit gate."""
    objective = looped_losses(params, tokens, targets, cfg)[0]
    with jax.named_scope("loss_reduce"):
        return objective.mean(axis=1)


def exit_stats(params, tokens, cfg: HybridConfig):
    """The exit distribution summed over ``tokens`` (B, T), ``(n_loops,)``
    f32, under ``params``: one forward pass, called outside the step."""
    hs, _ = encode_steps(params, tokens, cfg)
    return jnp.exp(exit_distribution(params["exit_gate"], hs)).sum(axis=(1, 2))


def publish_exit_stats(mass, tokens_total: float) -> float:
    """Add ``mass`` (``exit_stats`` summed over any batches, already on the
    host) to the counters ``loop.exit_mass.t<k>`` (k from 1) and
    ``loop.tokens_total``; returns the expected number of loop steps a token
    takes before it leaves."""
    METRICS.increment("loop.tokens_total", float(tokens_total))
    steps = 0.0
    for k, m in enumerate(mass, start=1):
        METRICS.increment(f"loop.exit_mass.t{k}", float(m))
        steps += k * float(m)
    return steps / max(float(tokens_total), 1.0)


def selections(params, tokens, cfg: HybridConfig):
    """What every ``SparseAttention`` layer selects for ``tokens`` (B, T)
    under ``params``: ``[bool (B, T, T) per layer]``, row ``t`` its query's
    keys.  One forward pass, called outside the step."""
    return _selection_pass(params, tokens, cfg, None)


def selection_stats(params, tokens, cfg: HybridConfig):
    """``(layers, 4)`` int32 over ``tokens`` (B, T): pairs selected, pairs
    ``s <= t``, ``(q_chunk, kv_chunk)`` tiles on or below the diagonal that
    hold no selected key, and such tiles in all.  One forward pass, called
    outside the step; no ``T x T`` array is made."""
    return jnp.stack([a.sum(axis=(0, 1))
                      for a in _selection_pass(params, tokens, cfg, _tile_counts)])


def _tile_counts(spec: SparseAttention, chosen, causal, start):
    c, n_keys = chosen.shape
    tile = spec.kv_chunk if n_keys % spec.kv_chunk == 0 else n_keys
    below = jnp.arange(0, n_keys, tile) < start + c
    empty = below & ~chosen.reshape(c, -1, tile).any(axis=(0, 2))
    return jnp.stack([chosen.sum(), causal.sum(), empty.sum(), below.sum()]
                     ).astype(jnp.int32)


def _selection_pass(params, tokens, cfg: HybridConfig, reduce):
    """``sparse_selection`` of every layer, each on the input the layers
    before it give."""
    with jax.named_scope("embed"):
        x = jnp.take(params["tok_embed"], tokens, axis=0).astype(cfg.base.dtype)
    out = []
    for i, lp in enumerate(params["layers"]):
        mixer = cfg.layers[i][0]
        out.append(sparse_selection(
            mixer, lp[mixer.key], rms_norm(x, lp["norm1"], cfg.norm_eps),
            cfg.base.dtype, reduce and functools.partial(reduce, mixer)))
        x = block(lp, x, cfg, i)[0]
    return out


def publish_selection_stats(counts) -> dict:
    """Add ``counts`` (``selection_stats`` summed over any batches, already on
    the host) to the counters ``dsa.pairs_selected``, ``dsa.pairs_causal``,
    ``dsa.tiles_empty`` and ``dsa.tiles_total``; returns the two shares."""
    selected, causal, empty, tiles = (float(v) for v in counts.sum(axis=0))
    METRICS.increment("dsa.pairs_selected", selected)
    METRICS.increment("dsa.pairs_causal", causal)
    METRICS.increment("dsa.tiles_empty", empty)
    METRICS.increment("dsa.tiles_total", tiles)
    return {"selected_share": selected / max(causal, 1.0),
            "empty_tile_share": empty / max(tiles, 1.0)}


def _first_moe(cfg: HybridConfig) -> MoE:
    return next(ffn for _, ffn in layer_specs(cfg) if isinstance(ffn, MoE))


def expert_choices(params, tokens, cfg: HybridConfig, targets=None):
    """Every block's expert choices for ``tokens`` (B, T), ``layer_specs``'
    order, each ``(n_loops, B, T[, top_k])`` or None for a block without
    experts: the layers' pass and, where there is a prediction module, its
    block's, which reads ``targets`` (B, T); no head runs."""
    _, choices, _, last = _run_layers(params, tokens, cfg)
    if cfg.mtp is not None:
        e = mtp_hidden(params, last, targets, cfg)[1]
        choices = choices + [None if e is None else e[None]]
    return choices


def routing_stats(params, tokens, cfg: HybridConfig, targets=None):
    """(Token, choice) pairs per expert, ``(blocks, n_experts)`` int32, for
    ``tokens`` (B, T) under ``params``, a row per block of ``layer_specs``
    (zeros for one without experts): one forward pass, called outside the
    step.  A prediction module's block reads ``targets`` (B, T)."""
    none = jnp.zeros((_first_moe(cfg).n_experts,), jnp.int32)
    return jnp.stack([none if e is None else expert_counts(ffn, e)
                      for (_, ffn), e in zip(
                          layer_specs(cfg),
                          expert_choices(params, tokens, cfg, targets))])


def place_experts(params, tokens, cfg: HybridConfig, targets=None):
    """Choose WHICH experts this chip holds, layer by layer, from the routing
    statistics of ``tokens`` (B, T): the experts are dealt to the chips that
    share a layer heaviest first, each to the chip with the lighter load so
    far (as expert-parallel deployments place experts by load), and the
    router's leaves that have a column an expert (``MoE.router_columns``: its
    output layer, and the selection bias where it has one) are reordered so
    that this chip's are its ``held`` range.  The experts' own weights are
    drawn alike, so the reordering is the whole placement.  Without it a
    randomly initialised router sends anything from a third to two thirds of
    the tokens here (PERF.md section 6, PR 28).  Expert layers are placed in
    ``layer_specs``' order, each on the statistics the placed layers before it
    give (``targets`` as ``routing_stats``').  Returns the parameters."""
    import numpy as np

    stats = jax.jit(lambda p: routing_stats(p, tokens, cfg, targets))
    specs = layer_specs(cfg)
    with trace.span("moe.place_experts", layers=len(specs)):
        for i, (_, ffn) in enumerate(specs):
            if not isinstance(ffn, MoE):
                continue
            counts = np.asarray(stats(params))[i]
            first, n = ffn.held
            chips = ffn.n_experts // n
            held = [[] for _ in range(chips)]
            for e in np.argsort(-counts, kind="stable"):
                open_ = [c for c in range(chips) if len(held[c]) < n]
                held[min(open_, key=lambda c: counts[held[c]].sum())].append(int(e))
            here = first // n
            order = jnp.asarray(
                sum(held[:here] + [held[here]] + held[here + 1:], []))
            lp = layer_params(params, cfg, i)
            router = dict(lp[ffn.key]["router"], **{
                name: lp[ffn.key]["router"][name][..., order]
                for name in ffn.router_columns})
            params = _with_layer(params, cfg, i, dict(
                lp, **{ffn.key: dict(lp[ffn.key], router=router)}))
    return params


def _with_layer(params, cfg: HybridConfig, i: int, lp):
    """``params`` with block ``i``'s parameters replaced by ``lp``."""
    a, b = layer_path(cfg, i)
    if a == "layers":
        return dict(params, layers=[lp if j == b else old
                                    for j, old in enumerate(params[a])])
    return dict(params, **{a: dict(params[a], **{b: lp})})


def publish_routing_stats(counts, cfg: HybridConfig) -> dict:
    """Add ``counts`` (``routing_stats`` summed over any batches, already on
    the host) to the counters ``moe.tokens_total``, ``moe.tokens_local`` and
    ``moe.expert_load.l<block>.e<expert>`` (held experts of the expert layers
    only; the unit is a (token, choice) pair, a token where the layer is
    top-1); returns the local share and the held experts' largest load over
    their mean."""
    first, n = _first_moe(cfg).held
    total = float(counts.sum())
    held = counts[:, first:first + n]
    METRICS.increment("moe.tokens_total", total)
    METRICS.increment("moe.tokens_local", float(held.sum()))
    for li, ((_, ffn), row) in enumerate(zip(layer_specs(cfg), held)):
        if not isinstance(ffn, MoE):
            continue
        for j, c in enumerate(row):
            METRICS.increment(f"moe.expert_load.l{li}.e{first + j}", float(c))
    per_expert = held.sum(axis=0)
    return {"local_share": float(held.sum()) / max(total, 1.0),
            "load_max_over_mean": float(per_expert.max())
            / max(float(per_expert.mean()), 1e-30)}


def publish_bias_stats(params, cfg: HybridConfig) -> float:
    """Set the gauge ``moe.bias_abs_max`` to the largest magnitude among the
    expert layers' selection biases under ``params`` (0 where no layer has
    one): how far the balancing rule has moved them.  Called outside the
    step."""
    biases = [layer_params(params, cfg, i)[ffn.key]["router"]["bias"]
              for i, (_, ffn) in enumerate(layer_specs(cfg))
              if getattr(ffn, "select_bias", False)]
    value = max((float(jnp.max(jnp.abs(b))) for b in biases), default=0.0)
    METRICS.gauge("moe.bias_abs_max", value)
    return value
