"""Plain reference of the ZAYA1 block: compressed convolutional attention
(CCA, arXiv:2510.04476) and an MLP-routed top-1 mixture of experts (ZAYA1
report, arXiv:2511.17127).  Forward, loss and gradients in straightforward
``jax.numpy``: float32, ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no ``vmap``, one example at a time, explicit ``T x T``
scores, a Python loop over the experts held (and, where the sizes ask for it,
one layer at a time with the chain rule written out: ``loss_and_grads``).

It imports nothing of the program.  The model is a plain dict (``model``, the
keys of the published ``config.json`` plus ``experts_held = [first, count]``,
this chip's share of the experts) and the parameters are the tree
``models/hybrid.init_params`` makes, so gradients compare leaf by leaf:

    tok_embed (V, E), final_norm (E,), layers[i]:
      norm1, norm2 (E,)
      cca: wq (E, H*d), wk (E, G*d), wv1, wv2 (E, d), conv0 (k0, C),
           conv1 (k1, H+G, d, d), temp (G,), wo (H*d, E);  C = (H+G)*d
      moe: router {wd (E, r), w1, w2 (r, r), b1, b2 (r,), w3 (r, n_experts)},
           wg, wu (held, E, F), wdn (held, F, E)

The equations (``u = RMSNorm(x)``; ``E`` hidden, ``H`` query heads, ``G`` KV
heads, ``d`` head width):

- block: ``h = x + CCA(RMSNorm_1(x))``, ``y = h + MoE(RMSNorm_2(h))``; a final
  RMSNorm; the head is the embedding transposed, no bias; causal.
- CCA: ``q~ = u wq``, ``k~ = u wk``, ``v = [u wv1 ; shift(u) wv2]`` (KV head 0
  from this token, head 1 from the previous one, zero at t = 0).
  ``z = [q~ ; k~]`` -> causal depthwise convolution over time (``conv0``, tap
  ``k0 - 1`` is the current token) -> causal grouped convolution (``conv1``,
  one group per head).  ``m = (q~ + repeat(k~)) / 2`` by head;
  ``q = conv_q + m``, ``k = conv_k + mean of m over the group's query heads``.
  ``q <- sqrt(d) q / |q|``, ``k <- temp_g sqrt(d) k / |k|``; RoPE on the first
  ``partial_rotary_factor * d`` features of each head (rotate-half pairs);
  causal ``softmax(q k^T / sqrt(d)) v``; query head ``h`` reads KV head
  ``h // (H / G)``; output through ``wo``.
- MoE: ``p = u wd``, ``a = GELU(p w1 + b1)``, ``b = GELU(a w2 + b2)``,
  ``logits = b w3``; ``pi = softmax(logits)``, ``e = argmax``; the layer gives
  ``pi_e (SiLU(u wg_e) * u wu_e) wdn_e`` for a token whose ``e`` is held here
  and zero for any other token.

``operand_dtype`` rounds both operands of every matrix product, forward and
backward, to that type (8-bit floats with a scale per tensor) before a float32
product: what the same mathematics gives in a lower
precision, for setting the comparison's limits (never the yardstick itself).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

#: the parameter groups a comparison reports, by leaf path
GROUPS = ("embedding", "cca_projections", "convolutions", "router", "experts",
          "norms")


def group_of(path: str) -> str:
    """The group (one of ``GROUPS``) of a leaf, from its ``/``-joined path."""
    if "router" in path:
        return "router"
    if "conv" in path:
        return "convolutions"
    if "moe" in path:
        return "experts"
    if "norm" in path or path.endswith("temp"):
        return "norms"
    return "cca_projections" if "cca" in path else "embedding"


def _mm(operand_dtype):
    """The matrix product; with ``operand_dtype``, both operands rounded to it
    first, in the backward products too (the cotangent and the other operand):
    what running every product of forward and backward in that precision
    gives.  An 8-bit float takes one scale per tensor, as such products are
    run: without it a backward pass's small cotangents all round to zero."""
    if operand_dtype is None:
        return jnp.matmul

    def rnd(a):
        if jnp.finfo(operand_dtype).bits > 8:
            return a.astype(operand_dtype).astype(jnp.float32)
        scale = jnp.max(jnp.abs(a)) / float(jnp.finfo(operand_dtype).max) + 1e-30
        return (a / scale).astype(operand_dtype).astype(jnp.float32) * scale

    def t(a):
        return jnp.swapaxes(a, -1, -2)

    @jax.custom_vjp
    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):           # every use has equal leading dimensions
        a, b = res
        return jnp.matmul(rnd(g), t(rnd(b))), jnp.matmul(t(rnd(a)), rnd(g))

    mm.defvjp(fwd, bwd)
    return mm


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(z, w, mm):
    """``z`` (T, C) through a causal convolution over time, written out: tap
    ``j`` of ``k`` multiplies the input ``k - 1 - j`` positions back (zeros
    before the start).  ``w`` is ``(k, C)`` for a depthwise one, or
    ``(k, groups, i, o)``: each group's ``i`` channels mix into its ``o``.
    (``lax.conv_general_dilated`` with ``feature_group_count`` read 77% off a
    float64 count on the TPU at 10 groups of 128 — my chip run, PR 28 — and
    is not used.)"""
    k, t = w.shape[0], z.shape[0]
    back = [jnp.concatenate([jnp.zeros_like(z[:k - 1 - j]), z[:t - (k - 1 - j)]])
            for j in range(k)]
    if w.ndim == 2:
        return sum(back[j] * w[j] for j in range(k))
    groups, i = w.shape[1], w.shape[2]
    return jnp.concatenate(
        [sum(mm(back[j][:, n * i:(n + 1) * i], w[j, n]) for j in range(k))
         for n in range(groups)], axis=-1)


def rope(x, theta: float, rotary: int):
    """``x`` (T, heads, d): rotate the first ``rotary`` features of each head,
    pairing feature ``i`` with ``i + rotary / 2``."""
    t = x.shape[0]
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]    # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def cca(p, u, model, mm):
    """``u`` (T, E) normed activations -> the mixer's output (T, E)."""
    t = u.shape[0]
    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    rep = h // g
    q0 = mm(u, p["wq"]).reshape(t, h, d)
    k0 = mm(u, p["wk"]).reshape(t, g, d)
    prev = jnp.concatenate([jnp.zeros_like(u[:1]), u[:-1]], axis=0)
    v = jnp.stack([mm(u, p["wv1"]), mm(prev, p["wv2"])], axis=1)      # (T, G, d)
    assert g == 2, "the value halves are KV heads 0 and 1"

    z = jnp.concatenate([q0.reshape(t, h * d), k0.reshape(t, g * d)], axis=-1)
    z = causal_conv(z, p["conv0"], mm)
    z = causal_conv(z, p["conv1"], mm)
    conv_q = z[:, :h * d].reshape(t, h, d)
    conv_k = z[:, h * d:].reshape(t, g, d)

    m = (q0 + jnp.repeat(k0, rep, axis=1)) / 2.0                       # (T, H, d)
    q = conv_q + m
    k = conv_k + m.reshape(t, g, rep, d).mean(axis=2)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)

    q = math.sqrt(d) * unit(q)
    k = p["temp"][None, :, None] * math.sqrt(d) * unit(k)
    rotary = int(model["partial_rotary_factor"] * d)
    q = rope(q, model["rope_theta"], rotary)
    k = rope(k, model["rope_theta"], rotary)

    kr, vr = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)    # (T, H, d)
    s = mm(q.transpose(1, 0, 2), kr.transpose(1, 2, 0)) / math.sqrt(d)  # (H, T, T)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = mm(w, vr.transpose(1, 0, 2))                                 # (H, T, d)
    return mm(out.transpose(1, 0, 2).reshape(t, h * d), p["wo"])


def route(r, u, mm):
    """The router MLP: ``(pi (T, n_experts), e (T,))``."""
    a = jax.nn.gelu(mm(mm(u, r["wd"]), r["w1"]) + r["b1"])
    b = jax.nn.gelu(mm(a, r["w2"]) + r["b2"])
    pi = jax.nn.softmax(mm(b, r["w3"]), axis=-1)
    return pi, jnp.argmax(pi, axis=-1)


def moe(p, u, model, mm, given=None):
    """``u`` (T, E) -> ``(this share's part of the layer's output, e)``, ``e``
    the router's own choice.  ``given`` (T,) sends each token to that expert
    instead, weighted by the router's probability OF THAT expert: how a
    comparison follows another computation's routing, so that a near-tie it
    decided the other way shows as one differing choice and not as every
    number downstream of it."""
    first, count = model["experts_held"]
    pi, e = route(p["router"], u, mm)
    to = e if given is None else given
    gate = jnp.take_along_axis(pi, to[:, None], axis=-1)               # (T, 1)
    out = jnp.zeros_like(u)
    for j in range(count):                       # every held expert, densely
        y = mm(jax.nn.silu(mm(u, p["wg"][j])) * mm(u, p["wu"][j]), p["wdn"][j])
        out = out + jnp.where((to == first + j)[:, None], gate * y, 0.0)
    return out, e


def block(lp, x, model, mm, given=None):
    """One layer: ``(x + CCA + MoE, e)``."""
    eps = model["rms_norm_eps"]
    x = x + cca(lp["cca"], rms_norm(x, lp["norm1"], eps), model, mm)
    y, e = moe(lp["moe"], rms_norm(x, lp["norm2"], eps), model, mm, given)
    return x + y, e


def hidden(params, tokens, model, operand_dtype=None):
    """``tokens`` (T,) -> ``(final normed hidden (T, E), [e per layer])``."""
    mm = _mm(operand_dtype)
    x = params["tok_embed"][tokens]
    choices = []
    for lp in params["layers"]:
        x, e = block(lp, x, model, mm)
        choices.append(e)
    return rms_norm(x, params["final_norm"], model["rms_norm_eps"]), choices


def logits(params, tokens, model, operand_dtype=None):
    """``tokens`` (T,) -> (T, V) over the vocabulary slice held."""
    with jax.default_matmul_precision("highest"):
        h, _ = hidden(params, tokens, model, operand_dtype)
        return _mm(operand_dtype)(h, params["tok_embed"].T)


def head_loss(x, final_norm, table, targets, model, mm, block: int = 0):
    """Mean cross entropy of one example from the last layer's output ``x``
    (T, E).  ``block`` > 0 makes the logits ``block`` positions at a time,
    recomputed in the backward pass, so that a 131k-row head fits; the same
    sum."""
    h = rms_norm(x, final_norm, model["rms_norm_eps"])
    t = h.shape[0]
    step = block or t

    def part(hb, tb, tab):
        lg = mm(hb, tab.T)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    total = 0.0
    for a in range(0, t, step):
        total = total + jax.checkpoint(part)(
            h[a:a + step], targets[a:a + step], table)
    return total / t


def loss(params, tokens, targets, model, operand_dtype=None, routing=None):
    """Mean cross entropy of one example, differentiable as a whole;
    ``routing`` (layers, T) as ``moe``'s ``given``."""
    mm = _mm(operand_dtype)
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens]
        for i, lp in enumerate(params["layers"]):
            x, _ = block(lp, x, model, mm,
                         None if routing is None else routing[i])
        return head_loss(x, params["final_norm"], params["tok_embed"], targets,
                         model, mm)


def loss_and_grads(params, tokens, targets, model, operand_dtype=None,
                   block_rows: int = 0, routing=None):
    """Batch ``(B, T)``: the mean of the examples' losses, its gradients, and
    the router's own choices ``(B, layers, T)``; with ``routing`` of that
    shape every token follows it instead (``moe``'s ``given``).  One example
    at a time and, so that
    4096 x 4096 scores and a 131k-row head fit and one layer's program serves
    every layer, one LAYER at a time: the chain rule by hand over the layers
    (``jax.vjp`` of ``block`` from the kept layer inputs), the head's logits
    ``block_rows`` positions at a time.  The same numbers as
    ``jax.value_and_grad(loss)`` (tested)."""
    mm = _mm(operand_dtype)
    n = tokens.shape[0]

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    forward = highest(lambda lp, x, to: block(lp, x, model, mm, to))

    def back(lp, x, to, ct):
        _, vjp = jax.vjp(lambda lp_, x_: block(lp_, x_, model, mm, to)[0], lp, x)
        return vjp(ct)

    backward = highest(back)
    head = highest(jax.value_and_grad(
        lambda x, w, tab, y: head_loss(x, w, tab, y, model, mm, block_rows),
        argnums=(0, 1, 2)))
    add = jax.jit(lambda acc, g: jax.tree_util.tree_map(
        lambda a, b: a + b / n, acc, g), donate_argnums=0)

    total, chosen = 0.0, []
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    # the reference imports nothing of the program, its spans included: it is
    # a yardstick run once in set-up  # graftlint: disable=HOT02
    for i in range(n):
        xs, es = [params["tok_embed"][tokens[i]]], []
        to = [None] * len(params["layers"]) if routing is None else routing[i]
        for lp, given in zip(params["layers"], to):
            x, e = forward(lp, xs[-1], given)
            xs.append(x)
            es.append(e)
        v, (ct, g_norm, g_table) = head(xs[-1], params["final_norm"],
                                        params["tok_embed"], targets[i])
        g_layers = []
        for lp, x, given in zip(reversed(params["layers"]), reversed(xs[:-1]),
                                reversed(list(to))):
            g_lp, ct = backward(lp, x, given, ct)
            g_layers.append(g_lp)
        g = {"tok_embed": g_table.at[tokens[i]].add(ct), "final_norm": g_norm,
             "layers": g_layers[::-1]}
        total = total + v / n
        grads = add(grads, g)
        chosen.append(jnp.stack(es))
    return total, grads, jnp.stack(chosen)


def compare_grads(got, want) -> dict:
    """Per group of ``GROUPS``: ``rel`` = |got - want| / |want| over the
    group's leaves taken as one vector, and ``cos`` of the two vectors."""
    sums = {g: [0.0, 0.0, 0.0, 0.0] for g in GROUPS}
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_w = jax.tree_util.tree_leaves(want)
    for (path, a), b in zip(flat_g, flat_w, strict=True):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        s = sums[group_of(name)]
        s[0] += float(jnp.sum((a - b) ** 2))
        s[1] += float(jnp.sum(b * b))
        s[2] += float(jnp.sum(a * a))
        s[3] += float(jnp.sum(a * b))
    return {g: {"rel": math.sqrt(d2 / max(w2, 1e-300)),
                "cos": ab / max(math.sqrt(w2 * g2), 1e-300)}
            for g, (d2, w2, g2, ab) in sums.items()}
