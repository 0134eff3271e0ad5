"""Plain reference of the sparse-attention mixture-of-experts block: grouped-
query attention restricted, query by query, to the keys a learned indexer ranks
highest (the "lightning indexer" of DeepSeek sparse attention, DeepSeek-V3.2-Exp
technical report, github.com/deepseek-ai/DeepSeek-V3.2-Exp) and a top-k mixture
of experts behind a linear softmax router (the Qwen3-MoE family's, whose key
names Keye-VL-2.0-30B-A3B's language-model config carries).  Forward, loss and
gradients in straightforward ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, no kernel, no cache, no ``vmap``,
one example at a time, explicit scores against every key (``block_rows`` queries
at a time so that 16,384 positions fit), ``jnp.argsort`` for both selections, a
Python loop over the experts held (and, where the sizes ask for it, one layer at
a time with the chain rule written out: ``loss_and_grads``).

It imports nothing of the program.  The model is a plain dict (``model``, the
keys of the published ``config.json`` plus ``experts_held = [first, count]``,
this chip's share of the experts) and the parameters are the tree
``models/hybrid.init_params`` makes, so gradients compare leaf by leaf:

    tok_embed (V, E), lm_head (E, V), final_norm (E,), layers[i]:
      norm1, norm2 (E,)
      dsa: wq (E, H*d), wk, wv (E, G*d), wo (H*d, E), q_norm, k_norm (d,),
           index: wq (E, J*c), wk (E, c), ww (E, J), k_norm_w, k_norm_b (c,)
      moe: router {w (E, n_experts)}, wg, wu (held, E, F), wdn (held, F, E)

The equations (``u = RMSNorm(x)``, ``u_ = stop_gradient(u)``; ``E`` hidden,
``H`` query heads, ``G`` KV heads of width ``d``; ``J`` index heads of width
``c``; ``k`` = ``sa_config.topk``):

- block: ``h = x + Attn(RMSNorm_1(x))``, ``y = h + MoE(RMSNorm_2(h))``; a final
  RMSNorm; an untied head, no bias; causal.
- attention: ``q = RoPE(RMSNorm_head(u wq))``, ``k = RoPE(RMSNorm_head(u wk))``,
  ``v = u wv``; RoPE on the whole head, rotate-half pairs.
  Indexer: ``qI = RoPE(u_ index.wq)``, ``kI = RoPE(LayerNorm(u_ index.wk))``,
  ``w = (u_ index.ww) / sqrt(J c)``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`` for ``s <= t``.  ``S_t`` = the ``min(t + 1, k)`` positions ``s <= t``
  with the largest ``I[t, s]``, ties to the lower ``s`` (a stable argsort).
  ``A[t, h, .] = softmax over S_t of q[t, h] . k[s, g(h)] / sqrt(d)``;
  ``o[t, h] = sum_{s in S_t} A[t, h, s] v[s, g(h)]``; output ``concat_h(o) wo``.
  The selection is a constant of the backward pass.
- the layer's index loss: ``p[t, .] = stop_gradient(mean_h A[t, h, .])``,
  ``L_I = mean_t sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t,
  .])[s])``.
- MoE: ``pi = softmax(u router.w)``; the ``num_experts_per_tok`` largest (a
  stable argsort), their weights divided by their sum; the layer gives ``sum
  over the chosen experts held here of g_e (SiLU(u wg_e) * u wu_e) wdn_e``; a
  choice that lives elsewhere adds zero.
- objective: ``L = L_LM + sum over layers of L_I``, ``L_LM`` the mean next-token
  cross entropy over the vocabulary held.

Departures from the published description, each listed under ``assumed`` or
``left_out`` in ``benchmark/configs/keye_vl2_30b_a3b_ep8.json``: the indexer
reads the normed hidden state (the model has no query latent to read); whole-
head rotary on the indexer's features; text-only positions, where the three
M-RoPE streams are equal and M-RoPE is RoPE; ``q_chunk_size`` / ``kv_chunk_size``
tile the computation and select nothing; no dense warm-up stage, no router
balancing loss, no vision tower.

``operand_dtype`` rounds both operands of every matrix product, forward and
backward, to that type (8-bit floats with a scale per tensor) before a float32
product: what the same mathematics gives in a lower precision, for setting the
comparison's limits (never the yardstick itself).  ``selection`` / ``routing``
make the layers FOLLOW another computation's selected keys and expert choices
while reporting their own beside them, so that a near-tie decided the other way
shows as one differing choice and not as every number downstream of it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

#: the parameter groups a comparison reports, by leaf path
GROUPS = ("embedding", "head", "attention_projections", "indexer", "router",
          "experts", "norms")


def group_of(path: str) -> str:
    """The group (one of ``GROUPS``) of a leaf, from its ``/``-joined path."""
    if "index" in path:
        return "indexer"
    if "router" in path:
        return "router"
    if "moe" in path:
        return "experts"
    if "norm" in path:
        return "norms"
    if "lm_head" in path:
        return "head"
    return "attention_projections" if "dsa" in path else "embedding"


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


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def rope(x, theta: float):
    """``x`` (T, heads, d): rotate each head's features by position, pairing
    feature ``i`` with ``i + d / 2``."""
    t, d = x.shape[0], x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]    # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def top_positions(scores, count):
    """bool like ``scores`` (R, T): in row ``r`` the ``count[r]`` positions of
    the largest scores, ties to the lower position."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)            # each position's place
    return rank < count[:, None]


def sparse_attention(p, u, model, mm, given=None, block_rows: int = 0):
    """``u`` (T, E) normed activations -> ``(the mixer's output (T, E), the
    layer's index loss, its own selection bool (T, T))``; row ``t`` of the
    selection holds query ``t``'s keys.  ``given`` (T, T) makes the attention
    and the index loss run over that selection instead.  ``block_rows``
    queries are scored at a time (all of them for 0, or where it does not
    divide T), one block after the other, each recomputed in the backward
    pass: the same sums."""
    t = u.shape[0]
    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    sa, eps, theta = model["sa_config"], model["rms_norm_eps"], model["rope_theta"]
    j, c, top = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    assert sa["indexer_num_kv_heads"] == 1
    rep = h // g

    q = rope(rms_norm(mm(u, p["wq"]).reshape(t, h, d), p["q_norm"], eps), theta)
    k = rope(rms_norm(mm(u, p["wk"]).reshape(t, g, d), p["k_norm"], eps), theta)
    v = mm(u, p["wv"]).reshape(t, g, d)
    kr, vr = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)    # (T, H, d)

    ix = p["index"]
    u_ = lax.stop_gradient(u)
    qi = rope(mm(u_, ix["wq"]).reshape(t, j, c), theta)
    ki = rope(layer_norm(mm(u_, ix["wk"]), ix["k_norm_w"], ix["k_norm_b"],
                         eps)[:, None, :], theta)[:, 0, :]
    w = mm(u_, ix["ww"]) / math.sqrt(j * c)

    def rows(first, q, qi, w, given):
        n = q.shape[0]
        tq = first + jnp.arange(n)
        causal = jnp.arange(t)[None, :] <= tq[:, None]
        pre = mm(qi.reshape(n * j, c), ki.T).reshape(n, j, t)
        index = jnp.sum(w[:, :, None] * jax.nn.relu(pre), axis=1)      # (n, T)
        own = top_positions(
            jnp.where(causal, lax.stop_gradient(index), -jnp.inf),
            jnp.minimum(tq + 1, top)) & causal
        sel = own if given is None else given
        s = mm(q.transpose(1, 0, 2), kr.transpose(1, 2, 0)) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(sel[None], s, -jnp.inf), axis=-1)  # (H, n, T)
        out = mm(a, vr.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(n, h * d)
        target = lax.stop_gradient(jnp.mean(a, axis=0))
        logp = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), axis=-1)
        kl = jnp.where(sel & (target > 0),
                       target * (jnp.log(jnp.where(target > 0, target, 1.0))
                                 - jnp.where(sel, logp, 0.0)), 0.0)
        return out, jnp.sum(kl), own

    step = block_rows if block_rows and t % block_rows == 0 else t
    n = t // step

    def blocks(a):
        return a.reshape(n, step, *a.shape[1:])

    out, kl, own = lax.map(
        lambda x: jax.checkpoint(rows)(*x),
        (jnp.arange(n) * step, blocks(q), blocks(qi), blocks(w),
         None if given is None else blocks(given)))
    return (mm(out.reshape(t, h * d), p["wo"]), jnp.sum(kl) / t,
            own.reshape(t, t))


def moe(p, u, model, mm, given=None):
    """``u`` (T, E) -> ``(this share's part of the layer's output, e (T, k))``,
    ``e`` the router's own choices, largest first.  ``given`` (T, k) sends each
    token to those experts instead, weighted by the router's probabilities OF
    THOSE experts, renormalised over them."""
    first, count = model["experts_held"]
    n = model["num_experts_per_tok"]
    pi = jax.nn.softmax(mm(u, p["router"]["w"]), axis=-1)
    e = jnp.argsort(-pi, axis=-1, stable=True)[:, :n]
    to = e if given is None else given
    gate = jnp.take_along_axis(pi, to, axis=-1)                        # (T, k)
    if model["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    out = jnp.zeros_like(u)
    for i in range(count):                       # every held expert, densely
        y = mm(jax.nn.silu(mm(u, p["wg"][i])) * mm(u, p["wu"][i]), p["wdn"][i])
        out = out + jnp.sum(jnp.where(to == first + i, gate, 0.0),
                            axis=-1, keepdims=True) * y
    return out, e


def block(lp, x, model, mm, selection=None, routing=None, block_rows: int = 0):
    """One layer: ``(x + attention + MoE, its index loss, its own selection
    (T, T), its router's own choices (T, k))``."""
    eps = model["rms_norm_eps"]
    a, index_loss, own = sparse_attention(
        lp["dsa"], rms_norm(x, lp["norm1"], eps), model, mm, selection, block_rows)
    x = x + a
    y, e = moe(lp["moe"], rms_norm(x, lp["norm2"], eps), model, mm, routing)
    return x + y, index_loss, own, e


def head_loss(x, final_norm, head, targets, model, mm, block: int = 0):
    """Mean cross entropy of one example from the last layer's output ``x``
    (T, E) through the untied head (E, V).  ``block`` > 0 makes the logits
    ``block`` positions at a time, recomputed in the backward pass; the same
    sum."""
    h = rms_norm(x, final_norm, model["rms_norm_eps"])
    t = h.shape[0]
    step = block or t

    def part(hb, tb, w):
        lg = mm(hb, w)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    total = 0.0
    for a in range(0, t, step):
        total = total + jax.checkpoint(part)(
            h[a:a + step], targets[a:a + step], head)
    return total / t


def loss(params, tokens, targets, model, operand_dtype=None, selection=None,
         routing=None):
    """One example's objective, differentiable as a whole: ``(L_LM + sum of the
    layers' index losses, (L_LM, that sum))``.  ``selection`` (layers, T, T)
    and ``routing`` (layers, T, k) as ``block``'s."""
    mm = _mm(operand_dtype)
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens]
        index = 0.0
        for i, lp in enumerate(params["layers"]):
            x, li, _, _ = block(lp, x, model, mm,
                                None if selection is None else selection[i],
                                None if routing is None else routing[i])
            index = index + li
        lm = head_loss(x, params["final_norm"], params["lm_head"], targets,
                       model, mm)
        return lm + index, (lm, index)


def loss_and_grads(params, tokens, targets, model, operand_dtype=None,
                   block_rows: int = 0, selection=None, routing=None):
    """Batch ``(B, T)``: the mean of the examples' objectives, its gradients,
    and ``{"lm", "index"`` (the two parts' means), ``"choices"`` (the routers'
    own, (B, layers, T, k)), ``"selected"`` (pairs the layers attended over),
    ``"selection_differs"`` (of those, pairs NOT in the layer's own
    selection on the same path: 0 without ``selection``)``}``.  ``selection``
    ([bool (B, T, T) per layer]) and ``routing`` ((B, layers, T, k)) make every
    layer follow them (``block``).  One example at a time and, so that 16,384
    positions and one layer's program serve every layer, one LAYER at a time:
    the chain rule by hand over the layers (``jax.vjp`` of ``block`` from the
    kept layer inputs), scores and head logits ``block_rows`` positions at a
    time.  The same numbers as ``jax.value_and_grad(loss)`` (tested)."""
    mm = _mm(operand_dtype)
    n = tokens.shape[0]

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def layer(lp, x, sel, to):
        return block(lp, x, model, mm, sel, to, block_rows)

    def forward_fn(lp, x, sel, to):
        y, li, own, e = layer(lp, x, sel, to)
        used = own if sel is None else sel
        return y, li, e, jnp.sum(used), jnp.sum(used & ~own)

    def back(lp, x, sel, to, ct):
        _, vjp = jax.vjp(lambda lp_, x_: layer(lp_, x_, sel, to)[:2], lp, x)
        return vjp((ct, jnp.ones((), jnp.float32)))

    forward, backward = highest(forward_fn), highest(back)
    head = highest(jax.value_and_grad(
        lambda x, w, hd, y: head_loss(x, w, hd, y, model, mm, block_rows),
        argnums=(0, 1, 2)))
    add = jax.jit(lambda acc, g: jax.tree_util.tree_map(
        lambda a, b: a + b / n, acc, g), donate_argnums=0)

    lm = index = 0.0
    selected = differs = 0
    chosen = []
    # added up layer by layer as the backward pass makes them: one layer's
    # gradients at a time beside the sum, not a second whole tree
    layers = [jax.tree_util.tree_map(jnp.zeros_like, lp) for lp in params["layers"]]
    rest = {k: jnp.zeros_like(params[k]) for k in ("tok_embed", "lm_head",
                                                   "final_norm")}
    depth = len(layers)
    # the reference imports nothing of the program, its spans included: it is
    # a yardstick run once in set-up  # graftlint: disable=HOT02
    for i in range(n):
        xs, es = [params["tok_embed"][tokens[i]]], []
        sels = [None if selection is None else selection[j][i] for j in range(depth)]
        tos = [None if routing is None else routing[i][j] for j in range(depth)]
        for lp, sel, to in zip(params["layers"], sels, tos):
            x, li, e, used, other = forward(lp, xs[-1], sel, to)
            xs.append(x)
            es.append(e)
            index = index + li / n
            selected, differs = selected + int(used), differs + int(other)
        v, (ct, g_norm, g_head) = head(xs.pop(), params["final_norm"],
                                       params["lm_head"], targets[i])
        for j in reversed(range(depth)):
            g_lp, ct = backward(params["layers"][j], xs.pop(), sels[j], tos[j], ct)
            layers[j] = add(layers[j], g_lp)
        rest = add(rest, {
            "tok_embed": jnp.zeros_like(params["tok_embed"]).at[tokens[i]].add(ct),
            "lm_head": g_head, "final_norm": g_norm})
        lm = lm + v / n
        chosen.append(jnp.stack(es))
    grads = dict(rest, layers=layers)
    return lm + index, grads, {
        "lm": lm, "index": index, "choices": jnp.stack(chosen),
        "selected": selected, "selection_differs": differs / max(selected, 1)}


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
