"""The benchmark's own copy of ``deeplearning4j_tpu/models/reference/ouro.py``
(kept here so that a later change to the program's file cannot move the
yardstick; ``tests/benchmark_tests`` holds the two to the same numbers).

Plain reference of the looped language model (Ouro, arXiv:2510.25741;
``config.json`` of ByteDance/Ouro-2.6B): a stack of sandwich-normed layers run
``total_ut_steps`` times over the same weights, an exit gate on each step's
state, and the first-stage objective, the steps' cross entropies weighted by
the exit distribution less ``beta`` times its entropy.  Forward, the losses,
the exit distribution, the objective and the gradients in straightforward
``jax.numpy``: float32, ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no ``vmap``, no scan, one example at a time, explicit
``T x T`` scores, a Python loop over loop steps and layers (and, where the
sizes ask for it, one layer APPLICATION at a time with the chain rule written
out: ``loss_and_grads``).

It imports nothing of the program.  The model is a plain dict (``model``: the
keys of the published ``config.json`` plus ``exit_beta``) and the parameters
are the tree ``models/hybrid.init_params`` makes, so gradients compare leaf by
leaf:

    tok_embed (V, E), lm_head (E, V), final_norm (E,),
    exit_gate: w (E,), b ()
    layers[i]:
      norm1, norm1_post, norm2, norm2_post (E,)
      attn: wq (E, H*d), wk, wv (E, G*d), wo (H*d, E)
      mlp: wg, wu (E, F), wdn (F, E)

The equations (``E`` hidden, ``H`` query heads, ``G`` KV heads, ``d`` head
width, ``n`` loop steps, ``N`` an RMSNorm with a learned scale):

- layer: ``a = Attn(N1(x))``, ``x <- x + N1post(a)``; ``m = W_down(silu(W_gate
  u) * (W_up u))`` with ``u = N2(x)``, ``x <- x + N2post(m)``.
- Attn: ``q, k, v = u wq, u wk, u wv``; rotary positions on the WHOLE head
  (rotate-half: feature ``i`` with ``i + d / 2``); causal ``softmax(q k^T /
  sqrt(d)) v``; query head ``h`` reads KV head ``h // (H / G)``; ``wo``.
- model: ``h_0 = Embed(tokens)``; for ``t = 1..n``: ``h_t = N_f(layers(h_{t-1}))``
  with the SAME layers: the normed state is the step's output and the next
  step's input.  ``logits_t = h_t lm_head``; ``lambda_t = sigmoid(w . h_t + b)``.
- exit distribution a token: ``S_0 = 1``; ``p_t = lambda_t S_{t-1}``,
  ``S_t = S_{t-1} (1 - lambda_t)`` for ``t < n``; ``p_n = S_{n-1}``.
- objective a token: ``sum_t p_t xent_t - beta H(p)``, ``H(p) = -sum_t p_t log
  p_t``; an example's loss is the mean over its positions.

Departures from the description, each so that a test can say something: a
parameter tree with ``n x`` the model's layers is run UNTIED, loop step ``t``
over its own slice of them (the test that a shared layer's gradient is the sum
over its uses); ``operand_dtype`` rounds both operands of every matrix
product, forward and backward, to that type (8-bit floats with a scale per
tensor) before a float32 product: what the same mathematics gives in a lower
precision, for setting the comparison's limits (never the yardstick itself).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

#: the parameter groups a comparison reports, by leaf path
GROUPS = ("embedding", "head", "attention_projections", "ffn", "norms",
          "exit_gate")


def group_of(path: str) -> str:
    """The group (one of ``GROUPS``) of a leaf, from its ``/``-joined path."""
    if "exit_gate" in path:
        return "exit_gate"
    if "norm" in path:
        return "norms"
    if "attn" in path:
        return "attention_projections"
    if "mlp" in path:
        return "ffn"
    return "head" if "lm_head" in path else "embedding"


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


def rope(x, theta: float):
    """``x`` (T, heads, d): rotate every feature of each head, pairing
    feature ``i`` with ``i + d / 2``."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]    # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, u, model, mm):
    """``u`` (T, E) normed activations -> the mixer's output (T, E)."""
    t = u.shape[0]
    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    q = rope(mm(u, p["wq"]).reshape(t, h, d), model["rope_theta"])
    k = rope(mm(u, p["wk"]).reshape(t, g, d), model["rope_theta"])
    v = mm(u, p["wv"]).reshape(t, g, d)
    kr, vr = jnp.repeat(k, h // g, axis=1), jnp.repeat(v, h // g, axis=1)
    s = mm(q.transpose(1, 0, 2), kr.transpose(1, 2, 0)) / math.sqrt(d)  # (H, T, T)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    out = mm(jax.nn.softmax(s, axis=-1), vr.transpose(1, 0, 2))         # (H, T, d)
    return mm(out.transpose(1, 0, 2).reshape(t, h * d), p["wo"])


def mlp(p, u, mm):
    return mm(jax.nn.silu(mm(u, p["wg"])) * mm(u, p["wu"]), p["wdn"])


def block(lp, x, model, mm):
    """One application of one layer, ``x`` (T, E) -> (T, E)."""
    eps = model["rms_norm_eps"]
    a = attention(lp["attn"], rms_norm(x, lp["norm1"], eps), model, mm)
    x = x + rms_norm(a, lp["norm1_post"], eps)
    m = mlp(lp["mlp"], rms_norm(x, lp["norm2"], eps), mm)
    return x + rms_norm(m, lp["norm2_post"], eps)


def layers_of_step(params, t: int, model):
    """Indices into ``params["layers"]`` of the layers loop step ``t`` (from
    0) runs: all of them, or, in a tree with ``n x`` as many, its own slice."""
    n_layers = model["num_hidden_layers"]
    if len(params["layers"]) == n_layers:
        return list(range(n_layers))
    assert len(params["layers"]) == n_layers * model["total_ut_steps"]
    return list(range(t * n_layers, (t + 1) * n_layers))


def states(params, tokens, model, operand_dtype=None):
    """``tokens`` (T,) -> every loop step's normed state, (n, T, E)."""
    mm = _mm(operand_dtype)
    x = params["tok_embed"][tokens]
    out = []
    for t in range(model["total_ut_steps"]):
        for i in layers_of_step(params, t, model):
            x = block(params["layers"][i], x, model, mm)
        x = rms_norm(x, params["final_norm"], model["rms_norm_eps"])
        out.append(x)
    return jnp.stack(out)


def exit_distribution(lam):
    """Gates ``lam`` (n, T) -> the distribution ``p`` (n, T) over the steps at
    which a token leaves; the last step takes what is left (its own gate is
    not read)."""
    left, p = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def objective(hs, head, gate, targets, model, mm, block_rows: int = 0):
    """One example, from its loop steps' states ``hs`` (n, T, E): ``(mean
    objective, (mean cross entropy of each step (n,), mean exit distribution
    (n,)))``.  ``block_rows`` > 0 makes the logits that many positions at a
    time, recomputed in the backward pass, so that 4 x 4096 rows of a 49k-row
    head fit; the same sums."""
    n, t, _ = hs.shape
    step = block_rows or t

    def part(hb, tb, w):
        lg = mm(hb, w)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return lse - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]

    xent = jnp.stack([
        jnp.concatenate([jax.checkpoint(part)(hs[k, a:a + step],
                                              targets[a:a + step], head)
                         for a in range(0, t, step)]) for k in range(n)])
    p = exit_distribution(jax.nn.sigmoid(jnp.sum(hs * gate["w"], axis=-1)
                                         + gate["b"]))
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    token = jnp.sum(p * xent, axis=0) - model["exit_beta"] * entropy
    return token.mean(), (xent.mean(axis=1), p.mean(axis=1))


def loss(params, tokens, targets, model, operand_dtype=None):
    """``(mean objective, (steps' mean cross entropies, mean exit
    distribution))`` of one example, differentiable as a whole."""
    with jax.default_matmul_precision("highest"):
        hs = states(params, tokens, model, operand_dtype)
        return objective(hs, params["lm_head"], params["exit_gate"], targets,
                         model, _mm(operand_dtype))


def loss_and_grads(params, tokens, targets, model, operand_dtype=None,
                   block_rows: int = 0):
    """Batch ``(B, T)``: the mean of the examples' objectives, its gradients,
    and ``{"xent": (n,), "exit": (n,)}``, the batch's mean cross entropy and
    mean exit mass of every loop step.  One example at a time and, so that
    4096 x 4096 scores and a 49k-row head fit and one layer's program serves
    every application of every layer, one LAYER APPLICATION at a time: the
    chain rule by hand (``jax.vjp`` of ``block`` and of the norm that closes a
    loop step, from the kept inputs), a layer's gradient added up over the
    loop steps that used it, the head's logits ``block_rows`` positions at a
    time.  The same numbers as ``jax.value_and_grad(loss)`` (tested)."""
    mm = _mm(operand_dtype)
    n_ex, n = tokens.shape[0], model["total_ut_steps"]
    eps = model["rms_norm_eps"]

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    forward = highest(lambda lp, x: block(lp, x, model, mm))
    backward = highest(
        lambda lp, x, ct: jax.vjp(lambda a, b: block(a, b, model, mm), lp, x)[1](ct))
    close = highest(lambda x, w: rms_norm(x, w, eps))
    close_back = highest(
        lambda x, w, ct: jax.vjp(lambda a, b: rms_norm(a, b, eps), x, w)[1](ct))
    tail = highest(jax.value_and_grad(
        lambda hs, head, gate, y: objective(hs, head, gate, y, model, mm, block_rows),
        argnums=(0, 1, 2), has_aux=True))
    add = jax.jit(lambda acc, g: jax.tree_util.tree_map(
        lambda a, b: a + b / n_ex, acc, g), donate_argnums=0)

    total, xent, exits = 0.0, 0.0, 0.0
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    # the reference imports nothing of the program, its spans included: it is
    # a yardstick run once in set-up  # graftlint: disable=HOT02
    for i in range(n_ex):
        x = params["tok_embed"][tokens[i]]
        inputs, ends, hs = [], [], []        # per application; per loop step
        for t in range(n):
            for j in layers_of_step(params, t, model):
                inputs.append((j, x))
                x = forward(params["layers"][j], x)
            ends.append(x)
            x = close(x, params["final_norm"])
            hs.append(x)
        (v, (xe, pe)), (g_hs, g_head, g_gate) = tail(
            jnp.stack(hs), params["lm_head"], params["exit_gate"], targets[i])
        grads["lm_head"] = add(grads["lm_head"], g_head)
        grads["exit_gate"] = add(grads["exit_gate"], g_gate)
        ct = jnp.zeros_like(x)               # of the state after the last step
        for t in reversed(range(n)):
            ct, g_norm = close_back(ends[t], params["final_norm"], ct + g_hs[t])
            grads["final_norm"] = add(grads["final_norm"], g_norm)
            for _ in layers_of_step(params, t, model):
                j, x_in = inputs.pop()
                g_lp, ct = backward(params["layers"][j], x_in, ct)
                grads["layers"][j] = add(grads["layers"][j], g_lp)
        grads["tok_embed"] = add(
            grads["tok_embed"], jnp.zeros_like(params["tok_embed"]).at[tokens[i]].add(ct))
        total, xent, exits = total + v / n_ex, xent + xe / n_ex, exits + pe / n_ex
    return total, grads, {"xent": xent, "exit": exits}


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
