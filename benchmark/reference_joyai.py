"""Plain reference of the DeepSeek-V3-class block as JoyAI-LLM-Flash's config
states it (huggingface.co/jdopensource/JoyAI-LLM-Flash, config.json; every key
is DeepSeek-V3's, arXiv:2412.19437): multi-head latent attention (section
2.1.1), a leading dense layer, then experts behind a sigmoid router that
selects on a biased score (section 2.1.2, the auxiliary-loss-free balancing)
beside a shared expert, and one multi-token-prediction module (section 2.2).
Forward, objective, gradients, the bias's update and one step of AdamW in straightforward
``jax.numpy``: float32, ``jax.default_matmul_precision("highest")``, no kernel,
no cache, no ``vmap``, one example at a time, explicit scores against every key
(``block_rows`` queries at a time so that 8192 positions fit), ``jnp.argsort``
for the experts, one held expert after the other over ALL the tokens (a
``lax.scan``, so that the compiler is handed one expert's program and not
sixteen) and, where the sizes ask for it, one layer at a time with the chain
rule written out (``loss_and_grads``).

It imports nothing of the program.  The model is a plain dict (``model``, the
keys of the published ``config.json`` plus ``experts_held = [first, count]``,
this chip's share of the experts, ``router_width``, and the two rates the
config does not carry, ``bias_update_rate`` and ``mtp_loss_weight``) and the
parameters are the tree ``models/hybrid.init_params`` makes, so gradients
compare leaf by leaf:

    tok_embed (V, E), lm_head (E, V), final_norm (E,), layers[i]:
      norm1, norm2 (E,)
      mla: wqa (E, rq), q_norm (rq,), wqb (rq, H*(dn+dr)), wkva (E, rkv+dr),
           kv_norm (rkv,), wkvb (rkv, H*(dn+dv)), wo (H*dv, E)
      mlp: wg, wu (E, F), wdn (F, E)              the leading dense layers
      moe: router {w (E, n_experts), bias (n_experts,)},
           wg, wu (held, E, F'), wdn (held, F', E), shared {wg, wu, wdn}
    mtp: enorm, hnorm, norm (E,), eh_proj (2E, E), block (one expert layer)

The equations (``u = RMSNorm(x)``; ``E`` hidden, ``H`` heads; ``rq`` =
``q_lora_rank``, ``rkv`` = ``kv_lora_rank``, ``dn`` / ``dr`` / ``dv`` =
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``):

- block: ``h = x + MLA(RMSNorm_1(x))``, ``y = h + FFN(RMSNorm_2(h))``, no norm
  after either half; a final RMSNorm; an untied head, no bias; causal.
- MLA: ``c_q = RMSNorm(u wqa)``; ``[q_nope_h ; q_rope_h] = c_q wqb``;
  ``[c_kv ; k_rope] = u wkva``, ``c_kv <- RMSNorm(c_kv)``; ``[k_nope_h ; v_h] =
  c_kv wkvb``.  RoPE on ``q_rope_h`` and on the ONE ``k_rope`` all heads share,
  interleaved pairs ``(2i, 2i + 1)`` (``rope_interleave``), theta
  ``rope_theta``, no scaling.  ``q_h = [q_nope_h ; q_rope_h]``, ``k_h =
  [k_nope_h ; k_rope]``; ``o_h = softmax_causal(q_h k_h^T / sqrt(dn + dr))
  v_h``; output ``concat_h(o_h) wo``.  The training form: nothing absorbed.
- FFN of the first ``first_k_dense_replace`` layers: ``(SiLU(u wg) * u wu) wdn``.
- FFN of the others: ``s = sigmoid(u router.w)`` over all ``router_width``; the
  ``num_experts_per_tok`` experts with the largest ``s + stop_gradient(bias)``
  (a stable argsort; ``n_group`` = ``topk_group`` = 1: no group limit); ``g =
  routed_scaling_factor * s_chosen / sum(s_chosen)`` (``norm_topk_prob``);
  ``y = Shared(u) + sum over the chosen experts HELD HERE of g_e Expert_e(u)``;
  a choice that lives elsewhere adds zero.
- the bias: no gradient reaches it; after a step, from that step's counts ``c``
  of (token, choice) pairs over all experts, ``bias += bias_update_rate *
  sign(mean(c) - c)`` (``bias_update``).
- MTP (one module): with ``x = t[0:T]``, ``y = t[1:T+1]`` and ``h_i`` the last
  block's output at ``i`` BEFORE the final norm, ``h'_i = [RMSNorm_e(Emb(y_i))
  ; RMSNorm_h(h_i)] eh_proj``, one expert-layer block on ``h'``, an RMSNorm,
  the MAIN model's head; it predicts ``y_{i+1}`` at ``i = 0..T-2``.
- objective of a sequence: ``mean_i CE_main + mtp_loss_weight * mean_{i<=T-2}
  CE_mtp`` over the vocabulary held.

Departures from the published description, each listed under ``assumed`` or
``left_out`` in ``benchmark/configs/joyai_llm_flash_ep16.json``: no
complementary sequence-wise balance loss (its coefficient is not in the
config); ``rope_scaling`` null, so no YaRN; ``bias_update_rate`` and
``mtp_loss_weight`` are DeepSeek-V3's; the module reads the state before the
final norm and concatenates the embedding first.

``operand_dtype`` rounds both operands of every matrix product, forward and
backward, to that type (8-bit floats with a scale per tensor) before a float32
product: what the same mathematics gives in a lower precision, for setting the
comparison's limits (never the yardstick itself).  ``routing`` makes the expert
layers FOLLOW another computation's expert choices while reporting their own
beside them, so that a near-tie decided the other way shows as one differing
choice and not as every number downstream of it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

#: the parameter groups a comparison reports, by leaf path
GROUPS = ("embedding", "head", "mla_down", "mla_up", "router", "experts",
          "shared_and_dense", "mtp_merge", "norms")


def group_of(path: str) -> str:
    """The group (one of ``GROUPS``) of a leaf, from its ``/``-joined path."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("enorm", "hnorm", "eh_proj"):
        return "mtp_merge"
    if "/mla/" in path:
        down = leaf in ("wqa", "wkva", "q_norm", "kv_norm")
        return "mla_down" if down else "mla_up"
    if "router" in path:
        return "router"
    if "shared" in path or "/mlp/" in path:
        return "shared_and_dense"
    if "/moe/" in path:
        return "experts"
    if "norm" in leaf:
        return "norms"
    return "head" if leaf == "lm_head" else "embedding"


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
    """``x`` (T, heads, d): rotate each head's features by position, pairing
    feature ``2i`` with ``2i + 1`` (interleaved), pair ``i`` at the rate
    ``theta ** (-2 i / d)``."""
    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def mla(p, u, model, mm, block_rows: int = 0):
    """``u`` (T, E) normed activations -> the mixer's output (T, E).
    ``block_rows`` queries are scored at a time against every key (all of them
    for 0, or where it does not divide T), one block after the other, each
    recomputed in the backward pass: the same sums."""
    t = u.shape[0]
    h, rkv = model["num_attention_heads"], model["kv_lora_rank"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    assert model["rope_interleave"] and model["rope_scaling"] is None

    cq = rms_norm(mm(u, p["wqa"]), p["q_norm"], eps)
    q = mm(cq, p["wqb"]).reshape(t, h, dn + dr)
    down = mm(u, p["wkva"])
    ckv = rms_norm(down[:, :rkv], p["kv_norm"], eps)
    kv = mm(ckv, p["wkvb"]).reshape(t, h, dn + dv)
    k_rope = rope(down[:, None, rkv:], theta)                  # one head
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.tile(k_rope, (1, h, 1))], axis=-1)
    v = kv[..., dn:]

    def rows(first, q):
        n = q.shape[0]
        causal = jnp.arange(t)[None, :] <= (first + jnp.arange(n))[:, None]
        s = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) / math.sqrt(dn + dr)
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return mm(a, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(n, h * dv)

    step = block_rows if block_rows and t % block_rows == 0 else t
    n = t // step
    out = lax.map(lambda x: jax.checkpoint(rows)(*x),
                  (jnp.arange(n) * step, q.reshape(n, step, h, dn + dr)))
    return mm(out.reshape(t, h * dv), p["wo"])


def gated(p, u, mm):
    return mm(jax.nn.silu(mm(u, p["wg"])) * mm(u, p["wu"]), p["wdn"])


def moe(p, u, model, mm, given=None, shared: bool = True):
    """``u`` (T, E) -> ``(this share's part of the layer's output, e (T, k))``,
    ``e`` the router's own choices, largest biased score first.  ``given``
    (T, k) sends each token to those experts instead, weighted by the
    router's scores OF THOSE experts, renormalised over them.  ``shared``
    False leaves the shared expert out: what every chip computes alike is
    counted once when the shares of a layer are added up."""
    first, count = model["experts_held"]
    n = model["num_experts_per_tok"]
    assert model["scoring_func"] == "sigmoid" and model["topk_method"] == "noaux_tc"
    assert model["n_group"] == model["topk_group"] == 1
    s = jax.nn.sigmoid(mm(u, p["router"]["w"]))
    e = jnp.argsort(-(s + lax.stop_gradient(p["router"]["bias"])), axis=-1,
                    stable=True)[:, :n]
    to = e if given is None else given
    gate = jnp.take_along_axis(s, to, axis=-1)                         # (T, k)
    if model["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate * model["routed_scaling_factor"]
    out = jnp.zeros_like(u)
    if shared and model["n_shared_experts"]:
        out = out + gated(p["shared"], u, mm)

    def one(out, held):                          # every held expert, densely
        i, w = held
        mine = jnp.sum(jnp.where(to == first + i, gate, 0.0), axis=-1,
                       keepdims=True)
        return out + mine * gated(w, u, mm), None

    out, _ = lax.scan(one, out, (jnp.arange(count),
                                 {k: p[k] for k in ("wg", "wu", "wdn")}))
    return out, e


def block(lp, x, model, mm, routing=None, block_rows: int = 0):
    """One layer: ``(x + MLA + FFN, its router's own choices (T, k), or None
    for a dense layer)``."""
    eps = model["rms_norm_eps"]
    x = x + mla(lp["mla"], rms_norm(x, lp["norm1"], eps), model, mm, block_rows)
    u = rms_norm(x, lp["norm2"], eps)
    if "mlp" in lp:
        return x + gated(lp["mlp"], u, mm), None
    y, e = moe(lp["moe"], u, model, mm, routing)
    return x + y, e


def token_xent(h, head, targets, mm, block: int = 0):
    """Every position's cross entropy ``(T,)`` of normed states ``h`` (T, E)
    through the head (E, V).  ``block`` > 0 makes the logits ``block``
    positions at a time, recomputed in the backward pass; the same numbers."""
    t = h.shape[0]
    step = block or t

    def part(hb, tb, w):
        lg = mm(hb, w)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return lse - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]

    return jnp.concatenate([jax.checkpoint(part)(
        h[a:a + step], targets[a:a + step], head) for a in range(0, t, step)])


def main_loss(last, final_norm, head, targets, model, mm, block: int = 0):
    """``mean_i CE_main`` of one example from the last layer's output ``last``
    (T, E)."""
    h = rms_norm(last, final_norm, model["rms_norm_eps"])
    return jnp.mean(token_xent(h, head, targets, mm, block))


def mtp_merge(m, emb, last, model, mm):
    """The module's input (T, E): ``[RMSNorm_e(emb) ; RMSNorm_h(last)]
    eh_proj``, ``emb`` the embeddings of the next tokens and ``last`` the
    trunk's last output before the final norm."""
    eps = model["rms_norm_eps"]
    both = jnp.concatenate([rms_norm(emb, m["enorm"], eps),
                            rms_norm(last, m["hnorm"], eps)], axis=-1)
    return mm(both, m["eh_proj"])


def mtp_head_loss(x, norm, head, targets, model, mm, block: int = 0):
    """``mean_{i <= T-2} CE_mtp`` from the module's block's output ``x``:
    position i predicts targets[i + 1]; the last one has nothing to predict."""
    h = rms_norm(x, norm, model["rms_norm_eps"])[:-1]
    return jnp.mean(token_xent(h, head, targets[1:], mm, block))


def mtp_loss(m, emb, last, head, targets, model, mm, routing=None,
             block_rows: int = 0):
    """``(mean_{i <= T-2} CE_mtp, the module's router's own choices)`` of one
    example."""
    x, e = block(m["block"], mtp_merge(m, emb, last, model, mm), model, mm,
                 routing, block_rows)
    return mtp_head_loss(x, m["norm"], head, targets, model, mm, block_rows), e


def loss(params, tokens, targets, model, operand_dtype=None, routing=None):
    """One example's objective, differentiable as a whole: ``(L_main +
    mtp_loss_weight L_mtp, (L_main, L_mtp))``.  ``routing``: per block (the
    trunk's layers, then the module's), ``(T, k)`` or None."""
    mm = _mm(operand_dtype)
    depth = len(params["layers"])
    routing = [None] * (depth + 1) if routing is None else routing
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens]
        for lp, to in zip(params["layers"], routing):
            x, _ = block(lp, x, model, mm, to)
        lm = main_loss(x, params["final_norm"], params["lm_head"], targets,
                       model, mm)
        mtp, _ = mtp_loss(params["mtp"], params["tok_embed"][targets], x,
                          params["lm_head"], targets, model, mm, routing[depth])
        return lm + model["mtp_loss_weight"] * mtp, (lm, mtp)


def expert_counts(choices, n_experts: int):
    """(Token, choice) pairs per expert over ALL ``n_experts``."""
    return jnp.zeros((n_experts,), jnp.int32).at[choices.reshape(-1)].add(1)


def bias_update(bias, counts, model):
    """The selection bias after a step whose (token, choice) pairs per expert
    were ``counts``: an expert above the mean load is chosen a little less."""
    c = counts.astype(jnp.float32)
    return bias + model["bias_update_rate"] * jnp.sign(jnp.mean(c) - c)


def loss_and_grads(params, tokens, targets, model, operand_dtype=None,
                   block_rows: int = 0, routing=None):
    """Batch ``(B, T)``: the mean of the examples' objectives, its gradients,
    and ``{"lm", "mtp"`` (the two parts' means), ``"choices"`` (the routers'
    own: per block, ``(B, T, k)`` or None)``}``.  ``routing`` (per block, ``(B,
    T, k)`` or None) makes every expert layer follow it (``block``).  One
    example at a time and, so that 8192 positions and one layer's program
    serve every layer of its kind, one LAYER at a time: the chain rule by hand
    (``jax.vjp`` of ``block`` from the kept layer inputs; the module and the
    main head each give the last state a cotangent, and the embedding gets the
    module's beside the trunk's), scores and head logits ``block_rows``
    positions at a time.  The same numbers as ``jax.value_and_grad(loss)``
    (tested)."""
    mm = _mm(operand_dtype)
    n, depth = tokens.shape[0], len(params["layers"])
    weight = model["mtp_loss_weight"]
    routing = [None] * (depth + 1) if routing is None else routing

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def layer(lp, x, to):
        return block(lp, x, model, mm, to, block_rows)

    def back(lp, x, to, ct):
        return jax.vjp(lambda lp_, x_: layer(lp_, x_, to)[0], lp, x)[1](ct)

    forward, backward = highest(layer), highest(back)
    head = highest(jax.value_and_grad(
        lambda x, w, hd, y: main_loss(x, w, hd, y, model, mm, block_rows),
        argnums=(0, 1, 2)))
    # the module's block is an expert layer: the layers' two programs serve it
    merge = highest(lambda m, emb, x: mtp_merge(m, emb, x, model, mm))
    merge_back = highest(lambda m, emb, x, ct: jax.vjp(
        lambda *a: mtp_merge(*a, model, mm), m, emb, x)[1](ct))
    module_head = highest(jax.value_and_grad(
        lambda x, w, hd, y: mtp_head_loss(x, w, hd, y, model, mm, block_rows),
        argnums=(0, 1, 2)))
    add = jax.jit(lambda acc, g, scale: jax.tree_util.tree_map(
        lambda a, b: a + b * scale, acc, g), donate_argnums=0)

    def zeros(tree):
        return jax.tree_util.tree_map(jnp.zeros_like, tree)

    lm = mtp = 0.0
    chosen = [[] for _ in range(depth + 1)]
    # added up layer by layer as the backward pass makes them: one layer's
    # gradients at a time beside the sum, not a second whole tree
    layers = [zeros(lp) for lp in params["layers"]]
    rest = zeros({k: params[k] for k in ("tok_embed", "lm_head", "final_norm",
                                         "mtp")})
    # the reference imports nothing of the program, its spans included: it is
    # a yardstick run once in set-up  # graftlint: disable=HOT02
    for i in range(n):
        tos = [None if to is None else to[i] for to in routing]
        xs = [params["tok_embed"][tokens[i]]]
        for j, lp in enumerate(params["layers"]):
            x, e = forward(lp, xs[-1], tos[j])
            xs.append(x)
            chosen[j].append(e)
        last = xs.pop()
        v, (ct, g_norm, g_head) = head(last, params["final_norm"],
                                       params["lm_head"], targets[i])
        m = params["mtp"]
        joined = {k: m[k] for k in ("enorm", "hnorm", "eh_proj")}
        emb = params["tok_embed"][targets[i]]
        merged = merge(joined, emb, last)
        x, e = forward(m["block"], merged, tos[depth])
        chosen[depth].append(e)
        v2, (ct_x, g_norm2, g_head2) = module_head(x, m["norm"],
                                                   params["lm_head"], targets[i])
        g_block, ct_merged = backward(m["block"], merged, tos[depth], ct_x)
        g_joined, g_emb, ct2 = merge_back(joined, emb, last, ct_merged)
        g_m = dict(g_joined, norm=g_norm2, block=g_block)
        ct = ct + weight * ct2
        for j in reversed(range(depth)):
            g_lp, ct = backward(params["layers"][j], xs.pop(), tos[j], ct)
            layers[j] = add(layers[j], g_lp, 1.0 / n)
        table = jnp.zeros_like(params["tok_embed"])
        rest = add(rest, {
            "tok_embed": table.at[tokens[i]].add(ct).at[targets[i]].add(
                weight * g_emb),
            "lm_head": g_head + weight * g_head2, "final_norm": g_norm,
            "mtp": jax.tree_util.tree_map(lambda g: weight * g, g_m)}, 1.0 / n)
        lm, mtp = lm + v / n, mtp + v2 / n
    grads = dict(rest, layers=layers)
    return lm + weight * mtp, grads, {
        "lm": lm, "mtp": mtp,
        "choices": [None if c[0] is None else jnp.stack(c) for c in chosen]}


def warmup_cosine(step: int, peak: float, warmup: int, total: int) -> float:
    """The learning rate of step ``step`` (counted from 0): a linear warm-up
    from zero over ``warmup`` steps, then a cosine down to zero at ``total``."""
    if step < warmup:
        return peak * step / warmup
    frac = min(max((step - warmup) / (total - warmup), 0.0), 1.0)
    return 0.5 * peak * (1.0 + math.cos(math.pi * frac))


def adamw_step(p, m, v, g, step: int, lr: float, decay: float, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8):
    """One leaf through one step of AdamW (Loshchilov & Hutter; the decay
    decoupled and scaled by the learning rate): ``(p', m', v')`` from the
    leaf ``p``, its moments ``m``, ``v`` and its gradient ``g`` at step
    ``step`` (counted from 0).  ``decay`` is 0 for a leaf that takes none."""
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    m_hat = m / (1.0 - b1 ** (step + 1))
    v_hat = v / (1.0 - b2 ** (step + 1))
    return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + decay * p), m, v


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
