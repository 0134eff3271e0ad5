"""``models/hybrid.py`` (the ZAYA1 layer: CCA mixer, top-1 routed experts)
against the plain reference ``models/reference/zaya.py`` at a small size on
the CPU, seeded random weights, float32 at the highest matmul precision:
logits, loss and every gradient leaf; each part of CCA moves the output;
routing loses no token under any imbalance; the shares add up to the uncut
layer; the trainer's two ways of taking per-example losses agree.  What the
tests read of the model's program is one compiled program (``case``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models.reference import zaya as ref
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   lm_head_loss,
                                                   lm_head_token_loss)
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import local_mesh
from hybrid_common import leaf_names, named, seeded_params

E, H, G, D, R, F, V, SEQ, BATCH = 64, 4, 2, 16, 32, 48, 512, 32, 3
N_EXPERTS, HELD = 8, (0, 4)


def config(held=HELD, n_layers=2, xent_chunk=16, dtype=jnp.float32):
    base = TransformerConfig(
        vocab_size=V, d_model=E, n_heads=H, n_kv_heads=G, n_layers=n_layers,
        d_ff=F, max_len=SEQ, causal=True, dtype=dtype,
        param_dtype=jnp.float32, remat=False, xent_chunk=xent_chunk)
    layer = (hybrid.CCA(H, G, D), hybrid.MoE(N_EXPERTS, held, R, F))
    return hybrid.HybridConfig(base=base, layers=(layer,) * n_layers)


def model(held=HELD):
    return {"num_attention_heads": H, "num_key_value_heads": G, "head_dim": D,
            "rms_norm_eps": 1e-5, "rope_theta": 5e6,
            "partial_rotary_factor": 0.5, "experts_held": list(held)}


@pytest.fixture(scope="module")
def case():
    """The model beside the reference: what the tests read of the program is
    ONE compiled program."""
    cfg = config()
    params = seeded_params(cfg)
    toks = jax.random.randint(jax.random.key(7), (BATCH, SEQ), 0, V)
    tgts = jnp.roll(toks, -1, axis=1)

    def program(params):
        return (jax.value_and_grad(hybrid.lm_loss)(params, toks, tgts, cfg),
                hybrid.forward(params, toks, cfg), hybrid.encode(params, toks, cfg)[1],
                hybrid.lm_loss_per_example(params, toks, tgts, cfg),
                hybrid.routing_stats(params, toks, cfg))

    with jax.default_matmul_precision("highest"):
        (loss, grads), logits, choices, rows, counts = jax.jit(program)(params)
        ref_loss, ref_grads, ref_e = ref.loss_and_grads(params, toks, tgts, model())
    return {"cfg": cfg, "params": params, "toks": toks, "tgts": tgts,
            "loss": loss, "grads": named(grads), "logits": logits,
            "choices": choices, "rows": rows, "counts": counts, "ref_loss": ref_loss,
            "ref_e": ref_e,
            "ref_grads": named(ref_grads)}


LEAVES = leaf_names(jax.eval_shape(
    lambda: hybrid.init_params(jax.random.key(0), config())))


def test_logits_match_the_reference(case):
    with jax.default_matmul_precision("highest"):
        one = jax.jit(lambda p, t: ref.logits(p, t, model()))
        want = jnp.stack([one(case["params"], t) for t in case["toks"]])
    np.testing.assert_allclose(case["logits"], want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1


def test_loss_and_routing_match_the_reference(case):
    assert abs(float(case["loss"]) - float(case["ref_loss"])) < 1e-5
    assert bool((jnp.stack(case["choices"], axis=1) == case["ref_e"]).all())
    # the comparison's own arithmetic: identical trees read 0 and 1
    same = ref.compare_grads(case["ref_grads"], case["ref_grads"])
    assert all(v["rel"] == 0 and abs(v["cos"] - 1) < 1e-6 for v in same.values())


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(case, leaf):
    got, want = case["grads"][leaf], case["ref_grads"][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, f"{leaf}: the reference's gradient is all zero"
    np.testing.assert_allclose(got, want, atol=2e-5 * max(scale, 1.0), rtol=2e-4)


def test_every_leaf_has_a_group():
    groups = {ref.group_of(name) for name in LEAVES}
    assert groups == set(ref.GROUPS)


# ------------------------------------------------------------------ CCA alone

def cca_case():
    spec = hybrid.CCA(H, G, D)
    p = spec.init(jax.random.key(3), E, jnp.float32)
    p["temp"] = jnp.asarray([0.7, 1.3], jnp.float32)
    u = jax.random.normal(jax.random.key(4), (2, SEQ, E))
    return spec, p, u


#: the mixer and the layer compiled once a spec (and a part left out)
cca = jax.jit(hybrid.cca_mixer, static_argnums=(0, 3), static_argnames=(
    "convs", "qk_mean", "value_shift", "rotary"))
moe = jax.jit(hybrid.moe_ffn, static_argnums=(0, 3))


def test_cca_matches_the_reference():
    spec, p, u = cca_case()
    with jax.default_matmul_precision("highest"):
        got = cca(spec, p, u, jnp.float32)
        one = jax.jit(lambda p, x: ref.cca(p, x, model(), jnp.matmul))
        want = jnp.stack([one(p, x) for x in u])
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("part", ["convs", "qk_mean", "value_shift", "rotary"])
def test_each_part_of_cca_moves_the_output(part):
    spec, p, u = cca_case()
    full = cca(spec, p, u, jnp.float32)
    without = cca(spec, p, u, jnp.float32, **{part: False})
    assert float(jnp.abs(full - without).max()) > 1e-3


def test_cca_is_causal():
    spec, p, u = cca_case()
    out = cca(spec, p, u, jnp.float32)
    later = u.at[:, SEQ // 2:].add(1.0)
    moved = cca(spec, p, later, jnp.float32)
    np.testing.assert_allclose(out[:, :SEQ // 2], moved[:, :SEQ // 2], atol=1e-5)
    assert float(jnp.abs(out - moved)[:, SEQ // 2:].max()) > 1e-3


# -------------------------------------------------------------------- routing

def forced_router(p, expert: int):
    """Router weights that send every token to ``expert``: a large second
    bias makes the last hidden layer positive, and only one column reads it."""
    r = dict(p["router"])
    r["b2"] = jnp.full_like(r["b2"], 5.0)
    r["w2"] = jnp.zeros_like(r["w2"])
    r["w3"] = jnp.zeros_like(r["w3"]).at[:, expert].set(1.0)
    return dict(p, router=r)


@pytest.mark.parametrize("held,expert", [((0, 4), 2), ((0, 4), 0), ((4, 4), 7),
                                         ((0, 4), 6), ((4, 4), 1)])
def test_forced_imbalance_loses_no_token(held, expert):
    """All tokens to one expert (the others get none): every token comes back
    with that expert's output where it is held, zero where it is not."""
    spec = hybrid.MoE(N_EXPERTS, held, R, F)
    p = forced_router(spec.init(jax.random.key(5), E, jnp.float32), expert)
    u = jax.random.normal(jax.random.key(6), (2, SEQ, E))
    with jax.default_matmul_precision("highest"):
        got, e = moe(spec, p, u, jnp.float32)
        one = jax.jit(lambda p, x: ref.moe(p, x, model(held), jnp.matmul)[0])
        want = jnp.stack([one(p, x) for x in u])
    assert bool((e == expert).all())
    np.testing.assert_allclose(got, want, atol=2e-5)
    local = held[0] <= expert < held[0] + held[1]
    assert bool((jnp.abs(got).sum(-1) > 0).all()) == local
    counts = hybrid.expert_counts(spec, e)
    assert int(counts[expert]) == 2 * SEQ and int(counts.sum()) == 2 * SEQ


def test_shares_add_up_to_the_uncut_layer():
    """What share 0 (experts 0-3) and share 1 (experts 4-7) each give, added,
    is the whole layer's output as the uncut reference computes it."""
    whole = hybrid.MoE(N_EXPERTS, (0, N_EXPERTS), R, F)
    p = whole.init(jax.random.key(8), E, jnp.float32)
    u = jax.random.normal(jax.random.key(9), (2, SEQ, E))
    half = N_EXPERTS // 2
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for first in (0, half):
            share = dict(p, **{k: p[k][first:first + half]
                               for k in ("wg", "wu", "wdn")})
            out, e = moe(hybrid.MoE(N_EXPERTS, (first, half), R, F), share, u,
                         jnp.float32)
            assert 0 < int(((e >= first) & (e < first + half)).sum()) < e.size
            total = total + out
        one = jax.jit(lambda p, x: ref.moe(p, x, model((0, N_EXPERTS)), jnp.matmul)[0])
        want = jnp.stack([one(p, x) for x in u])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert bool((jnp.abs(want).sum(-1) > 0).all())


def test_routing_stats_publish_counters(case):
    cfg, counts = case["cfg"], np.asarray(case["counts"])
    assert counts.shape == (2, N_EXPERTS) and (counts.sum(axis=1) == BATCH * SEQ).all()
    METRICS.reset()
    out = hybrid.publish_routing_stats(counts, cfg)
    c = METRICS.snapshot()["counters"]
    assert c["moe.tokens_total"] == 2 * BATCH * SEQ
    assert c["moe.tokens_local"] == counts[:, :4].sum()
    assert c["moe.expert_load.l1.e3"] == counts[1, 3]
    assert "moe.expert_load.l0.e4" not in c
    assert out["local_share"] == counts[:, :4].sum() / counts.sum()
    held = counts[:, :4].sum(axis=0)
    assert out["load_max_over_mean"] == pytest.approx(held.max() / held.mean())


# --------------------------------------------------------- loss and the trainer

@pytest.mark.parametrize("chunk", [0, 16, 24, 4096])
def test_per_example_head_loss_is_the_mean_loss(chunk):
    cfg = config(xent_chunk=chunk).base
    params = {"tok_embed": 0.02 * jax.random.normal(jax.random.key(0), (V, E))}
    h = jax.random.normal(jax.random.key(1), (BATCH, SEQ, E))
    tgts = jax.random.randint(jax.random.key(2), (BATCH, SEQ), 0, V)
    per = lm_head_loss(params, h, tgts, cfg, per_example=True)
    assert per.shape == (BATCH,)
    rows = jnp.stack([lm_head_loss(params, h[i:i + 1], tgts[i:i + 1], cfg)
                      for i in range(BATCH)])
    np.testing.assert_allclose(per, rows, rtol=1e-5)
    np.testing.assert_allclose(per.mean(), lm_head_loss(params, h, tgts, cfg),
                               rtol=1e-5)
    g1 = jax.grad(lambda p: lm_head_loss(p, h, tgts, cfg, per_example=True).mean())(params)
    g2 = jax.grad(lambda p: lm_head_loss(p, h, tgts, cfg))(params)
    np.testing.assert_allclose(g1["tok_embed"], g2["tok_embed"], atol=1e-6)


def head_case(dtype, chunk):
    cfg = config(xent_chunk=chunk, dtype=dtype).base
    params = {"tok_embed": 0.5 * jax.random.normal(jax.random.key(0), (V, E))}
    h = jax.random.normal(jax.random.key(1), (4, SEQ, E))
    tgts = jax.random.randint(jax.random.key(2), (4, SEQ), 0, V)
    return cfg, params, h, tgts


def weighted_head_loss(cfg, tgts):
    """``(loss, (d tok_embed, d h))`` of the rows' losses under the weights
    ``w``, which arrive at run time as the trainer's ``mask / n_valid`` does."""
    return jax.jit(jax.value_and_grad(
        lambda p, h, w: (lm_head_loss(p, h, tgts, cfg, per_example=True) * w).sum(),
        (0, 1)))


COTANGENTS = {"equal": np.full(4, 0.25, np.float32),
              "padded": np.array([1, 1, 1, 0], np.float32) / 3,
              "random": np.asarray(jax.random.normal(jax.random.key(3), (4,)))}


@pytest.mark.parametrize("weights", COTANGENTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_chunked_head_loss_takes_any_cotangent(dtype, weights):
    """The chunked per-example loss makes the head's gradients in its forward
    scan and scales them when the rows' cotangents are equal (a mean over a
    full batch), and recomputes the chunks when they are not (a padded batch,
    any other weights): loss and both gradients are the unchunked path's."""
    w = jnp.asarray(COTANGENTS[weights])
    cfg, params, h, tgts = head_case(dtype, 16)
    loss, (d_p, d_h) = weighted_head_loss(cfg, tgts)(params, h, w)
    want, (w_p, w_h) = weighted_head_loss(
        dataclasses.replace(cfg, xent_chunk=0), tgts)(params, h, w)
    got = (loss, d_p["tok_embed"], d_h)
    want = (want, w_p["tok_embed"], w_h)
    assert all(a.dtype == b.dtype for a, b in zip(got, want))
    if dtype == jnp.float32:
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    else:
        for a, b in zip(got, want):
            assert (np.linalg.norm(np.asarray(a - b, np.float32))
                    <= 0.01 * np.linalg.norm(np.asarray(b, np.float32)))


@pytest.mark.parametrize("chunk,path", [(16, "fused"), (0, "plain"),
                                        (4096, "plain")])
def test_head_loss_counts_its_path_once_a_trace(chunk, path):
    cfg, params, h, tgts = head_case(jnp.float32, chunk)
    step = weighted_head_loss(cfg, tgts)
    counts = lambda: {k: METRICS.snapshot()["counters"].get(
        f"lm_head_loss.path.{k}", 0) for k in ("fused", "plain", "weighted")}
    before = counts()
    for _ in range(2):                      # the second call traces nothing
        step(params, h, jnp.full(4, 0.25))
    after = counts()
    assert {k: after[k] - before[k] for k in after} == {
        "fused": int(path == "fused"), "plain": int(path == "plain"),
        "weighted": 0}                      # no weights handed in: ZAYA's call


def head_products(jaxpr, branch=None):
    """How many ``dot_general``s with the vocabulary on a side ``jaxpr`` holds,
    sub-programs included; of a ``cond`` the one ``branch``, or none of it."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            n += any(V in v.aval.shape for v in (*eqn.invars, *eqn.outvars))
        if eqn.primitive.name != "cond":
            subs = jax.core.jaxprs_in_params(eqn.params)
        else:
            subs = [] if branch is None else [eqn.params["branches"][branch].jaxpr]
        n += sum(head_products(sub, branch) for sub in subs)
    return n


def test_equal_cotangents_cost_three_head_products_a_chunk():
    """Forward scan: logits, ``dh`` and ``dW`` of each chunk, and nothing of
    the head's shape on the equal-cotangent side of the backward; the other
    side recomputes the logits and makes ``dW`` again (the unfused chunked
    loss needs four: the logits twice)."""
    cfg, params, h, tgts = head_case(jnp.bfloat16, 16)
    jaxpr = jax.make_jaxpr(weighted_head_loss(cfg, tgts))(
        params, h, jnp.full(4, 0.25)).jaxpr
    assert head_products(jaxpr) == 3
    # lax.cond(pred, scaled, recomputed) lists the false branch first
    assert head_products(jaxpr, branch=1) == 3
    assert head_products(jaxpr, branch=0) >= 3 + 2


def test_looped_objective_costs_three_head_products_a_chunk():
    """The same count for the looped model's whole step: the exit
    distribution goes into the head's call as the tokens' weights, so the
    program outside the ``cond`` holds logits, ``dh`` and ``dW`` of each chunk
    and the branch a full batch takes holds no product of the head's shape;
    the recomputed chunks are compiled on the other branch only."""
    base = dataclasses.replace(config().base, tie_embeddings=False, remat=True)
    cfg = hybrid.HybridConfig(
        base=base, layers=((hybrid.Attention(H, G, D, 1e6), hybrid.GatedMLP(F)),),
        norm_eps=1e-6, n_loops=4, exit_beta=0.1)
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    toks = jnp.zeros((BATCH, SEQ), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: hybrid.looped_lm_loss_per_example(p, toks, toks, cfg).mean()
    ))(params).jaxpr
    assert head_products(jaxpr) == 3
    assert head_products(jaxpr, branch=1) == 3
    assert head_products(jaxpr, branch=0) >= 3 + 2


def count_eqns(jaxpr, name):
    """How many ``name`` equations ``jaxpr`` holds, sub-programs included."""
    return sum((eqn.primitive.name == name)
               + sum(count_eqns(sub, name)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def test_unweighted_head_loss_multiplies_by_no_weights():
    """``lm_head_loss(per_example=True)`` hands the chunked loss no weights,
    and its program holds the multiplies it held before the loss took any (5:
    the rows' weights outside and their transpose, ``dh * g``, ``g0 * dW``,
    and the softmax's own in the recomputed chunks' backward); the weighted
    call holds more (the weight in the scan's body, ``w * xent``, ``g *
    xent``)."""
    cfg, params, h, tgts = head_case(jnp.bfloat16, 16)
    rows = jax.make_jaxpr(weighted_head_loss(cfg, tgts))(
        params, h, jnp.full(4, 0.25)).jaxpr
    assert count_eqns(rows, "mul") == 5
    tokens = jax.make_jaxpr(jax.grad(
        lambda p, h_, w: lm_head_token_loss(p, h_, tgts, cfg, weights=w)[0].sum(),
        (0, 1)))(params, h, jnp.full((4, SEQ), 0.25)).jaxpr
    assert count_eqns(tokens, "mul") > 5
    assert head_products(tokens, branch=1) == 3


def test_loss_is_the_same_row_by_row_and_as_a_whole_batch(case):
    """What the trainer's singleton ``vmap`` would compute for each row (one
    example's tokens grouped alone; ``lax.ragged_dot`` itself has no batching
    rule for it) is what the whole batch grouped at once gives for that row."""
    cfg, params, toks, tgts = (case[k] for k in ("cfg", "params", "toks", "tgts"))
    whole, one = case["rows"], jax.jit(hybrid.lm_loss, static_argnums=3)
    rows = jnp.stack([one(params, toks[i:i + 1], tgts[i:i + 1], cfg)
                      for i in range(BATCH)])
    np.testing.assert_allclose(whole, rows, rtol=2e-5)
    assert float(whole.mean()) == pytest.approx(float(case["loss"]), rel=1e-6)


def test_trainer_takes_per_example_losses_either_way():
    """One step through ``DataParallelTrainer`` on the dense model: a loss
    that returns the rows' losses for the whole batch, and the same loss as a
    mean under the trainer's singleton ``vmap``, give the same loss and the
    same update, on a padded batch (3 rows in a bucket of 4) too."""
    from deeplearning4j_tpu.models.transformer import (encode_local,
                                                       init_params,
                                                       lm_loss_local)
    cfg = config().base
    tx = T.adamw(1e-3, weight_decay=0.0)
    toks = np.asarray(jax.random.randint(jax.random.key(7), (4, SEQ), 0, V))
    tgts = np.roll(toks, -1, axis=1)

    def rows_loss(p, x, y, key=None):
        return lm_head_loss(p, encode_local(p, x, cfg), y, cfg, per_example=True)

    def mean_loss(p, x, y, key=None):
        return lm_loss_local(p, x, y, cfg)

    results = []
    for fn, per_example in ((rows_loss, True), (mean_loss, False)):
        trainer = DataParallelTrainer(fn, tx, mesh=local_mesh(1),
                                      per_example_loss=per_example)
        state = trainer.init_state(init_params(jax.random.key(0), cfg))
        state, full = trainer.step(state, toks, tgts)
        state, padded = trainer.step(state, toks[:3], tgts[:3])
        results.append((float(full), float(padded), state.params))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    assert results[0][1] == pytest.approx(results[1][1], rel=1e-5)
    for a, b in zip(*(jax.tree_util.tree_leaves(r[2]) for r in results)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_trainer_steps_the_hybrid_model(case):
    """The hybrid model through ``DataParallelTrainer(per_example_loss=True)``
    with a padded batch: the step's loss is the mean of the real rows' losses
    and every parameter group moves."""
    cfg, toks, tgts = case["cfg"], np.asarray(case["toks"]), np.asarray(case["tgts"])
    trainer = DataParallelTrainer(
        lambda p, x, y, key=None: hybrid.lm_loss_per_example(p, x, y, cfg),
        T.adamw(1e-3, weight_decay=0.0), mesh=local_mesh(1),
        per_example_loss=True)
    state = trainer.init_state(case["params"])
    trainer._bucket_size(4)                 # nominal batch 4: 3 rows get padded
    state, loss = trainer.step(state, toks, tgts)
    assert float(loss) == pytest.approx(float(case["loss"]), rel=1e-5)
    moved = ref.compare_grads(state.params, case["params"])
    assert all(v["rel"] > 0 for v in moved.values()), moved


def test_bf16_compute_stays_near_the_reference(case):
    """The configuration's precision (bf16 matmuls over f32 parameters): the
    loss within a few thousandths of the float32 reference's."""
    cfg = config(dtype=jnp.bfloat16)
    loss = jax.jit(hybrid.lm_loss, static_argnums=3)(
        case["params"], case["toks"], case["tgts"], cfg)
    assert abs(float(loss) - float(case["ref_loss"])) < 0.02


# ------------------------------------------------------------ names in a trace

def test_sublayer_names_in_the_lowered_step():
    """Every matmul, sort, gather and scatter of the hybrid step lowers under
    one of the trainer's sublayer names, and the parts of the expert layer and
    of CCA under their own scopes inside ``ffn`` / ``qkv_proj``: what the
    benchmark's ``scope_share`` and ``scope_split`` readers attribute by."""
    import re
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmark.readers.scope_share import SUBLAYERS
    from benchmark.trace_spans import scope_of

    cfg = config(n_layers=1)
    cfg = hybrid.HybridConfig(base=dataclasses.replace(cfg.base, remat=True),
                              layers=cfg.layers)
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    x = jnp.zeros((2, SEQ), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p: hybrid.lm_loss(p, x, x, cfg))).lower(params).compiler_ir(
            dialect="hlo").as_hlo_module().to_string()
    paths = {}
    for line in text.splitlines():
        op = re.search(r"= \S+ (dot|sort|gather|scatter|ragged-dot)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if op and name:
            paths.setdefault(name.group(1), op.group(1))
    assert len(paths) > 20
    # jit-wrapped calls (``jnp.take``, ``einsum``) keep their own short
    # metadata as well as the inlined, fully scoped copy: read the latter
    paths = {p: op for p, op in paths.items() if p.startswith("jit(")}
    outer = {path: scope_of(path, SUBLAYERS) for path in paths}
    lost = [p for p, (scope, _, _) in outer.items() if scope is None]
    assert not lost, lost
    parts = {"moe.router": "ffn", "moe.dispatch": "ffn", "moe.experts": "ffn",
             "cca.mix": "qkv_proj"}
    for part, sublayer in parts.items():
        under = [p for p in paths if f"/{part}/" in p]
        assert under, part
        assert all(outer[p][:2] == (sublayer, part) for p in under), part
        assert any(outer[p][2] for p in under) and not all(outer[p][2] for p in under)
        # the reader that takes its names from its arguments finds them alone
        assert all(scope_of(p, (part,))[0] == part for p in under)
    assert {outer[p][0] for p in paths} >= {"qkv_proj", "attention",
                                            "attn_out", "ffn"}
    every = {scope_of(p, SUBLAYERS)[0]
             for p in re.findall(r'op_name="([^"]*)"', text)}
    assert every >= {"embed", "layernorm", "lm_head_loss"}


def test_experts_are_placed_by_load():
    """``place_experts`` reorders each router's output columns so that the
    held half carries about half of the batch's tokens, whatever the random
    router prefers; nothing but the columns' order changes."""
    cfg = config()
    params = seeded_params(cfg, seed=11)
    toks = jax.random.randint(jax.random.key(12), (8, SEQ), 0, V)
    stats = jax.jit(hybrid.routing_stats, static_argnums=2)
    before = np.asarray(stats(params, toks, cfg))
    placed = hybrid.place_experts(params, toks, cfg)
    after = np.asarray(stats(placed, toks, cfg))
    share = after[:, :4].sum(axis=1) / after.sum(axis=1)
    worst = np.abs(before[:, :4].sum(axis=1) / before.sum(axis=1) - 0.5).max()
    assert np.abs(share - 0.5).max() < 0.1 < worst, (share, worst)
    for old, new in zip(params["layers"], placed["layers"]):
        a, b = np.asarray(old["moe"]["router"]["w3"]), np.asarray(new["moe"]["router"]["w3"])
        assert sorted(map(tuple, a.T.round(6))) == sorted(map(tuple, b.T.round(6)))
        np.testing.assert_array_equal(old["moe"]["wg"], new["moe"]["wg"])
    np.testing.assert_array_equal(sorted(after[0]), sorted(before[0]))
