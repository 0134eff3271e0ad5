"""``models/hybrid.py``'s looped family (``Attention`` mixer, ``GatedMLP`` ffn,
sandwich norms, ``n_loops`` passes over the same layers, an exit gate and the
exit-weighted objective) against the plain reference
``models/reference/ouro.py`` at a small size on the CPU, seeded random
weights, float32 at the highest matmul precision: objective, every loop
step's loss, the exit distribution and every gradient leaf; a shared layer's
gradient is the sum over its uses; one loop step without a gate is one plain
pass; each part moves the output; any mixer goes with any ffn; the head's
chunked loss takes token weights; the trainer steps it.  What the tests read
of the model's program is one compiled program (``case``).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models.reference import ouro as ref
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   lm_head_token_loss)
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import local_mesh
from hybrid_common import leaf_names, named, seeded_params

E, H, G, D, F, V, SEQ, BATCH, LAYERS, LOOPS = 64, 4, 2, 16, 96, 512, 32, 3, 2, 4
BETA = 0.1


def config(n_layers=LAYERS, n_loops=LOOPS, beta=BETA, xent_chunk=16,
           dtype=jnp.float32, remat=True, mixer=None, ffn=None, tied=False):
    base = TransformerConfig(
        vocab_size=V, d_model=E, n_heads=H, n_kv_heads=G, n_layers=n_layers,
        d_ff=F, max_len=SEQ, causal=True, tie_embeddings=tied, dtype=dtype,
        param_dtype=jnp.float32, remat=remat, xent_chunk=xent_chunk)
    layer = (mixer or hybrid.Attention(H, G, D, 1e6), ffn or hybrid.GatedMLP(F))
    return hybrid.HybridConfig(base=base, layers=(layer,) * n_layers,
                               norm_eps=1e-6, n_loops=n_loops, exit_beta=beta)


def model(n_layers=LAYERS, n_loops=LOOPS, beta=BETA):
    return {"num_attention_heads": H, "num_key_value_heads": G, "head_dim": D,
            "rms_norm_eps": 1e-6, "rope_theta": 1e6, "exit_beta": beta,
            "num_hidden_layers": n_layers, "total_ut_steps": n_loops}


def batch(seed=7, n=BATCH):
    toks = jax.random.randint(jax.random.key(seed), (n, SEQ), 0, V)
    return toks, jnp.roll(toks, -1, axis=1)


def mean_objective(params, toks, tgts, cfg):
    return hybrid.looped_lm_loss_per_example(params, toks, tgts, cfg).mean()


@pytest.fixture(scope="module")
def case():
    """The model beside the reference: what the tests read of the program is
    ONE compiled program."""
    cfg = config()
    params = seeded_params(cfg)
    toks, tgts = batch()

    def program(params):
        return (jax.value_and_grad(mean_objective)(params, toks, tgts, cfg),
                hybrid.looped_losses(params, toks, tgts, cfg)[1:])

    with jax.default_matmul_precision("highest"):
        (loss, grads), (xent, log_p) = jax.jit(program)(params)
        ref_loss, ref_grads, ref_aux = ref.loss_and_grads(
            params, toks, tgts, model(), block_rows=8)
    return {"cfg": cfg, "params": params, "toks": toks, "tgts": tgts,
            "loss": loss, "xent": xent, "log_p": log_p,
            "grads": named(grads), "ref_loss": ref_loss, "ref_aux": ref_aux,
            "ref_grads": named(ref_grads)}


LEAVES = leaf_names(jax.eval_shape(
    lambda: hybrid.init_params(jax.random.key(0), config())))


# ------------------------------------------------------- against the reference

def test_objective_and_step_losses_match_the_reference(case):
    assert float(case["loss"]) == pytest.approx(float(case["ref_loss"]), rel=2e-6)
    np.testing.assert_allclose(case["xent"].mean(axis=(1, 2)),
                               case["ref_aux"]["xent"], rtol=2e-6)
    # later loop steps are not the first one again
    assert len(set(np.asarray(case["ref_aux"]["xent"]).round(4))) == LOOPS


def test_exit_distribution_matches_the_reference(case):
    p = jnp.exp(case["log_p"])
    np.testing.assert_allclose(p.mean(axis=(1, 2)), case["ref_aux"]["exit"],
                               rtol=1e-5)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    assert float(p.min()) > 0


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(case, leaf):
    got, want = case["grads"][leaf], case["ref_grads"][leaf]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_every_leaf_has_a_group():
    groups = {name: ref.group_of(name) for name in LEAVES}
    assert set(groups.values()) == set(ref.GROUPS)
    assert groups["layers/0/attn/wq"] == "attention_projections"
    assert groups["layers/1/mlp/wdn"] == "ffn"
    assert groups["layers/0/norm2_post"] == groups["final_norm"] == "norms"
    assert (groups["lm_head"], groups["tok_embed"]) == ("head", "embedding")
    assert groups["exit_gate/w"] == groups["exit_gate/b"] == "exit_gate"


def test_layer_by_layer_gradients_are_the_whole_models(case):
    """``loss_and_grads`` (chain rule by hand over 8 layer applications and 4
    closing norms, head in blocks) against ``jax.value_and_grad`` of the whole
    ``loss``."""
    params, toks, tgts = case["params"], case["toks"][:2], case["tgts"][:2]
    total, grads, aux = ref.loss_and_grads(params, toks, tgts, model(), block_rows=8)
    whole_loss = jax.jit(jax.value_and_grad(
        lambda p, x, y: ref.loss(p, x, y, model()), has_aux=True))
    whole = [whole_loss(params, toks[i], tgts[i]) for i in range(2)]
    assert float(total) == pytest.approx(
        sum(float(v) for (v, _), _ in whole) / 2, rel=1e-6)
    np.testing.assert_allclose(
        aux["exit"], (whole[0][0][1][1] + whole[1][0][1][1]) / 2, rtol=1e-5)
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, whole[0][1], whole[1][1])
    same = ref.compare_grads(grads, mean)
    assert all(v["rel"] < 1e-5 for v in same.values()), same


def test_shared_layers_gradient_is_the_sum_over_its_uses(case):
    """The reference run UNTIED — loop step ``t`` over its own copy of the
    layers, 8 distinct layers with equal values — gives each copy a gradient
    of its own; the program's gradient of a shared layer is their sum."""
    params, toks, tgts = case["params"], case["toks"], case["tgts"]
    untied = dict(params, layers=list(params["layers"]) * LOOPS)
    with jax.default_matmul_precision("highest"):
        loss, grads, _ = ref.loss_and_grads(untied, toks, tgts, model(), block_rows=8)
    assert float(loss) == pytest.approx(float(case["ref_loss"]), rel=1e-6)
    copies = [grads["layers"][t * LAYERS:(t + 1) * LAYERS] for t in range(LOOPS)]
    # the uses differ: no copy's gradient is a quarter of the sum
    first, last = copies[0][0]["attn"]["wq"], copies[-1][0]["attn"]["wq"]
    assert float(jnp.linalg.norm(first - last)) > 0.1 * float(jnp.linalg.norm(first))
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *copies)
    for name, got in zip(leaf_names({"layers": summed}),
                         jax.tree_util.tree_leaves(summed)):
        want = case["grads"][name]
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(want).max()))


def test_bf16_compute_stays_near_the_reference(case):
    """The configuration's precision (bf16 matmuls over f32 parameters): the
    objective within a few hundredths of the float32 reference's after 8
    layer applications."""
    cfg = config(dtype=jnp.bfloat16)
    loss = jax.jit(mean_objective, static_argnums=3)(
        case["params"], case["toks"], case["tgts"], cfg)
    assert abs(float(loss) - float(case["ref_loss"])) < 0.03


@pytest.fixture(scope="module")
def exact(case):
    """The first row's float32 reference gradients, for both precisions."""
    return ref.loss_and_grads(case["params"], case["toks"][:1], case["tgts"][:1],
                              model())[1]


@pytest.mark.parametrize("operand,looser", [("bfloat16", 1e-4), ("float8_e4m3fn", 1e-3)])
def test_lower_precision_operands_move_the_reference(case, exact, operand, looser):
    """The reading that sets the benchmark's limits: the same mathematics
    with every matrix product's operands rounded."""
    params, toks, tgts = case["params"], case["toks"][:1], case["tgts"][:1]
    _, rounded, _ = ref.loss_and_grads(params, toks, tgts, model(),
                                       operand_dtype=getattr(jnp, operand))
    err = ref.compare_grads(rounded, exact)
    assert all(v["rel"] > looser for v in err.values()), err


# ------------------------------------------------------------------- the loop

def test_one_loop_step_without_a_gate_is_one_plain_pass():
    cfg = config(n_loops=1, beta=None, remat=False)
    params = seeded_params(cfg)
    assert "exit_gate" not in params
    toks, tgts = batch()
    x = params["tok_embed"][toks]
    for i, lp in enumerate(params["layers"]):
        x, e, _ = hybrid.block(lp, x, cfg, i)
        assert e is None
    want = hybrid.rms_norm(x, params["final_norm"], cfg.norm_eps)
    got, choices = hybrid.encode(params, toks, cfg)
    np.testing.assert_array_equal(got, want)
    assert choices == [None] * LAYERS
    hs, _ = hybrid.encode_steps(params, toks, cfg)
    assert hs.shape == (1, BATCH, SEQ, E)
    # and the plain loss and logits of the family run on it, untied head
    loss = hybrid.lm_loss(params, toks, tgts, cfg)
    logits = hybrid.forward(params, toks, cfg)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgts[..., None], axis=-1)[..., 0]
    assert float(loss) == pytest.approx(float((lse - gold).mean()), rel=1e-5)


def test_loop_steps_chain_through_the_closing_norm(case):
    """Step ``t``'s state is one plain pass over step ``t - 1``'s normed
    state: the scan's body is the plain pass."""
    cfg, params, toks = case["cfg"], case["params"], case["toks"]
    hs, _ = hybrid.encode_steps(params, toks, cfg)
    once = dataclasses.replace(cfg, n_loops=1)
    x = params["tok_embed"][toks]
    for t in range(LOOPS):
        for i, lp in enumerate(params["layers"]):
            x, _, _ = hybrid.block(lp, x, once, i)
        x = hybrid.rms_norm(x, params["final_norm"], cfg.norm_eps)
        np.testing.assert_allclose(hs[t], x, rtol=1e-4, atol=1e-5)
    last, _ = hybrid.encode(params, toks, cfg)
    np.testing.assert_array_equal(last, hs[-1])


def test_the_loop_traces_each_layer_once(case):
    """One trace of the looped step holds ``LAYERS`` blocks, not ``LAYERS x
    LOOPS``: attention asked its path once a layer, and the jaxpr has one
    scan over the loop steps with ``LAYERS`` checkpointed blocks in it."""
    cfg = config(n_layers=3)                 # a shape no other test has traced
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    toks, tgts = batch()
    before = METRICS.snapshot()["counters"]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: mean_objective(p, toks, tgts, cfg)))(params)
    after = METRICS.snapshot()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in (
        "attention.path.xla", "attention.path.kernel", "loop.steps",
        "loop.layer_applications", "lm_head_loss.path.fused",
        "lm_head_loss.path.weighted")}
    assert moved == {"attention.path.xla": 3, "attention.path.kernel": 0,
                     "loop.steps": LOOPS, "loop.layer_applications": 3 * LOOPS,
                     "lm_head_loss.path.fused": 1,
                     "lm_head_loss.path.weighted": 1}
    scans = [e.params["jaxpr"].jaxpr.eqns for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "scan" and e.params["length"] == LOOPS]
    assert len(scans) == 2                   # the loop, forward and backward
    names = [[e.primitive.name for e in eqns] for eqns in scans]
    # forward: a layer's nine products (q, k, v, scores, values, out; gate,
    # up, down), each layer once; backward: each layer's checkpointed block
    assert names[0].count("dot_general") == 3 * 9 and "remat2" not in names[0]
    assert names[1].count("remat2") == 3


# ------------------------------------------------- exit distribution, objective

def test_exit_distribution_sums_to_one_for_any_gate():
    gate = {"w": jnp.zeros((E,)), "b": jnp.zeros(())}
    hs = jax.random.normal(jax.random.key(0), (LOOPS, 2, SEQ, E))
    p = jnp.exp(hybrid.exit_distribution(gate, hs))
    # a gate of one half: 1/2, 1/4, 1/8 and the rest; 1.875 steps expected
    np.testing.assert_allclose(p[:, 0, 0], [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    for scale in (0.3, 3.0, 30.0, 300.0):
        gate = {"w": scale * jax.random.normal(jax.random.key(1), (E,)),
                "b": jnp.asarray(0.5)}
        log_p = hybrid.exit_distribution(gate, hs)
        p = jnp.exp(log_p)
        np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-5)
        assert bool(jnp.isfinite(p * log_p).all())      # the entropy's terms


def test_a_gate_forced_open_at_the_first_step_gives_its_loss(case):
    cfg, toks, tgts = case["cfg"], case["toks"], case["tgts"]
    params = dict(case["params"], exit_gate={"w": jnp.zeros((E,)),
                                             "b": jnp.asarray(1e4)})
    losses = jax.jit(hybrid.looped_losses, static_argnums=3)     # run twice
    objective, xent, log_p = losses(params, toks, tgts, cfg)
    np.testing.assert_allclose(jnp.exp(log_p[0]), 1.0)
    np.testing.assert_allclose(objective, xent[0], rtol=1e-6)
    grads = jax.jit(jax.grad(mean_objective), static_argnums=3)(params, toks, tgts, cfg)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree_util.tree_leaves(grads))
    # shut until the last step: its loss alone
    params["exit_gate"]["b"] = jnp.asarray(-1e4)
    objective, xent, _ = losses(params, toks, tgts, cfg)
    np.testing.assert_allclose(objective, xent[-1], rtol=1e-6)


def test_a_larger_beta_raises_the_entropy_after_a_step_of_descent(case):
    params, toks, tgts = case["params"], case["toks"], case["tgts"]

    def entropy(p_, cfg):
        log_p = hybrid.looped_losses(p_, toks, tgts, cfg)[2]
        return -(jnp.exp(log_p) * log_p).sum(axis=0).mean()

    def after_a_step(params, cfg):               # one program a beta
        g = jax.grad(mean_objective)(params, toks, tgts, cfg)
        # only the gate moves: what beta pulls on
        moved = dict(params, exit_gate=jax.tree_util.tree_map(
            lambda a, b: a - 0.5 * b, params["exit_gate"], g["exit_gate"]))
        return entropy(moved, cfg)

    after = {beta: float(jax.jit(after_a_step, static_argnums=1)(
        params, config(beta=beta))) for beta in (0.0, 0.1, 1.0)}
    assert after[0.0] < after[0.1] < after[1.0], after


# -------------------------------------------------- each part moves the output

def without(part):
    """The configuration with ``part`` switched off.  The sandwich norms are
    what a spec says (``post_norm``, a field of both since PR 38)."""
    if part == "second norm":
        return config(mixer=hybrid.Attention(H, G, D, 1e6, post_norm=False))
    if part == "fourth norm":
        return config(ffn=hybrid.GatedMLP(F, post_norm=False))
    return config(mixer=hybrid.Attention(H, G, D, 1e6, rotary_factor=0.5))


@pytest.mark.parametrize("part", ["second norm", "fourth norm",
                                  "whole-head rotary"])
def test_each_part_moves_the_output(case, part):
    params, toks = case["params"], case["toks"]
    want, _ = hybrid.encode_steps(params, toks, case["cfg"])
    got, _ = hybrid.encode_steps(params, toks, without(part))
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) > 0.05


def test_the_final_norm_closes_every_loop_step(case):
    """The normed state is the next step's input: with the final norm's scale
    doubled the first step's output doubles and no more, while the later
    steps, whose input doubled under layers that add normed (unscaled)
    outputs to it, are no multiple of what they were.  A norm applied only to
    what is handed back would double every step alike."""
    cfg, params, toks = case["cfg"], case["params"], case["toks"]
    want, _ = hybrid.encode_steps(params, toks, cfg)
    got, _ = hybrid.encode_steps(
        dict(params, final_norm=2.0 * params["final_norm"]), toks, cfg)
    np.testing.assert_allclose(got[0], 2.0 * want[0], rtol=1e-5, atol=1e-6)
    for step in range(1, LOOPS):
        assert float(jnp.abs(got[step] - 2.0 * want[step]).max()) > 0.05


def test_attention_is_causal(case):
    cfg, params, toks = case["cfg"], case["params"], case["toks"]
    moved = toks.at[:, SEQ // 2:].set((toks[:, SEQ // 2:] + 1) % V)
    a, _ = hybrid.encode_steps(params, toks, cfg)
    b, _ = hybrid.encode_steps(params, moved, cfg)
    np.testing.assert_allclose(a[:, :, :SEQ // 2], b[:, :, :SEQ // 2], atol=1e-6)
    assert float(jnp.abs(a[:, :, SEQ // 2:] - b[:, :, SEQ // 2:]).max()) > 0.05


# ------------------------------------------------------- the registry is free

PAIRS = {"Attention+MoE": (hybrid.Attention(H, G, D, 1e6),
                           hybrid.MoE(8, (0, 4), 32, F)),
         "CCA+GatedMLP": (hybrid.CCA(H, 2, D), hybrid.GatedMLP(F))}


@pytest.mark.parametrize("pair", PAIRS)
@pytest.mark.parametrize("n_loops,tied", [(1, True), (2, False)])
def test_any_mixer_goes_with_any_ffn(pair, n_loops, tied):
    mixer, ffn = PAIRS[pair]
    cfg = config(mixer=mixer, ffn=ffn, n_loops=n_loops, tied=tied,
                 beta=None if n_loops == 1 else BETA)
    params = hybrid.init_params(jax.random.key(0), cfg)
    lp = params["layers"][0]
    assert {mixer.key, ffn.key, "norm1", "norm2"} <= set(lp)
    assert ("norm1_post" in lp, "norm2_post" in lp) == (mixer.post_norm,
                                                         ffn.post_norm)
    assert ("lm_head" in params) == (not tied)
    toks, tgts = batch()
    fn = (hybrid.lm_loss_per_example if n_loops == 1
          else hybrid.looped_lm_loss_per_example)
    moe = isinstance(ffn, hybrid.MoE)
    (hs, choices), stats, (loss, grads) = jax.jit(lambda p: (   # one program
        hybrid.encode_steps(p, toks, cfg),
        hybrid.routing_stats(p, toks, cfg) if moe else None,
        jax.value_and_grad(lambda p: fn(p, toks, tgts, cfg).mean())(p)))(params)
    assert hs.shape == (n_loops, BATCH, SEQ, E)
    if moe:
        assert all(e.shape == (n_loops, BATCH, SEQ) for e in choices)
        assert stats.shape == (LAYERS, 8)
    else:
        assert choices == [None] * LAYERS
    assert np.isfinite(float(loss))
    norms = {n: float(jnp.linalg.norm(g)) for n, g in
             zip(leaf_names(grads), jax.tree_util.tree_leaves(grads))}
    assert all(np.isfinite(v) for v in norms.values())
    assert norms[f"layers/0/{mixer.key}/wo"] > 0
    assert norms[f"layers/1/{ffn.key}/wdn"] > 0


def test_registries_hold_two_specs_each_and_block_names_neither():
    import inspect
    # (two each when PR 32 wrote this; PR 34 added the sparse mixer, PR 38
    # the latent-attention one)
    assert set(hybrid.MIXERS) == {hybrid.CCA, hybrid.Attention,
                                  hybrid.SparseAttention, hybrid.MLA}
    assert set(hybrid.FFNS) == {hybrid.MoE, hybrid.GatedMLP}
    source = inspect.getsource(hybrid.block) + inspect.getsource(hybrid.init_params)
    assert not re.search(r"cca|moe|attn|mlp|dsa", source, re.I)
    assert len({s.key for s in (*hybrid.MIXERS, *hybrid.FFNS)}) == 6


# ------------------------------------------------ the head under token weights

WEIGHTS = {"equal": np.full((4, SEQ), 1.0 / (4 * SEQ), np.float32),
           "by step": np.repeat(np.array([[0.4], [0.3], [0.2], [0.1]], np.float32),
                                SEQ, axis=1) / SEQ,
           "random": np.asarray(jax.random.uniform(jax.random.key(3), (4, SEQ)))}


def token_weighted(cfg, tgts, how):
    """``(loss, (d head, d h, d w))`` of the tokens' losses under the weights
    ``w``, handed to the chunked loss or applied to what it returns."""
    def loss(p, h, w):
        if how == "handed in":
            return lm_head_token_loss(p, h, tgts, cfg, weights=w)[0].sum()
        return (lm_head_token_loss(p, h, tgts, cfg) * w).sum()

    return jax.jit(jax.value_and_grad(loss, (0, 1, 2)))


@pytest.mark.parametrize("how", ["handed in", "applied outside"])
@pytest.mark.parametrize("weights", WEIGHTS)
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_chunked_token_losses_take_token_weights(weights, tied, how):
    """The weighted sum of the chunked per-token losses differentiates to what
    plain autodiff of the unchunked loss gives, for the head, the hidden
    states and the weights themselves.  Handed in, any weights go into the
    gradients made in the forward scan (the cotangent is one number).  Applied
    outside, equal weights take those gradients and unequal ones (a padded
    batch's) the recomputed chunks, ``_fused_xent``'s ``lax.cond`` decided at
    run time."""
    cfg = config(tied=tied).base
    head = "tok_embed" if tied else "lm_head"
    shape = (V, E) if tied else (E, V)
    params = {head: 0.5 * jax.random.normal(jax.random.key(0), shape)}
    h = jax.random.normal(jax.random.key(1), (4, SEQ, E))
    tgts = jax.random.randint(jax.random.key(2), (4, SEQ), 0, V)
    w = jnp.asarray(WEIGHTS[weights])
    loss, (d_p, d_h, d_w) = token_weighted(cfg, tgts, how)(params, h, w)
    want, (w_p, w_h, w_w) = token_weighted(
        dataclasses.replace(cfg, xent_chunk=0), tgts, "applied outside")(params, h, w)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for got, want_ in ((d_p[head], w_p[head]), (d_h, w_h), (d_w, w_w)):
        np.testing.assert_allclose(got, want_, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(want_).max()))
    if how == "handed in":      # the losses beside the weighted ones: plain
        np.testing.assert_allclose(
            lm_head_token_loss(params, h, tgts, cfg, weights=w)[1], w_w, rtol=1e-5)


# --------------------------------------------------- the trainer, names, counts

def trainer_for(cfg, tx=None):
    def loss(p, x, y, key=None):
        return hybrid.looped_lm_loss_per_example(p, x, y, cfg)

    return DataParallelTrainer(loss, tx or T.adamw(3e-3, weight_decay=0.0),
                               mesh=local_mesh(1), per_example_loss=True)


def test_trainer_steps_the_looped_model(case):
    """Through ``DataParallelTrainer(per_example_loss=True).fit``: the first
    loss is the objective, every group of parameters moves, the objective
    falls, one compile."""
    cfg = case["cfg"]
    trainer = trainer_for(cfg)
    state = trainer.init_state(case["params"])
    toks, tgts = np.asarray(case["toks"]), np.asarray(case["tgts"])
    before = METRICS.snapshot()["counters"].get("train_step.recompile", 0)
    state, losses = trainer.fit(state, [(toks, tgts)] * 12, resolve_every=4)
    assert METRICS.snapshot()["counters"]["train_step.recompile"] - before == 1
    assert losses[0] == pytest.approx(float(case["loss"]), rel=1e-5)
    assert losses[-1] < losses[0] - 0.5
    moved = ref.compare_grads(state.params, case["params"])
    assert all(v["rel"] > 0 for v in moved.values()), moved


def test_full_batches_take_the_stored_head_gradient(monkeypatch):
    """What ``_fused_xent``'s ``lax.cond`` decides at run time, read through a
    callback on its predicate: the looped objective through the trainer hands
    the head one number for every token and loop step on a full batch (the
    exit weights are inside the call), so the ``dW`` the forward scan made is
    taken; a padded batch (3 rows in a bucket of 4) hands ``mask / n_valid``
    and recomputes, with the weights.  Either way the step is plain
    autodiff's of the unchunked loss."""
    seen, cond = [], jax.lax.cond

    def spy(pred, true_fun, false_fun, *operands):
        if true_fun.__name__ == "scaled":
            jax.debug.callback(lambda took: seen.append(bool(took)), pred)
        return cond(pred, true_fun, false_fun, *operands)

    monkeypatch.setattr(jax.lax, "cond", spy)
    cfg = config()
    start = seeded_params(cfg)
    toks, tgts = (np.asarray(a) for a in batch(n=4))
    after = {}
    for chunk in (16, 0):
        trainer = trainer_for(
            dataclasses.replace(cfg, base=dataclasses.replace(
                cfg.base, xent_chunk=chunk)), T.sgd_lr(0.5))
        for rows in (4, 3):
            state, loss = trainer.step(trainer.init_state(start),
                                       toks[:rows], tgts[:rows])
            after[chunk, rows] = (float(loss), state.params)
    jax.effects_barrier()
    assert seen == [True, False]        # the unchunked loss has no cond
    for rows in (4, 3):
        (loss, got), (want_loss, want) = after[16, rows], after[0, rows]
        assert loss == pytest.approx(want_loss, rel=1e-5)
        for a, b, p0 in zip(*map(jax.tree_util.tree_leaves, (got, want, start))):
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-4 * float(jnp.abs(b - p0).max()) + 1e-7)


def test_loop_names_in_the_lowered_step():
    """The sublayer names ``scope_share`` reads, ``loop.exit`` nested in
    ``lm_head_loss`` and the recomputed head chunks under
    ``lm_head.recompute`` (compiled for a padded batch, run by no full one):
    what the benchmark's readers attribute by."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmark.readers.scope_share import SUBLAYERS
    from benchmark.trace_spans import scope_of

    cfg = config(n_layers=1)
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    x = jnp.zeros((2, SEQ), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p: mean_objective(p, x, x, cfg))).lower(params).compiler_ir(
            dialect="hlo").as_hlo_module().to_string()
    # (the operations of a scan's body carry their path from the body on in
    # this text; the compiled step's carry the whole of it)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    outer = {p: scope_of(p, SUBLAYERS) for p in paths}
    assert {s for s, _, _ in outer.values()} >= {
        "embed", "layernorm", "qkv_proj", "attention", "attn_out", "ffn",
        "lm_head_loss"}
    for part in ("loop.exit", "lm_head.fused", "lm_head.recompute"):
        under = [p for p in paths if f"/{part}/" in p]
        assert under, part
        assert all(outer[p][0] == "lm_head_loss" for p in under), part
        assert all(scope_of(p, (part,))[0] == part for p in under)
    # the gate, the exit distribution and the objective, forward and backward
    exit_ops = [p for p in paths if "/loop.exit/" in p]
    assert any("transpose(" in p for p in exit_ops)
    assert any(p.endswith(("logistic", "log_sigmoid", "log1p", "exp")) for p in exit_ops)


def test_exit_stats_publish_counters(case):
    cfg, params, toks = case["cfg"], case["params"], case["toks"]
    mass = np.asarray(hybrid.exit_stats(params, toks, cfg))
    assert mass.shape == (LOOPS,)
    assert mass.sum() == pytest.approx(BATCH * SEQ, rel=1e-5)
    np.testing.assert_allclose(mass / (BATCH * SEQ), case["ref_aux"]["exit"],
                               rtol=1e-4)
    names = ["loop.tokens_total"] + [f"loop.exit_mass.t{k}" for k in range(1, LOOPS + 1)]
    before = METRICS.snapshot()["counters"]
    expected = hybrid.publish_exit_stats(mass, BATCH * SEQ)
    after = METRICS.snapshot()["counters"]
    moved = [after[n] - before.get(n, 0) for n in names]
    assert moved[0] == BATCH * SEQ
    np.testing.assert_allclose(moved[1:], mass, rtol=1e-6)
    assert expected == pytest.approx(
        sum(k * m for k, m in enumerate(mass, 1)) / (BATCH * SEQ))
    assert 1.0 < expected < LOOPS
