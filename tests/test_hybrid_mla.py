"""``models/hybrid.py`` (the latent-attention family: the ``MLA`` mixer, a
leading dense layer, experts behind a sigmoid router that selects on a biased
score beside a shared expert, one multi-token-prediction module) against the
plain reference ``models/reference/joyai.py`` at a small size on the CPU,
seeded random weights, float32 at the highest matmul precision: the logits,
both parts of the objective, every gradient leaf and the expert choices; the
chunked attention against the unchunked; interleaved against rotate-half
rotary positions; selection on ``s + b`` with weights from ``s``; the bias
after one trainer step against the rule on that step's counts, its gradient
and its AdamW moments zero; the module's target shift and masked last
position; the sixteen shares of a layer adding up to the uncut layer;
``place_experts`` carrying the bias with the router's columns; the new scopes
and counters; and the repairs: ``post_norm`` as a field, the router's columns
by what the spec says.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models.reference import joyai as ref
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.ops.pallas.attention import attention_candidate
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import local_mesh

E, H, RQ, RKV, DN, DR, DV = 64, 4, 32, 16, 16, 8, 16
F_DENSE, F, V, SEQ, BATCH, ROWS = 96, 32, 512, 64, 2, 16
N_EXPERTS, HELD, PER_TOKEN, RATE, WEIGHT, SCALE = 16, (0, 4), 4, 0.001, 0.3, 2.5


def mixer(**kw):
    return dataclasses.replace(hybrid.MLA(
        H, RQ, RKV, DN, DR, DV, rope_theta=1e4, rows=ROWS), **kw)


def expert_layer(held=HELD, **kw):
    return dataclasses.replace(hybrid.MoE(
        N_EXPERTS, held, 0, F, top_k=PER_TOKEN, renormalize=True,
        scoring="sigmoid", bias_rate=RATE, scale=SCALE,
        shared_ff=F), **kw)


def config(held=HELD, n_expert_layers=2, dtype=jnp.float32, remat=False,
           mtp=True, **mixer_kw):
    base = TransformerConfig(
        vocab_size=V, d_model=E, n_heads=H, n_kv_heads=H,
        n_layers=1 + n_expert_layers, d_ff=F_DENSE, max_len=SEQ, causal=True,
        tie_embeddings=False, dtype=dtype, param_dtype=jnp.float32, remat=remat,
        xent_chunk=32)
    m, ffn = mixer(**mixer_kw), expert_layer(held)
    return hybrid.HybridConfig(
        base=base, norm_eps=1e-6, mtp=(m, ffn) if mtp else None, mtp_weight=WEIGHT,
        layers=((m, hybrid.GatedMLP(F_DENSE, post_norm=False)),)
        + ((m, ffn),) * n_expert_layers)


def model(held=HELD):
    return {"num_attention_heads": H, "q_lora_rank": RQ, "kv_lora_rank": RKV,
            "qk_nope_head_dim": DN, "qk_rope_head_dim": DR, "v_head_dim": DV,
            "rms_norm_eps": 1e-6, "rope_theta": 1e4, "rope_interleave": True,
            "rope_scaling": None, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "routed_scaling_factor": SCALE,
            "n_shared_experts": 1, "num_experts_per_tok": PER_TOKEN,
            "experts_held": list(held), "router_width": N_EXPERTS,
            "bias_update_rate": RATE, "mtp_loss_weight": WEIGHT}


def seeded_params(cfg, seed=0):
    """Init, with every vector (norms, selection biases) moved off its
    neutral value so that a dropped one would show."""
    params = hybrid.init_params(jax.random.key(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return tree.unflatten([
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])


def leaf_names(tree):
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def named(tree):
    return dict(zip(leaf_names(tree), jax.tree_util.tree_leaves(tree)))


def objective(params, toks, tgts, cfg):
    return hybrid.lm_loss_per_example(params, toks, tgts, cfg).mean()


def batch(seed=7):
    toks = jax.random.randint(jax.random.key(seed), (BATCH, SEQ), 0, V)
    return toks, jnp.roll(toks, -1, axis=1)


@pytest.fixture(scope="module")
def case():
    cfg = config()
    params = seeded_params(cfg)
    toks, tgts = batch()
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(objective)(params, toks, tgts, cfg)
        parts, choices = hybrid.objective_parts(params, toks, tgts, cfg)
        ref_loss, ref_grads, ref_aux = ref.loss_and_grads(
            params, toks, tgts, model(), block_rows=16)
    return {"cfg": cfg, "params": params, "toks": toks, "tgts": tgts,
            "loss": loss, "parts": parts, "choices": choices,
            "grads": named(grads), "ref_loss": ref_loss, "ref_aux": ref_aux,
            "ref_grads": named(ref_grads)}


LEAVES = leaf_names(jax.eval_shape(
    lambda: hybrid.init_params(jax.random.key(0), config())))
BIASES = [n for n in LEAVES if n.endswith("router/bias")]


# ------------------------------------------------------- program and reference

def test_the_tree_is_the_one_the_reference_documents():
    assert len(BIASES) == 3 and "mtp/block/moe/router/bias" in BIASES
    assert "layers/0/mlp/wg" in LEAVES and "layers/1/moe/shared/wg" in LEAVES
    assert not any("norm1_post" in n or "norm2_post" in n for n in LEAVES)
    assert {"mtp/enorm", "mtp/hnorm", "mtp/eh_proj", "mtp/norm"} <= set(LEAVES)
    shapes = named(jax.eval_shape(
        lambda: hybrid.init_params(jax.random.key(0), config())))
    assert shapes["layers/0/mla/wqb"].shape == (RQ, H * (DN + DR))
    assert shapes["layers/0/mla/wkva"].shape == (E, RKV + DR)
    assert shapes["layers/0/mla/wkvb"].shape == (RKV, H * (DN + DV))
    assert shapes["layers/0/mla/wo"].shape == (H * DV, E)
    assert shapes["mtp/eh_proj"].shape == (2 * E, E)


def test_objective_and_its_parts_match_the_reference(case):
    parts = case["parts"]
    assert parts["own"] is None and parts["lm"].shape == parts["mtp"].shape == (BATCH,)
    assert abs(float(case["loss"]) - float(case["ref_loss"])) < 2e-5
    assert abs(float(parts["lm"].mean()) - float(case["ref_aux"]["lm"])) < 2e-5
    assert abs(float(parts["mtp"].mean()) - float(case["ref_aux"]["mtp"])) < 2e-5
    np.testing.assert_allclose(
        parts["objective"], parts["lm"] + WEIGHT * parts["mtp"], rtol=2e-6)
    assert float(parts["mtp"].mean()) > 5.0      # a loss, not a rounding error
    same = ref.compare_grads(case["ref_grads"], case["ref_grads"])
    assert all(v["rel"] == 0 and abs(v["cos"] - 1) < 1e-6 for v in same.values())


def test_logits_match_the_reference(case):
    cfg, params, toks = case["cfg"], case["params"], case["toks"]
    with jax.default_matmul_precision("highest"):
        got = hybrid.forward(params, toks, cfg)
        x = params["tok_embed"][toks[0]]
        for lp in params["layers"]:
            x = ref.block(lp, x, model(), jnp.matmul)[0]
        want = ref.rms_norm(x, params["final_norm"], 1e-6) @ params["lm_head"]
    np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("leaf", [n for n in LEAVES if n not in BIASES])
def test_gradient_leaf_matches_the_reference(case, leaf):
    got, want = case["grads"][leaf], case["ref_grads"][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, f"{leaf}: the reference's gradient is all zero"
    np.testing.assert_allclose(got, want, atol=3e-5 * max(scale, 1.0), rtol=3e-4)


@pytest.mark.parametrize("leaf", BIASES)
def test_no_gradient_reaches_a_selection_bias(case, leaf):
    assert not np.any(np.asarray(case["grads"][leaf]))
    assert not np.any(np.asarray(case["ref_grads"][leaf]))


def test_every_leaf_has_a_group():
    assert {ref.group_of(name) for name in LEAVES} == set(ref.GROUPS)
    groups = {g: [n for n in LEAVES if ref.group_of(n) == g] for g in ref.GROUPS}
    assert len(groups["mtp_merge"]) == 3 and len(groups["embedding"]) == 1
    assert all(n.rsplit("/", 1)[1] in ("wqa", "wkva", "q_norm", "kv_norm")
               for n in groups["mla_down"])
    assert all(n.rsplit("/", 1)[1] in ("wqb", "wkvb", "wo") for n in groups["mla_up"])
    assert len(groups["shared_and_dense"]) == 3 + 3 * 3
    assert len(groups["experts"]) == 3 * 3 and len(groups["router"]) == 2 * 3


def test_expert_choices_are_the_references(case):
    assert case["choices"][0] is None and case["ref_aux"]["choices"][0] is None
    with jax.default_matmul_precision("highest"):      # the pass with no head
        alone = hybrid.expert_choices(case["params"], case["toks"], case["cfg"],
                                      case["tgts"])
    assert alone[0] is None and len(alone) == 4
    for a, b in zip(alone[1:], case["choices"][1:]):
        np.testing.assert_array_equal(a, b)
    for got, want in zip(case["choices"][1:], case["ref_aux"]["choices"][1:]):
        assert got.shape == (1, BATCH, SEQ, PER_TOKEN)
        np.testing.assert_array_equal(np.sort(got[0], -1), np.sort(want, -1))


def test_layer_by_layer_gradients_are_the_whole_models(case):
    params, toks, tgts = case["params"], case["toks"], case["tgts"]
    with jax.default_matmul_precision("highest"):
        whole = [jax.value_and_grad(ref.loss, has_aux=True)(
            params, toks[i], tgts[i], model()) for i in range(BATCH)]
    mean = jax.tree_util.tree_map(lambda *g: sum(g) / BATCH, *[g for _, g in whole])
    assert abs(sum(float(v) for (v, _), _ in whole) / BATCH
               - float(case["ref_loss"])) < 2e-6
    got = named(mean)
    for leaf in LEAVES:
        scale = max(float(jnp.abs(got[leaf]).max()), 1.0)
        np.testing.assert_allclose(case["ref_grads"][leaf], got[leaf],
                                   atol=2e-5 * scale, rtol=2e-4)


def test_following_the_programs_choices_changes_nothing_when_they_agree(case):
    params, toks, tgts = case["params"], case["toks"], case["tgts"]
    routing = [None if e is None else e[0] for e in case["choices"]]
    with jax.default_matmul_precision("highest"):
        loss, _, aux = ref.loss_and_grads(params, toks, tgts, model(),
                                          block_rows=16, routing=routing)
    assert abs(float(loss) - float(case["ref_loss"])) < 1e-6


@pytest.mark.parametrize("operand", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "float8"])
def test_lower_precision_reads_further_off(case, operand):
    """What the benchmark's control rests on: the same mathematics with its
    products' operands rounded reads further from the reference, float8 far
    further than bfloat16."""
    params, toks, tgts = case["params"], case["toks"], case["tgts"]
    routing = [None if e is None else e[0] for e in case["choices"]]
    with jax.default_matmul_precision("highest"):
        _, grads, _ = ref.loss_and_grads(params, toks, tgts, model(),
                                         operand_dtype=operand, block_rows=16,
                                         routing=routing)
    off = ref.compare_grads(grads, jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(grads), list(case["ref_grads"].values())))
    floor = 0.03 if operand == jnp.float8_e4m3fn else 0.001
    assert all(v["rel"] > floor for v in off.values()), off


# ------------------------------------------------------------------ the mixer

@pytest.mark.parametrize("rows", [8, 16, SEQ, 24], ids=lambda r: f"rows{r}")
def test_chunked_attention_is_the_unchunked(rows):
    """Queries a chunk at a time against the keys so far, each chunk
    checkpointed, at a length that is several chunks (and one that the chunk
    does not divide: one chunk): values and gradients of plain attention."""
    q, k = (jax.random.normal(jax.random.key(i), (BATCH, SEQ, H, DN + DR))
            for i in (0, 1))
    v = jax.random.normal(jax.random.key(2), (BATCH, SEQ, H, DV))
    spec = mixer(rows=rows)
    assert len(hybrid._key_spans(spec, SEQ)[1]) == (1 if rows in (SEQ, 24) else 4)

    def plain(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q, k) * (DN + DR) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)

    def chunked(q, k, v):
        return hybrid.chunked_attend(spec, (q, k, v), "mla.attend")

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(plain, q, k, v)
        got, pull_c = jax.vjp(chunked, q, k, v)
        ct = jax.random.normal(jax.random.key(3), want.shape)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for a, b in zip(pull_c(ct), pull(ct)):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_unequal_widths_have_no_kernel_and_are_counted_on_the_xla_side():
    before = dict(METRICS.snapshot()["counters"])
    assert attention_candidate(256, 4, 192, d_v=128, asked="fused") is None
    assert attention_candidate(256, 4, 128, d_v=128, asked="fused") == "fused"
    assert attention_candidate(256, 4, 128, asked="fused") == "fused"
    after = METRICS.snapshot()["counters"]
    assert after["attention.path.xla"] - before.get("attention.path.xla", 0) == 1
    assert after["attention.path.kernel"] - before.get("attention.path.kernel", 0) == 2


def test_the_rotary_key_is_one_head_shared_by_all():
    cfg = config()
    p = seeded_params(cfg)["layers"][0]["mla"]
    u = jax.random.normal(jax.random.key(4), (BATCH, SEQ, E))
    parts = hybrid.mla_qkv(mixer(), p, u, jnp.float32)
    assert [a.shape[2:] for a in parts] == [(H, DN), (H, DR), (H, DN), (DR,), (H, DV)]
    q, k, v = hybrid.mla_heads(*parts)
    assert q.shape == k.shape == (BATCH, SEQ, H, DN + DR) and v.shape[-1] == DV
    for h in range(1, H):
        np.testing.assert_array_equal(k[:, :, h, DN:], k[:, :, 0, DN:])
    assert float(jnp.abs(k[:, :, 1, :DN] - k[:, :, 0, :DN]).max()) > 0.1


def test_interleaved_and_rotate_half_differ_by_a_permutation_of_the_features():
    x = jax.random.normal(jax.random.key(5), (BATCH, SEQ, H, DR + 4))
    inter = hybrid._rope(x, 1e4, DR, interleaved=True)
    half = hybrid._rope(x, 1e4, DR)
    assert float(jnp.abs(inter - half).max()) > 0.1
    np.testing.assert_array_equal(inter[..., DR:], x[..., DR:])
    # column 2i of a pair (2i, 2i + 1) is column i of a pair (i, i + DR / 2)
    perm = np.concatenate([np.arange(0, DR, 2), np.arange(1, DR, 2),
                           np.arange(DR, DR + 4)])
    np.testing.assert_allclose(hybrid._rope(x[..., perm], 1e4, DR),
                               inter[..., perm], atol=1e-6)
    # and the reference's own form of the interleaved pairs
    np.testing.assert_allclose(ref.rope(x[0, ..., :DR], 1e4), inter[0, ..., :DR],
                               atol=5e-6)


def test_rotary_pairing_moves_the_mixer_unless_the_weights_columns_move_too():
    """The two pairings are two models on the same weights, and the same
    model once the rotary columns of ``wqb`` and ``wkva`` are permuted."""
    cfg = config()
    p = seeded_params(cfg)["layers"][0]["mla"]
    u = jax.random.normal(jax.random.key(6), (BATCH, SEQ, E))
    with jax.default_matmul_precision("highest"):
        inter = hybrid.mla_mixer(mixer(), p, u, jnp.float32)
        half = hybrid.mla_mixer(mixer(rope_interleave=False), p, u, jnp.float32)
        assert float(jnp.abs(inter - half).max()) > 1e-3
        perm = np.concatenate([np.arange(0, DR, 2), np.arange(1, DR, 2)])
        wqb = p["wqb"].reshape(RQ, H, DN + DR)
        wqb = jnp.concatenate([wqb[..., :DN], wqb[..., DN:][..., perm]], axis=-1)
        moved = dict(p, wqb=wqb.reshape(RQ, -1), wkva=jnp.concatenate(
            [p["wkva"][:, :RKV], p["wkva"][:, RKV:][:, perm]], axis=-1))
        again = hybrid.mla_mixer(mixer(rope_interleave=False), moved, u, jnp.float32)
    np.testing.assert_allclose(again, inter, atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------- the expert layer

def test_selection_uses_the_biased_score_and_weights_the_score_itself():
    spec = expert_layer()
    r = hybrid.MoE.init(spec, jax.random.key(0), E, jnp.float32)["router"]
    u = jax.random.normal(jax.random.key(1), (32, E))
    gate, e = hybrid.route(spec, r, u)
    s = jax.nn.sigmoid(u @ r["w"])
    np.testing.assert_array_equal(np.sort(e.T, -1),
                                  np.sort(np.argsort(-s, -1)[:, :PER_TOKEN], -1))
    np.testing.assert_allclose(gate.sum(axis=0), SCALE, rtol=1e-5)
    # lift token 0's least-scored expert into its chosen eight... four
    low = int(np.argmin(s[0]))
    biased = dict(r, bias=r["bias"].at[low].set(2.0))
    gate2, e2 = hybrid.route(spec, biased, u)
    assert low not in np.asarray(e[:, 0]) and low in np.asarray(e2[:, 0])
    # the weights come from s, not s + b: the lifted expert's is the smallest,
    # and every chosen expert's weight is SCALE x its s over the chosen's sum
    chosen = np.asarray(e2[:, 0])
    want = SCALE * s[0, chosen] / s[0, chosen].sum()
    np.testing.assert_allclose(gate2[:, 0], want, rtol=1e-5)
    assert float(gate2[list(chosen).index(low), 0]) == float(gate2[:, 0].min())
    # the experts both choose keep their SCORES: only the normalisation moved
    both = [x for x in chosen if x in np.asarray(e[:, 0])]
    assert len(both) == PER_TOKEN - 1
    # and no gradient reaches the bias
    g = jax.grad(lambda b: hybrid.route(spec, dict(r, bias=b), u)[0].sum())(
        biased["bias"])
    assert not np.any(np.asarray(g))


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (12, 4), (0, 16)])
def test_expert_layer_matches_the_reference(held):
    spec = expert_layer(held)
    p = seeded_params(config(held))["layers"][1]["moe"]
    u = jax.random.normal(jax.random.key(2), (BATCH, SEQ, E))
    with jax.default_matmul_precision("highest"):
        got, e = hybrid.moe_ffn(spec, p, u, jnp.float32)
        want, own = ref.moe(p, u.reshape(-1, E), model(held), jnp.matmul)
    np.testing.assert_allclose(got.reshape(-1, E), want, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.sort(e.reshape(-1, PER_TOKEN), -1),
                                  np.sort(own, -1))


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The share tied to the model: sixteen chips of one expert each share
    the layer; their routed parts plus the shared expert, which every chip
    computes alike, counted ONCE, are the uncut reference's layer."""
    chips = N_EXPERTS
    whole = expert_layer((0, N_EXPERTS))
    p = hybrid.MoE.init(whole, jax.random.key(3), E, jnp.float32)
    p["router"]["bias"] = 0.05 * jax.random.normal(jax.random.key(4), (N_EXPERTS,))
    u = jax.random.normal(jax.random.key(5), (BATCH, SEQ, E))
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.moe(p, u.reshape(-1, E), model((0, N_EXPERTS)), jnp.matmul)
        shared = hybrid._gated_silu(p["shared"], u, jnp.float32)
        total = shared
        for c in range(chips):
            mine = dict(p, **{k: p[k][c:c + 1] for k in ("wg", "wu", "wdn")})
            share, _ = hybrid.moe_ffn(expert_layer((c, 1)), mine, u, jnp.float32)
            total = total + (share - shared)         # counted once, above
            part, _ = ref.moe(mine, u.reshape(-1, E), model((c, 1)), jnp.matmul,
                              shared=False)
            np.testing.assert_allclose((share - shared).reshape(-1, E), part,
                                       atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(total.reshape(-1, E), uncut, atol=5e-5, rtol=5e-5)
    assert float(jnp.abs(shared).max()) > 0.1 and float(jnp.abs(uncut).max()) > 0.1


def test_place_experts_permutes_the_bias_with_the_routers_columns(case):
    cfg, params, toks, tgts = (case[k] for k in ("cfg", "params", "toks", "tgts"))
    assert expert_layer().router_columns == ("w", "bias")
    assert hybrid.MoE(router_hidden=8).router_columns == ("w3",)
    assert hybrid.MoE(router_hidden=0, top_k=2).router_columns == ("w",)
    placed = hybrid.place_experts(params, toks, cfg, tgts)
    before = np.asarray(hybrid.routing_stats(params, toks, cfg, tgts))
    after = np.asarray(hybrid.routing_stats(placed, toks, cfg, tgts))
    assert before.shape == (4, N_EXPERTS) and not before[0].any()
    for i in (1, 2, 3):
        old = hybrid.layer_params(params, cfg, i)["moe"]["router"]
        new = hybrid.layer_params(placed, cfg, i)["moe"]["router"]
        # one permutation moved both leaves: find it from the biases, which
        # seeded_params made distinct
        order = [int(np.argmax(np.asarray(old["bias"]) == b))
                 for b in np.asarray(new["bias"])]
        assert sorted(order) == list(range(N_EXPERTS))
        np.testing.assert_array_equal(new["w"], old["w"][:, np.asarray(order)])
        if i == 1:                 # the first placed layer saw unplaced inputs
            np.testing.assert_array_equal(after[i], before[i][np.asarray(order)])
        assert after[i].sum() == before[i].sum() == BATCH * SEQ * PER_TOKEN
    # dealt by load: this chip's four are no heavier than a quarter and a bit
    assert after[1][:4].sum() <= before[1].sum() / 4 + before[1].max()
    assert placed["layers"][0] is params["layers"][0]


def test_routing_stats_publish_counters_over_the_modules_layer_too(case):
    cfg, params, toks, tgts = (case[k] for k in ("cfg", "params", "toks", "tgts"))
    counts = np.asarray(hybrid.routing_stats(params, toks, cfg, tgts))
    METRICS.reset()
    out = hybrid.publish_routing_stats(counts, cfg)
    c = METRICS.snapshot()["counters"]
    assert c["moe.tokens_total"] == 3 * BATCH * SEQ * PER_TOKEN
    assert c["moe.tokens_local"] == counts[:, :4].sum()
    assert "moe.expert_load.l3.e0" in c and "moe.expert_load.l0.e0" not in c
    assert 0 < out["local_share"] < 1 and out["load_max_over_mean"] >= 1
    assert hybrid.publish_bias_stats(params, cfg) == pytest.approx(max(
        float(jnp.abs(hybrid.layer_params(params, cfg, i)["moe"]["router"]["bias"]).max())
        for i in (1, 2, 3)))
    assert "moe.bias_abs_max" in METRICS.snapshot()["gauges"]


def test_a_checkpointed_block_keeps_the_choices_its_forward_made():
    """Under ``remat`` the block's backward pass makes the activations again;
    the router's choices are kept by name, so a near-tie is not decided a
    second time (and perhaps the other way) on the recomputed scores."""
    from jax._src.ad_checkpoint import saved_residuals
    cfg = config(remat=True)
    params = seeded_params(cfg)
    toks, _ = batch()
    x = jnp.take(params["tok_embed"], toks, axis=0)
    kept = saved_residuals(lambda lp, x: hybrid._block_fn(cfg)(lp, x, cfg, 1)[0],
                           params["layers"][1], x)
    named_ = [why for _, why in kept if "named" in why]
    assert any("moe.chosen" in why for why in named_)
    chosen = [a for a, why in kept if "moe.chosen" in why]
    assert chosen[0].shape == (BATCH * SEQ, PER_TOKEN) and chosen[0].dtype == jnp.int32


# ----------------------------------------------------------- the bias's update

def trainer_for(cfg, tx=None, **kw):
    def loss(p, xb, yb, key=None):
        return hybrid.lm_loss_and_moves(p, xb, yb, cfg)
    tx = tx or T.adamw(T.warmup_cosine(1e-3, 2, 100), weight_decay=0.01)
    return DataParallelTrainer(loss, tx, mesh=local_mesh(1),
                               per_example_loss=True, **kw)


@pytest.fixture(scope="module")
def stepped():
    """One trainer step and a second, from a state whose biases are not
    neutral, with a learning rate that is not zero at the first step."""
    cfg = config()
    params = seeded_params(cfg)
    toks, tgts = batch()
    trainer = trainer_for(cfg, T.adamw(1e-3, weight_decay=0.1))
    state = trainer.init_state(params)
    one, losses = trainer.fit(state, [(np.asarray(toks), np.asarray(tgts))],
                              resolve_every=1)
    choices = hybrid.objective_parts(params, toks, tgts, cfg)[1]
    return {"cfg": cfg, "params": params, "one": one, "losses": losses,
            "choices": choices, "toks": toks, "tgts": tgts}


@pytest.mark.parametrize("leaf", BIASES)
def test_the_bias_after_one_step_is_the_rule_on_that_steps_counts(stepped, leaf):
    cfg = stepped["cfg"]
    i = 3 if leaf.startswith("mtp") else int(leaf.split("/")[1])
    counts = ref.expert_counts(stepped["choices"][i], N_EXPERTS)
    assert int(counts.sum()) == BATCH * SEQ * PER_TOKEN
    before = named(stepped["params"])[leaf]
    want = ref.bias_update(before, counts, model())
    got = named(stepped["one"].params)[leaf]
    np.testing.assert_array_equal(got, want)              # exactly
    moved = np.asarray(got - before)
    assert {round(float(m), 6) for m in np.unique(np.abs(moved))} <= {0.0, RATE}
    assert (moved > 0).any() and (moved < 0).any()
    # the program's own rule says the same, by the same path name
    moves = hybrid.bias_moves(cfg, stepped["choices"])
    assert set(moves) == set(BIASES)
    np.testing.assert_array_equal(
        before + moves[leaf](jnp.ones((BATCH,), bool)), got)


def test_the_optimizer_left_the_biases_alone_and_moved_everything_else(stepped):
    """No moment moves for a bias, no decay reaches it (weight_decay 0.1 at a
    rate of 1e-3 would show at once), and every other leaf took AdamW's step."""
    before, after = named(stepped["params"]), named(stepped["one"].params)
    moments = [named(t) for t in jax.tree_util.tree_leaves(
        stepped["one"].tstate, is_leaf=lambda x: isinstance(x, dict)
        and "tok_embed" in x) if isinstance(t, dict)]
    assert len(moments) == 2                              # AdamW's m and v
    for leaf in LEAVES:
        if leaf in BIASES:
            assert all(not np.any(np.asarray(m[leaf])) for m in moments)
        else:
            assert all(np.any(np.asarray(m[leaf])) for m in moments), leaf
            assert np.any(np.asarray(after[leaf] != before[leaf])), leaf
    assert np.isfinite(stepped["losses"]).all()


def test_a_padded_batchs_rows_move_no_bias():
    """A ragged batch is padded up to its bucket with repeated rows that the
    loss weighs by zero; the rule's counts leave them out too.  After a whole
    batch of four, a step on three rows (padded with the first again) moves
    every bias by the rule on those THREE rows' counts."""
    cfg = config()
    rows = [np.concatenate([np.asarray(a) for a in pair])
            for pair in zip(batch(7), batch(8))]
    trainer = trainer_for(cfg, T.adamw(1e-3))
    one, _ = trainer.fit(trainer.init_state(seeded_params(cfg)), [tuple(rows)])
    params = jax.tree_util.tree_map(jnp.copy, one.params)
    METRICS.reset()
    two, _ = trainer.fit(one, [tuple(rows)] + [tuple(a[:3] for a in rows)])
    assert METRICS.snapshot()["counters"]["train_step.padded_samples"] == 1
    padded = [jnp.concatenate([a[:3], a[:1]]) for a in rows]
    choices = hybrid.objective_parts(params, *padded, cfg)[1]
    moves = hybrid.bias_moves(cfg, choices)
    told = False
    for leaf in BIASES:
        i = 3 if leaf.startswith("mtp") else int(leaf.split("/")[1])
        counts = ref.expert_counts(choices[i][:, :3], N_EXPERTS)
        assert int(counts.sum()) == 3 * SEQ * PER_TOKEN
        want = ref.bias_update(named(params)[leaf], counts, model())
        np.testing.assert_array_equal(named(two.params)[leaf], want)
        whole = ref.bias_update(named(params)[leaf],
                                ref.expert_counts(choices[i], N_EXPERTS), model())
        told |= bool(np.any(np.asarray(want != whole)))
        np.testing.assert_array_equal(
            named(params)[leaf] + moves[leaf](jnp.ones((4,), bool)), whole)
    assert told, "counting the padding would have moved the same biases the same way"


def test_a_loss_without_moves_compiles_the_step_it_did():
    """The trainer's replicated step for a loss that hands back the rows'
    losses alone holds the same operations with and without this PR's aux
    output: no leaf moved, nothing added."""
    cfg = config(mtp=False)
    cfg = dataclasses.replace(cfg, layers=tuple(
        (m, dataclasses.replace(f, bias_rate=0.0) if isinstance(f, hybrid.MoE) else f)
        for m, f in cfg.layers))
    toks, tgts = batch()
    plain = DataParallelTrainer(
        lambda p, x, y, key=None: hybrid.lm_loss_per_example(p, x, y, cfg),
        T.adamw(1e-3), mesh=local_mesh(1), per_example_loss=True)
    moved = trainer_for(cfg, T.adamw(1e-3))
    params = seeded_params(cfg)
    a, la = plain.fit(plain.init_state(params), [(np.asarray(toks), np.asarray(tgts))])
    b, lb = moved.fit(moved.init_state(params), [(np.asarray(toks), np.asarray(tgts))])
    assert la == lb
    for x, y in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_array_equal(x, y)


def test_a_path_that_names_no_leaf_is_an_error():
    cfg = config()
    trainer = DataParallelTrainer(
        lambda p, x, y, key=None: (hybrid.lm_loss_per_example(p, x, y, cfg),
                                   {"layers/9/moe/router/bias": jnp.zeros((N_EXPERTS,))}),
        T.adamw(1e-3), mesh=local_mesh(1), per_example_loss=True)
    toks, tgts = batch()
    with pytest.raises(KeyError, match="layers/9/moe/router/bias"):
        trainer.fit(trainer.init_state(seeded_params(cfg)),
                    [(np.asarray(toks), np.asarray(tgts))])


def test_the_shard_local_steps_refuse_a_loss_that_moves_leaves():
    cfg = config()
    trainer = trainer_for(cfg, zero_stage=1)
    toks, tgts = batch()
    with pytest.raises(NotImplementedError, match="zero_stage=0"):
        trainer.fit(trainer.init_state(seeded_params(cfg)),
                    [(np.asarray(toks), np.asarray(tgts))])


# ------------------------------------------------------ the prediction module

def test_the_module_predicts_the_token_after_the_next_and_masks_the_last(case):
    """Moving the LAST target moves the main loss at the last position only
    and the module's input there, whose own loss is masked; moving target 5
    moves the module's loss at position 4 (its target) and from 5 on (its
    input), and nothing before."""
    cfg, params, toks, tgts = (case[k] for k in ("cfg", "params", "toks", "tgts"))

    def tokens_losses(tgts):
        hs, _, _, last = hybrid._run_layers(params, toks, cfg)
        h2, _ = hybrid.mtp_hidden(params, last, tgts, cfg)
        later = jnp.roll(tgts, -1, axis=1)
        from deeplearning4j_tpu.models.transformer import lm_head_token_loss
        return (lm_head_token_loss(params, hs[-1], tgts, cfg.base),
                lm_head_token_loss(params, h2, later, cfg.base))

    with jax.default_matmul_precision("highest"):
        main0, mtp0 = tokens_losses(tgts)
        parts0 = hybrid.objective_parts(params, toks, tgts, cfg)[0]
        np.testing.assert_allclose(parts0["mtp"], mtp0[:, :-1].mean(axis=1), rtol=1e-5)
        np.testing.assert_allclose(parts0["lm"], main0.mean(axis=1), rtol=1e-5)
        last = tgts.at[:, -1].set((tgts[:, -1] + 1) % V)
        main1, mtp1 = tokens_losses(last)
        parts1 = hybrid.objective_parts(params, toks, last, cfg)[0]
        np.testing.assert_array_equal(main1[:, :-1], main0[:, :-1])
        assert float(jnp.abs(main1[:, -1] - main0[:, -1]).min()) > 1e-3
        np.testing.assert_array_equal(mtp1[:, :-2], mtp0[:, :-2])
        assert float(jnp.abs(mtp1[:, -2] - mtp0[:, -2]).min()) > 1e-3   # its target
        np.testing.assert_allclose(parts1["mtp"] * (SEQ - 1),
                                   mtp1[:, :-1].sum(axis=1), rtol=1e-5)
        mid = tgts.at[:, 5].set((tgts[:, 5] + 1) % V)
        _, mtp2 = tokens_losses(mid)
        np.testing.assert_array_equal(mtp2[:, :4], mtp0[:, :4])
        assert float(jnp.abs(mtp2[:, 4] - mtp0[:, 4]).min()) > 1e-3
        assert float(jnp.abs(mtp2[:, 5:] - mtp0[:, 5:]).max()) > 1e-4


def test_embedding_and_head_are_the_main_models_own_and_their_gradients_add(case):
    cfg, params, toks, tgts = (case[k] for k in ("cfg", "params", "toks", "tgts"))

    def part(name):
        return jax.grad(lambda p: hybrid.objective_parts(
            p, toks, tgts, cfg)[0][name].mean())(params)

    assert "tok_embed" not in params["mtp"] and "lm_head" not in params["mtp"]
    # lm and mtp are values beside the objective (the weights are inside the
    # head's one call): the objective's gradient is the sum of both paths
    with jax.default_matmul_precision("highest"):
        zero = part("lm")
        assert not np.any(np.asarray(zero["lm_head"]))
        both = jax.grad(objective)(params, toks, tgts, cfg)
        main_only = jax.grad(objective)(params, toks, tgts, dataclasses.replace(
            cfg, mtp_weight=0.0))
    for leaf in ("tok_embed", "lm_head"):
        assert float(jnp.abs(both[leaf] - main_only[leaf]).max()) > 1e-5
    assert not np.any(np.asarray(main_only["mtp"]["eh_proj"]))
    assert np.any(np.asarray(both["mtp"]["eh_proj"]))


def test_both_head_passes_are_one_weighted_call(case):
    cfg, params, toks, tgts = (case[k] for k in ("cfg", "params", "toks", "tgts"))
    METRICS.reset()
    jax.eval_shape(lambda p: hybrid.lm_loss_and_moves(p, toks, tgts, cfg)[0], params)
    c = METRICS.snapshot()["counters"]
    assert c["lm_head_loss.path.weighted"] == c["lm_head_loss.path.fused"] == 1
    assert c["mtp.modules"] == 1 and c["mla.layers"] == 4
    assert c["attention.path.xla"] == 4 and "attention.path.kernel" not in c
    assert c["moe.dispatch.path.pairs"] == 3 and c["moe.bias_updates"] == 3
    assert (c["loop.steps"], c["loop.layer_applications"]) == (1, 3)


# ------------------------------------------------------------- scopes, repairs

SCOPES = {"mla.down": "qkv_proj", "mla.up": "qkv_proj", "mla.rope": "qkv_proj",
          "mla.attend": "attention", "moe.shared": "ffn", "mtp.merge": "embed",
          "moe.bias_update": "optimizer", "moe.router": "ffn"}


@pytest.fixture(scope="module")
def step_hlo():
    cfg = config(dtype=jnp.bfloat16, remat=True)
    trainer = trainer_for(cfg)
    state = trainer.init_state(seeded_params(cfg))
    toks = jnp.zeros((BATCH, SEQ), jnp.int32)
    i32 = jnp.zeros((), jnp.int32)
    return trainer._step_for(BATCH).lower(
        state.params, state.tstate, toks, toks, state.key, i32,
        i32 + BATCH).compile().as_text()


@pytest.mark.parametrize("inner,outer", sorted(SCOPES.items()))
def test_new_scopes_nest_in_the_sublayers_the_readers_know(step_hlo, inner, outer):
    paths = [p for p in re.findall(r'op_name="([^"]*)"', step_hlo)
             if p.startswith("jit(")]
    mine = [p for p in paths if f"/{inner}/" in p or p.endswith(f"/{inner}")]
    assert mine, f"no operation under {inner}"
    assert all(outer in p.split(inner)[0] for p in mine), mine[:3]
    assert any("transpose(" in p for p in mine) or inner in (
        "moe.bias_update", "moe.router")
    family = inner.split(".")[0] + "."
    for p in mine:                # a part never encloses another of its family
        inside = p.split(inner, 1)[1].replace(f"/{inner}", "")
        assert not re.search(rf"/{re.escape(family)}\w+", inside), p


def test_a_checkpointed_block_keeps_the_attentions_output(step_hlo):
    """Under ``remat`` the block's recomputed forward stops at the mixer's
    inputs: a block's loop over its examples runs twice (forward, and the
    examples' own backward), and a chunk's scores are made again once (in
    that backward), not twice."""
    loops = re.findall(r'= \([^)]*\) while\([^\n]*op_name="([^"]*mla\.attend[^"]*)"',
                       step_hlo)
    outer = [p for p in loops if p.count("/while") == 1]
    assert len(outer) == 2 * 4, outer                      # x 4 blocks
    assert not any("rematted_computation/attention" in p for p in loops)
    scores = re.findall(r'op_name="([^"]*mla\.attend[^"]*thd,shd->hts/dot_general)"',
                        step_hlo)
    forward = [p for p in scores if "transpose(" not in p]
    again = [p for p in scores if "rematted_computation" in p]
    assert forward and len(again) == len(forward)


def test_post_norm_is_what_a_configuration_says():
    assert hybrid.Attention().post_norm and hybrid.GatedMLP().post_norm
    base = config().base
    for post in (True, False):
        cfg = hybrid.HybridConfig(base=base, layers=((
            hybrid.Attention(H, H, 16, post_norm=post),
            hybrid.GatedMLP(F, post_norm=post)),))
        lp = hybrid.init_params(jax.random.key(0), cfg)["layers"][0]
        assert ("norm1_post" in lp) == ("norm2_post" in lp) == post
        x = jax.random.normal(jax.random.key(1), (1, 8, E))
        assert hybrid.block(lp, x, cfg, 0)[0].shape == x.shape


def test_the_dense_layer_and_the_shared_expert_are_one_gated_mlp():
    p = hybrid.GatedMLP(F).init(jax.random.key(0), E, jnp.float32)
    u = jax.random.normal(jax.random.key(1), (BATCH, SEQ, E))
    got, none = hybrid.gated_mlp(hybrid.GatedMLP(F), p, u, jnp.float32)
    want = ref.gated(p, u, jnp.matmul)
    assert none is None
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(hybrid._gated_silu(p, u[0], jnp.float32, jnp.float32),
                               want[0], atol=1e-5, rtol=1e-5)
