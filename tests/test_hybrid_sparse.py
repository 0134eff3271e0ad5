"""``models/hybrid.py`` (the sparse-attention layer: ``SparseAttention`` mixer
with its indexer and index loss, top-k routed experts behind a linear router)
against the plain reference ``models/reference/keye.py`` at a small size on
the CPU, seeded random weights, float32 at the highest matmul precision: both
parts of the objective, every gradient leaf, the selected keys and the expert
choices, at a length where the selection bites; the mixer without a selection
to make is plain attention; the selection is exact under planted ties; the two
parts' gradients never meet; the eight shares add up to the uncut layer; a
top-1 layer through the new dispatch is the parent's program, text and all;
which path the index scores took is counted, and on the index kernels the
selection made outside the step is the one the mixer attends over.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models.reference import keye as ref
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.ops.pallas import attention as pallas_attention

E, H, G, D, F, V, SEQ, BATCH = 64, 4, 2, 16, 32, 512, 64, 2
J, C, TOP, ROWS = 4, 8, 8, 16
N_EXPERTS, HELD, PER_TOKEN = 16, (0, 4), 4


def mixer(**kw):
    return dataclasses.replace(hybrid.SparseAttention(
        H, G, D, 1e7, True, J, C, top_k=TOP, q_chunk=8, kv_chunk=8, rows=ROWS), **kw)


def config(held=HELD, n_layers=2, dtype=jnp.float32, remat=False, **mixer_kw):
    base = TransformerConfig(
        vocab_size=V, d_model=E, n_heads=H, n_kv_heads=G, n_layers=n_layers,
        d_ff=F, max_len=SEQ, causal=True, tie_embeddings=False, dtype=dtype,
        param_dtype=jnp.float32, remat=remat, xent_chunk=32)
    ffn = hybrid.MoE(N_EXPERTS, held, 0, F, top_k=PER_TOKEN, renormalize=True)
    return hybrid.HybridConfig(base=base, norm_eps=1e-6,
                               layers=((mixer(**mixer_kw), ffn),) * n_layers)


def model(held=HELD, top=TOP):
    return {"num_attention_heads": H, "num_key_value_heads": G, "head_dim": D,
            "rms_norm_eps": 1e-6, "rope_theta": 1e7, "experts_held": list(held),
            "num_experts_per_tok": PER_TOKEN, "norm_topk_prob": True,
            "sa_config": {"indexer_num_heads": J, "indexer_head_dim": C,
                          "indexer_num_kv_heads": 1, "topk": top,
                          "q_chunk_size": 8, "kv_chunk_size": 8}}


def seeded_params(cfg, seed=0):
    """Init, with every vector (norms, the key LayerNorm's scale and bias)
    moved off its neutral value so that a dropped one would show."""
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


@pytest.fixture(scope="module")
def case():
    cfg = config()
    params = seeded_params(cfg)
    toks = jax.random.randint(jax.random.key(7), (BATCH, SEQ), 0, V)
    tgts = jnp.roll(toks, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(objective)(params, toks, tgts, cfg)
        parts = hybrid.loss_parts(params, toks, tgts, cfg)
        ref_loss, ref_grads, ref_aux = ref.loss_and_grads(
            params, toks, tgts, model(), block_rows=16)
    return {"cfg": cfg, "params": params, "toks": toks, "tgts": tgts,
            "loss": loss, "parts": parts, "grads": named(grads),
            "ref_loss": ref_loss, "ref_aux": ref_aux, "ref_grads": named(ref_grads)}


LEAVES = leaf_names(jax.eval_shape(
    lambda: hybrid.init_params(jax.random.key(0), config())))
INDEXER = [n for n in LEAVES if "index" in n]


# ------------------------------------------------------- program and reference

def test_the_selection_bites():
    """SEQ > TOP: most queries keep fewer keys than they could see."""
    assert SEQ > 4 * TOP and len(INDEXER) == 2 * 5


def test_objective_and_its_parts_match_the_reference(case):
    lm, own = case["parts"]
    assert own.shape == lm.shape == (BATCH,)
    assert abs(float(case["loss"]) - float(case["ref_loss"])) < 2e-5
    assert abs(float(lm.mean()) - float(case["ref_aux"]["lm"])) < 2e-5
    assert abs(float(own.mean()) - float(case["ref_aux"]["index"])) < 2e-5
    assert float(own.mean()) > 0.05          # a loss, not a rounding error
    # the comparison's own arithmetic: identical trees read 0 and 1
    same = ref.compare_grads(case["ref_grads"], case["ref_grads"])
    assert all(v["rel"] == 0 and abs(v["cos"] - 1) < 1e-6 for v in same.values())


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(case, leaf):
    got, want = case["grads"][leaf], case["ref_grads"][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, f"{leaf}: the reference's gradient is all zero"
    np.testing.assert_allclose(got, want, atol=3e-5 * max(scale, 1.0), rtol=3e-4)


def test_every_leaf_has_a_group():
    assert {ref.group_of(name) for name in LEAVES} == set(ref.GROUPS)
    assert {ref.group_of(n) for n in INDEXER} == {"indexer"}


def test_selected_keys_are_the_references(case):
    """Layer by layer, example by example: the program's bisection and the
    reference's argsort pick the same keys."""
    with jax.default_matmul_precision("highest"):
        got = hybrid.selections(case["params"], case["toks"], case["cfg"])
        _, _, followed = ref.loss_and_grads(
            case["params"], case["toks"], case["tgts"], model(), block_rows=16,
            selection=got)
    assert len(got) == 2 and got[0].shape == (BATCH, SEQ, SEQ)
    want = sum(min(t + 1, TOP) for t in range(SEQ))
    for layer in got:
        assert bool((layer.sum(axis=-1) == jnp.minimum(
            jnp.arange(SEQ) + 1, TOP)).all())
        assert not bool(jnp.triu(layer, k=1).any())       # no later key
    assert followed["selected"] == 2 * BATCH * want
    assert followed["selection_differs"] == 0.0


def test_expert_choices_are_the_references(case):
    with jax.default_matmul_precision("highest"):
        _, choices = hybrid.encode(case["params"], case["toks"], case["cfg"])
    got = jnp.stack(choices, axis=1)                      # (B, layers, T, k)
    assert got.shape == (BATCH, 2, SEQ, PER_TOKEN)
    assert bool((got == case["ref_aux"]["choices"]).all())


def test_following_the_programs_choices_changes_nothing_when_they_agree(case):
    with jax.default_matmul_precision("highest"):
        got = hybrid.selections(case["params"], case["toks"], case["cfg"])
        routing = jnp.stack(hybrid.encode(
            case["params"], case["toks"], case["cfg"])[1], axis=1)
        loss, grads, _ = ref.loss_and_grads(
            case["params"], case["toks"], case["tgts"], model(), block_rows=16,
            selection=got, routing=routing)
    assert abs(float(loss) - float(case["ref_loss"])) < 1e-6
    same = ref.compare_grads(grads, jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(grads), list(case["ref_grads"].values())))
    assert all(v["rel"] < 1e-5 for v in same.values()), same


def test_layer_by_layer_gradients_are_the_whole_models(case):
    """``loss_and_grads`` (chain rule by hand, scores in blocks) against
    ``jax.value_and_grad`` of the reference's whole ``loss``."""
    params, toks, tgts = case["params"], case["toks"], case["tgts"]
    whole = [jax.value_and_grad(ref.loss, has_aux=True)(
        params, toks[i], tgts[i], model()) for i in range(BATCH)]
    assert float(case["ref_loss"]) == pytest.approx(
        sum(float(v) for (v, _), _ in whole) / BATCH, rel=1e-6)
    mean = jax.tree_util.tree_map(lambda *g: sum(g) / BATCH, *[g for _, g in whole])
    got = jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(mean),
                                       list(case["ref_grads"].values()))
    same = ref.compare_grads(got, mean)
    assert all(v["rel"] < 1e-5 for v in same.values()), same


@pytest.mark.parametrize("operand", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bfloat16", "float8"])
def test_lower_precision_reads_further_off(case, operand):
    """The reading that sets a cell's limits: every matrix product's operands
    rounded, forward and backward, the reference following the exact one's
    choices; float8 moves every group at least twice as far as bfloat16."""
    params, toks, tgts = case["params"], case["toks"], case["tgts"]
    exact = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), list(case["ref_grads"].values()))
    with jax.default_matmul_precision("highest"):
        picked = hybrid.selections(params, toks, case["cfg"])    # the exact one's
    err = {}
    for dt in (jnp.bfloat16, operand):
        _, g, aux = ref.loss_and_grads(
            params, toks, tgts, model(), operand_dtype=dt, selection=picked,
            routing=case["ref_aux"]["choices"])
        err[dt] = ref.compare_grads(g, exact)
        assert 0 < aux["selection_differs"] < 0.2     # its own would differ
    for group in ref.GROUPS:
        assert err[jnp.bfloat16][group]["rel"] > 1e-4
        if operand is not jnp.bfloat16:
            assert err[operand][group]["rel"] > 2 * err[jnp.bfloat16][group]["rel"]


def test_bfloat16_program_stays_near_the_reference_it_follows():
    """The benchmark's comparison at a tiny size: bf16 compute over f32
    parameters, remat on, the reference along the program's own choices."""
    cfg = config(dtype=jnp.bfloat16, remat=True)
    params = seeded_params(cfg)
    toks = jax.random.randint(jax.random.key(7), (BATCH, SEQ), 0, V)
    tgts = jnp.roll(toks, -1, axis=1)
    loss, grads = jax.value_and_grad(objective)(params, toks, tgts, cfg)
    picked = hybrid.selections(params, toks, cfg)
    routing = jnp.stack(hybrid.encode(params, toks, cfg)[1], axis=1)
    want, ref_grads, aux = ref.loss_and_grads(
        params, toks, tgts, model(), block_rows=16, selection=picked,
        routing=routing)
    assert abs(float(loss) - float(want)) < 0.01
    assert aux["selection_differs"] < 0.05
    near = ref.compare_grads(grads, ref_grads)
    assert all(v["rel"] < 0.3 and v["cos"] > 0.95 for v in near.values()), near


# ------------------------------------------------------------ the mixer alone

def mixer_case(spec, t=SEQ, seed=3):
    p = spec.init(jax.random.key(seed), E, jnp.float32)
    u = jax.random.normal(jax.random.key(seed + 1), (BATCH, t, E))
    return p, u


@pytest.mark.parametrize("t", [8, 16])
def test_without_a_selection_to_make_it_is_plain_attention(t):
    """``T <= top_k``: every query keeps every earlier key, and the mixer
    without head norms is ``Attention`` on the same weights, to rounding."""
    spec = mixer(qk_norm=False, top_k=16, rows=8)
    plain = hybrid.Attention(H, G, D, 1e7, 1.0)
    p, u = mixer_case(spec, t)
    with jax.default_matmul_precision("highest"):
        got, loss = hybrid.sparse_attention_mixer(spec, p, u, jnp.float32)
        want = hybrid.attention_mixer(
            plain, {k: p[k] for k in ("wq", "wk", "wv", "wo")}, u, jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert loss.shape == (BATCH,) and bool((loss > 0).all())


def test_head_norms_are_the_references():
    """With q / k norms and a selection that bites: the mixer against the
    reference's, output and index loss, one example at a time."""
    spec = mixer()
    p, u = mixer_case(spec)
    p = dict(p, q_norm=p["q_norm"] * 1.3, k_norm=p["k_norm"] * 0.7)
    with jax.default_matmul_precision("highest"):
        got, loss = hybrid.sparse_attention_mixer(spec, p, u, jnp.float32)
        for i in range(BATCH):
            want, want_loss, _ = ref.sparse_attention(p, u[i], model(), jnp.matmul)
            np.testing.assert_allclose(got[i], want, atol=3e-5)
            assert float(loss[i]) == pytest.approx(float(want_loss), rel=1e-4)
        bare, _ = hybrid.sparse_attention_mixer(
            dataclasses.replace(spec, qk_norm=False), p, u, jnp.float32)
    assert float(jnp.abs(bare - got).max()) > 1e-3        # the norms matter


@pytest.mark.parametrize("rows", [8, 32, SEQ, 24],
                         ids=["8", "32", "whole", "no-divisor"])
def test_chunking_changes_nothing(rows):
    """Any number of queries at a time, over any runs of key lengths: the
    same output, loss and gradient (24 does not divide 64: one chunk)."""
    p, u = mixer_case(mixer())

    def run(spec):
        def f(p, u):
            out, loss = hybrid.sparse_attention_mixer(spec, p, u, jnp.float32)
            return jnp.sum(out * out) + jnp.sum(loss), (out, loss)
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(f, has_aux=True)(p, u)

    (_, (want, want_loss)), want_g = run(mixer())
    (_, (got, got_loss)), got_g = run(mixer(rows=rows))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=2e-4 * max(1.0, float(jnp.abs(b).max())))


@pytest.mark.parametrize("t,rows,want", [
    (64, 16, (16, [(0, 1, 16), (1, 1, 32), (2, 1, 48), (3, 1, 64)])),
    (64, 8, (8, [(0, 2, 16), (2, 2, 32), (4, 2, 48), (6, 2, 64)])),
    (64, 24, (64, [(0, 1, 64)])),
    (48, 16, (16, [(0, 1, 16), (1, 1, 32), (2, 1, 48)])),
    (16384, 256, (256, [(0, 16, 4096), (16, 16, 8192), (32, 16, 12288),
                        (48, 16, 16384)]))])
def test_key_spans_cover_each_chunk_to_its_end(t, rows, want):
    """Every chunk's keys reach its own last query, in at most four lengths."""
    c, spans = hybrid._key_spans(mixer(rows=rows), t)
    assert (c, spans) == want
    for first, n, keys in spans:
        assert keys >= (first + n) * c and keys <= t
    assert sum(n for _, n, _ in spans) * c == t


# --------------------------------------------------------------- the selection

def brute_force(scores, allowed, count):
    """Row by row on the host: the ``count`` allowed positions with the
    largest score, the lower position first among equals."""
    out = np.zeros(scores.shape, bool)
    for r, row in enumerate(np.asarray(scores)):
        ok = np.flatnonzero(np.asarray(allowed[r]))
        order = sorted(ok, key=lambda s: (-float(row[s]) + 0.0, s))
        out[r, order[:int(count[r])]] = True
    return out


def planted(kind):
    rng = np.random.default_rng(5)
    c, n = 16, 48
    scores = rng.normal(size=(c, n)).astype(np.float32)
    if kind == "all-equal":
        scores[:] = 0.25
    elif kind == "signed-zeros":
        scores = np.where(rng.random((c, n)) < 0.5, 0.0, -0.0).astype(np.float32)
    elif kind == "zeros-and-numbers":
        scores[rng.random((c, n)) < 0.6] = -0.0
        scores[rng.random((c, n)) < 0.2] = 0.0
    elif kind == "few-levels":
        scores = rng.integers(-2, 3, (c, n)).astype(np.float32) * 0.5
    elif kind == "tie-at-the-cut":
        scores[:, 10:30] = 1.5                       # 20 equal, above the rest
        scores[:, :10] = 3.0
    elif kind == "extremes":
        scores[:, ::7] = np.float32(3e38)
        scores[:, 1::7] = np.float32(-3e38)
        scores[:, 2::7] = np.float32(1e-45)          # a subnormal
    return jnp.asarray(scores)


KINDS = ["random", "all-equal", "signed-zeros", "zeros-and-numbers",
         "few-levels", "tie-at-the-cut", "extremes"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("top", [1, 5, 16, 48])
def test_selected_count_is_exact_under_planted_ties(kind, top):
    """Exactly ``min(t + 1, k)`` keys a query, none later than it, the ones a
    stable sort keeps (``-0.0`` and ``0.0`` are one value, as the reference's
    argsort has them)."""
    scores = planted(kind)
    c, n = scores.shape
    start = 20
    tq = start + jnp.arange(c)
    allowed = jnp.arange(n)[None, :] <= tq[:, None]
    count = jnp.minimum(tq + 1, top)
    got = np.asarray(jax.jit(hybrid.select_top_k)(scores, allowed, count))
    assert (got.sum(axis=1) == np.asarray(count)).all()
    assert not (got & ~np.asarray(allowed)).any()
    np.testing.assert_array_equal(got, brute_force(scores, allowed, count))
    want = ref.top_positions(jnp.where(allowed, scores, -jnp.inf), count) & allowed
    np.testing.assert_array_equal(got, np.asarray(want))


def test_selection_stats_count_what_the_masks_hold(case):
    params, toks, cfg = case["params"], case["toks"], case["cfg"]
    masks = hybrid.selections(params, toks, cfg)
    stats = np.asarray(hybrid.selection_stats(params, toks, cfg))
    assert stats.shape == (2, 4)
    tiles = SEQ // 8
    for layer, row in zip(masks, stats):
        m = np.asarray(layer)
        held = m.reshape(BATCH, tiles, 8, tiles, 8).any(axis=(2, 4))
        below = np.tril(np.ones((tiles, tiles), bool))
        assert list(row) == [m.sum(), BATCH * SEQ * (SEQ + 1) // 2,
                             (below & ~held).sum(), BATCH * below.sum()]
    METRICS.reset()
    shares = hybrid.publish_selection_stats(stats)
    counters = METRICS.snapshot()["counters"]
    assert counters["dsa.pairs_selected"] == stats[:, 0].sum()
    assert counters["dsa.pairs_causal"] == 2 * BATCH * SEQ * (SEQ + 1) // 2
    assert counters["dsa.tiles_total"] == 2 * BATCH * tiles * (tiles + 1) // 2
    assert shares["selected_share"] == pytest.approx(
        sum(min(t + 1, TOP) for t in range(SEQ)) / (SEQ * (SEQ + 1) / 2))
    assert 0.0 <= shares["empty_tile_share"] < 1.0


def test_a_tile_nobody_selects_is_counted_empty():
    """Index scores that rank the newest keys highest: each query keeps its
    last ``top_k`` keys, and every older tile below the diagonal is empty."""
    spec = mixer(rows=8)
    chosen = jnp.asarray(np.tril(np.ones((8, 64), bool), 32 + 0)
                         & ~np.tril(np.ones((8, 64), bool), 32 - 8))
    causal = jnp.arange(64)[None, :] <= (32 + jnp.arange(8))[:, None]
    got = hybrid._tile_counts(spec, chosen, causal, 32)
    # queries 32..39, keys t-7..t: tiles 3 and 4 of the 5 below the diagonal
    assert list(np.asarray(got)) == [64, int(causal.sum()), 3, 5]


# ------------------------------------------------------- the gradients' two ways

def parts_grads(case, part):
    def f(params):
        lm, own = hybrid.loss_parts(params, case["toks"], case["tgts"], case["cfg"])
        return (lm if part == "lm" else own).mean()
    with jax.default_matmul_precision("highest"):
        return named(jax.grad(f)(case["params"]))


@pytest.fixture(scope="module")
def lm_grads(case):
    return parts_grads(case, "lm")


@pytest.fixture(scope="module")
def index_grads(case):
    return parts_grads(case, "index")


@pytest.mark.parametrize("leaf", LEAVES)
def test_each_loss_reaches_its_own_parameters_alone(lm_grads, index_grads, leaf):
    """The language-model loss's gradient is exactly zero on every indexer
    leaf; the index loss's exactly zero on every other leaf; and each is
    alive on its own side."""
    mine, other = ((index_grads, lm_grads) if leaf in INDEXER
                   else (lm_grads, index_grads))
    assert float(jnp.abs(other[leaf]).max()) == 0.0
    assert float(jnp.abs(mine[leaf]).max()) > 0.0


def test_the_two_gradients_add_up_to_the_objectives(case, lm_grads, index_grads):
    for leaf in LEAVES:
        np.testing.assert_allclose(lm_grads[leaf] + index_grads[leaf],
                                   case["grads"][leaf], atol=1e-6)


# ----------------------------------------------------------- the expert layer

def moe_case(spec, seed=5):
    p = spec.init(jax.random.key(seed), E, jnp.float32)
    u = jax.random.normal(jax.random.key(seed + 1), (BATCH, SEQ, E))
    return p, u


def test_linear_router_keeps_the_largest_and_renormalises():
    spec = hybrid.MoE(N_EXPERTS, HELD, 0, F, top_k=PER_TOKEN, renormalize=True)
    p, u = moe_case(spec)
    assert set(p["router"]) == {"w"} and p["router"]["w"].shape == (E, N_EXPERTS)
    gate, e = hybrid.route(spec, p["router"], u.reshape(-1, E))
    assert gate.shape == e.shape == (PER_TOKEN, BATCH * SEQ)
    np.testing.assert_allclose(gate.sum(axis=0), 1.0, rtol=1e-6)
    pi = jax.nn.softmax(jnp.dot(u.reshape(-1, E), p["router"]["w"],
                                precision=lax.Precision.HIGHEST), axis=-1)
    want = jnp.argsort(-pi, axis=-1, stable=True)[:, :PER_TOKEN]
    assert bool((e.T == want).all())
    assert bool((jnp.diff(gate, axis=0) <= 0).all())      # largest first
    plain = dataclasses.replace(spec, renormalize=False)
    raw, _ = hybrid.route(plain, p["router"], u.reshape(-1, E))
    np.testing.assert_allclose(raw / raw.sum(axis=0), gate, rtol=1e-6)


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (12, 4), (0, 16)])
def test_expert_layer_matches_the_reference(held):
    spec = hybrid.MoE(N_EXPERTS, held, 0, F, top_k=PER_TOKEN, renormalize=True)
    p, u = moe_case(spec)
    with jax.default_matmul_precision("highest"):
        got, e = hybrid.moe_ffn(spec, p, u, jnp.float32)
        for i in range(BATCH):
            want, want_e = ref.moe(p, u[i], model(held), jnp.matmul)
            np.testing.assert_allclose(got[i], want, atol=2e-5)
            assert bool((e[i] == want_e).all())
    assert e.shape == (BATCH, SEQ, PER_TOKEN)
    counts = hybrid.expert_counts(spec, e)
    assert int(counts.sum()) == BATCH * SEQ * PER_TOKEN    # a pair is the unit


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four experts a chip over four chips, each choice counted once (on the
    chip that holds its expert): the parts add up to what the reference gives
    for the whole layer, and a token's choices land on several chips."""
    whole = hybrid.MoE(N_EXPERTS, (0, N_EXPERTS), 0, F, top_k=PER_TOKEN,
                       renormalize=True)
    p, u = moe_case(whole)
    with jax.default_matmul_precision("highest"):
        total, landed = 0.0, 0
        for first in range(0, N_EXPERTS, 4):
            share = dataclasses.replace(whole, held=(first, 4))
            part = dict(p, **{k: p[k][first:first + 4] for k in ("wg", "wu", "wdn")})
            out, e = hybrid.moe_ffn(share, part, u, jnp.float32)
            total = total + out
            landed += int(((e >= first) & (e < first + 4)).sum())
            mine = ((e >= first) & (e < first + 4)).any(axis=-1)
            assert float(jnp.abs(out[~mine]).max()) == 0.0   # nothing stands in
        uncut = jnp.stack([ref.moe(p, u[i], model((0, N_EXPERTS)), jnp.matmul)[0]
                           for i in range(BATCH)])
    assert landed == BATCH * SEQ * PER_TOKEN
    np.testing.assert_allclose(total, uncut, atol=3e-5)
    assert float(jnp.abs(uncut).max()) > 0.05


def parents_moe_ffn(spec, p, u, dt):
    """``moe_ffn`` as the parent commit had it (top-1 behind the router MLP),
    kept here letter for letter: the yardstick of the test below."""
    shape = u.shape
    u = u.reshape(-1, shape[-1]).astype(dt)
    n = u.shape[0]
    first, count = spec.held
    with jax.named_scope("moe.router"):
        hi = lax.Precision.HIGHEST
        r = p["router"]
        x = u.astype(jnp.float32)
        a = jnp.dot(x, r["wd"].astype(jnp.float32), precision=hi)
        b = jax.nn.gelu(jnp.dot(a, r["w1"].astype(jnp.float32), precision=hi)
                        + r["b1"])
        c = jax.nn.gelu(jnp.dot(b, r["w2"].astype(jnp.float32), precision=hi)
                        + r["b2"])
        pi = jax.nn.softmax(
            jnp.dot(c, r["w3"].astype(jnp.float32), precision=hi), axis=-1)
        e = jnp.argmax(pi, axis=-1).astype(jnp.int32)
        gate = jnp.take_along_axis(pi, e[:, None], axis=-1)[:, 0]
    with jax.named_scope("moe.dispatch"):
        local = (e >= first) & (e < first + count)
        slot = jnp.where(local, e - first, count)
        order = jnp.argsort(slot, stable=True)
        back = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        sizes = jnp.zeros((count + 1,), jnp.int32).at[slot].add(1)[:count]
        xs = u[order]
    with jax.named_scope("moe.experts"):
        held_rows = (jnp.arange(n) < sizes.sum())[:, None]

        def grouped(x, w):
            out = lax.ragged_dot(jnp.where(held_rows, x, 0), w.astype(dt), sizes,
                                 preferred_element_type=jnp.float32)
            return jnp.where(held_rows, out, 0.0)

        hidden = (jax.nn.silu(grouped(xs, p["wg"]))
                  * grouped(xs, p["wu"])).astype(dt)
        ys = grouped(hidden, p["wdn"])
    with jax.named_scope("moe.dispatch"):
        y = ys[back] * jnp.where(local, gate, 0.0)[:, None]
    return y.astype(dt).reshape(shape), e.reshape(shape[:-1])


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_top_1_through_the_new_dispatch_is_the_parents_program(dt):
    """A top-1 layer behind the router MLP (ZAYA's): the generalised
    ``moe_ffn`` gives the parent's output bit for bit and lowers to the
    parent's text, forward and backward."""
    spec = hybrid.MoE(8, (0, 4), 32, 48)
    assert (spec.top_k, spec.renormalize) == (1, False)
    p, u = moe_case(spec)

    def loss(fn):
        return lambda p, u: jnp.sum(fn(spec, p, u, dt)[0].astype(jnp.float32) ** 2)

    got, e = hybrid.moe_ffn(spec, p, u, dt)
    want, want_e = parents_moe_ffn(spec, p, u, dt)
    assert bool((got == want).all()) and bool((e == want_e).all())
    texts = [jax.jit(jax.value_and_grad(loss(fn), argnums=(0, 1))).lower(p, u).as_text()
             for fn in (hybrid.moe_ffn, parents_moe_ffn)]
    assert texts[0] == texts[1]
    assert "stablehlo.while" not in texts[0]              # no scan at k = 1


def parents_one_choice(spec, p, u, gate, e, dt):
    """``_one_choice`` as the parent commit had it, kept here letter for
    letter for the yardstick below."""
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


def parents_choices_added_up(spec, experts, u, gate, e, dt):
    """The parent commit's scan over a token's choices (``moe_ffn`` above
    top-1, from the router's result on), kept letter for letter: the yardstick
    of the blocks of sorted pairs.  ``gate``, ``e`` are ``(top_k, N)``."""
    one = jax.checkpoint(functools.partial(parents_one_choice, spec, dt=dt))

    def add_choice(acc, ge):
        y = one(experts, u, *ge)
        with jax.named_scope("moe.combine"):
            return acc + y, None

    with jax.named_scope("moe.combine"):
        zero = jnp.zeros(u.shape, jnp.float32)
    y, _ = lax.scan(add_choice, zero, (gate, e))
    return y


def parents_top_k_moe_ffn(spec, p, u, dt):
    """The rest of the parent's ``moe_ffn`` above top-1, around that scan."""
    shape = u.shape
    with jax.named_scope("moe.combine"):     # the layer's own shaping and sum
        u = u.reshape(-1, shape[-1]).astype(dt)
    gate, e = hybrid.route(spec, p["router"], u)
    experts = {name: p[name] for name in ("wg", "wu", "wdn")}
    y = parents_choices_added_up(spec, experts, u, gate, e, dt)
    with jax.named_scope("moe.combine"):
        return y.astype(dt).reshape(shape), e.T.reshape(*shape[:-1], spec.top_k)


@pytest.fixture
def block_rows(monkeypatch):
    """Sets the module's rows a block; a traced function is cached by what it
    was traced from, not by the constant it read."""
    def set_rows(rows):
        monkeypatch.setattr(hybrid, "PAIR_BLOCK_ROWS", rows)
        jax.clear_caches()
    yield set_rows
    jax.clear_caches()


PAIRS = BATCH * SEQ * PER_TOKEN                 # 512
#: dtype -> (the output's, the gradients') largest error over the yardstick's
#: largest entry: float32 differs by the order of a token's adds alone; in
#: bfloat16 the parent summed a token's input gradient in bfloat16
CLOSE = {"f32": (jnp.float32, 3e-5, 3e-5), "bf16": (jnp.bfloat16, 1e-2, 3e-2)}


def assert_close(got, want, tol, what):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all(), what
    scale = max(1.0, float(np.abs(want).max()))
    assert float(np.abs(got - want).max()) <= tol * scale, what


@pytest.mark.parametrize("close", sorted(CLOSE))
@pytest.mark.parametrize("held,rows", [
    ((0, 4), 32), ((12, 4), 96), ((0, 16), 32), ((4, 4), 2 * PAIRS),
    ((0, 4), PAIRS)],
    ids=["16_blocks", "padded_last_block", "every_pair_local",
         "rows_above_the_pairs", "one_block"])
def test_blocks_of_sorted_pairs_give_the_scan_over_the_choices(
        block_rows, held, rows, close):
    """The layer above top-1, router and all: output and every gradient
    against the parent's scan over the choices.  96 rows do not divide 512
    pairs (the last block is padded); with all 16 experts held every block
    runs; rows above the pairs are one block of all of them."""
    dt, out_tol, grad_tol = CLOSE[close]
    block_rows(rows)
    spec = hybrid.MoE(N_EXPERTS, held, 0, F, top_k=PER_TOKEN, renormalize=True)
    p, u = moe_case(spec)
    assert hybrid._pair_block_shape(PAIRS) == (min(rows, PAIRS),
                                               -(-PAIRS // min(rows, PAIRS)))
    v = jax.random.normal(jax.random.key(11), u.shape)

    def value(fn):
        return lambda p, u: jnp.sum(fn(spec, p, u, dt)[0].astype(jnp.float32) * v)

    with jax.default_matmul_precision("highest"):
        got, e = hybrid.moe_ffn(spec, p, u, dt)
        want, want_e = parents_top_k_moe_ffn(spec, p, u, dt)
        (_, got_p), (_, want_p) = (
            jax.jit(jax.value_and_grad(value(fn), argnums=(0, 1)))(p, u)
            for fn in (hybrid.moe_ffn, parents_top_k_moe_ffn))
    assert bool((e == want_e).all())
    assert_close(got, want, out_tol, "output")
    assert float(jnp.abs(want).max()) > 0.05
    for name, a in named(got_p).items():
        assert_close(a, named(want_p)[name], grad_tol, name)
        assert float(jnp.abs(named(want_p)[name]).max()) > 0.0, name


def planted_choices(sizes, held=(4, 4)):
    """Choices ``e (top_k, N)`` that send exactly ``sizes[g]`` pairs to held
    expert ``first + g`` (choice ``g`` of its first tokens in a shuffled
    order) and every other pair to an expert below or above the held ones; a
    token's choices are distinct experts."""
    n, (first, count) = BATCH * SEQ, held
    assert len(sizes) == count == PER_TOKEN and first >= PER_TOKEN
    choice = jnp.arange(PER_TOKEN)[:, None]
    e = jnp.where(jnp.arange(n)[None] % 2 == 0, choice, first + count + choice)
    for g, size in enumerate(sizes):
        tokens = jax.random.permutation(jax.random.key(20 + g), n)[:size]
        e = e.at[g, tokens].set(first + g)
    return e.astype(jnp.int32)


PLANTED = {
    "no_pair_local": (0, 0, 0, 0),
    "exactly_three_blocks": (20, 44, 0, 32),    # 96 = 3 x 32; a group of none
    "one_row_past_a_block": (10, 5, 18, 0),     # 33 = 32 + 1
    "a_group_over_two_blocks_and_three_groups_in_one": (5, 7, 40, 3),
    "a_group_over_five_blocks": (1, 128, 2, 0),
}


@pytest.mark.parametrize("close", sorted(CLOSE))
@pytest.mark.parametrize("planted", sorted(PLANTED))
def test_planted_counts_of_local_pairs(block_rows, planted, close):
    """Blocks of 32 rows over 512 pairs of which a PLANTED number has an
    expert here: the dispatch alone (``_pair_blocks``) against the parent's
    scan, output and the gradients of the experts, the tokens and the gates."""
    dt, out_tol, grad_tol = CLOSE[close]
    sizes = PLANTED[planted]
    block_rows(32)
    spec = hybrid.MoE(N_EXPERTS, (4, 4), 0, F, top_k=PER_TOKEN, renormalize=True)
    p, u = moe_case(spec)
    experts = {name: p[name] for name in ("wg", "wu", "wdn")}
    u = u.reshape(-1, E).astype(dt)
    e = planted_choices(sizes)
    assert [int((e == 4 + g).sum()) for g in range(4)] == list(sizes)
    gate = jax.nn.softmax(jax.random.normal(jax.random.key(3), e.shape), axis=0)
    v = jax.random.normal(jax.random.key(11), u.shape)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda w, u, gate: jnp.sum(fn(w, u, gate) * v), argnums=(0, 1, 2),
            has_aux=False))(experts, u, gate)

    with jax.default_matmul_precision("highest"):
        got = hybrid._pair_blocks(spec, dt, experts, u, gate, e)
        want = parents_choices_added_up(spec, experts, u, gate, e, dt)
        _, got_g = run(lambda w, u, gate: hybrid._pair_blocks(spec, dt, w, u, gate, e))
        _, want_g = run(lambda w, u, gate: parents_choices_added_up(
            spec, w, u, gate, e, dt))
    assert got.dtype == jnp.float32 and got.shape == u.shape
    assert_close(got, want, out_tol, "output")
    for (name, a), b in zip(named(got_g).items(), jax.tree_util.tree_leaves(want_g)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert_close(a, b, grad_tol, name)
    if sum(sizes) == 0:                    # every block skipped
        assert float(jnp.abs(got).max()) == 0.0
        assert all(float(jnp.abs(a).max()) == 0.0
                   for a in jax.tree_util.tree_leaves(got_g))
    else:
        local = (e >= 4) & (e < 8)
        assert float(jnp.abs(jnp.where(local.any(axis=0)[:, None], 0, got)).max()) == 0.0
        assert float(jnp.abs(got_g[2][~local]).max()) == 0.0
        assert float(jnp.abs(got_g[2][local]).min()) > 0.0


def test_top_k_sorts_the_pairs_once_and_runs_them_in_blocks(block_rows):
    """Above top-1 the layer is ONE scan over blocks of sorted (token, choice)
    pairs with a conditional inside it (a block past the last local pair is
    skipped), one sort, no array of (token, choice) x hidden rows and no
    gathered array taller than a block."""
    rows = 64
    block_rows(rows)
    spec = hybrid.MoE(N_EXPERTS, HELD, 0, F, top_k=PER_TOKEN, renormalize=True)
    p, u = moe_case(spec)

    def value(p, u):
        return jnp.sum(hybrid.moe_ffn(spec, p, u, jnp.float32)[0])

    forward = jax.jit(lambda p, u: hybrid.moe_ffn(spec, p, u, jnp.float32)[0]
                      ).lower(p, u).as_text()
    both = jax.jit(jax.value_and_grad(value, argnums=(0, 1))).lower(p, u).as_text()
    for text, loops in ((forward, 1), (both, 2)):     # forward; and its mirror
        assert text.count("stablehlo.while") == loops
        assert text.count("stablehlo.case") + text.count("stablehlo.if") == loops
        assert text.count("stablehlo.sort") == 1
        assert f"{PAIRS}x{E}x" not in text and f"{PAIRS}x{F}x" not in text
        gathered = re.findall(r'"stablehlo.gather".*-> tensor<(\d+)x\d+xf32>', text)
        assert gathered and {int(n) for n in gathered} == {rows}
    body = forward[forward.index("stablehlo.while"):]
    assert "stablehlo.case" in body or "stablehlo.if" in body
    assert f"{BATCH * SEQ}x{E}" in forward


def test_place_experts_reorders_a_linear_routers_columns(case):
    params, toks, cfg = case["params"], case["toks"], case["cfg"]
    placed = hybrid.place_experts(params, toks, cfg)
    before = np.asarray(hybrid.routing_stats(params, toks, cfg))
    after = np.asarray(hybrid.routing_stats(placed, toks, cfg))
    assert before.sum() == after.sum() == 2 * BATCH * SEQ * PER_TOKEN
    for a, b, lp, lq in zip(before, after, params["layers"], placed["layers"]):
        assert sorted(a) == sorted(b) or True        # later layers see new inputs
        wa, wb = np.asarray(lp["moe"]["router"]["w"]), np.asarray(lq["moe"]["router"]["w"])
        assert sorted(map(tuple, wa.T)) == sorted(map(tuple, wb.T))
    # dealt by load, heaviest first to the lightest of the 4 chips: this
    # chip's share of the first layer's pairs is near a quarter
    share = after[0][:4].sum() / after[0].sum()
    assert abs(share - 0.25) < abs(before[0][:4].sum() / before[0].sum() - 0.25) + 0.02


# ------------------------------------------------------- scopes, counters, remat

def test_counters_say_what_one_trace_held(case):
    METRICS.reset()
    jax.jit(lambda p: objective(p, case["toks"], case["tgts"], case["cfg"])
            ).lower(case["params"])
    c = METRICS.snapshot()["counters"]
    assert c["dsa.layers"] == 2 and c["attention.path.xla"] == 2
    assert "attention.path.kernel" not in c
    assert (c["loop.steps"], c["loop.layer_applications"]) == (1, 2)


def kernel_chunks(**kw):
    """What the index kernels take: 128 queries at a time, index heads of 64."""
    return dict(dict(index_dim=64, rows=128, top_k=48, q_chunk=128, kv_chunk=128),
                **kw)


@pytest.mark.parametrize("asked,mixer_kw,want", [
    ("auto", kernel_chunks(), (0, 2)),
    ("selected", kernel_chunks(), (2, 0)),
    ("selected", kernel_chunks(index_dim=32), (0, 2)),
    ("selected", {}, (0, 2)),
    ("ring", kernel_chunks(), (0, 2))],
    ids=["cpu", "kernels", "index-width-32", "chunks-of-16", "xla-attention"])
def test_counters_say_which_path_the_index_scores_took(monkeypatch, asked, mixer_kw,
                                                       want):
    """``dsa.index_path.kernel`` / ``.xla`` once per layer per trace: the
    kernel where the attention takes the selection kernels and the index
    kernels take the chunk, else the XLA path."""
    monkeypatch.setattr(hybrid, "sparse_attend", functools.partial(
        hybrid.sparse_attend, asked=asked))
    jax.clear_caches()
    cfg = config(**mixer_kw)
    params = hybrid.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((1, 256), jnp.int32)
    METRICS.reset()
    jax.jit(jax.grad(lambda p: objective(p, toks, toks, cfg))).lower(params)
    monkeypatch.undo()
    jax.clear_caches()
    c = METRICS.snapshot()["counters"]
    assert (c.get("dsa.index_path.kernel", 0), c.get("dsa.index_path.xla", 0)) == want
    assert c["dsa.layers"] == 2


def test_the_selection_outside_the_step_is_the_one_the_mixer_attends_over(monkeypatch):
    """On the kernels' path, ``sparse_selection`` scores in the same index
    kernel as the mixer's chunks (once a span of chunks), and the reference
    attending over ITS keys gives the mixer's output and index loss."""
    spec = mixer(**kernel_chunks())
    p, u = mixer_case(spec, t=256)
    # both ask the one predicate: forced to the kernels, as a parity test does
    candidate = pallas_attention.attention_candidate
    monkeypatch.setattr(pallas_attention, "attention_candidate",
                        lambda *a, asked="auto", **k: candidate(*a, asked="selected", **k))
    jax.clear_caches()
    with jax.default_matmul_precision("highest"):
        got, loss = hybrid.sparse_attention_mixer(spec, p, u, jnp.float32)
        picked = hybrid.sparse_selection(spec, p, u, jnp.float32)
    text = jax.jit(lambda u: hybrid.sparse_selection(
        spec, p, u, jnp.float32)).lower(u).as_text()
    monkeypatch.undo()
    jax.clear_caches()
    spans = len(hybrid._key_spans(spec, 256)[1])
    assert len(re.findall(r"call @_index_fwd(_\d+)?\(", text)) == spans
    m = dict(model(top=48), sa_config=dict(model()["sa_config"], topk=48,
                                           indexer_head_dim=64))
    with jax.default_matmul_precision("highest"):
        for i in range(BATCH):
            want, want_loss, own = ref.sparse_attention(p, u[i], m, jnp.matmul,
                                                        given=picked[i])
            np.testing.assert_allclose(got[i], want, atol=3e-5)
            assert float(loss[i]) == pytest.approx(float(want_loss), rel=1e-4)
            assert bool((own == picked[i]).all())


def zaya_config(n_layers=4):
    """ZAYA's layer at this file's widths: CCA and a top-1 expert layer
    behind the router MLP."""
    layer = (hybrid.CCA(H, G, D), hybrid.MoE(8, (0, 4), 32, F))
    return hybrid.HybridConfig(base=config().base, layers=(layer,) * n_layers)


@pytest.mark.parametrize("family,pairs,choice,blocks", [
    ("sparse", 6, 0, 6 * 4), ("zaya", 0, 4, 0)])
def test_counters_say_which_dispatch_each_expert_layer_took(
        block_rows, family, pairs, choice, blocks):
    """One trace of the model: a top-k layer counts ``moe.dispatch.path.pairs``
    and the blocks its scan HAS (512 pairs in blocks of 128), a top-1 layer
    ``moe.dispatch.path.choice`` and no block."""
    block_rows(128)
    cfg = config(n_layers=6) if family == "sparse" else zaya_config()
    params = hybrid.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((BATCH, SEQ), jnp.int32)
    METRICS.reset()
    jax.jit(lambda p: objective(p, toks, toks, cfg)).lower(params)
    c = METRICS.snapshot()["counters"]
    assert (c.get("moe.dispatch.path.pairs", 0), c.get("moe.dispatch.path.choice", 0),
            c.get("moe.dispatch.blocks", 0)) == (pairs, choice, blocks)


SCOPES = {"dsa.index_proj": "qkv_proj", "dsa.index_scores": "attention",
          "dsa.select": "attention", "dsa.index_loss": "attention",
          "moe.router": "ffn", "moe.dispatch": "ffn", "moe.experts": "ffn",
          "moe.combine": "ffn"}


@pytest.fixture(scope="module")
def step_hlo():
    cfg = config(dtype=jnp.bfloat16, remat=True)
    params = seeded_params(cfg)
    toks = jnp.zeros((BATCH, SEQ), jnp.int32)
    return jax.jit(jax.grad(objective), static_argnums=3).lower(
        params, toks, toks, cfg).compile().as_text()


@pytest.mark.parametrize("inner,outer", sorted(SCOPES.items()))
def test_new_scopes_nest_in_the_sublayers_the_readers_know(step_hlo, inner, outer):
    # (inside a loop's body the CPU's text keeps a path from the body on; a
    # device trace carries the whole path, and whole paths are what is read)
    paths = [p for p in re.findall(r'op_name="([^"]*)"', step_hlo)
             if p.startswith("jit(")]
    mine = [p for p in paths if f"/{inner}/" in p or p.endswith(f"/{inner}")]
    assert mine, f"no operation under {inner}"
    assert all(outer in p.split(inner)[0] for p in mine), mine[:3]
    assert any("transpose(" in p for p in mine) or inner in (
        "dsa.select", "moe.router")          # the backward pass is there too


def test_a_checkpointed_block_keeps_the_mixers_output(step_hlo):
    """Under ``remat`` the block's recomputed forward stops at the chunks'
    inputs: the selection runs twice a span (forward, and in each chunk's own
    backward), not three times."""
    loops = re.findall(r'= \([^)]*\) while\([^\n]*dsa\.select', step_hlo)
    spans = len(hybrid._key_spans(mixer(), SEQ)[1])
    assert len(loops) == 2 * spans * 2, len(loops)        # x 2 layers


def test_block_hands_up_the_mixers_loss_and_no_other_family_has_one(case):
    cfg, params, toks = case["cfg"], case["params"], case["toks"]
    x = jnp.take(params["tok_embed"], toks, axis=0)
    y, e, own = hybrid.block(params["layers"][0], x, cfg, 0)
    assert y.shape == x.shape and e.shape == (BATCH, SEQ, PER_TOKEN)
    assert own.shape == (BATCH,)
    hs, choices, total = hybrid.run_layers(params, toks, cfg)
    assert hs.shape == (1, BATCH, SEQ, E) and total.shape == (BATCH,)
    assert hybrid.encode_steps(params, toks, cfg)[1][0].shape == (1, BATCH, SEQ, PER_TOKEN)
    dense = hybrid.HybridConfig(base=cfg.base, norm_eps=1e-6, layers=(
        (hybrid.Attention(H, G, D), hybrid.GatedMLP(F)),))
    dense_params = hybrid.init_params(jax.random.key(0), dense)
    assert hybrid.run_layers(dense_params, toks, dense)[2] is None
    assert hybrid.block(dense_params["layers"][0], x, dense, 0)[2] is None
