"""``models/hybrid.py`` (the sparse-attention layer: ``SparseAttention`` mixer
with its indexer and index loss, top-k routed experts behind a linear router)
against the plain reference ``models/reference/keye.py`` at a small size on
the CPU, seeded random weights, float32 at the highest matmul precision: both
parts of the objective, every gradient leaf, the selected keys and the expert
choices, at a length where the selection bites; the mixer without a selection
to make is plain attention; the selection is exact under planted ties; the two
parts' gradients never meet; which path the index scores took is counted, and
on the index kernels the selection made outside the step is the one the mixer
attends over.  The expert layer alone is ``test_hybrid_sparse_experts.py``.
Each program is compiled once: the model's in the module fixtures ``case``
and ``bf16``, a mixer's once a spec in the jitted functions below.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models.reference import keye as ref
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.ops.pallas import attention as pallas_attention
from hybrid_common import leaf_names, named, objective, seeded_params

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


def batch():
    toks = jax.random.randint(jax.random.key(7), (BATCH, SEQ), 0, V)
    return toks, jnp.roll(toks, -1, axis=1)


@pytest.fixture(scope="module")
def case():
    """The float32 model beside the reference: what the tests read of the
    program is ONE compiled program."""
    cfg = config()
    params = seeded_params(cfg)
    toks, tgts = batch()

    def program(params):
        def part(i):
            return named(jax.grad(lambda p: hybrid.loss_parts(
                p, toks, tgts, cfg)[i].mean())(params))
        return (jax.value_and_grad(objective)(params, toks, tgts, cfg),
                hybrid.loss_parts(params, toks, tgts, cfg), part(0), part(1),
                hybrid.selections(params, toks, cfg),
                hybrid.encode(params, toks, cfg)[1],
                hybrid.selection_stats(params, toks, cfg))

    with jax.default_matmul_precision("highest"):
        (loss, grads), parts, lm_grads, index_grads, picked, choices, stats = (
            jax.jit(program)(params))
        ref_loss, ref_grads, ref_aux = ref.loss_and_grads(
            params, toks, tgts, model(), block_rows=16)
    return {"cfg": cfg, "params": params, "toks": toks, "tgts": tgts,
            "loss": loss, "parts": parts, "grads": named(grads),
            "lm_grads": lm_grads, "index_grads": index_grads, "picked": picked,
            "choices": choices, "stats": stats, "ref_loss": ref_loss,
            "ref_aux": ref_aux, "ref_grads": named(ref_grads)}


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
    got = case["picked"]
    with jax.default_matmul_precision("highest"):
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
    got = jnp.stack(case["choices"], axis=1)              # (B, layers, T, k)
    assert got.shape == (BATCH, 2, SEQ, PER_TOKEN)
    assert bool((got == case["ref_aux"]["choices"]).all())


def test_following_the_programs_choices_changes_nothing_when_they_agree(case):
    routing = jnp.stack(case["choices"], axis=1)
    with jax.default_matmul_precision("highest"):
        loss, grads, _ = ref.loss_and_grads(
            case["params"], case["toks"], case["tgts"], model(), block_rows=16,
            selection=case["picked"], routing=routing)
    assert abs(float(loss) - float(case["ref_loss"])) < 1e-6
    same = ref.compare_grads(grads, jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(grads), list(case["ref_grads"].values())))
    assert all(v["rel"] < 1e-5 for v in same.values()), same


def test_layer_by_layer_gradients_are_the_whole_models(case):
    """``loss_and_grads`` (chain rule by hand, scores in blocks) against
    ``jax.value_and_grad`` of the reference's whole ``loss``."""
    params, toks, tgts = case["params"], case["toks"], case["tgts"]
    whole_loss = jax.jit(jax.value_and_grad(
        lambda p, x, y: ref.loss(p, x, y, model()), has_aux=True))
    whole = [whole_loss(params, toks[i], tgts[i]) for i in range(BATCH)]
    assert float(case["ref_loss"]) == pytest.approx(
        sum(float(v) for (v, _), _ in whole) / BATCH, rel=1e-6)
    mean = jax.tree_util.tree_map(lambda *g: sum(g) / BATCH, *[g for _, g in whole])
    got = jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(mean),
                                       list(case["ref_grads"].values()))
    same = ref.compare_grads(got, mean)
    assert all(v["rel"] < 1e-5 for v in same.values()), same


@pytest.fixture(scope="module")
def rounded(case):
    """The reference with every matrix product's operands rounded to a dtype,
    following the exact program's choices: run once a dtype."""
    @functools.cache
    def run(dt):
        return ref.loss_and_grads(
            case["params"], case["toks"], case["tgts"], model(), operand_dtype=dt,
            selection=case["picked"], routing=case["ref_aux"]["choices"])[1:]
    return run


@pytest.mark.parametrize("operand", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bfloat16", "float8"])
def test_lower_precision_reads_further_off(case, rounded, operand):
    """The reading that sets a cell's limits: every matrix product's operands
    rounded, forward and backward, the reference following the exact one's
    choices; float8 moves every group at least twice as far as bfloat16."""
    exact = jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(
        case["params"]), list(case["ref_grads"].values()))
    err = {}
    for dt in (jnp.bfloat16, operand):
        g, aux = rounded(dt)
        err[dt] = ref.compare_grads(g, exact)
        assert 0 < aux["selection_differs"] < 0.2     # its own would differ
    for group in ref.GROUPS:
        assert err[jnp.bfloat16][group]["rel"] > 1e-4
        if operand is not jnp.bfloat16:
            assert err[operand][group]["rel"] > 2 * err[jnp.bfloat16][group]["rel"]


@pytest.fixture(scope="module")
def bf16():
    """bf16 compute over f32 parameters, remat on: the step compiled once,
    for the comparison with the reference and for the text it holds."""
    cfg = config(dtype=jnp.bfloat16, remat=True)
    params = seeded_params(cfg)
    toks, tgts = batch()
    step = jax.jit(jax.value_and_grad(objective), static_argnums=3).lower(
        params, toks, tgts, cfg).compile()
    return cfg, params, toks, tgts, step


def test_bfloat16_program_stays_near_the_reference_it_follows(bf16):
    """The benchmark's comparison at a tiny size: bf16 compute over f32
    parameters, remat on, the reference along the program's own choices."""
    cfg, params, toks, tgts, step = bf16
    loss, grads = step(params, toks, tgts)
    picked, routing = jax.jit(lambda p: (hybrid.selections(p, toks, cfg), jnp.stack(
        hybrid.encode(p, toks, cfg)[1], axis=1)))(params)
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


#: the mixers compiled once a spec and a shape, for every test that runs them
sparse_mixer = jax.jit(hybrid.sparse_attention_mixer, static_argnums=(0, 3))


@functools.partial(jax.jit, static_argnums=0)
def mixer_value_and_grad(spec, p, u):
    def f(p, u):
        out, loss = hybrid.sparse_attention_mixer(spec, p, u, jnp.float32)
        return jnp.sum(out * out) + jnp.sum(loss), (out, loss)
    return jax.value_and_grad(f, has_aux=True)(p, u)


@pytest.mark.parametrize("t", [8, 16])
def test_without_a_selection_to_make_it_is_plain_attention(t):
    """``T <= top_k``: every query keeps every earlier key, and the mixer
    without head norms is ``Attention`` on the same weights, to rounding."""
    spec = mixer(qk_norm=False, top_k=16, rows=8)
    plain = hybrid.Attention(H, G, D, 1e7, 1.0)
    p, u = mixer_case(spec, t)
    with jax.default_matmul_precision("highest"):
        got, loss = sparse_mixer(spec, p, u, jnp.float32)
        want = jax.jit(hybrid.attention_mixer, static_argnums=(0, 3))(
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
        got, loss = sparse_mixer(spec, p, u, jnp.float32)
        reference = jax.jit(lambda p, x: ref.sparse_attention(p, x, model(), jnp.matmul))
        for i in range(BATCH):
            want, want_loss, _ = reference(p, u[i])
            np.testing.assert_allclose(got[i], want, atol=3e-5)
            assert float(loss[i]) == pytest.approx(float(want_loss), rel=1e-4)
        bare, _ = sparse_mixer(
            dataclasses.replace(spec, qk_norm=False), p, u, jnp.float32)
    assert float(jnp.abs(bare - got).max()) > 1e-3        # the norms matter


@pytest.mark.parametrize("rows", [8, 32, SEQ, 24],
                         ids=["8", "32", "whole", "no-divisor"])
def test_chunking_changes_nothing(rows):
    """Any number of queries at a time, over any runs of key lengths: the
    same output, loss and gradient (24 does not divide 64: one chunk)."""
    p, u = mixer_case(mixer())
    with jax.default_matmul_precision("highest"):   # mixer()'s compiled once
        (_, (want, want_loss)), want_g = mixer_value_and_grad(mixer(), p, u)
        (_, (got, got_loss)), got_g = mixer_value_and_grad(mixer(rows=rows), p, u)
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
    masks, stats = case["picked"], np.asarray(case["stats"])
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

@pytest.mark.parametrize("leaf", LEAVES)
def test_each_loss_reaches_its_own_parameters_alone(case, leaf):
    """The language-model loss's gradient is exactly zero on every indexer
    leaf; the index loss's exactly zero on every other leaf; and each is
    alive on its own side."""
    lm_grads, index_grads = case["lm_grads"], case["index_grads"]
    mine, other = ((index_grads, lm_grads) if leaf in INDEXER
                   else (lm_grads, index_grads))
    assert float(jnp.abs(other[leaf]).max()) == 0.0
    assert float(jnp.abs(mine[leaf]).max()) > 0.0


def test_the_two_gradients_add_up_to_the_objectives(case):
    for leaf in LEAVES:
        np.testing.assert_allclose(case["lm_grads"][leaf] + case["index_grads"][leaf],
                                   case["grads"][leaf], atol=1e-6)


def test_place_experts_reorders_a_linear_routers_columns(case):
    params, toks, cfg = case["params"], case["toks"], case["cfg"]
    stats = jax.jit(hybrid.routing_stats, static_argnums=2)
    placed = hybrid.place_experts(params, toks, cfg)
    before = np.asarray(stats(params, toks, cfg))
    after = np.asarray(stats(placed, toks, cfg))
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
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    toks = jnp.zeros((1, 256), jnp.int32)
    METRICS.reset()                              # counted while tracing
    jax.jit(jax.grad(lambda p: objective(p, toks, toks, cfg))).trace(params)
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
        got, loss = jax.jit(hybrid.sparse_attention_mixer, static_argnums=(0, 3))(
            spec, p, u, jnp.float32)
        selection = jax.jit(lambda u: hybrid.sparse_selection(
            spec, p, u, jnp.float32)).lower(u)
        picked = selection.compile()(u)
    text = selection.as_text()
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


SCOPES = {"dsa.index_proj": "qkv_proj", "dsa.index_scores": "attention",
          "dsa.select": "attention", "dsa.index_loss": "attention",
          "moe.router": "ffn", "moe.dispatch": "ffn", "moe.experts": "ffn",
          "moe.combine": "ffn"}


@pytest.fixture(scope="module")
def step_hlo(bf16):
    return bf16[-1].as_text()


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

    def shapes(fn, *args, **kw):          # traced, not run: shapes are what is read
        return jax.eval_shape(functools.partial(fn, **dict(dict(cfg=cfg), **kw)), *args)

    y, e, own = shapes(hybrid.block, params["layers"][0], x, i=0)
    assert y.shape == x.shape and e.shape == (BATCH, SEQ, PER_TOKEN)
    assert own.shape == (BATCH,)
    hs, choices, total = shapes(hybrid.run_layers, params, toks)
    assert hs.shape == (1, BATCH, SEQ, E) and total.shape == (BATCH,)
    assert shapes(hybrid.encode_steps, params, toks)[1][0].shape == (
        1, BATCH, SEQ, PER_TOKEN)
    dense = hybrid.HybridConfig(base=cfg.base, norm_eps=1e-6, layers=(
        (hybrid.Attention(H, G, D), hybrid.GatedMLP(F)),))
    dense_params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), dense))
    assert shapes(hybrid.run_layers, dense_params, toks, cfg=dense)[2] is None
    assert shapes(hybrid.block, dense_params["layers"][0], x, cfg=dense, i=0)[2] is None
