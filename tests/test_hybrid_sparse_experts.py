"""The sparse family's expert layer (top-k behind a linear router) at
``test_hybrid_sparse.py``'s sizes: against ``models/reference/keye.py``, its
shares, the parent's top-1 program, the blocks of sorted pairs against the
parent's scan (planted counts too), and the dispatch each layer counts.
"""

import dataclasses
import functools
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models.reference import keye as ref
from deeplearning4j_tpu.observability import METRICS
from hybrid_common import named, objective
from test_hybrid_sparse import (BATCH, D, E, F, G, H, HELD, N_EXPERTS, PER_TOKEN,
                                SEQ, config, model)


@functools.cache         # arrays outlive the caches a test here clears
def moe_case(spec, seed=5):
    p = spec.init(jax.random.key(seed), E, jnp.float32)
    u = jax.random.normal(jax.random.key(seed + 1), (BATCH, SEQ, E))
    return p, u


#: the layer compiled once a spec and a dtype
moe_layer = jax.jit(hybrid.moe_ffn, static_argnums=(0, 3))


def reference(held):
    """The reference's layer over one example, compiled once a call."""
    return jax.jit(lambda p, x: ref.moe(p, x, model(held), jnp.matmul))


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
        got, e = moe_layer(spec, p, u, jnp.float32)
        one = reference(held)
        for i in range(BATCH):
            want, want_e = one(p, u[i])
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
            out, e = moe_layer(share, part, u, jnp.float32)
            total = total + out
            landed += int(((e >= first) & (e < first + 4)).sum())
            mine = ((e >= first) & (e < first + 4)).any(axis=-1)
            assert float(jnp.abs(out[~mine]).max()) == 0.0   # nothing stands in
        one = reference((0, N_EXPERTS))
        uncut = jnp.stack([one(p, u[i])[0] for i in range(BATCH)])
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

    got, e = moe_layer(spec, p, u, dt)
    want, want_e = jax.jit(parents_moe_ffn, static_argnums=(0, 3))(spec, p, u, dt)
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

    def run(fn):            # output, choices and gradients: one program
        def value(p, u):
            y, e = fn(spec, p, u, dt)
            return jnp.sum(y.astype(jnp.float32) * v), (y, e)
        return jax.jit(jax.value_and_grad(value, argnums=(0, 1), has_aux=True))(p, u)

    with jax.default_matmul_precision("highest"):
        (_, (got, e)), got_p = run(hybrid.moe_ffn)
        (_, (want, want_e)), want_p = run(parents_top_k_moe_ffn)
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


@pytest.fixture(scope="module")
def planted_programs():
    """The dispatch over blocks of 32 rows (``_pair_blocks``) and the
    parent's scan, each with the gradients of ``sum(output * v)``: compiled
    once a dtype, the planted choices an argument."""
    spec = hybrid.MoE(N_EXPERTS, (4, 4), 0, F, top_k=PER_TOKEN, renormalize=True)
    experts = {k: a for k, a in jax.eval_shape(lambda: spec.init(
        jax.random.key(0), E, jnp.float32)).items() if k in ("wg", "wu", "wdn")}
    pairs = jax.ShapeDtypeStruct((PER_TOKEN, BATCH * SEQ), jnp.int32)
    rows = jax.ShapeDtypeStruct((BATCH * SEQ, E), jnp.float32)
    programs = {}
    with pytest.MonkeyPatch.context() as mp, jax.default_matmul_precision("highest"):
        mp.setattr(hybrid, "PAIR_BLOCK_ROWS", 32)
        jax.clear_caches()
        for (close, (dt, _, _)), name in itertools.product(CLOSE.items(),
                                                           ("blocks", "parent")):
            def value(w, u, gate, e, v, dt=dt, name=name):
                y = (hybrid._pair_blocks(spec, dt, w, u, gate, e) if name == "blocks"
                     else parents_choices_added_up(spec, w, u, gate, e, dt))
                return jnp.sum(y * v), y
            programs[name, close] = jax.jit(jax.value_and_grad(
                value, argnums=(0, 1, 2), has_aux=True)).lower(
                    experts, rows.update(dtype=dt), pairs.update(dtype=jnp.float32),
                    pairs, rows).compile()
    jax.clear_caches()
    return programs


@pytest.mark.parametrize("close", sorted(CLOSE))
@pytest.mark.parametrize("planted", sorted(PLANTED))
def test_planted_counts_of_local_pairs(planted_programs, planted, close):
    """Blocks of 32 rows over 512 pairs of which a PLANTED number has an
    expert here: the dispatch alone (``_pair_blocks``) against the parent's
    scan, output and the gradients of the experts, the tokens and the gates."""
    dt, out_tol, grad_tol = CLOSE[close]
    sizes = PLANTED[planted]
    spec = hybrid.MoE(N_EXPERTS, (4, 4), 0, F, top_k=PER_TOKEN, renormalize=True)
    p, u = moe_case(spec)
    experts = {name: p[name] for name in ("wg", "wu", "wdn")}
    u = u.reshape(-1, E).astype(dt)
    e = planted_choices(sizes)
    assert [int((e == 4 + g).sum()) for g in range(4)] == list(sizes)
    gate = jax.nn.softmax(jax.random.normal(jax.random.key(3), e.shape), axis=0)
    v = jax.random.normal(jax.random.key(11), u.shape)

    (_, got), got_g = planted_programs["blocks", close](experts, u, gate, e, v)
    (_, want), want_g = planted_programs["parent", close](experts, u, gate, e, v)
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
        local, d_gate = np.asarray((e >= 4) & (e < 8)), np.asarray(got_g[2])
        assert float(np.abs(np.where(local.any(axis=0)[:, None], 0, got)).max()) == 0.0
        assert float(np.abs(d_gate[~local]).max()) == 0.0
        assert float(np.abs(d_gate[local]).min()) > 0.0


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
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    toks = jnp.zeros((BATCH, SEQ), jnp.int32)
    METRICS.reset()                              # counted while tracing
    jax.jit(lambda p: objective(p, toks, toks, cfg)).trace(params)
    c = METRICS.snapshot()["counters"]
    assert (c.get("moe.dispatch.path.pairs", 0), c.get("moe.dispatch.path.choice", 0),
            c.get("moe.dispatch.blocks", 0)) == (pairs, choice, blocks)


