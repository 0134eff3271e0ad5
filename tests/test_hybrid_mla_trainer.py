"""The latent-attention family through the trainer at ``test_hybrid_mla.py``'s
sizes: the selection bias's update by its rule and never by the optimizer, a
padded batch's rows left out, the steps that refuse moves, and the compiled
step's scopes and checkpointed attention.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models.reference import joyai as ref
from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import local_mesh
from hybrid_common import named, seeded_params
from test_hybrid_mla import (BATCH, BIASES, LEAVES, N_EXPERTS, PER_TOKEN, RATE, SEQ,
                             batch, config, model)


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
    choices = jax.jit(lambda p: hybrid.objective_parts(p, toks, tgts, cfg)[1])(params)
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
    choices = jax.jit(lambda p, x, y: hybrid.objective_parts(p, x, y, cfg)[1])(
        params, *padded)
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



# ------------------------------------------------------- the compiled step's scopes

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
