"""What PR 34 adds to the benchmark, off the chip: ``flops_sparse`` against a
hand count, the configuration file against the catalog's numbers, the
``train_sparse`` runner at a tiny size on the CPU and the arithmetic of its
comparison with the reference, the float8 control at a tiny size, the
benchmark's copy of the reference against the program's, the ``dsa_counters``
reader on hand-made counters, and the ``scope_split`` reader finding the four
``dsa.*`` scopes and the ``moe.*`` ones in a hand-written row file.  Nothing
here is a device number."""

import copy
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import (control_sparse, flops_sparse, harness,  # noqa: E402
                       reference_keye, trace_reduce as tr)
from benchmark.readers import (dsa_counters, moe_counters, scope_share,  # noqa: E402
                               scope_split)
from benchmark.runners import train_sparse  # noqa: E402
from deeplearning4j_tpu.models import hybrid  # noqa: E402
from deeplearning4j_tpu.models.reference import keye as program_reference  # noqa: E402

HERE = Path(__file__).resolve().parent
TINY = HERE / "tiny"
MANIFEST = harness.manifest()
CONFIG = harness.load("configs", "keye_vl2_30b_a3b_ep8")
CELL_NAME = "keye_vl2_30b_a3b_ep8.train_b1_s16384"
CELL = harness.load("workloads", CELL_NAME)
T = 16384
#: the catalog row's ``config`` (architectures.jsonl, Keye-VL-2.0-30B-A3B),
#: every key but the three that ``reduced`` lists
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False}
LAYER = 96_899_456            # parameters of one layer at 16 of 128 experts


# ------------------------------------------------------------------- the FLOPs

def test_flops_against_a_hand_count():
    """The published widths, 6 layers, 16 of 128 experts at the uniform share
    of an eighth, 16,384 positions: forward per token and layer, by part, as
    ISSUE 34 counts them; 33.45 TFLOP a step trained."""
    parts = flops_sparse.forward_flops_per_token(CONFIG, T, 1 / 8)
    assert parts == {
        "projections": 6 * 2 * (2 * 2048 * 4096 + 2 * 2048 * 512),   # 37.75 M
        "index_projections": 6 * 2 * 2048 * (1024 + 64 + 16),         # 4.52 M
        "index_scores": 6 * 2 * 16 * 64 * (T + 1) / 2,                # 16.78 M
        "attention": 6 * 4 * 4096 * 31_458_304 / T,                   # 31.46 M
        "router": 6 * 2 * 2048 * 128,                                 # 0.52 M
        "experts": 6 * 2 * 3 * 2048 * 768 * 8 / 8,                    # 9.44 M
        "head": 2 * 18992 * 2048,                                     # 77.79 M
    }
    per_layer = {k: round(v / 6 / 1e6, 1) for k, v in parts.items() if k != "head"}
    assert per_layer == {"projections": 37.7, "index_projections": 4.5,
                         "index_scores": 16.8, "attention": 31.5, "router": 0.5,
                         "experts": 9.4}
    forward = sum(parts.values())
    assert round(forward / 1e6) == 681
    assert flops_sparse.train_flops_per_token(CONFIG, T, 1 / 8) == 3.0 * forward
    assert round(3 * forward * T / 1e12, 1) == 33.5               # TFLOP a step
    mechanism = (parts["index_projections"] + parts["index_scores"]
                 + parts["attention"])
    assert 52 <= 100 * mechanism / (forward - parts["head"]) < 53


@pytest.mark.parametrize("t,k,want", [
    (4, 8, 10), (8, 8, 36), (9, 8, 44), (2048, 2048, 2048 * 2049 // 2),
    (T, 2048, 2048 * 2049 // 2 + (T - 2048) * 2048)])
def test_attention_counts_selected_pairs(t, k, want):
    assert flops_sparse.selected_pairs(t, k) == want
    assert want == sum(min(s + 1, k) for s in range(t))


@pytest.mark.parametrize("t,share", [(2048, 100.0), (4096, 75.0), (8192, 43.7),
                                     (T, 23.4)])
def test_the_selection_keeps_this_share_of_the_causal_pairs(t, share):
    """Why 16,384: the mechanism is idle up to 2048 positions."""
    got = 100 * flops_sparse.selected_pairs(t, 2048) / (t * (t + 1) / 2)
    assert round(got, 1) == share


def test_a_masked_dense_attention_would_not_read_as_a_faster_model():
    """The FLOPs follow what is SELECTED: more keys kept is more work, all
    causal keys is plain attention's count, and the length moves the index
    scores (every causal pair) and not the attention past ``topk``."""
    def at(topk, t=T):
        model = dict(CONFIG, sa_config=dict(CONFIG["sa_config"], topk=topk))
        return flops_sparse.forward_flops_per_token(model, t, 1 / 8)
    assert at(4096)["attention"] > 1.8 * at(2048)["attention"]
    assert at(T)["attention"] == 6 * 4 * 4096 * (T + 1) / 2
    for part in ("projections", "index_scores", "experts", "head"):
        assert at(4096)[part] == at(2048)[part]
    assert at(2048, 2 * T)["index_scores"] > 1.99 * at(2048)["index_scores"]
    assert at(2048, 2 * T)["attention"] < 1.04 * at(2048)["attention"]
    half = flops_sparse.forward_flops_per_token(CONFIG, T, 1 / 16)
    assert half["experts"] * 2 == at(2048)["experts"]


# ------------------------------------------------------- the configuration file

def test_configuration_keeps_the_published_numbers():
    assert {k: CONFIG[k] for k in CATALOG} == CATALOG
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (6, 16, 18992)
    assert CONFIG["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                   "vocab_size": 151936}
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert set(CONFIG["changed"]) >= set(CONFIG["reduced"])
    assert CONFIG["vocab_size"] * 8 == 151936 and CONFIG["num_experts"] * 8 == 128
    assert (CONFIG["router_width"], CONFIG["experts_held"]) == (128, [0, 16])
    for key in ("qk_norm", "rope", "indexer", "selection", "index_loss", "router",
                "experts", "init", "precision", "memory", "placement"):
        assert CONFIG["assumed"][key]
    assert set(CONFIG["left_out"]) == {"vision_tower", "balance_loss",
                                       "dense_warm_up"}
    assert "8 pipeline stages of 6" in CONFIG["deployment"]
    assert "659,190,016" in CONFIG["deployment"] and "10.55 GB" in CONFIG["deployment"]
    names = [c["name"] for c in MANIFEST["configs"]]
    # appended: after the three configurations the benchmark had (a later
    # PR appends after it; no test here says "last")
    assert names[:4] == ["bert_base", "zaya1_8b_ep2", "ouro_2_6b_l8",
                         "keye_vl2_30b_a3b_ep8"]
    entry = MANIFEST["configs"][3]
    assert (entry["source"], entry["reduced"]) == (CONFIG["source"], CONFIG["reduced"])
    assert entry["file"] == "benchmark/configs/keye_vl2_30b_a3b_ep8.json"


def test_configuration_builds_the_model_at_its_widths():
    cfg = train_sparse.hybrid_config(CONFIG)
    mixer, ffn = cfg.layers[0]
    assert len(cfg.layers) == 6 and len(set(cfg.layers)) == 1
    assert mixer == hybrid.SparseAttention(
        32, 4, 128, 1e7, True, 16, 64, 2048, 512, 512, norm_eps=1e-6)
    assert ffn == hybrid.MoE(128, (0, 16), 0, 768, top_k=8, renormalize=True)
    assert not mixer.post_norm and not ffn.post_norm and mixer.aux_loss
    assert (cfg.n_loops, cfg.exit_beta, cfg.norm_eps) == (1, None, 1e-6)
    assert (cfg.base.vocab_size, cfg.base.d_model) == (18992, 2048)
    assert not cfg.base.tie_embeddings and cfg.base.remat
    assert cfg.base.dtype == jnp.bfloat16 and cfg.base.param_dtype == jnp.float32
    shapes = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == 659_190_016                    # x 16 B = 10.55 GB of state
    assert n == 6 * LAYER + 2 * 18992 * 2048 + 2048
    layer = sum(a.size for a in jax.tree_util.tree_leaves(shapes["layers"][0]))
    outside = layer - 16 * 3 * 2048 * 768
    assert (layer, outside) == (LAYER, 21_401_984)
    index = sum(a.size for a in jax.tree_util.tree_leaves(
        shapes["layers"][0]["dsa"]["index"]))
    assert index == 2048 * 1024 + 2048 * 64 + 2048 * 16 + 128 == 2_261_120
    assert shapes["lm_head"].shape == (2048, 18992)
    assert shapes["layers"][0]["moe"]["router"]["w"].shape == (2048, 128)
    assert shapes["layers"][0]["moe"]["wg"].shape == (16, 2048, 768)


def test_cell_is_what_the_issue_names():
    want = {"runner": "train_sparse", "chips": 1, "n_dp": 1, "zero_stage": 0,
            "global_batch": 1, "seq_len": T, "pool_batches": 8,
            "warmup_batches": 2, "reference_block": 256}
    assert {k: CELL[k] for k in want} == want
    assert 1 <= CELL["resolve_every"] <= 8
    a, b = CELL["trace_slice_s"]
    assert b - a >= 12                      # two whole steps of 3-4 s and more
    lo, hi = CELL["first_loss_band"]
    # ln 18,992 = 9.85, plus half the variance of a unit-variance logit, plus
    # six layers' index losses
    assert lo < 9.85 + 0.5 < hi
    limits = CELL["compare"]
    assert set(limits["grad_rel"]) == set(limits["grad_cos"]) == set(
        reference_keye.GROUPS)
    assert set(limits) == {"loss_abs", "lm_abs", "index_abs", "grad_rel",
                           "grad_cos", "routing_differs", "selection_differs"}
    assert set(limits) | {"what"} == set(CELL["compare_why"])
    assert [w["name"] for w in MANIFEST["workloads"]].index(CELL_NAME) == 4
    entry = MANIFEST["workloads"][4]             # after the four accepted cells
    assert (entry["name"], entry["config"], entry["traffic"], entry["chips"]) == (
        CELL_NAME, "keye_vl2_30b_a3b_ep8", "train_b1_s16384", 1)
    assert len(entry["why"]) <= 200 and "1/8" in entry["why"]


def test_cell_reports_the_common_metrics_and_its_own():
    names = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", CELL_NAME)}
    zaya = {m["name"] for m in harness.cell_metrics(
        MANIFEST, "per_layer", "zaya1_8b_ep2.train_b4_s4096")}
    own = {"dsa_share.index_proj.train", "dsa_share.index_scores.train",
           "dsa_share.select.train", "dsa_share.index_loss.train",
           "dsa_selected_pair_share.train", "dsa_empty_tile_share.train"}
    assert names - zaya == own
    assert zaya - names == {"cca_mix_share.train"}
    assert len(names) == 14 + 6 + 6
    order = [m["name"] for m in MANIFEST["per_layer"]]
    first = order.index("dsa_share.index_proj.train")
    assert first == order.index("loop_expected_steps.train") + 1    # appended
    assert set(order[first:first + 6]) == own
    for m in MANIFEST["per_layer"][first:first + 6]:
        assert m["workloads"] == [CELL_NAME]
        assert m["moves"] == "train_tokens_per_s" and m["layer"] == "model"
        spec = harness.load("layer_metrics", m["name"])
        assert (spec["name"], spec["unit"]) == (m["name"], m["unit"] == "%" and "%")
    assert [m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", CELL_NAME)
            ] == ["train_tokens_per_s", "setup_s"]
    # every list the cell joined got it after the accepted cells, once
    accepted = ["bert_base.train_b64", "bert_base.train_dp4",
                "zaya1_8b_ep2.train_b4_s4096", "ouro_2_6b_l8.train_b2_s4096"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        listed = m.get("workloads", [])
        if CELL_NAME in listed:
            before = listed[:listed.index(CELL_NAME)]
            assert before == [c for c in accepted if c in before]
            assert listed.count(CELL_NAME) == 1
    assert CELL_NAME not in next(m for m in MANIFEST["per_layer"] if m[
        "name"] == "scope_share.grad_sync.train")["workloads"]


# ------------------------- the looped cell's entries, wherever in the lists
# (what three tests of test_benchmark_looped.py hold besides "PR 32's entries
# are the LAST": tests/conftest.py says why those three are expected to fail)

LOOPED = harness.load("configs", "ouro_2_6b_l8")
LOOPED_CELL = "ouro_2_6b_l8.train_b2_s4096"


def test_looped_configuration_keeps_its_published_numbers():
    assert (LOOPED["num_hidden_layers"], LOOPED["total_ut_steps"]) == (8, 4)
    assert LOOPED["published"] == {"num_hidden_layers": 48}
    assert LOOPED["reduced"] == ["num_hidden_layers"]
    assert set(LOOPED["left_out"]) == {"second_stage", "early_exit"}
    assert "612,438,017" in LOOPED["deployment"] and "9.80 GB" in LOOPED["deployment"]
    entry = MANIFEST["configs"][2]
    assert (entry["name"], entry["source"], entry["reduced"]) == (
        "ouro_2_6b_l8", LOOPED["source"], LOOPED["reduced"])


def test_looped_cell_is_what_its_issue_named():
    cell = harness.load("workloads", LOOPED_CELL)
    want = {"runner": "train_looped", "chips": 1, "n_dp": 1, "zero_stage": 0,
            "global_batch": 2, "seq_len": 4096, "resolve_every": 8,
            "pool_batches": 8, "warmup_batches": 2, "trace_slice_s": [5, 12]}
    assert {k: cell[k] for k in want} == want
    assert all(k in cell["compare_why"] for k in (
        "what", "loss_abs", "xent_abs", "exit_abs", "grad_rel", "grad_cos"))
    entry = MANIFEST["workloads"][3]
    assert (entry["name"], entry["config"], entry["chips"]) == (
        LOOPED_CELL, "ouro_2_6b_l8", 1)


def test_looped_cell_reports_the_common_metrics_and_its_own():
    names = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", LOOPED_CELL)}
    zaya = {m["name"] for m in harness.cell_metrics(
        MANIFEST, "per_layer", "zaya1_8b_ep2.train_b4_s4096")}
    own = {"head_recompute_share.train", "loop_exit_share.train",
           "loop_expected_steps.train"}
    assert names - zaya == own and len(names) == 14 + 1 + 3
    for m in MANIFEST["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [LOOPED_CELL]
    assert [m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", LOOPED_CELL)
            ] == ["train_tokens_per_s", "setup_s"]


# ------------------------------------------------------------- the comparison

GROUPS = reference_keye.GROUPS
SIDE = {"objective": 14.2000, "lm": 10.3500, "index": 3.8500}
GOOD = {"ref": SIDE, "program": copy.deepcopy(SIDE),
        "grads": {g: {"rel": 0.01, "cos": 0.9999} for g in GROUPS},
        "routing_differs": 0.004, "selection_differs": 0.002, "selected": 1000}
LIMITS = {"loss_abs": 0.001, "lm_abs": 0.001, "index_abs": 0.002,
          "grad_rel": {g: 0.05 for g in GROUPS},
          "grad_cos": {g: 0.998 for g in GROUPS},
          "routing_differs": 0.01, "selection_differs": 0.005}


def test_comparison_passes_inside_its_limits():
    checks = train_sparse.judge_compare(GOOD, 14.2004, LIMITS)
    assert len(checks) == 3 + len(GROUPS) + 2
    assert all(ok for ok, _ in checks), checks
    assert "<= 0.001" in checks[0][1] and "<= 0.002" in checks[2][1]
    assert "1000 selected" in checks[-1][1]


BROKEN = ([("first", None, None, 0), ("lm", None, None, 1), ("index", None, None, 2)]
          + [("rel", g, 0.0501, 3 + i) for i, g in enumerate(GROUPS)]
          + [("cos", g, 0.9979, 3 + i) for i, g in enumerate(GROUPS)]
          + [("routing_differs", None, 0.0101, 3 + len(GROUPS)),
             ("selection_differs", None, 0.0051, 4 + len(GROUPS))])


@pytest.mark.parametrize("what,group,value,failing", BROKEN,
                         ids=[f"{w}-{g}" if g else w for w, g, _, _ in BROKEN])
def test_one_reading_past_its_limit_fails_one_check(what, group, value, failing):
    readings, first = copy.deepcopy(GOOD), 14.2000
    if what == "first":
        first += 0.0011
    elif what in ("lm", "index"):
        readings["program"][what] += 0.0021
    elif group:
        readings["grads"][group][what] = value
    else:
        readings[what] = value
    checks = train_sparse.judge_compare(readings, first, LIMITS)
    assert [i for i, (ok, _) in enumerate(checks) if not ok] == [failing]


@pytest.mark.parametrize("got,own,want", [
    ([[0, 1, 2, 3]], [[3, 2, 1, 0]], 0.0),           # another order: the same set
    ([[0, 1, 2, 3]], [[0, 1, 2, 9]], 0.25),
    ([[0, 1, 2, 3], [4, 5, 6, 7]], [[0, 1, 2, 3], [7, 6, 9, 8]], 0.25),
    ([[0, 1, 2, 3]], [[4, 5, 6, 7]], 1.0)])
def test_differing_choices_compare_sets_of_experts(got, own, want):
    assert train_sparse.differing_choices(
        jnp.asarray(got)[None, None], jnp.asarray(own)[None, None]) == want


# -------------------------------------------------------------- the reference

def tiny_case(seed=3):
    config = harness.load("configs", "tiny_keye", TINY)
    cfg = train_sparse.hybrid_config(config)
    params = hybrid.init_params(jax.random.key(seed), cfg)
    toks = jax.random.randint(jax.random.key(seed + 1), (2, 64), 0, 512)
    return cfg, config, params, toks, jnp.roll(toks, -1, 1)


def test_benchmark_copy_is_the_programs_reference():
    """Letter for letter after the copy's own heading, so that the two can
    only part by an edit that this test makes visible."""
    mark = "Plain reference of the sparse-attention mixture-of-experts block"
    mine = (REPO / "benchmark" / "reference_keye.py").read_text()
    theirs = (REPO / "deeplearning4j_tpu" / "models" / "reference" / "keye.py").read_text()
    assert mine.split(mark, 1)[1] == theirs.split(mark, 1)[1]
    assert reference_keye.GROUPS == program_reference.GROUPS


def test_layer_by_layer_gradients_are_the_whole_models():
    """The benchmark's ``loss_and_grads`` (chain rule by hand over the layers,
    scores and head in blocks) against ``jax.value_and_grad`` of its whole
    ``loss``."""
    _, model, params, toks, tgts = tiny_case()
    total, grads, aux = reference_keye.loss_and_grads(
        params, toks, tgts, model, block_rows=16)
    whole = [jax.value_and_grad(reference_keye.loss, has_aux=True)(
        params, toks[i], tgts[i], model) for i in range(2)]
    assert float(total) == pytest.approx(
        sum(float(v) for (v, _), _ in whole) / 2, rel=1e-6)
    assert float(aux["lm"] + aux["index"]) == pytest.approx(float(total), rel=1e-6)
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, whole[0][1], whole[1][1])
    same = reference_keye.compare_grads(grads, mean)
    assert all(v["rel"] < 1e-5 for v in same.values()), same
    assert aux["choices"].shape == (2, 2, 64, 4)
    assert aux["selected"] == 2 * 2 * flops_sparse.selected_pairs(64, 8)
    assert aux["selection_differs"] == 0.0


# ------------------------------------------------------------------ the runner

def test_train_sparse_runs_the_tiny_cell():
    w = harness.load("workloads", "tiny_keye.train", TINY)
    cell = harness.Cell(
        workload=w, config=harness.load("configs", w["config"], TINY),
        seed=2**31 + 11, seconds=1.0, devices=jax.devices()[:1],
        process_t0=time.perf_counter())
    opened = []
    cell.on_window = opened.append
    out = train_sparse.run(cell)
    assert out.correct and out.attempted >= 8 and out.failed == 0 and len(opened) == 1
    assert out.end_to_end["train_tokens_per_s"] > 0 and out.end_to_end["setup_s"] > 0
    f = out.facts
    c = f["counters"]
    assert f["tokens_per_step"] == 2 * 64 and f["chips"] == 1
    pairs = 8 * 2 * 64 * 4 * 2             # pool x batch x seq x choices x layers
    assert c["moe.tokens_total"] == pairs and 0 < c["moe.tokens_local"] < pairs
    local = c["moe.tokens_local"] / pairs
    assert f["flops_per_token"] == flops_sparse.train_flops_per_token(
        cell.config, 64, local)
    assert moe_counters.read({"what": "local_share"}, {"facts": f}) == 100 * local
    assert c["dsa.pairs_causal"] == 8 * 2 * 2 * 64 * 65 // 2
    assert c["dsa.pairs_selected"] == 8 * 2 * 2 * flops_sparse.selected_pairs(64, 8)
    assert c["dsa.tiles_total"] == 8 * 2 * 2 * 36
    assert c.get("train_step.recompile", 0) == 0
    share = dsa_counters.read({"what": "selected_pair_share"}, {"facts": f})
    assert share == pytest.approx(100 * flops_sparse.selected_pairs(64, 8) / 2080)
    assert 0 <= dsa_counters.read({"what": "empty_tile_share"}, {"facts": f}) < 100


def test_control_comes_out_as_not_correct_on_the_tiny_cell():
    """``control_sparse.controls`` at a tiny size: the float8 reference in the
    program's place gives readings in the runner's own form and reads past the
    REAL cell's gradient limits even here (the tiny cell's own limits are
    loose: it runs in bf16 on a CPU)."""
    cfg, model, params, toks, tgts = tiny_case()
    w = harness.load("workloads", "tiny_keye.train", TINY)
    found = control_sparse.controls(params, toks, tgts, cfg, model, w)
    assert set(found) == {"float8"}
    readings, checks = found["float8"]
    assert len(checks) == 3 + len(GROUPS) + 2 and set(readings["grads"]) == set(GROUPS)
    assert readings["selected"] == 2 * 2 * flops_sparse.selected_pairs(64, 8)
    assert readings["selection_differs"] > 0 and readings["routing_differs"] > 0
    strict = train_sparse.judge_compare(
        readings, readings["program"]["objective"], CELL["compare"])
    assert sum(not ok for ok, _ in strict) >= 1


# ----------------------------------------------------------------- the readers

def test_dsa_counters_reader():
    counters = {"dsa.pairs_selected": 31_458_304.0 * 6, "dsa.pairs_causal":
                T * (T + 1) / 2 * 6, "dsa.tiles_empty": 66.0,
                "dsa.tiles_total": 528.0 * 6, "dsa.layers": 6.0}
    run = {"facts": {"counters": counters}}
    assert round(dsa_counters.read({"what": "selected_pair_share"}, run), 2) == 23.44
    assert dsa_counters.read({"what": "empty_tile_share"}, run) == 100 * 66 / 3168
    none = dict(counters, **{"dsa.tiles_empty": 0.0})
    assert dsa_counters.read({"what": "empty_tile_share"},
                             {"facts": {"counters": none}}) == 0.0
    # a program without the mixer, a run without counters, an unknown quantity
    assert dsa_counters.read({"what": "selected_pair_share"},
                             {"facts": {"counters": {"moe.tokens_total": 4.0}}}) is None
    assert dsa_counters.read({"what": "selected_pair_share"}, {"facts": {}}) is None
    assert dsa_counters.read({"what": "pairs"}, run) is None
    for name, what in (("dsa_selected_pair_share.train", "selected_pair_share"),
                       ("dsa_empty_tile_share.train", "empty_tile_share")):
        spec = harness.load("layer_metrics", name)
        assert (spec["reader"], spec["args"]) == ("dsa_counters", {"what": what})


RECORDED = json.loads((HERE / "trace_rows_sparse.json").read_text())
TPU0 = "/device:TPU:0"


def recorded_ops():
    return [(TPU0, name, start * 1e3, dur * 1e3, path)
            for name, start, dur, path in RECORDED["ops"]]


def recorded_runs():
    rows = ([(TPU0, tr.OP_LINE, n, s * 1e3, d * 1e3) for n, s, d, _ in RECORDED["ops"]]
            + [(TPU0, tr.MODULE_LINE, n, s * 1e3, d * 1e3) for n, s, d in RECORDED["modules"]])
    return tr.whole_runs(rows, "jit_step")


@pytest.mark.parametrize("metric,per_step_us", [
    ("dsa_share.index_proj.train", 30), ("dsa_share.index_scores.train", 120),
    ("dsa_share.select.train", 80), ("dsa_share.index_loss.train", 40),
    ("moe_share.router.train", 10), ("moe_share.dispatch.train", 60),
    ("moe_share.experts.train", 50), ("head_fused_share.train", 100)])
def test_scope_split_on_the_recorded_rows(metric, per_step_us):
    """Each metric file's own arguments, on two whole executions of a step."""
    spec = harness.load("layer_metrics", metric)
    assert (spec["reader"], spec["args"]["module_prefix"]) == ("scope_split", "jit_step")
    runs = recorded_runs()
    assert runs == {TPU0: [(300e3, 1300e3), (1400e3, 2400e3)]}
    under, busy = scope_split.split(recorded_ops(), runs, spec["args"]["scopes"])
    assert busy == 2 * 890e3 and under == 2 * per_step_us * 1e3


def test_scope_share_reads_the_new_scopes_under_the_sublayers():
    """The four ``dsa.*`` scopes count under ``qkv_proj`` and ``attention``
    for the reader of outermost sublayers, whose list was not edited; the
    chunks' loops hide no sublayer."""
    assert "dsa" not in " ".join(scope_share.SUBLAYERS)
    tab = scope_share.table(recorded_ops(), recorded_runs())
    per_step = {scope: (t["fwd"] + t["bwd"]) / 2e3 for scope, t in tab.items()}
    assert per_step == {"embed": 20, "qkv_proj": 130, "attention": 440,
                        "attn_out": 20, "ffn": 120, "layernorm": 10,
                        "lm_head_loss": 100, "optimizer": 40, None: 10}
    inner = {k: v / 2e3 for k, v in tab["attention"]["inner"].items()}
    assert inner == {"dsa.index_scores": 120, "dsa.select": 80, "dsa.index_loss": 40}
    assert {k: v / 2e3 for k, v in tab["qkv_proj"]["inner"].items()} == {
        "dsa.index_proj": 30}
    assert {k: v / 2e3 for k, v in tab["ffn"]["inner"].items()} == {
        "moe.router": 10, "moe.dispatch": 60, "moe.experts": 50}
    assert tab["attention"]["bwd"] / 2e3 == 60 + 30 + 110 + 25


def test_new_readers_find_nothing_in_a_program_without_the_mixer():
    """What the parent commit reports in a traced run of an accepted cell: the
    metric is left out of the line, and nothing raises."""
    plain = [r[:4] + (r[4].replace("dsa.index_scores/", "").replace(
        "dsa.select/", "").replace("dsa.index_loss/", "").replace(
            "dsa.index_proj/", ""),) for r in recorded_ops()]
    runs = recorded_runs()
    for scope in ("index_proj", "index_scores", "select", "index_loss"):
        assert scope_split.split(plain, runs, [f"dsa.{scope}"]) is None
        spec = harness.load("layer_metrics", f"dsa_share.{scope}.train")
        assert scope_split.read(spec["args"], {"facts": {}}) is None
