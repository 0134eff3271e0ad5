"""What PR 38 adds to the benchmark, off the chip: ``flops_mla`` against a hand
count, the configuration file against the catalog's numbers, the ``train_mla``
runner at a tiny size on the CPU and the arithmetic of its comparison with the
reference (one reading past its limit fails one check), the float8 control at
a tiny size, the benchmark's copy of the reference against the program's, the
``scope_split`` / ``scope_rest`` readers finding the new scopes in hand-written
rows, and what three accepted tests held beside a position and a list's length
(``tests/conftest.py`` says why those three are expected to fail).  Nothing
here is a device number."""

import copy
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import (control_mla, flops_mla, harness,  # noqa: E402
                       reference_joyai)
from benchmark.readers import scope_rest, scope_split  # noqa: E402
from benchmark.runners import train_mla  # noqa: E402
from deeplearning4j_tpu.models import hybrid  # noqa: E402
from deeplearning4j_tpu.optimize import transforms as tfm  # noqa: E402
from deeplearning4j_tpu.models.reference import joyai as program_reference  # noqa: E402

HERE = Path(__file__).resolve().parent
TINY = HERE / "tiny"
MANIFEST = harness.manifest()
CONFIG = harness.load("configs", "joyai_llm_flash_ep16")
CELL_NAME = "joyai_llm_flash_ep16.train_b2_s8192"
CELL = harness.load("workloads", CELL_NAME)
T = 8192
#: the catalog row's ``config`` (architectures.jsonl, JoyAI-LLM-Flash), every
#: key but the three that ``reduced`` lists
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128}
ATTENTION = 26_347_520        # parameters of one MLA mixer
EXPERT = 3 * 2048 * 768       # of one expert
OUTSIDE = 31_594_752          # of an expert layer outside its routed experts
ACCEPTED = ["bert_base.train_b64", "bert_base.train_dp4",
            "zaya1_8b_ep2.train_b4_s4096", "ouro_2_6b_l8.train_b2_s4096",
            "keye_vl2_30b_a3b_ep8.train_b1_s16384"]
NEW = ["mla_share.down.train", "mla_share.up.train", "mla_share.rope.train",
       "mla_share.attend.train", "mla_share.unnamed.train",
       "moe_share.shared.train", "moe_share.left.train", "mtp_share.merge.train"]


# ------------------------------------------------------------------- the FLOPs

def test_flops_against_a_hand_count():
    """The published widths, the dense layer + 4 expert layers + the module,
    16 of 256 experts at the uniform share of a sixteenth, 8192 positions:
    forward per token and block, by part, as ISSUE 38 counts them; 55.7 TFLOP
    a step trained."""
    parts = flops_mla.forward_flops_per_token(CONFIG, T, 1 / 16)
    assert flops_mla.blocks(CONFIG) == (1, 4, 1)
    projections = 2 * (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                       + 4096 * 2048)
    assert parts == {
        "projections": 6 * projections,                               # 52.7 M
        "attention": 6 * 32 * (192 + 128) * (T + 1),                  # 83.9 M
        "dense_ffn": 2 * 3 * 2048 * 7168,                             # 88.1 M
        "router": 5 * 2 * 2048 * 256,                                 # 1.0 M
        "shared": 5 * 2 * 3 * 2048 * 768,                             # 9.4 M
        "experts": 5 * 2 * 3 * 2048 * 768 * 8 / 16,                   # 4.7 M
        "mtp_merge": 2 * 4096 * 2048,                                 # 16.8 M
        "head": 2 * 16160 * 2048 * (1 + (T - 1) / T),                 # 2 x 66.2 M
    }
    a_block = {k: round(parts[k] / n / 1e6, 1) for k, n in (
        ("projections", 6), ("attention", 6), ("router", 5), ("shared", 5),
        ("experts", 5), ("dense_ffn", 1))}
    assert a_block == {"projections": 52.7, "attention": 83.9, "router": 1.0,
                       "shared": 9.4, "experts": 4.7, "dense_ffn": 88.1}
    layer = 52.7 + 83.9 + 1.0 + 9.4 + 4.7
    assert 89 < 100 * (52.7 + 83.9) / layer < 91        # the mixer: ~90%
    assert 54 < 100 * 83.9 / layer < 56                  # attention alone: 55%
    forward = sum(parts.values())
    assert flops_mla.train_flops_per_token(CONFIG, T, 1 / 16) == 3.0 * forward
    assert round(3 * forward * 2 * T / 1e12, 1) == 55.7           # TFLOP a step
    # 2 * 2 * 8192 head rows less the two that have no target
    assert round(parts["head"] / (2 * 16160 * 2048) * 2 * T) == 32766


def test_flops_follow_the_length_the_share_and_the_blocks():
    at_4096 = flops_mla.forward_flops_per_token(CONFIG, 4096, 1 / 16)
    layer = lambda p: (p["projections"] / 6 + p["attention"] / 6 + (  # noqa: E731
        p["router"] + p["shared"] + p["experts"]) / 5)
    assert 37 < 100 * at_4096["attention"] / 6 / layer(at_4096) < 39   # 38% at 4096
    twice = flops_mla.forward_flops_per_token(CONFIG, T, 1 / 8)
    base = flops_mla.forward_flops_per_token(CONFIG, T, 1 / 16)
    assert twice["experts"] == 2 * base["experts"]
    assert all(twice[k] == base[k] for k in base if k != "experts")
    no_module = dict(CONFIG, num_nextn_predict_layers=0)
    less = flops_mla.forward_flops_per_token(no_module, T, 1 / 16)
    assert less["mtp_merge"] == 0 and less["head"] == 2 * 16160 * 2048
    assert less["attention"] * 6 == base["attention"] * 5


# ------------------------------------------------------- the configuration file

def test_configuration_keeps_the_published_numbers():
    assert {k: CONFIG[k] for k in CATALOG} == CATALOG
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 16, 16160)
    assert CONFIG["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert set(CONFIG["changed"]) >= set(CONFIG["reduced"])
    assert CONFIG["vocab_size"] * 8 == 129280 and CONFIG["n_routed_experts"] * 16 == 256
    assert (CONFIG["router_width"], CONFIG["experts_held"]) == (256, [0, 16])
    assert (CONFIG["bias_update_rate"], CONFIG["mtp_loss_weight"]) == (0.001, 0.3)
    for key in ("bias_update_rate", "mtp_loss_weight", "mtp_input_state",
                "mtp_concat_order", "router", "attention", "init", "precision",
                "memory", "placement"):
        assert CONFIG["assumed"][key]
    assert set(CONFIG["left_out"]) == {"balance_loss", "yarn", "tokenizer"}
    assert "16 v5e chips" in CONFIG["deployment"]
    assert "680,441,088" in CONFIG["deployment"] and "10.89 GB" in CONFIG["deployment"]
    names = [c["name"] for c in MANIFEST["configs"]]
    assert names[:5] == ["bert_base", "zaya1_8b_ep2", "ouro_2_6b_l8",
                         "keye_vl2_30b_a3b_ep8", "joyai_llm_flash_ep16"]
    entry = MANIFEST["configs"][4]
    assert (entry["source"], entry["reduced"]) == (CONFIG["source"], CONFIG["reduced"])
    assert entry["file"] == "benchmark/configs/joyai_llm_flash_ep16.json"
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k.replace(
        "vocab_size", "") for k in CONFIG["reduced"])


def test_configuration_builds_the_model_at_its_widths():
    cfg = train_mla.hybrid_config(CONFIG)
    mixer, dense = cfg.layers[0]
    assert len(cfg.layers) == 5 and len(set(cfg.layers[1:])) == 1
    assert cfg.mtp == cfg.layers[1] and cfg.mtp_weight == 0.3
    assert mixer == hybrid.MLA(32, 1536, 512, 128, 64, 128, 32e6, True,
                               norm_eps=1e-6)
    assert dense == hybrid.GatedMLP(7168, post_norm=False)
    assert cfg.layers[1][1] == hybrid.MoE(
        256, (0, 16), 0, 768, top_k=8, renormalize=True, scoring="sigmoid",
        bias_rate=0.001, scale=2.5, shared_ff=768)
    assert not mixer.post_norm and not dense.post_norm and not mixer.aux_loss
    assert (cfg.n_loops, cfg.exit_beta, cfg.norm_eps) == (1, None, 1e-6)
    assert (cfg.base.vocab_size, cfg.base.d_model) == (16160, 2048)
    assert not cfg.base.tie_embeddings and cfg.base.remat
    assert (2 * 2 * T) % cfg.base.xent_chunk == 0
    assert cfg.base.dtype == jnp.bfloat16 and cfg.base.param_dtype == jnp.float32
    shapes = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))

    def size(tree):
        return sum(a.size for a in jax.tree_util.tree_leaves(tree))

    assert size(shapes["layers"][0]["mla"]) == ATTENTION
    assert size(shapes["layers"][0]) == ATTENTION + 3 * 2048 * 7168 + 4096 == 70_391_808
    assert size(shapes["layers"][1]) == OUTSIDE + 16 * EXPERT == 107_092_224
    assert OUTSIDE == ATTENTION + 2048 * 256 + 256 + EXPERT + 4096
    assert size(shapes["mtp"]) == 107_092_224 + 2 * 2048 * 2048 + 3 * 2048 == 115_486_976
    n = size(shapes)
    assert n == 680_441_088                    # x 16 B = 10.89 GB of state
    assert n == 70_391_808 + 4 * 107_092_224 + 115_486_976 + 2 * 16160 * 2048 + 2048
    assert round(n * 16 / 1e9, 2) == 10.89
    # whole, an expert layer is 1.24 G parameters: a chip cannot hold one
    assert round((OUTSIDE + 256 * EXPERT) * 16 / 1e9, 1) == 19.8
    assert shapes["lm_head"].shape == (2048, 16160)
    router = shapes["layers"][1]["moe"]["router"]
    assert router["w"].shape == (2048, 256) and router["bias"].shape == (256,)
    assert shapes["layers"][1]["moe"]["wg"].shape == (16, 2048, 768)
    assert shapes["layers"][1]["moe"]["shared"]["wg"].shape == (2048, 768)


def test_cell_is_what_the_issue_names():
    want = {"runner": "train_mla", "chips": 1, "n_dp": 1, "zero_stage": 0,
            "global_batch": 2, "seq_len": T, "pool_batches": 8,
            "warmup_batches": 2, "resolve_every": 4}
    assert {k: CELL[k] for k in want} == want
    a, b = CELL["trace_slice_s"]
    assert b - a >= 8                       # several whole steps of 1-2 s
    lo, hi = CELL["first_loss_band"]
    # 1.3 x (ln 16,160 = 9.69, plus half the variance of a unit-variance logit)
    assert lo < 1.3 * (9.69 + 0.5) < hi
    limits = CELL["compare"]
    assert set(limits["grad_rel"]) == set(limits["grad_cos"]) == set(
        reference_joyai.GROUPS)
    assert set(limits) == {"loss_abs", "lm_abs", "mtp_abs", "grad_rel", "grad_cos",
                           "routing_differs", "update_rel", "bias_count_margin"}
    assert set(limits) | {"what"} == set(CELL["compare_why"])
    assert [w["name"] for w in MANIFEST["workloads"]].index(CELL_NAME) == 5
    entry = MANIFEST["workloads"][5]             # after the five accepted cells
    assert (entry["name"], entry["config"], entry["traffic"], entry["chips"]) == (
        CELL_NAME, "joyai_llm_flash_ep16", "train_b2_s8192", 1)
    assert len(entry["why"]) <= 200 and "1/16" in entry["why"]
    config = MANIFEST["configs"][-1]
    assert config["name"] == "joyai_llm_flash_ep16" and len(config["why"]) <= 200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_cell_reports_the_common_metrics_the_expert_layers_and_its_own():
    names = [m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", CELL_NAME)]
    keye = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", ACCEPTED[4])}
    assert set(names) - keye == set(NEW)
    assert keye - set(names) == {
        "dsa_share.index_proj.train", "dsa_share.index_scores.train",
        "dsa_share.select.train", "dsa_share.index_loss.train",
        "dsa_selected_pair_share.train", "dsa_empty_tile_share.train",
        "dsa_share.attend.train", "dsa_share.unnamed.train",
        "moe_share.unnamed.train"}       # its file would call the shared expert unnamed
    assert len(names) == 15 + 7 + 8
    assert [m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", CELL_NAME)
            ] == ["train_tokens_per_s", "setup_s"]
    # appended: this PR's eight close the list, each for this cell alone
    assert [m["name"] for m in MANIFEST["per_layer"]][-8:] == NEW
    for m in MANIFEST["per_layer"][-8:]:
        assert m == {"name": m["name"], "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "model",
                     "moves": "train_tokens_per_s", "workloads": [CELL_NAME]}
    # every list the cell joined got it after the accepted cells, once
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        listed = m.get("workloads", [])
        if CELL_NAME in listed:
            assert listed[-1] == CELL_NAME and listed.count(CELL_NAME) == 1
            assert listed[:-1] == [c for c in ACCEPTED if c in listed]
    for name in ("scope_share.grad_sync.train", "step_share.zero_layout.train",
                 "cca_mix_share.train", "head_recompute_share.train"):
        assert CELL_NAME not in next(
            m for m in MANIFEST["per_layer"] if m["name"] == name)["workloads"]


@pytest.mark.parametrize("name,reader,args", [
    ("mla_share.down.train", "scope_split", {"scopes": ["mla.down"]}),
    ("mla_share.up.train", "scope_split", {"scopes": ["mla.up"]}),
    ("mla_share.rope.train", "scope_split", {"scopes": ["mla.rope"]}),
    ("mla_share.attend.train", "scope_split", {"scopes": ["mla.attend"]}),
    ("mla_share.unnamed.train", "scope_rest",
     {"within": "qkv_proj", "scopes": ["mla.down", "mla.up", "mla.rope"]}),
    ("moe_share.shared.train", "scope_split", {"scopes": ["moe.shared"]}),
    ("moe_share.left.train", "scope_rest",
     {"within": "ffn", "scopes": ["moe.router", "moe.dispatch", "moe.experts",
                                  "moe.combine", "moe.shared"]}),
    ("mtp_share.merge.train", "scope_split", {"scopes": ["mtp.merge"]}),
])
def test_metric_files(name, reader, args):
    spec = harness.load("layer_metrics", name)
    assert spec == {"name": name, "unit": "%", "layer": "model",
                    "moves": "train_tokens_per_s", "reader": reader,
                    "args": {"module_prefix": "jit_step", **args}}
    harness.load_module("readers", reader)


# ------------- what three accepted tests held beside a position and a length
# (tests/benchmark_tests/test_benchmark_scope_rest.py holds PR 36's six entries
# to the END of per_layer and to exact lists of cells; tests/conftest.py says
# why those are expected to fail since this PR appended its own)

PR36 = ["unnamed_share.train", "step_share.zero_layout.train",
        "dsa_share.attend.train", "moe_share.combine.train",
        "dsa_share.unnamed.train", "moe_share.unnamed.train"]


def test_pr_36s_entries_keep_their_order_and_their_accepted_cells():
    order = [m["name"] for m in MANIFEST["per_layer"]]
    first = order.index("dsa_share.index_proj.train")
    assert first == order.index("loop_expected_steps.train") + 1
    assert order[first + 6:first + 12] == PR36
    assert order[first + 12:] == NEW
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    want = {"unnamed_share.train": ACCEPTED + [CELL_NAME],
            "step_share.zero_layout.train": ACCEPTED[1:2],
            "dsa_share.attend.train": ACCEPTED[4:],
            "moe_share.combine.train": [ACCEPTED[2], ACCEPTED[4], CELL_NAME],
            "dsa_share.unnamed.train": ACCEPTED[4:],
            "moe_share.unnamed.train": [ACCEPTED[2], ACCEPTED[4]]}
    for name, cells in want.items():
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "lower", "source": "device_trace",
            "layer": "model", "moves": "train_tokens_per_s", "workloads": cells}
        for cell in cells:
            assert name in [m["name"] for m in harness.cell_metrics(
                MANIFEST, "per_layer", cell)]
    for cell in ACCEPTED + [CELL_NAME]:
        assert [m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", cell)
                ] == ["train_tokens_per_s", "setup_s"]


def test_the_sparse_cells_own_metrics_are_what_they_were():
    names = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", ACCEPTED[4])}
    zaya = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", ACCEPTED[2])}
    own = {"dsa_share.index_proj.train", "dsa_share.index_scores.train",
           "dsa_share.select.train", "dsa_share.index_loss.train",
           "dsa_selected_pair_share.train", "dsa_empty_tile_share.train"}
    assert (names - zaya) - set(PR36) == own
    assert (names - zaya) & set(PR36) == {"dsa_share.attend.train",
                                          "dsa_share.unnamed.train"}
    assert zaya - names == {"cca_mix_share.train"}
    assert len(names - set(PR36)) == 14 + 6 + 6 and not names & set(NEW)
    order = [m["name"] for m in MANIFEST["per_layer"]]
    first = order.index("dsa_share.index_proj.train")
    assert set(order[first:first + 6]) == own
    for m in MANIFEST["per_layer"][first:first + 6]:
        assert m["workloads"] == [ACCEPTED[4]]


# ------------------------------------------------ the reference and the runner

GROUPS = reference_joyai.GROUPS


def tiny(seed=0):
    config = harness.load("configs", "tiny_joyai", TINY)
    cfg = train_mla.hybrid_config(config)
    params = hybrid.init_params(jax.random.key(seed), cfg)
    toks = jax.random.randint(jax.random.key(seed + 1), (2, 64), 0, 512)
    return cfg, config, params, toks, jnp.roll(toks, -1, 1)


def test_benchmark_copy_is_the_programs_reference():
    """Letter for letter, so that the two can only part by an edit that this
    test makes visible."""
    mine = (REPO / "benchmark" / "reference_joyai.py").read_text()
    theirs = (REPO / "deeplearning4j_tpu" / "models" / "reference" / "joyai.py"
              ).read_text()
    assert mine == theirs
    assert reference_joyai.GROUPS == program_reference.GROUPS
    assert "import deeplearning4j_tpu" not in mine and "from deeplearning4j_tpu" not in mine
    assert "from benchmark" not in mine


def test_tiny_cell_through_the_runner():
    w = harness.load("workloads", "tiny_joyai.train", TINY)
    cell = harness.Cell(
        workload=w, config=harness.load("configs", w["config"], TINY),
        seed=2**31 + 11, seconds=1.0, devices=jax.devices()[:1],
        process_t0=time.perf_counter())
    opened = []
    cell.on_window = opened.append
    out = train_mla.run(cell)
    assert out.correct and out.attempted >= 8 and out.failed == 0 and len(opened) == 1
    assert out.end_to_end["train_tokens_per_s"] > 0 and out.end_to_end["setup_s"] > 0
    f = out.facts
    c = f["counters"]
    assert f["tokens_per_step"] == 2 * 64 and f["chips"] == 1
    pairs = 8 * 2 * 64 * 4 * 3             # pool x batch x seq x choices x expert layers
    assert c["moe.tokens_total"] == pairs and 0 < c["moe.tokens_local"] < pairs
    assert "moe.expert_load.l3.e0" in c and "moe.expert_load.l0.e0" not in c
    share = c["moe.tokens_local"] / pairs
    assert f["flops_per_token"] == flops_mla.train_flops_per_token(
        cell.config, 64, share)
    assert f["timers"] and c["train_step.iterations"] == out.attempted


def warmed_up(trainer, cfg, config, params, toks, tgts, w):
    """``checked_warm_up``'s readings on two batches (the second the first's
    rows the other way round), as ``run`` takes them."""
    passed = train_mla.reference_pass(params, toks, tgts, cfg, config, 16)
    pool = [(np.asarray(toks), np.asarray(tgts)),
            (np.asarray(toks[::-1]), np.asarray(tgts[::-1]))]
    state, warm, r = train_mla.checked_warm_up(
        trainer, trainer.init_state(params), pool, passed, cfg, config, w)
    return dict(r, routing=passed["routing"]), warm


@pytest.fixture(scope="module")
def readings():
    cfg, config, params, toks, tgts = tiny()
    w = harness.load("workloads", "tiny_joyai.train", TINY)
    r, warm = warmed_up(train_mla.new_trainer(cfg, w), cfg, config, params,
                        toks, tgts, w)
    return dict(r, first_loss=warm[0]), cfg, config


def test_comparison_reads_what_the_limits_name(readings):
    r, cfg, config = readings
    w = harness.load("workloads", "tiny_joyai.train", TINY)
    assert set(r["grads"]) == set(GROUPS) and r["bias_grad_abs_max"] == 0.0
    assert abs(r["program"]["objective"] - (
        r["program"]["lm"] + 0.3 * r["program"]["mtp"])) < 1e-4
    assert len(r["routing"]) == 4 and r["routing"][0] is None
    assert 0 <= r["routing_differs"] < 0.05
    # the gradients are the TRAINER'S step's: its first loss is that program's
    assert abs(r["first_loss"] - r["program"]["objective"]) < 1e-3
    assert 0 < r["update"]["rel"] < 1e-3 and r["update"]["lr"] == 1e-5
    assert r["update"]["bias_moments_abs_max"] == 0.0
    assert r["bias"]["wrong"] == 0
    checks = train_mla.judge_compare(r, r["first_loss"], w["compare"])
    assert len(checks) == 3 + len(GROUPS) + 3 and all(ok for ok, _ in checks)


@pytest.mark.parametrize("bend", [
    lambda r: r["ref"].update(objective=r["ref"]["objective"] + 0.06),
    lambda r: r["program"].update(lm=r["program"]["lm"] + 0.06),
    lambda r: r["program"].update(mtp=r["program"]["mtp"] - 0.06),
    lambda r: r["grads"]["mla_up"].update(rel=0.16),
    lambda r: r["grads"]["mtp_merge"].update(cos=0.97),
    lambda r: r.update(routing_differs=0.06),
    lambda r: r.update(bias={"wrong": 1, "near": 0, "reach": 99.0, "experts": 48}),
    lambda r: r.update(bias_grad_abs_max=1e-9),
    lambda r: r["update"].update(rel=0.02),
    lambda r: r["update"].update(bias_moments_abs_max=1e-12),
], ids=["loss", "lm", "mtp", "grad_rel", "grad_cos", "routing", "bias", "bias_grad",
        "update", "bias_moments"])
def test_one_reading_past_its_limit_fails_one_check(readings, bend):
    r, _, _ = readings
    w = harness.load("workloads", "tiny_joyai.train", TINY)
    r = copy.deepcopy({k: v for k, v in r.items() if k != "routing"})
    first = r["first_loss"]
    bend(r)
    checks = train_mla.judge_compare(r, first, w["compare"])
    assert sum(not ok for ok, _ in checks) == 1


def test_first_bias_update_counts_the_experts_off_the_rule():
    cfg, config, params, toks, tgts = tiny()
    routing = train_mla.program_forward(params, toks, tgts, cfg)[1]
    moves = hybrid.bias_moves(cfg, [None if e is None else e[None] for e in routing])
    stepped = params
    for i in (1, 2, 3):
        lp = hybrid.layer_params(stepped, cfg, i)
        router = dict(lp["moe"]["router"], bias=lp["moe"]["router"]["bias"] + moves[
            "/".join(map(str, (*hybrid.layer_path(cfg, i), "moe", "router", "bias")))](
                jnp.ones((toks.shape[0],), bool)))
        stepped = hybrid._with_layer(stepped, cfg, i, dict(
            lp, moe=dict(lp["moe"], router=router)))
    found = train_mla.first_bias_update(stepped, routing, cfg, config, 0)
    assert found == {"wrong": 0, "near": 0, "reach": 0.0, "experts": 48}
    # one bias moved the other way: wrong outside the margin, near inside it
    lp = hybrid.layer_params(stepped, cfg, 2)
    bias = lp["moe"]["router"]["bias"]
    j = int(jnp.argmax(jnp.abs(bias)))
    bent = hybrid._with_layer(stepped, cfg, 2, dict(lp, moe=dict(
        lp["moe"], router=dict(lp["moe"]["router"], bias=bias.at[j].set(-bias[j])))))
    assert train_mla.first_bias_update(bent, routing, cfg, config, 0)["wrong"] == 1
    wide = train_mla.first_bias_update(bent, routing, cfg, config, 10_000)
    assert (wide["wrong"], wide["near"]) == (0, 1) and wide["reach"] > 0
    # a value that is no step of the rule is wrong whatever the margin
    odd = hybrid._with_layer(stepped, cfg, 2, dict(lp, moe=dict(
        lp["moe"], router=dict(lp["moe"]["router"], bias=bias.at[j].set(0.5)))))
    assert train_mla.first_bias_update(odd, routing, cfg, config, 10_000)["wrong"] == 1


REAL_OPTIMIZER, REAL_LOSS = train_mla.optimizer, hybrid.lm_loss_and_moves


def nothing_moves():
    """AdamW's state and a step that leaves everything where it was."""
    real = REAL_OPTIMIZER()
    return tfm.GradientTransform(
        real.init, lambda g, s, p=None, i=0: (
            jax.tree_util.tree_map(jnp.zeros_like, g), s), real.state_spec)


def half_the_batch(p, x, y, cfg):
    """The objective's value, and the gradient of the first half of the rows
    alone."""
    per, moves = REAL_LOSS(p, x, y, cfg)
    first = jnp.arange(per.shape[0]) < per.shape[0] // 2
    return jnp.where(first, per, jax.lax.stop_gradient(per)), moves


def wrong_decay():
    o = train_mla.OPTIMIZER
    return tfm.adamw(tfm.warmup_cosine(o["peak"], o["warmup"], o["total"]),
                     weight_decay=100 * o["weight_decay"])


@pytest.mark.parametrize("plant,fails", [
    (lambda mp: mp.setattr(train_mla, "optimizer", nothing_moves),
     {"gradients of", "the second step"}),
    (lambda mp: mp.setattr(hybrid, "lm_loss_and_moves", half_the_batch),
     {"gradients of"}),
    (lambda mp: mp.setattr(train_mla, "optimizer", wrong_decay),
     {"the second step"}),
], ids=["state_unchanged", "half_of_the_batch", "wrong_decay"])
def test_a_planted_fault_in_the_trainers_step_is_not_correct(monkeypatch, plant, fails):
    """What the comparison has to see in the TIMED program: a step that
    leaves the state where it was, one that learns from half of the batch, one
    that decays a hundred times too much.  Each comes out as not correct, by the
    checks that read it and no other of the comparison's."""
    plant(monkeypatch)
    cfg, config, params, toks, tgts = tiny()
    w = harness.load("workloads", "tiny_joyai.train", TINY)
    r, warm = warmed_up(train_mla.new_trainer(cfg, w), cfg, config, params,
                        toks, tgts, w)
    checks = train_mla.judge_compare(r, warm[0], w["compare"])
    failed = {what for ok, what in checks if not ok}
    assert failed and all(any(what.startswith(f) for f in fails) for what in failed)
    assert all(any(what.startswith(f) for what in failed) for f in fails)


def test_float8_control_fails_the_cells_limits():
    """The control at a tiny size, through the REAL cell's limits: float8
    operands read further off than any limit the cell sets."""
    cfg, model, params, toks, tgts = tiny()
    w = harness.load("workloads", "tiny_joyai.train", TINY)
    found = control_mla.controls(params, toks, tgts, cfg, model, w)
    assert set(found) == {"float8"}
    readings, checks = found["float8"]
    assert len(checks) == 3 + len(GROUPS) + 1 and set(readings["grads"]) == set(GROUPS)
    assert readings["routing_differs"] > 0
    strict = train_mla.judge_compare(
        readings, readings["program"]["objective"], CELL["compare"])
    assert sum(not ok for ok, _ in strict) >= 1


# ----------------------------------------------------------------- the readers

P = "/device:TPU:0"


def rows(step):
    return [(P, f"%{n} = f32[8,8]{{1,0:T(8,128)}} fusion(%p.1), kind=kLoop",
             2000.0 + a, float(ns), path) for n, a, ns, path in step]


STEP = [
    ("fusion.1", 0, 100, "jit(step)/jvp(qkv_proj)/mla.down/btd,df->btf/dot_general"),
    ("fusion.2", 100, 150, "jit(step)/transpose(jvp(qkv_proj))/mla.up/dot_general"),
    ("fusion.3", 250, 50, "jit(step)/jvp(qkv_proj)/mla.rope/mul"),
    ("fusion.4", 300, 40, "jit(step)/jvp(qkv_proj)/convert_element_type"),
    ("fusion.5", 340, 400, "jit(step)/transpose(jvp(attention))/mla.attend/while/body/"
                           "checkpoint/rematted_computation/thd,shd->hts/dot_general"),
    ("fusion.6", 740, 60, "jit(step)/jvp(ffn)/moe.shared/...d,df->...f/dot_general"),
    ("fusion.7", 800, 30, "jit(step)/jvp(ffn)/moe.router/dot_general"),
    ("fusion.8", 830, 20, "jit(step)/jvp(ffn)/add"),
    ("fusion.9", 850, 70, "jit(step)/jvp(embed)/mtp.merge/layernorm/mul"),
    ("fusion.10", 920, 80, "jit(step)/optimizer/moe.bias_update/reduce_sum"),
]
RUNS = {P: [(2000.0, 3000.0)]}


@pytest.mark.parametrize("scopes,want", [
    (["mla.down"], 10.0), (["mla.up"], 15.0), (["mla.rope"], 5.0),
    (["mla.attend"], 40.0), (["moe.shared"], 6.0), (["mtp.merge"], 7.0),
    (["moe.bias_update"], 8.0)])
def test_scope_split_finds_the_new_scopes(scopes, want):
    from benchmark import trace_spans
    under, busy = scope_split.split(
        trace_spans.inside(rows(STEP), RUNS), RUNS, scopes)
    assert busy == 1000.0 and 100.0 * under / busy == want


@pytest.mark.parametrize("within,scopes,want", [
    ("qkv_proj", ["mla.down", "mla.up", "mla.rope"], 40.0),
    ("ffn", ["moe.router", "moe.dispatch", "moe.experts", "moe.combine",
             "moe.shared"], 20.0),
    # the accepted file's four names would call the shared expert unnamed
    ("ffn", ["moe.router", "moe.dispatch", "moe.experts", "moe.combine"], 80.0)])
def test_scope_rest_reads_what_the_parts_leave_of_their_sublayer(within, scopes, want):
    found = scope_rest.rest(scope_rest.self_times(rows(STEP), RUNS), {}, scopes, within)
    assert found["busy"] == 1000.0 and found["unnamed"] == want


def test_a_program_without_the_scopes_reads_nothing():
    """What the parent gives for a metric new in this PR: nothing, no error."""
    from benchmark import trace_spans
    old = [r for r in rows(STEP) if not any(
        s in r[4] for s in ("mla.", "moe.shared", "mtp."))]
    assert scope_split.split(trace_spans.inside(old, RUNS), RUNS, ["mla.attend"]) is None
    assert scope_split.read({"module_prefix": "jit_step", "scopes": ["mla.up"]},
                            {"facts": {}}) is None
