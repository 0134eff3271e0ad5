"""What PR 28 adds to the benchmark, off the chip: ``flops_moe`` against a
hand count, the ``train_moe`` runner at a tiny size on the CPU and the
arithmetic of its comparison with the reference, the benchmark's copy of the
reference against the program's, the ``scope_split`` reader on a recorded row
file, the ``moe_counters`` reader, and the configuration file against the
published numbers.  Nothing here is a device number."""

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

from benchmark import flops_moe, harness, reference_zaya, trace_reduce as tr  # noqa: E402
from benchmark.readers import moe_counters, scope_split  # noqa: E402
from benchmark.runners import train_moe  # noqa: E402
from deeplearning4j_tpu.models import hybrid  # noqa: E402
from deeplearning4j_tpu.models.reference import zaya as program_reference  # noqa: E402

HERE = Path(__file__).resolve().parent
TINY = HERE / "tiny"
CONFIG = harness.load("configs", "zaya1_8b_ep2")
CELL = harness.load("workloads", "zaya1_8b_ep2.train_b4_s4096")


# ------------------------------------------------------------------- the FLOPs

def test_flops_against_a_hand_count():
    """ZAYA1-8B's published widths, 4 layers, half the vocabulary, 4096
    positions, half the tokens routed here: forward per token, by part."""
    parts = flops_moe.forward_flops_per_token(CONFIG, 4096, 0.5)
    assert parts == {
        "projections": 4 * 2 * (2048 * 1024 + 2 * 2048 * 256 + 1024 * 2048),  # 41.9 M
        "convolution": 4 * 2 * 2 * 10 * 128 * 128,                            # 2.6 M
        "attention": 4 * 2 * 4096 * 1024,                                     # 33.6 M
        "router": 4 * 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 16),            # 5.3 M
        "experts": 4 * 2 * 3 * 2048 * 2048 * 0.5,                             # 50.3 M
        "head": 2 * 131136 * 2048,                                            # 537.1 M
    }
    assert flops_moe.train_flops_per_token(CONFIG, 4096, 0.5) == 2_012_577_792.0


@pytest.mark.parametrize("share,experts", [(0.0, 0.0), (0.25, 25_165_824.0),
                                           (1.0, 100_663_296.0)])
def test_flops_count_active_experts_only(share, experts):
    parts = flops_moe.forward_flops_per_token(CONFIG, 4096, share)
    assert parts["experts"] == experts
    rest = sum(v for k, v in parts.items() if k != "experts")
    assert flops_moe.train_flops_per_token(CONFIG, 4096, share) == 3.0 * (rest + experts)


# ------------------------------------------------------- the configuration file

def test_configuration_keeps_the_published_widths():
    published = {"hidden_size": 2048, "num_attention_heads": 8,
                 "num_key_value_heads": 2, "head_dim": 128, "cca_time0": 2,
                 "cca_time1": 2, "moe_intermediate_size": 2048,
                 "router_hidden_size": 256, "num_experts_per_tok": 1,
                 "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
                 "max_position_embeddings": 131072, "tie_word_embeddings": True,
                 "attention_bias": False, "lm_head_bias": False,
                 "hidden_act": "silu", "sliding_window": None}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"}
    assert CONFIG["layer_types"] == ["hybrid"] * 40
    # the three cuts, and the published numbers beside them
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 8, 131136)
    assert CONFIG["published"] == {"num_hidden_layers": 40, "num_experts": 16,
                                   "vocab_size": 262272}
    assert CONFIG["router_width"] == 16 and CONFIG["experts_held"] == [0, 8]
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert set(CONFIG["changed"]) >= set(CONFIG["reduced"]) | {"left_out"}


def test_configuration_builds_the_model_at_its_widths():
    cfg = train_moe.hybrid_config(CONFIG)
    mixer, ffn = cfg.layers[0]
    assert len(cfg.layers) == 4 and len(set(cfg.layers)) == 1
    assert mixer == hybrid.CCA(8, 2, 128, (2, 2), 5_000_000.0, 0.5)
    assert ffn == hybrid.MoE(16, (0, 8), 256, 2048)
    assert (cfg.base.vocab_size, cfg.base.d_model) == (131136, 2048)
    assert cfg.base.dtype == jnp.bfloat16 and cfg.base.param_dtype == jnp.float32
    shapes = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == 696_170_504                    # x 16 B = 11.14 GB of state
    assert shapes["layers"][0]["moe"]["wg"].shape == (8, 2048, 2048)
    assert shapes["layers"][0]["moe"]["router"]["w3"].shape == (256, 16)


def test_cell_is_what_the_issue_names():
    want = {"runner": "train_moe", "chips": 1, "n_dp": 1, "zero_stage": 0,
            "global_batch": 4, "seq_len": 4096, "resolve_every": 8,
            "pool_batches": 8, "warmup_batches": 2, "trace_slice_s": [5, 8]}
    assert {k: CELL[k] for k in want} == want
    lo, hi = CELL["first_loss_band"]
    # ln 131,136 = 11.78, plus half the logits' variance at a 0.02 embedding
    # under a unit-norm hidden state (0.02^2 x 2048 / 2 = 0.41)
    assert 11.78 < lo < 11.78 + 0.41 < hi
    limits = CELL["compare"]
    assert set(limits["grad_rel"]) == set(limits["grad_cos"]) == set(
        reference_zaya.GROUPS)
    assert CELL["compare_why"] and all(
        k in CELL["compare_why"] for k in ("loss_abs", "grad_rel", "grad_cos",
                                           "routing_differs"))


# ------------------------------------------------------------- the comparison

GOOD = {"ref_loss": 11.8000,
        "grads": {g: {"rel": 0.01, "cos": 0.9999} for g in reference_zaya.GROUPS},
        "routing_differs": 0.004}
LIMITS = {"loss_abs": 0.01,
          "grad_rel": {g: 0.05 for g in reference_zaya.GROUPS},
          "grad_cos": {g: 0.998 for g in reference_zaya.GROUPS},
          "routing_differs": 0.02}


def test_comparison_passes_inside_its_limits():
    checks = train_moe.judge_compare(GOOD, 11.8040, LIMITS)
    assert len(checks) == 2 + len(reference_zaya.GROUPS)
    assert all(ok for ok, _ in checks), checks
    assert "0.00400" in checks[0][1] and "<= 0.01" in checks[0][1]


BROKEN = ([("loss", None, None, 0)]
          + [("rel", g, 0.0501, 1 + i) for i, g in enumerate(reference_zaya.GROUPS)]
          + [("cos", g, 0.9979, 1 + i) for i, g in enumerate(reference_zaya.GROUPS)]
          + [("routing", None, 0.0201, 1 + len(reference_zaya.GROUPS))])


@pytest.mark.parametrize("what,group,value,failing", BROKEN,
                         ids=[f"{w}-{g}" if g else w for w, g, _, _ in BROKEN])
def test_one_reading_past_its_limit_fails_one_check(what, group, value, failing):
    readings, first = copy.deepcopy(GOOD), 11.8040
    if what == "loss":
        first = 11.8101
    elif what == "routing":
        readings["routing_differs"] = value
    else:
        readings["grads"][group][what] = value
    checks = train_moe.judge_compare(readings, first, LIMITS)
    assert [i for i, (ok, _) in enumerate(checks) if not ok] == [failing]


# -------------------------------------------------------------- the reference

def tiny_case():
    config = harness.load("configs", "tiny_zaya", TINY)
    cfg = train_moe.hybrid_config(config)
    params = hybrid.init_params(jax.random.key(3), cfg)
    toks = jax.random.randint(jax.random.key(4), (2, 32), 0, 512)
    return cfg, train_moe.reference_model(config), params, toks, jnp.roll(toks, -1, 1)


def test_benchmark_copy_is_the_programs_reference():
    """Letter for letter after the copy's own heading, so that the two can
    only part by an edit that this test makes visible."""
    mine = (REPO / "benchmark" / "reference_zaya.py").read_text()
    theirs = (REPO / "deeplearning4j_tpu" / "models" / "reference" / "zaya.py").read_text()
    assert mine.split("Plain reference of the ZAYA1 block", 1)[1] == \
        theirs.split("Plain reference of the ZAYA1 block", 1)[1]
    assert reference_zaya.GROUPS == program_reference.GROUPS


def test_layer_by_layer_gradients_are_the_whole_models():
    """``loss_and_grads`` (chain rule by hand over the layers, head in
    blocks) against ``jax.value_and_grad`` of the whole ``loss``."""
    _, model, params, toks, tgts = tiny_case()
    total, grads, chosen = reference_zaya.loss_and_grads(
        params, toks, tgts, model, block_rows=8)
    whole = [jax.value_and_grad(reference_zaya.loss)(params, toks[i], tgts[i], model)
             for i in range(2)]
    assert float(total) == pytest.approx(sum(float(v) for v, _ in whole) / 2, rel=1e-6)
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, whole[0][1], whole[1][1])
    same = reference_zaya.compare_grads(grads, mean)
    assert all(v["rel"] < 1e-5 for v in same.values()), same
    assert chosen.shape == (2, 2, 32) and int(chosen.max()) < 8


@pytest.mark.parametrize("operand,looser", [("bfloat16", 1e-4), ("float8_e4m3fn", 1e-3)])
def test_lower_precision_operands_move_the_reference(operand, looser):
    """The reading that sets the limits: the same mathematics with every
    matrix product's operands rounded.  Each step down moves the gradients
    further from the float32 reference."""
    _, model, params, toks, tgts = tiny_case()
    _, exact, _ = reference_zaya.loss_and_grads(params, toks, tgts, model)
    _, rounded, _ = reference_zaya.loss_and_grads(
        params, toks, tgts, model, operand_dtype=getattr(jnp, operand))
    err = reference_zaya.compare_grads(rounded, exact)
    assert all(v["rel"] > looser for v in err.values()), err


def test_float8_reads_further_off_than_bfloat16():
    _, model, params, toks, tgts = tiny_case()
    _, exact, _ = reference_zaya.loss_and_grads(params, toks, tgts, model)
    err = {}
    for operand in (jnp.bfloat16, jnp.float8_e4m3fn):
        _, g, _ = reference_zaya.loss_and_grads(params, toks, tgts, model,
                                                operand_dtype=operand)
        err[operand] = reference_zaya.compare_grads(g, exact)
    for group in reference_zaya.GROUPS:
        assert err[jnp.float8_e4m3fn][group]["rel"] > 2 * err[jnp.bfloat16][group]["rel"]


# ------------------------------------------------------------------ the runner

def test_train_moe_runs_the_tiny_cell():
    w = harness.load("workloads", "tiny_zaya.train", TINY)
    cell = harness.Cell(
        workload=w, config=harness.load("configs", w["config"], TINY),
        seed=2**31 + 11, seconds=1.0, devices=jax.devices()[:1],
        process_t0=time.perf_counter())
    opened = []
    cell.on_window = opened.append
    out = train_moe.run(cell)
    assert out.correct and out.attempted >= 8 and out.failed == 0 and len(opened) == 1
    assert out.end_to_end["train_tokens_per_s"] > 0 and out.end_to_end["setup_s"] > 0
    f = out.facts
    c = f["counters"]
    assert f["tokens_per_step"] == 4 * 32 and f["chips"] == 1
    assert c["moe.tokens_total"] == 2 * 8 * 4 * 32          # layers x pool x batch x seq
    share = c["moe.tokens_local"] / c["moe.tokens_total"]
    assert f["flops_per_token"] == flops_moe.train_flops_per_token(cell.config, 32, share)
    assert c.get("train_step.recompile", 0) == 0
    run = {"facts": f}
    assert moe_counters.read({"what": "local_share"}, run) == pytest.approx(100 * share)
    assert moe_counters.read({"what": "load_max_over_mean"}, run) >= 1.0


# ----------------------------------------------------------------- the readers

def test_moe_counters_reader():
    counters = {"moe.tokens_total": 400.0, "moe.tokens_local": 100.0,
                "moe.expert_load.l0.e0": 10.0, "moe.expert_load.l1.e0": 30.0,
                "moe.expert_load.l0.e1": 40.0, "moe.expert_load.l1.e1": 20.0}
    run = {"facts": {"counters": counters}}
    assert moe_counters.read({"what": "local_share"}, run) == 25.0
    assert moe_counters.read({"what": "load_max_over_mean"}, run) == 60.0 / 50.0
    for what in ("local_share", "load_max_over_mean"):      # a program without experts
        assert moe_counters.read({"what": what}, {"facts": {"counters": {}}}) is None
        assert moe_counters.read({"what": what}, {"facts": {}}) is None


RECORDED = json.loads((HERE / "trace_rows_moe.json").read_text())
TPU0 = "/device:TPU:0"


def recorded_ops():
    return [(TPU0, name, start * 1e3, dur * 1e3, path)
            for name, start, dur, path in RECORDED["ops"]]


def recorded_runs():
    rows = ([(TPU0, tr.OP_LINE, n, s * 1e3, d * 1e3) for n, s, d, _ in RECORDED["ops"]]
            + [(TPU0, tr.MODULE_LINE, n, s * 1e3, d * 1e3) for n, s, d in RECORDED["modules"]])
    return tr.whole_runs(rows, "jit_step")


@pytest.mark.parametrize("scopes,per_step_us", [
    (["moe.router"], 30), (["moe.dispatch"], 75), (["moe.experts"], 360),
    (["cca.mix"], 125), (["moe.router", "moe.dispatch", "moe.experts"], 465)])
def test_scope_split_on_the_recorded_rows(scopes, per_step_us):
    runs = recorded_runs()
    assert runs == {TPU0: [(300e3, 1300e3), (1400e3, 2400e3)]}
    under, busy = scope_split.split(recorded_ops(), runs, scopes)
    assert busy == 2 * 980e3 and under == 2 * per_step_us * 1e3


def test_scope_split_finds_nothing_in_a_program_without_the_scopes():
    runs = recorded_runs()
    assert scope_split.split(recorded_ops(), runs, ["moe.combine"]) is None
    dense = [r[:4] + (r[4].replace("moe.", "").replace("cca.mix/", ""),)
             for r in recorded_ops()]
    assert scope_split.split(dense, runs, ["moe.router", "cca.mix"]) is None
    assert scope_split.read({"module_prefix": "jit_step", "scopes": ["cca.mix"]},
                            {"facts": {}}) is None          # no trace at all


def test_reference_follows_a_given_routing():
    """Given its own choices the reference gives the same numbers; given
    another computation's it follows them and still reports its own."""
    _, model, params, toks, tgts = tiny_case()
    loss, grads, own = reference_zaya.loss_and_grads(params, toks, tgts, model)
    again = reference_zaya.loss_and_grads(params, toks, tgts, model, routing=own)
    assert float(again[0]) == pytest.approx(float(loss), rel=1e-6)
    assert all(v["rel"] < 1e-6 for v in
               reference_zaya.compare_grads(again[1], grads).values())
    other = (own + 1) % 4                       # every token to another held expert
    moved = reference_zaya.loss_and_grads(params, toks, tgts, model, routing=other)
    assert bool((moved[2][:, 0] == own[:, 0]).all())    # layer 0 sees the same input
    assert reference_zaya.compare_grads(moved[1], grads)["experts"]["rel"] > 0.1
    whole = jax.value_and_grad(reference_zaya.loss)(
        params, toks[0], tgts[0], model, None, other[0])
    one = reference_zaya.loss_and_grads(params, toks[:1], tgts[:1], model,
                                        routing=other[:1])
    assert float(one[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    assert all(v["rel"] < 1e-5 for v in
               reference_zaya.compare_grads(one[1], whole[1]).values())
