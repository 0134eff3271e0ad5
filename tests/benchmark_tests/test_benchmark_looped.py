"""What PR 32 adds to the benchmark, off the chip: ``flops_looped`` against a
hand count, the configuration file against the published numbers, the
``train_looped`` runner at a tiny size on the CPU and the arithmetic of its
comparison with the reference, the benchmark's copy of the reference against
the program's, the ``loop_counters`` reader on hand-made counters, and the
``scope_split`` reader finding ``loop.exit`` and ``lm_head.recompute`` in a
hand-written row file.  Nothing here is a device number."""

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

from benchmark import (control_looped, flops_looped, harness,  # noqa: E402
                       reference_ouro, trace_reduce as tr)
from benchmark.readers import loop_counters, scope_share, scope_split  # noqa: E402
from benchmark.runners import train_looped  # noqa: E402
from deeplearning4j_tpu.models import hybrid  # noqa: E402
from deeplearning4j_tpu.models.reference import ouro as program_reference  # noqa: E402

HERE = Path(__file__).resolve().parent
TINY = HERE / "tiny"
MANIFEST = harness.manifest()
CONFIG = harness.load("configs", "ouro_2_6b_l8")
CELL_NAME = "ouro_2_6b_l8.train_b2_s4096"
CELL = harness.load("workloads", CELL_NAME)
CATALOG = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5632, "max_position_embeddings": 65536,
           "max_window_layers": 48, "model_type": "ouro",
           "num_attention_heads": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
           "sliding_window": None, "tie_word_embeddings": False,
           "total_ut_steps": 4, "early_exit_threshold": 1,
           "use_sliding_window": False, "vocab_size": 49152}


# ------------------------------------------------------------------- the FLOPs

def test_flops_against_a_hand_count():
    """Ouro-2.6B's published widths, 8 layers, 4 loop steps, 4096 positions:
    forward per token, by part; 13.89 GFLOP a token trained."""
    parts = flops_looped.forward_flops_per_token(CONFIG, 4096)
    assert parts == {
        "projections": 32 * 2 * 4 * 2048 * 2048,         # 1073.7 M
        "ffn": 32 * 2 * 3 * 2048 * 5632,                 # 2214.6 M
        "attention": 32 * 2 * 4096 * 2048,               # 536.9 M
        "head": 4 * 2 * 49152 * 2048,                    # 805.3 M
        "gate": 4 * 2 * 2048,
    }
    total = flops_looped.train_flops_per_token(CONFIG, 4096)
    assert total == 3.0 * sum(parts.values()) == 13_891_584_000.0
    # the issue's own arithmetic: 3 x 4 x [8 x (2 x 51.38 M + 2 x 4096 x 2048)
    # + 2 x 49,152 x 2048], the gate besides
    by_hand = 3 * 4 * (8 * (2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
                            + 2 * 4096 * 2048) + 2 * 49152 * 2048)
    assert total - by_hand == 3 * 4 * 2 * 2048
    assert round(total * 8192 / 1e12, 1) == 113.8         # TFLOP a step


@pytest.mark.parametrize("key,factor", [("total_ut_steps", 2.0),
                                        ("num_hidden_layers", None)])
def test_flops_count_a_parameter_once_a_use(key, factor):
    """Twice the loop steps is twice everything; twice the layers is twice the
    layers' part and the same head."""
    twice = dict(CONFIG, **{key: 2 * CONFIG[key]})
    a = flops_looped.forward_flops_per_token(CONFIG, 4096)
    b = flops_looped.forward_flops_per_token(twice, 4096)
    for part in a:
        per_loop = part in ("head", "gate")
        assert b[part] == a[part] * (factor or (1.0 if per_loop else 2.0))


# ------------------------------------------------------- the configuration file

def test_configuration_keeps_the_published_numbers():
    assert {k: CONFIG[k] for k in CATALOG} == CATALOG
    assert CONFIG["layer_types"] == ["full_attention"] * 48
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["published"] == {"num_hidden_layers": 48}
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert set(CONFIG["changed"]) >= set(CONFIG["reduced"])
    for key in ("norms", "loop", "gate", "objective", "attention", "init",
                "precision"):
        assert CONFIG["assumed"][key]
    assert set(CONFIG["left_out"]) == {"second_stage", "early_exit"}
    assert "6 pipeline stages of 8" in CONFIG["deployment"]
    assert "612,438,017" in CONFIG["deployment"] and "9.80 GB" in CONFIG["deployment"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "ouro_2_6b_l8")
    assert entry == MANIFEST["configs"][-1]          # appended, not inserted
    assert (entry["source"], entry["reduced"]) == (CONFIG["source"], CONFIG["reduced"])


def test_configuration_builds_the_model_at_its_widths():
    cfg = train_looped.hybrid_config(CONFIG)
    mixer, ffn = cfg.layers[0]
    assert len(cfg.layers) == 8 and len(set(cfg.layers)) == 1
    assert mixer == hybrid.Attention(16, 16, 128, 1_000_000.0, 1.0)
    assert ffn == hybrid.GatedMLP(5632) and mixer.post_norm and ffn.post_norm
    assert (cfg.n_loops, cfg.exit_beta, cfg.norm_eps) == (4, 0.1, 1e-6)
    assert (cfg.base.vocab_size, cfg.base.d_model) == (49152, 2048)
    assert not cfg.base.tie_embeddings and cfg.base.remat
    assert cfg.base.dtype == jnp.bfloat16 and cfg.base.param_dtype == jnp.float32
    shapes = jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg))
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert n == 612_438_017                    # x 16 B = 9.80 GB of state
    assert n == 8 * 51_388_416 + 2 * 49152 * 2048 + 2048 + 2049
    assert shapes["lm_head"].shape == (2048, 49152)
    assert shapes["layers"][0]["mlp"]["wg"].shape == (2048, 5632)
    assert shapes["exit_gate"]["w"].shape == (2048,)


def test_cell_is_what_the_issue_names():
    want = {"runner": "train_looped", "chips": 1, "n_dp": 1, "zero_stage": 0,
            "global_batch": 2, "seq_len": 4096, "resolve_every": 8,
            "pool_batches": 8, "warmup_batches": 2, "trace_slice_s": [5, 12]}
    assert {k: CELL[k] for k in want} == want
    lo, hi = CELL["first_loss_band"]
    # ln 49,152 = 10.80, plus half the variance of a unit-variance logit,
    # less at most 0.1 x ln 4 of entropy
    assert lo < 10.80 + 0.5 - 0.139 and 10.80 + 0.5 < hi
    limits = CELL["compare"]
    assert set(limits["grad_rel"]) == set(limits["grad_cos"]) == set(
        reference_ouro.GROUPS)
    assert all(k in CELL["compare_why"] for k in (
        "what", "loss_abs", "xent_abs", "exit_abs", "grad_rel", "grad_cos"))
    entry = MANIFEST["workloads"][-1]
    assert (entry["name"], entry["config"], entry["chips"]) == (
        CELL_NAME, "ouro_2_6b_l8", 1)


def test_cell_reports_the_common_metrics_and_its_own():
    names = {m["name"] for m in harness.cell_metrics(MANIFEST, "per_layer", CELL_NAME)}
    zaya = {m["name"] for m in harness.cell_metrics(
        MANIFEST, "per_layer", "zaya1_8b_ep2.train_b4_s4096")}
    own = {"head_recompute_share.train", "loop_exit_share.train",
           "loop_expected_steps.train"}
    assert names - zaya == own
    assert {n for n in zaya - names} == {
        "moe_share.router.train", "moe_share.dispatch.train",
        "moe_share.experts.train", "cca_mix_share.train",
        "moe_local_token_share.train", "moe_expert_load_max_over_mean.train"}
    assert len(names) == 14 + 1 + 3
    for m in MANIFEST["per_layer"][-3:]:
        assert m["name"] in own and m["workloads"] == [CELL_NAME]
    assert [m["name"] for m in harness.cell_metrics(MANIFEST, "end_to_end", CELL_NAME)
            ] == ["train_tokens_per_s", "setup_s"]


# ------------------------------------------------------------- the comparison

GROUPS = reference_ouro.GROUPS
SIDE = {"objective": 11.2000, "xent": [11.30, 11.31, 11.32, 11.33],
        "exit": [0.5, 0.25, 0.125, 0.125]}
GOOD = {"ref": SIDE, "program": copy.deepcopy(SIDE),
        "grads": {g: {"rel": 0.01, "cos": 0.9999} for g in GROUPS}}
LIMITS = {"step_abs": 0.0001, "xent_abs": 0.01, "exit_abs": 0.001,
          "grad_rel": {g: 0.05 for g in GROUPS},
          "grad_cos": {g: 0.998 for g in GROUPS}}


def test_comparison_passes_inside_its_limits():
    checks = train_looped.judge_compare(GOOD, LIMITS)
    assert len(checks) == 2 + len(GROUPS)
    assert all(ok for ok, _ in checks), checks
    assert "<= 0.01" in checks[0][1] and "<= 0.001" in checks[1][1]


@pytest.mark.parametrize("first_loss,passes", [(11.20009, True), (11.19989, False)])
def test_the_trainers_first_loss_is_held_to_the_compared_program(first_loss, passes):
    """The reference's objective is beside it and judged by nothing."""
    readings = copy.deepcopy(GOOD)
    readings["ref"]["objective"] = 11.2040
    (ok, what), = train_looped.judge_step(readings, first_loss, LIMITS["step_abs"])
    assert ok is passes and "<= 0.0001" in what
    assert "0.004000 and 0.00" in what and "no limit" in what


BROKEN = ([("xent", None, None, 0), ("exit", None, None, 1)]
          + [("rel", g, 0.0501, 2 + i) for i, g in enumerate(GROUPS)]
          + [("cos", g, 0.9979, 2 + i) for i, g in enumerate(GROUPS)])


@pytest.mark.parametrize("what,group,value,failing", BROKEN,
                         ids=[f"{w}-{g}" if g else w for w, g, _, _ in BROKEN])
def test_one_reading_past_its_limit_fails_one_check(what, group, value, failing):
    readings = copy.deepcopy(GOOD)
    if what == "xent":
        readings["program"]["xent"][2] += 0.0101
    elif what == "exit":
        readings["program"]["exit"][3] -= 0.0011
    else:
        readings["grads"][group][what] = value
    checks = train_looped.judge_compare(readings, LIMITS)
    assert [i for i, (ok, _) in enumerate(checks) if not ok] == [failing]


def test_float8_reading_fails_the_cells_limits_and_the_programs_passes():
    """The two chip readings behind the cell's limits (my chip runs, PR 32;
    ``compare_why``): the program's largest over its seeds passes every check;
    what ``benchmark/control_looped.py`` read with float8 operands fails every
    one, even with each limit given the reading NEAREST to it of the control's
    4 seeds.  The arithmetic only: the control itself runs on the chip."""
    side = dict(SIDE)
    largest = {"ref": side, "program": dict(
        side, xent=[v + 0.00077 for v in side["xent"]],
        exit=[side["exit"][0] + 0.0021] + side["exit"][1:]),
        "grads": {g: {"rel": r, "cos": c} for g, r, c in zip(
            GROUPS, (0.0339, 0.0212, 0.0330, 0.0348, 0.0328, 0.0209),
            (0.99942, 0.99977, 0.99945, 0.99939, 0.99946, 0.99979))}}
    float8 = {"ref": side, "program": dict(
        side, xent=[v + 0.00258 for v in side["xent"]],
        exit=[side["exit"][0] + 0.0101] + side["exit"][1:]),
        "grads": {g: {"rel": r, "cos": c} for g, r, c in zip(
            GROUPS, (0.261, 0.188, 0.258, 0.281, 0.255, 0.142),
            (0.9658, 0.9823, 0.9661, 0.9597, 0.9676, 0.9907))}}
    # the trainer's first loss: the program's largest, half a batch's smallest
    limit = CELL["compare"]["step_abs"]
    assert train_looped.judge_step(largest, 11.20001, limit)[0][0]
    assert not train_looped.judge_step(largest, 11.2077, limit)[0][0]
    assert all(ok for ok, _ in train_looped.judge_compare(largest, CELL["compare"]))
    assert not any(ok for ok, _ in train_looped.judge_compare(float8, CELL["compare"]))
    assert "loss_abs" not in CELL["compare"]         # compare_why says why
    # every limit the runner and the controls read is there, with its why
    assert set(CELL["compare"]) == {"step_abs", "xent_abs", "exit_abs",
                                    "grad_rel", "grad_cos"}
    assert set(CELL["compare"]) | {"what", "loss_abs"} == set(CELL["compare_why"])


# -------------------------------------------------------------- the reference

def tiny_case():
    config = harness.load("configs", "tiny_ouro", TINY)
    cfg = train_looped.hybrid_config(config)
    params = hybrid.init_params(jax.random.key(3), cfg)
    toks = jax.random.randint(jax.random.key(4), (2, 32), 0, 512)
    return cfg, config, params, toks, jnp.roll(toks, -1, 1)


def test_benchmark_copy_is_the_programs_reference():
    """Letter for letter after the copy's own heading, so that the two can
    only part by an edit that this test makes visible."""
    mark = "Plain reference of the looped language model"
    mine = (REPO / "benchmark" / "reference_ouro.py").read_text()
    theirs = (REPO / "deeplearning4j_tpu" / "models" / "reference" / "ouro.py").read_text()
    assert mine.split(mark, 1)[1] == theirs.split(mark, 1)[1]
    assert reference_ouro.GROUPS == program_reference.GROUPS


def test_layer_by_layer_gradients_are_the_whole_models():
    """The benchmark's ``loss_and_grads`` (chain rule by hand over the layer
    applications, head in blocks) against ``jax.value_and_grad`` of its whole
    ``loss``."""
    _, model, params, toks, tgts = tiny_case()
    total, grads, aux = reference_ouro.loss_and_grads(
        params, toks, tgts, model, block_rows=8)
    whole = [jax.value_and_grad(reference_ouro.loss, has_aux=True)(
        params, toks[i], tgts[i], model) for i in range(2)]
    assert float(total) == pytest.approx(
        sum(float(v) for (v, _), _ in whole) / 2, rel=1e-6)
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, whole[0][1], whole[1][1])
    same = reference_ouro.compare_grads(grads, mean)
    assert all(v["rel"] < 1e-5 for v in same.values()), same
    assert aux["xent"].shape == aux["exit"].shape == (4,)
    assert float(aux["exit"].sum()) == pytest.approx(1.0, rel=1e-5)


def test_float8_reads_further_off_than_bfloat16():
    """The reading that sets the limits: every matrix product's operands
    rounded, forward and backward; each step down moves every group further
    from the float32 reference."""
    _, model, params, toks, tgts = tiny_case()
    _, exact, _ = reference_ouro.loss_and_grads(params, toks, tgts, model)
    err = {}
    for operand in (jnp.bfloat16, jnp.float8_e4m3fn):
        _, g, _ = reference_ouro.loss_and_grads(params, toks, tgts, model,
                                                operand_dtype=operand)
        err[operand] = reference_ouro.compare_grads(g, exact)
    for group in GROUPS:
        assert err[jnp.bfloat16][group]["rel"] > 1e-4
        assert err[jnp.float8_e4m3fn][group]["rel"] > 2 * err[jnp.bfloat16][group]["rel"]


# ------------------------------------------------------------------ the runner

def test_train_looped_runs_the_tiny_cell():
    w = harness.load("workloads", "tiny_ouro.train", TINY)
    cell = harness.Cell(
        workload=w, config=harness.load("configs", w["config"], TINY),
        seed=2**31 + 11, seconds=1.0, devices=jax.devices()[:1],
        process_t0=time.perf_counter())
    opened = []
    cell.on_window = opened.append
    out = train_looped.run(cell)
    assert out.correct and out.attempted >= 8 and out.failed == 0 and len(opened) == 1
    assert out.end_to_end["train_tokens_per_s"] > 0 and out.end_to_end["setup_s"] > 0
    f = out.facts
    c = f["counters"]
    assert f["tokens_per_step"] == 4 * 32 and f["chips"] == 1
    assert f["flops_per_token"] == flops_looped.train_flops_per_token(cell.config, 32)
    assert c["loop.tokens_total"] == 8 * 4 * 32                  # pool x batch x seq
    mass = [c[f"loop.exit_mass.t{k}"] for k in (1, 2, 3, 4)]
    assert sum(mass) == pytest.approx(c["loop.tokens_total"], rel=1e-5)
    assert c.get("train_step.recompile", 0) == 0
    expected = loop_counters.read({"what": "expected_steps"}, {"facts": f})
    assert expected == pytest.approx(
        sum(k * m for k, m in enumerate(mass, 1)) / c["loop.tokens_total"])
    assert 1.0 < expected < 4.0


def test_controls_come_out_as_not_correct_on_the_tiny_cell():
    """``control_looped.controls`` at a tiny size: both controls give readings
    in the runner's own form, a step on half of the batch fails ``judge_step``
    (by far), and float8 operands read past the real cell's gradient limits
    even here (the tiny cell's own limits are loose: it runs in bf16 on a CPU)."""
    cfg, model, params, toks, tgts = tiny_case()
    w = harness.load("workloads", "tiny_ouro.train", TINY)
    found = control_looped.controls(params, toks, tgts, cfg, model, w)
    assert set(found) == {"float8", "half_batch"}
    readings, checks = found["half_batch"]
    assert [ok for ok, _ in checks] == [False]
    assert abs(readings["first_loss"] - readings["program"]["objective"]) > 20 * w[
        "compare"]["step_abs"]
    readings, checks = found["float8"]
    assert len(checks) == 2 + len(GROUPS) and set(readings["grads"]) == set(GROUPS)
    strict = train_looped.judge_compare(readings, CELL["compare"])
    assert sum(not ok for ok, _ in strict) >= len(GROUPS)


# ----------------------------------------------------------------- the readers

def test_loop_counters_reader():
    half = {"loop.tokens_total": 800.0, "loop.exit_mass.t1": 400.0,
            "loop.exit_mass.t2": 200.0, "loop.exit_mass.t3": 100.0,
            "loop.exit_mass.t4": 100.0, "loop.steps": 4.0}
    args = {"what": "expected_steps"}
    assert loop_counters.read(args, {"facts": {"counters": half}}) == 1.875
    shut = dict(half, **{"loop.exit_mass.t1": 0.0, "loop.exit_mass.t2": 0.0,
                         "loop.exit_mass.t3": 0.0, "loop.exit_mass.t4": 800.0})
    assert loop_counters.read(args, {"facts": {"counters": shut}}) == 4.0
    # a program without a loop, a run without counters, an unknown quantity
    assert loop_counters.read(args, {"facts": {"counters": {"moe.tokens_total": 4.0}}}) is None
    assert loop_counters.read(args, {"facts": {}}) is None
    assert loop_counters.read({"what": "steps"}, {"facts": {"counters": half}}) is None


RECORDED = json.loads((HERE / "trace_rows_looped.json").read_text())
TPU0 = "/device:TPU:0"


def recorded_ops():
    return [(TPU0, name, start * 1e3, dur * 1e3, path)
            for name, start, dur, path in RECORDED["ops"]]


def recorded_runs():
    rows = ([(TPU0, tr.OP_LINE, n, s * 1e3, d * 1e3) for n, s, d, _ in RECORDED["ops"]]
            + [(TPU0, tr.MODULE_LINE, n, s * 1e3, d * 1e3) for n, s, d in RECORDED["modules"]])
    return tr.whole_runs(rows, "jit_step")


@pytest.mark.parametrize("metric,per_step_us", [
    ("loop_exit_share.train", 45), ("head_recompute_share.train", 150),
    ("head_fused_share.train", 145)])
def test_scope_split_on_the_recorded_rows(metric, per_step_us):
    """Each metric file's own arguments, on two whole executions of a step."""
    spec = harness.load("layer_metrics", metric)
    assert (spec["reader"], spec["args"]["module_prefix"]) == ("scope_split", "jit_step")
    runs = recorded_runs()
    assert runs == {TPU0: [(300e3, 1300e3), (1400e3, 2400e3)]}
    under, busy = scope_split.split(recorded_ops(), runs, spec["args"]["scopes"])
    assert busy == 2 * 950e3 and under == 2 * per_step_us * 1e3


def test_scope_share_reads_the_loop_under_the_sublayers():
    """``loop.exit`` and both halves of the head count under ``lm_head_loss``
    for the reader of outermost sublayers; the loop's own ``while`` hides no
    sublayer."""
    tab = scope_share.table(recorded_ops(), recorded_runs())
    per_step = {scope: (t["fwd"] + t["bwd"]) / 2e3 for scope, t in tab.items()}
    assert per_step == {"embed": 20, "qkv_proj": 150, "attention": 50,
                        "attn_out": 20, "ffn": 310, "layernorm": 10,
                        "lm_head_loss": 340, "optimizer": 40, None: 10}
    inner = {k: v / 2e3 for k, v in tab["lm_head_loss"]["inner"].items()}
    # a dotted name inside a sublayer reads as a kernel's: the table's inner
    # column shows the exit's passes beside the head's two halves
    assert inner == {"lm_head.fused": 145, "lm_head.recompute": 150,
                     "loop.exit": 45}


def test_new_readers_find_nothing_in_a_program_without_the_loop():
    dense = [r[:4] + (r[4].replace("loop.exit/", "").replace(
        "lm_head.recompute/", ""),) for r in recorded_ops()]
    runs = recorded_runs()
    assert scope_split.split(dense, runs, ["loop.exit"]) is None
    assert scope_split.split(dense, runs, ["lm_head.recompute"]) is None
    for metric in ("loop_exit_share.train", "head_recompute_share.train"):
        spec = harness.load("layer_metrics", metric)
        assert scope_split.read(spec["args"], {"facts": {}}) is None
