"""Runner ``train_mla``: a model of ``models/hybrid.py`` whose blocks are
multi-head latent attention (``MLA``: 192-wide queries and keys over 128-wide
values out of low-rank latents) and, after the leading dense layers, experts
behind a sigmoid router that selects on a biased score beside a shared expert,
with one multi-token-prediction module behind the trunk, through
``DataParallelTrainer.fit`` over a window of seconds, judged against the plain
reference first.

The workload file gives what ``train``'s gives (``n_dp``, ``zero_stage``,
``global_batch``, ``seq_len``, ``resolve_every``, ``pool_batches``,
``warmup_batches``, ``first_loss_band``) plus ``compare``: the limits of the
comparison with ``benchmark/reference_joyai.py`` and ``reference_block``, the
queries (and positions of head logits) the reference scores at a time.  Data,
window, rate and the checks on the losses are ``train``'s own functions.

Order.  Weights from ``--seed`` on the device and the experts placed by load
(``hybrid.place_experts`` on pool batch 0); then, BEFORE the optimizer state
exists, on pool batch 0 at the timed sizes: the program's objective parts and
expert choices (one forward program), and the reference's objective, its two
parts, gradients and own choices ALONG the program's (float32, one example and
one layer at a time; the gradients go to the host).  Then the trainer.  Its
FIRST step is the warm-up's step at a learning rate of zero, which leaves in
AdamW's first moment ``(1 - b1) x`` the step's own gradient: the gradients
that are held against the reference's are the TRAINER'S compiled step's, the
timed program's, read back out of its state (``step_gradients``), and every
selection bias is held against the rule on the program's counts.  The SECOND
step (the first whose learning rate is not zero) is held, leaf by leaf, to the
reference's plain AdamW step from the state before it and the gradient its
moments took in (``update_against_reference``): a state left unchanged reads
1 there.  Then the rest of the warm-up (the first step's loss is compared with
the reference's objective); the window; and after it one pass of the
program's routing statistics over the pool with the final parameters: the
``moe.*`` counters, and the local share of (token, choice) pairs that
``flops_mla`` counts the experts by.
"""

from __future__ import annotations

import gc
import math
import time

from benchmark import flops_mla, reference_joyai
from benchmark.harness import (Cell, Outcome, live_bytes, say, seed32,
                               transformer_config)
from benchmark.runners.train import (host_batches, judge, step_program_bytes,
                                     window)

#: counted while the step is traced: one per block, or one per trace
TRACED = ("mla.layers", "attention.path.kernel", "attention.path.xla",
          "mtp.modules", "lm_head_loss.path.fused", "lm_head_loss.path.weighted",
          "lm_head_loss.path.plain", "moe.dispatch.path.pairs",
          "moe.bias_updates")


def hybrid_config(config: dict):
    """The program's ``HybridConfig`` from the config file's published keys;
    the trunk is its ``transformer_config`` group."""
    from deeplearning4j_tpu.models import hybrid

    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert config["rope_scaling"] is None and config["moe_layer_freq"] == 1
    assert config["n_group"] == config["topk_group"] == 1
    assert not config["tie_word_embeddings"]
    assert (config["qk_head_dim"]
            == config["qk_nope_head_dim"] + config["qk_rope_head_dim"])
    mixer = hybrid.MLA(
        n_heads=config["num_attention_heads"], q_rank=config["q_lora_rank"],
        kv_rank=config["kv_lora_rank"], nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rope_interleave=config["rope_interleave"],
        norm_eps=config["rms_norm_eps"])
    biased = config["topk_method"] == "noaux_tc"
    ffn = hybrid.MoE(
        n_experts=config["router_width"], held=tuple(config["experts_held"]),
        router_hidden=0, d_ff=config["moe_intermediate_size"],
        top_k=config["num_experts_per_tok"], renormalize=config["norm_topk_prob"],
        scoring=config["scoring_func"],
        bias_rate=config["bias_update_rate"] if biased else None,
        scale=config["routed_scaling_factor"],
        shared_ff=config["n_shared_experts"] * config["moe_intermediate_size"])
    assert ffn.held[1] == config["n_routed_experts"]
    dense = hybrid.GatedMLP(config["intermediate_size"], post_norm=False)
    first = config["first_k_dense_replace"]
    assert config["num_nextn_predict_layers"] in (0, 1)
    return hybrid.HybridConfig(
        base=transformer_config(config), norm_eps=config["rms_norm_eps"],
        layers=(((mixer, dense),) * first
                + ((mixer, ffn),) * (config["num_hidden_layers"] - first)),
        mtp=(mixer, ffn) if config["num_nextn_predict_layers"] else None,
        mtp_weight=config["mtp_loss_weight"])


#: AdamW over a warm-up then a cosine, the training cells' optimizer, as the
#: numbers both sides read: ``optimizer`` builds the trainer's transform from
#: them and ``update_against_reference`` hands them to the reference's step
OPTIMIZER = {"peak": 1e-4, "warmup": 10, "total": 1000, "weight_decay": 0.01,
             "b1": 0.9, "b2": 0.999, "eps": 1e-8}


def optimizer():
    from deeplearning4j_tpu.optimize import transforms as T

    o = OPTIMIZER
    return T.adamw(T.warmup_cosine(o["peak"], o["warmup"], o["total"]),
                   weight_decay=o["weight_decay"], b1=o["b1"], b2=o["b2"],
                   eps=o["eps"])


def program_forward(params, x, y, cfg):
    """One forward pass of the program on ``(x, y)``: ``({objective, lm, mtp}
    (the batch's means), the experts it chooses per block of
    hybrid.layer_specs: int32 (B, T, k), or None for a dense layer)``."""
    import jax

    from deeplearning4j_tpu.models import hybrid

    def forward(p, a, b):
        parts, choices = hybrid.objective_parts(p, a, b, cfg)
        return ({k: parts[k].mean() for k in ("objective", "lm", "mtp")},
                [None if e is None else e[0] for e in choices])

    parts, choices = jax.jit(forward)(params, x, y)
    return {k: float(v) for k, v in parts.items()}, choices


def differing_choices(got, own) -> float:
    """Share of ``got``'s (block, example, token, choice) experts that are not
    among ``own``'s for the same token, whatever their order."""
    import jax.numpy as jnp

    miss = [~jnp.any(g[..., :, None] == o[..., None, :], axis=-1)
            for g, o in zip(got, own) if g is not None]
    return float(jnp.mean(jnp.stack(miss)))


def first_bias_update(params, routing, cfg, model: dict, margin: int) -> dict:
    """Every selection bias after ONE trainer step (they start at zero)
    against ``reference_joyai.bias_update`` on the counts of ``routing`` (the
    program's choices on the same batch under the same weights, from a
    program of its own).  The step's own choices differ from those by a few
    near-ties, so an expert whose count lies within ``margin`` pairs of the
    mean may have moved the other way: ``wrong`` counts the experts that
    differ from the rule OUTSIDE that margin (and any value that is not 0 or
    +-rate anywhere), ``near`` those inside it, ``reach`` the largest distance
    from the mean among the differing."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models import hybrid

    rate = model["bias_update_rate"]
    wrong = near = experts = 0
    reach = 0.0
    for i, ((_, ffn), to) in enumerate(zip(hybrid.layer_specs(cfg), routing)):
        if to is None:
            continue
        got = np.asarray(hybrid.layer_params(params, cfg, i)[ffn.key]["router"]["bias"])
        counts = reference_joyai.expert_counts(to, ffn.n_experts)
        want = np.asarray(reference_joyai.bias_update(
            jnp.zeros_like(got), counts, model))
        off = np.abs(np.asarray(counts, np.float64) - float(counts.sum()) / counts.size)
        rule = np.isin(got, np.asarray([0.0, rate, -rate], got.dtype))
        differs = got != want
        wrong += int(np.sum(~rule | (differs & (off > margin))))
        near += int(np.sum(rule & differs & (off <= margin)))
        reach = max(reach, float(off[differs].max(initial=0.0)))
        experts += got.size
    return {"wrong": wrong, "near": near, "reach": reach, "experts": experts}


def readings_of(parts, grads, ref, ref_grads, ref_aux, routing) -> dict:
    """One comparison's numbers on the host: what ``judge_compare`` reads.
    ``grads`` may be any positive multiple of the gradients: both readings of
    a group are taken after ``ref_grads`` is scaled alike."""
    return {"ref": {"objective": float(ref), "lm": float(ref_aux["lm"]),
                    "mtp": float(ref_aux["mtp"])},
            "program": dict(parts),
            "grads": reference_joyai.compare_grads(grads, ref_grads),
            "routing_differs": differing_choices(routing, ref_aux["choices"])}


def reference_pass(params, x, y, cfg, model: dict, block: int) -> dict:
    """Before the trainer exists, on one batch: the program's objective parts
    and expert choices (``program_forward``) and the reference's objective,
    parts, own choices and gradients (on the host: 2.7 GB that do not fit
    beside the trainer's state and step) ALONG the program's choices, so that
    a near-tie decided the other way is one differing choice there, not a
    difference in every number downstream of it.  The batch goes in as
    arguments: as constants it would be part of the programs, and every seed
    would compile them anew."""
    import jax

    t0 = time.perf_counter()
    parts, routing = program_forward(params, x, y, cfg)
    t1 = time.perf_counter()
    ref, ref_grads, ref_aux = reference_joyai.loss_and_grads(
        params, x, y, model, block_rows=block, routing=routing)
    return {"parts": parts, "routing": routing, "ref": ref, "ref_aux": ref_aux,
            "ref_grads": jax.device_get(ref_grads), "program_s": t1 - t0,
            "ref_s": time.perf_counter() - t1}


def first_step_readings(passed: dict, state) -> dict:
    """``readings_of`` with the TRAINER'S gradients: after its first step,
    whose learning rate is zero, AdamW's first moment is ``(1 - b1) x`` the
    gradient that step took, so the compiled step the window times is the
    program that is compared.  The reference's gradients are scaled by ``1 -
    b1`` on the host in place of a second tree on the device.  No gradient
    reaches a selection bias: both its moments are still zero, exactly."""
    import jax
    import numpy as np

    mu, nu = state.tstate[0]                    # chain(scale_by_adam, ...)
    scale = np.float32(1.0 - OPTIMIZER["b1"])
    ref_grads = jax.tree_util.tree_map(lambda g: g * scale, passed["ref_grads"])
    out = readings_of(passed["parts"], mu, passed["ref"], ref_grads,
                      passed["ref_aux"], passed["routing"])
    out["bias_grad_abs_max"] = max(
        float(abs(m).max()) for tree in (mu, nu) for path, m in
        jax.tree_util.tree_flatten_with_path(tree)[0]
        if getattr(path[-1], "key", None) == "bias")
    return out


def update_against_reference(before, state, step: int) -> dict:
    """The parameters after the step counted ``step`` (from 0) against the
    reference's plain AdamW step (``reference_joyai.adamw_step``) from
    ``before``, the host's copy of ``(parameters, first moments, second
    moments)`` as they stood before that step, and the gradient the step's
    first moment took in: per leaf ``|p' - expected p'| / |expected p' - p|``,
    the worst leaf's reported.  A state left unchanged reads 1; a wrong
    decay, second moment, bias correction or learning rate reads its error
    against the step's size.  The selection biases move by their own rule
    (``first_bias_update``): here their moments have to be zero still."""
    import jax
    import jax.numpy as jnp

    o = OPTIMIZER
    lr = reference_joyai.warmup_cosine(step, o["peak"], o["warmup"], o["total"])

    @jax.jit
    def sums(p0, m0, v0, p1, m1, decay):
        g = (m1 - o["b1"] * m0) / (1.0 - o["b1"])
        want = reference_joyai.adamw_step(p0, m0, v0, g, step, lr, decay,
                                          o["b1"], o["b2"], o["eps"])[0]
        return jnp.sum((p1 - want) ** 2), jnp.sum((want - p0) ** 2)

    names, found, still = [], [], []
    mu, nu = state.tstate[0]
    # once, in set-up: one small program a leaf, each resolved before the next
    # leaf's three host copies go up (together they would not fit beside the
    # trainer's state)  # graftlint: disable=HOT02
    for (path, p1), m1, v1, p0, m0, v0 in zip(
            jax.tree_util.tree_flatten_with_path(state.params)[0],
            *(jax.tree_util.tree_leaves(t) for t in (mu, nu, *before))):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if name.endswith("router/bias"):
            still += [jnp.max(jnp.abs(m1)), jnp.max(jnp.abs(v1))]
            continue
        names.append(name)
        found.append(jax.device_get(sums(  # graftlint: disable=HS01
            p0, m0, v0, p1, m1,
            jnp.float32(o["weight_decay"] if p0.ndim >= 2 else 0.0))))
    still = jax.device_get(jnp.stack(still))
    rel = [math.sqrt(float(d2) / max(float(s2), 1e-300)) for d2, s2 in found]
    worst = max(range(len(rel)), key=rel.__getitem__)
    return {"rel": rel[worst], "leaf": names[worst], "lr": lr,
            "bias_moments_abs_max": float(still.max())}


def new_trainer(cfg, w: dict):
    """The cell's trainer: the loss hands back the biases' moves beside the
    rows' losses."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import local_mesh

    def loss(p, xb, yb, key=None):
        return hybrid.lm_loss_and_moves(p, xb, yb, cfg)

    return DataParallelTrainer(loss, optimizer(), mesh=local_mesh(w["n_dp"]),
                               zero_stage=w["zero_stage"], per_example_loss=True)


def checked_warm_up(trainer, state, pool, passed: dict, cfg, model: dict, w: dict):
    """The warm-up's steps through ``trainer.fit``, the first two one at a
    time: ``(state, their losses, the comparison's readings)``.  After the
    first (learning rate 0) its gradients, read out of the first moment, and
    the selection biases; after the second the update, against the state the
    host kept (``fit`` passes over the ``state.step`` batches a fresh iterable
    starts with)."""
    import jax

    state, warm = trainer.fit(state, pool[:1], resolve_every=1)
    readings = first_step_readings(passed, state)
    readings["bias"] = first_bias_update(
        state.params, passed["routing"], cfg, model,
        w["compare"]["bias_count_margin"])
    kept = jax.device_get((state.params, *state.tstate[0]))
    state, more = trainer.fit(state, pool[:2], resolve_every=1)
    readings["update"] = update_against_reference(kept, state, 1)
    del kept
    warm = warm + more
    if w["warmup_batches"] > 2:
        state, more = trainer.fit(state, pool[:w["warmup_batches"]],
                                  resolve_every=w["resolve_every"])
        warm = warm + more
    return state, warm, readings


def judge_compare(readings: dict, first_loss: float, limits: dict):
    """The comparison's checks, each reading beside its limit."""
    ref, got = readings["ref"], readings["program"]
    d = abs(first_loss - ref["objective"])
    checks = [(d <= limits["loss_abs"],
               f"the warm-up's first loss {first_loss:.5f} against the "
               f"reference's objective {ref['objective']:.5f}: |difference| "
               f"{d:.5f} <= {limits['loss_abs']}")]
    for part, what in (("lm", "main cross entropy"),
                       ("mtp", "prediction module's cross entropy")):
        d = abs(got[part] - ref[part])
        checks.append((d <= limits[f"{part}_abs"],
                       f"the program's {what} {got[part]:.5f} against the "
                       f"reference's {ref[part]:.5f}: |difference| {d:.5f} <= "
                       f"{limits[f'{part}_abs']}"))
    for group, r in readings["grads"].items():
        rel_max, cos_min = limits["grad_rel"][group], limits["grad_cos"][group]
        checks.append((r["rel"] <= rel_max and r["cos"] >= cos_min,
                       f"gradients of {group}: relative error of the norm "
                       f"{r['rel']:.5f} <= {rel_max}, cosine {r['cos']:.6f} "
                       f">= {cos_min}"))
    checks.append((readings["routing_differs"] <= limits["routing_differs"],
                   f"share of (block, token, choice) experts outside the "
                   f"reference's own top choices {readings['routing_differs']:.5f}"
                   f" <= {limits['routing_differs']}"))
    if "update" in readings:         # the controls take no trainer step
        u = readings["update"]
        checks.append((
            u["rel"] <= limits["update_rel"] and u["bias_moments_abs_max"] == 0.0,
            f"the second step (learning rate {u['lr']:g}) against the "
            f"reference's AdamW step from the state before it: |p' - expected| "
            f"/ |expected - p| at the worst leaf ({u['leaf']}) {u['rel']:.3g} "
            f"<= {limits['update_rel']} (a state left unchanged reads 1); the "
            f"biases' moments |max| {u['bias_moments_abs_max']:g} == 0"))
    if "bias" in readings:
        b = readings["bias"]
        checks.append((
            b["wrong"] == 0 and readings["bias_grad_abs_max"] == 0.0,
            f"after one step, of {b['experts']} selection biases {b['wrong']} "
            f"differ from rate x sign(mean - count) on the program's counts at "
            f"more than {limits['bias_count_margin']} pairs from the mean "
            f"({b['near']} nearer, the farthest {b['reach']:.1f}); no "
            f"gradient reached a bias: their moments |max| "
            f"{readings['bias_grad_abs_max']:g} == 0"))
    return checks


def stats_over_pool(params, pool, cfg):
    """``routing_stats`` summed over the pool's batches, on the host."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models import hybrid

    stats = jax.jit(lambda p, x, y: hybrid.routing_stats(p, x, cfg, y))
    return sum(np.asarray(stats(params, jax.device_put(x), jax.device_put(y)))
               for x, y in pool)


def run(cell: Cell) -> Outcome:
    import jax

    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.observability import METRICS
    w = cell.workload
    cfg = hybrid_config(cell.config)
    batch, seq = w["global_batch"], w["seq_len"]
    METRICS.reset()

    params = jax.jit(lambda key: hybrid.init_params(key, cfg))(
        jax.random.key(seed32(cell.seed)))
    pool = host_batches(cfg.base.vocab_size, batch, seq, w["pool_batches"],
                        cell.seed)
    x0, y0 = (jax.device_put(a) for a in pool[0])
    # which experts live here: dealt by load on the first batch, as a
    # deployment places them; the reference is given the placed weights
    params = hybrid.place_experts(params, x0, cfg, y0)
    passed = reference_pass(params, x0, y0, cfg, cell.config, w["reference_block"])
    say(f"comparison on pool batch 0 ({batch} x {seq}): reference "
        f"{passed['ref_s']:.1f}s, the program's forward pass "
        f"{passed['program_s']:.1f}s")
    gc.collect()

    trainer = new_trainer(cfg, w)
    state = trainer.init_state(params)
    del params
    jax.block_until_ready((state.params, state.tstate))
    gc.collect()

    before = METRICS.snapshot()["counters"]
    ref_s = passed["ref_s"]
    state, warm, readings = checked_warm_up(trainer, state, pool, passed, cfg,
                                            cell.config, w)
    del passed
    snap = METRICS.snapshot()
    compiles = snap["counters"].get("train_step.recompile", 0)
    traced = {k: snap["counters"].get(k, 0) - before.get(k, 0) for k in TRACED}
    say(f"warm-up: {len(warm)} steps, losses "
        + " ".join(f"{v:.4f}" for v in warm)
        + f"; first dispatch {snap['timers']['train_step.compile']['max_s']:.1f}s;"
        f" train_step.recompile {compiles:g}; traced {traced}; "
        f"moe.bias_abs_max {hybrid.publish_bias_stats(state.params, cfg):g}")
    METRICS.reset()

    # the reference's own seconds are the yardstick's, not the system's set-up
    setup_s = time.perf_counter() - cell.process_t0 - ref_s
    state, losses, wall = window(trainer, state, pool, cell.seconds,
                                 w["resolve_every"], cell.on_window)
    recompiled = METRICS.snapshot()["counters"].get("train_step.recompile", 0)

    tokens_per_s = len(losses) * batch * seq / wall
    say(f"window: {len(losses)} steps of {batch} x {seq} in {wall:.3f}s "
        f"({wall / max(1, len(losses)) * 1e3:.2f} ms/step); losses "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    # outside the window: where the final parameters send the pool's tokens
    local = hybrid.publish_routing_stats(
        stats_over_pool(state.params, pool, cfg), cfg)
    bias = hybrid.publish_bias_stats(state.params, cfg)
    snap = METRICS.snapshot()
    say(f"over the pool, final parameters: {100 * local['local_share']:.2f}% of "
        f"(token, choice) pairs to the {cfg.layers[-1][1].held[1]} experts held, "
        f"largest held expert's load over the mean {local['load_max_over_mean']:.3f}; "
        f"moe.bias_abs_max {bias:g}")
    live = live_bytes(cell.devices)
    program = step_program_bytes(trainer, state, batch, seq)
    say(f"memory per chip: {live} B live after the window; the compiled step "
        f"holds {program} B (its temporaries are not in memory_stats())")

    checks = judge_compare(readings, warm[0], w["compare"])
    checks += judge(warm[0], w["first_loss_band"], losses, recompiled)
    checks.append((compiles == 1, f"exactly one compile before the window "
                                  f"(train_step.recompile == {compiles:g})"))
    for ok, what in checks:
        say(f"  {'ok' if ok else 'FAILED'}: {what}")
    bad = sum(not math.isfinite(v) for v in losses)
    return Outcome(
        correct=all(ok for ok, _ in checks), attempted=len(losses), failed=bad,
        end_to_end={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        facts={"tokens_per_s": tokens_per_s, "chips": w["n_dp"],
               "flops_per_token": flops_mla.train_flops_per_token(
                   cell.config, seq, local["local_share"]),
               "tokens_per_step": batch * seq,
               "device_bytes_with_program": live + program["temporaries"],
               "timers": snap["timers"], "counters": snap["counters"]})
