"""Runner ``train_looped``: a looped model (``models/hybrid.py``: the same
layers run ``total_ut_steps`` times, an exit gate, the exit-weighted
objective) through ``DataParallelTrainer.fit`` over a window of seconds,
judged against the plain reference first.

The workload file gives what ``train``'s gives (``n_dp``, ``zero_stage``,
``global_batch``, ``seq_len``, ``resolve_every``, ``pool_batches``,
``warmup_batches``, ``first_loss_band``) plus ``compare``: the limits of the
comparison with ``benchmark/reference_ouro.py`` (``step_abs`` among them: the
trainer's own first loss against the compared program's) and
``reference_block``, the positions of head logits the reference makes at a
time.  Data, window, rate
and the checks on the losses are ``train``'s own functions.

Order.  Weights from ``--seed`` on the device; then, BEFORE the optimizer state
exists (the reference's gradients and the program's are 2.45 GB each), on pool
batch 0 at the timed sizes: the reference's objective, its loop steps' mean
cross entropies, its mean exit distribution and its gradients (float32, one
example and one layer application at a time), and the same of the program's
own loss.  Then the trainer, the warm-up (whose first step's loss is held to
the compared program's objective: the timed path is the compared one), the
window, and after it one forward pass of the exit
distribution over the pool with the final parameters, which gives the
``loop.exit_mass.*`` counters.
"""

from __future__ import annotations

import gc
import math
import time

from benchmark import flops_looped, reference_ouro
from benchmark.harness import (Cell, Outcome, live_bytes, say, seed32,
                               transformer_config)
from benchmark.runners.train import (host_batches, judge, step_program_bytes,
                                     window)


def hybrid_config(config: dict):
    """The program's ``HybridConfig`` from the config file's published keys;
    the trunk is its ``transformer_config`` group."""
    from deeplearning4j_tpu.models import hybrid

    mixer = hybrid.Attention(
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]))
    ffn = hybrid.GatedMLP(d_ff=config["intermediate_size"])
    assert not config["tie_word_embeddings"] and config["hidden_act"] == "silu"
    return hybrid.HybridConfig(
        base=transformer_config(config), norm_eps=config["rms_norm_eps"],
        layers=((mixer, ffn),) * config["num_hidden_layers"],
        n_loops=config["total_ut_steps"], exit_beta=config["exit_beta"])


def side(loss, aux) -> dict:
    """One side's numbers on the host: what ``judge_compare`` reads."""
    return {"objective": float(loss), "xent": [float(v) for v in aux["xent"]],
            "exit": [float(v) for v in aux["exit"]]}


def program_loss(cfg):
    """``(params, tokens, targets) -> (mean objective, {xent, exit})`` of the
    program's own loss: every loop step's mean cross entropy and exit mass."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import hybrid

    def measured(p, a, b):
        objective, xent, log_p = hybrid.looped_losses(p, a, b, cfg)
        return objective.mean(), {"xent": xent.mean(axis=(1, 2)),
                                  "exit": jnp.exp(log_p).mean(axis=(1, 2))}
    return measured


def compare(params, x, y, cfg, model: dict, block: int) -> dict:
    """Program against reference on one batch: each side's ``objective``,
    ``xent`` and ``exit`` (``side``), and ``grads`` (per group ``rel`` and
    ``cos``)."""
    import jax

    # the batch goes in as arguments: as constants it would be part of the
    # programs, and every seed would compile them anew
    t0 = time.perf_counter()
    ref_loss, ref_grads, ref_aux = reference_ouro.loss_and_grads(
        params, x, y, model, block_rows=block)
    ref_grads = jax.device_get(ref_grads)   # 2.45 GB off the device: the two
    t1 = time.perf_counter()                # sets of gradients do not fit beside
    (loss, aux), grads = jax.jit(           # the program's own temporaries
        jax.value_and_grad(program_loss(cfg), has_aux=True))(params, x, y)
    out = {"ref": side(ref_loss, ref_aux), "program": side(loss, aux),
           "grads": reference_ouro.compare_grads(grads, ref_grads),
           "ref_s": t1 - t0, "program_s": time.perf_counter() - t1}
    del grads, ref_grads
    return out


def judge_step(readings: dict, first_loss: float, limit: float):
    """The trainer's compiled step held to the program that was compared: the
    warm-up's first loss is that step's own, on the same batch and weights.
    What the comparison's limits say of ``compare``'s program they say of the
    timed path only through this check.  The objective against the
    reference's is reported beside it and not judged: rounding cancels in a
    mean over every token and loop step, and float8 operands read what bf16's
    do there (``compare_why`` in the workload file)."""
    ref, got = readings["ref"]["objective"], readings["program"]["objective"]
    off = abs(first_loss - got)
    return [(off <= limit,
             f"the warm-up's first loss {first_loss:.6f} against the compared "
             f"program's objective {got:.6f}: |difference| {off:.6f} <= {limit} "
             f"(the reference's {ref:.6f}: {abs(got - ref):.6f} and "
             f"{abs(first_loss - ref):.6f} off, no limit)")]


def judge_compare(readings: dict, limits: dict):
    """The comparison's checks, each reading beside its limit."""
    ref, got = readings["ref"], readings["program"]

    def worst(key):
        return max(abs(a - b) for a, b in zip(got[key], ref[key]))

    checks = [
        (worst("xent") <= limits["xent_abs"],
         "the loop steps' mean cross entropies "
         + " ".join(f"{v:.5f}" for v in got["xent"]) + " against "
         + " ".join(f"{v:.5f}" for v in ref["xent"])
         + f": largest |difference| {worst('xent'):.5f} <= {limits['xent_abs']}"),
        (worst("exit") <= limits["exit_abs"],
         "the mean exit distribution "
         + " ".join(f"{v:.5f}" for v in got["exit"]) + " against "
         + " ".join(f"{v:.5f}" for v in ref["exit"])
         + f": largest |difference| {worst('exit'):.6f} <= {limits['exit_abs']}"),
    ]
    for group, r in readings["grads"].items():
        rel_max, cos_min = limits["grad_rel"][group], limits["grad_cos"][group]
        checks.append((r["rel"] <= rel_max and r["cos"] >= cos_min,
                       f"gradients of {group}: relative error of the norm "
                       f"{r['rel']:.5f} <= {rel_max}, cosine {r['cos']:.6f} "
                       f">= {cos_min}"))
    return checks


def exit_mass_over_pool(params, pool, cfg):
    """``exit_stats`` summed over the pool's batches: (n_loops,) on the host."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models import hybrid

    stats = jax.jit(lambda p, x: hybrid.exit_stats(p, x, cfg))
    return sum(np.asarray(stats(params, jax.device_put(x))) for x, _ in pool)


def run(cell: Cell) -> Outcome:
    import jax

    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.observability import METRICS
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import local_mesh

    w = cell.workload
    cfg = hybrid_config(cell.config)
    batch, seq = w["global_batch"], w["seq_len"]
    METRICS.reset()

    def loss(p, xb, yb, key=None):
        return hybrid.looped_lm_loss_per_example(p, xb, yb, cfg)

    params = jax.jit(lambda key: hybrid.init_params(key, cfg))(
        jax.random.key(seed32(cell.seed)))
    pool = host_batches(cfg.base.vocab_size, batch, seq, w["pool_batches"],
                        cell.seed)
    x0, y0 = (jax.device_put(a) for a in pool[0])
    readings = compare(params, x0, y0, cfg, cell.config, w["reference_block"])
    say(f"comparison on pool batch 0 ({batch} x {seq}): reference "
        f"{readings['ref_s']:.1f}s, program {readings['program_s']:.1f}s")
    gc.collect()

    tx = T.adamw(T.warmup_cosine(1e-4, 10, 1000), weight_decay=0.01)
    trainer = DataParallelTrainer(loss, tx, mesh=local_mesh(w["n_dp"]),
                                  zero_stage=w["zero_stage"],
                                  per_example_loss=True)
    state = trainer.init_state(params)
    del params
    jax.block_until_ready((state.params, state.tstate))
    gc.collect()

    before = METRICS.snapshot()["counters"]
    state, warm = trainer.fit(state, pool[:w["warmup_batches"]],
                              resolve_every=w["resolve_every"])
    snap = METRICS.snapshot()
    compiles = snap["counters"].get("train_step.recompile", 0)
    # counted while the step is traced: which side each block's attention and
    # the head's loss took, how many loop steps and layer applications
    traced = {k: snap["counters"].get(k, 0) - before.get(k, 0) for k in (
        "attention.path.kernel", "attention.path.xla", "lm_head_loss.path.fused",
        "lm_head_loss.path.plain", "loop.steps", "loop.layer_applications")}
    say(f"warm-up: {len(warm)} steps, losses "
        + " ".join(f"{v:.4f}" for v in warm)
        + f"; first dispatch {snap['timers']['train_step.compile']['max_s']:.1f}s;"
        f" train_step.recompile {compiles:g}; traced {traced}")
    METRICS.reset()

    # the reference's own seconds are the yardstick's, not the system's set-up
    setup_s = time.perf_counter() - cell.process_t0 - readings["ref_s"]
    state, losses, wall = window(trainer, state, pool, cell.seconds,
                                 w["resolve_every"], cell.on_window)
    recompiled = METRICS.snapshot()["counters"].get("train_step.recompile", 0)

    tokens_per_s = len(losses) * batch * seq / wall
    say(f"window: {len(losses)} steps of {batch} x {seq} in {wall:.3f}s "
        f"({wall / max(1, len(losses)) * 1e3:.2f} ms/step); losses "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    # outside the window: where the final parameters' gates let tokens leave
    tokens_total = len(pool) * batch * seq
    mass = exit_mass_over_pool(state.params, pool, cfg)
    expected = hybrid.publish_exit_stats(mass, tokens_total)
    snap = METRICS.snapshot()
    say("exit distribution over the pool, final parameters: "
        + " ".join(f"{m / tokens_total:.4f}" for m in mass)
        + f"; expected loop steps a token {expected:.4f}")
    live = live_bytes(cell.devices)
    program = step_program_bytes(trainer, state, batch, seq)
    say(f"memory per chip: {live} B live after the window; the compiled step "
        f"holds {program} B (its temporaries are not in memory_stats())")

    checks = judge_compare(readings, w["compare"])
    checks += judge_step(readings, warm[0], w["compare"]["step_abs"])
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
               "flops_per_token": flops_looped.train_flops_per_token(
                   cell.config, seq),
               "tokens_per_step": batch * seq,
               "device_bytes_with_program": live + program["temporaries"],
               "timers": snap["timers"], "counters": snap["counters"]})
