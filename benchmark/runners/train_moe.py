"""Runner ``train_moe``: a model of (mixer, ffn) layers (``models/hybrid.py``)
through ``DataParallelTrainer.fit`` over a window of seconds, judged against
the plain reference first.

The workload file gives what ``train``'s gives (``n_dp``, ``zero_stage``,
``global_batch``, ``seq_len``, ``resolve_every``, ``pool_batches``,
``warmup_batches``, ``first_loss_band``) plus ``compare``: the limits of the
comparison with ``benchmark/reference_zaya.py`` and ``reference_block``, the
positions of head logits the reference makes at a time.  Data, window, rate
and the checks on the losses are ``train``'s own functions.

Order.  Weights from ``--seed`` on the device and the experts placed by load
(``hybrid.place_experts`` on pool batch 0); then, BEFORE the optimizer state
exists (the reference's gradients and the program's are 2.8 GB each), on pool
batch 0 at the timed sizes: the program's expert choices, the reference's
loss, gradients and own choices along the program's routing (float32, one
example and one layer at a time), and the gradients of the trainer's own
``loss``.  Then the trainer, the warm-up
(whose first step's loss is compared with the reference's), the window, and
after it one pass of the program's routing statistics over the pool with the
final parameters, which gives the share of tokens routed to the experts held
here: the ``s`` of ``flops_moe`` and the two counter metrics.
"""

from __future__ import annotations

import gc
import math
import time

from benchmark import flops_moe, reference_zaya
from benchmark.harness import (Cell, Outcome, live_bytes, say, seed32,
                               transformer_config)
from benchmark.runners.train import (host_batches, judge, step_program_bytes,
                                     window)


def hybrid_config(config: dict):
    """The program's ``HybridConfig`` from the config file's published keys;
    the trunk is its ``transformer_config`` group."""
    from deeplearning4j_tpu.models import hybrid

    mixer = hybrid.CCA(
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        conv_kernels=(config["cca_time0"], config["cca_time1"]),
        rope_theta=float(config["rope_parameters"]["hybrid"]["rope_theta"]),
        rotary_factor=config["partial_rotary_factor"])
    ffn = hybrid.MoE(
        n_experts=config["router_width"], held=tuple(config["experts_held"]),
        router_hidden=config["router_hidden_size"],
        d_ff=config["moe_intermediate_size"])
    assert config["num_experts_per_tok"] == 1 and ffn.held[1] == config["num_experts"]
    return hybrid.HybridConfig(
        base=transformer_config(config), norm_eps=config["rms_norm_eps"],
        layers=((mixer, ffn),) * config["num_hidden_layers"])


def reference_model(config: dict) -> dict:
    """The dict the reference reads: the published keys, theta lifted out."""
    return dict(config, rope_theta=float(
        config["rope_parameters"]["hybrid"]["rope_theta"]))


def compare(params, x, y, cfg, loss_fn, model: dict, block: int) -> dict:
    """Program against reference on one batch: ``ref_loss``, ``grads`` (per
    group ``rel`` and ``cos``), ``routing_differs`` (share of (layer, token)
    pairs where the reference's router, fed the same path, chooses another
    expert than the program's did)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import hybrid

    # the batch goes in as arguments: as constants it would be part of the
    # programs, and every seed would compile them anew
    t0 = time.perf_counter()
    got_e = jax.jit(lambda p, a: jnp.stack(hybrid.encode(p, a, cfg)[1], axis=1))(
        params, x)
    # the reference follows the program's routing and reports its own choice
    # beside it: a near-tie decided the other way is one differing choice
    # there, not a difference in every number downstream of it
    ref_loss, ref_grads, ref_e = reference_zaya.loss_and_grads(
        params, x, y, model, block_rows=block, routing=got_e)
    ref_grads = jax.device_get(ref_grads)   # 2.8 GB off the device: the two
    t1 = time.perf_counter()                # sets of gradients do not fit beside
    grads = jax.jit(jax.grad(lambda p, a, b: loss_fn(p, a, b).mean()))(
        params, x, y)                       # the program's own temporaries
    out = {"ref_loss": float(ref_loss),
           "grads": reference_zaya.compare_grads(grads, ref_grads),
           "routing_differs": float(jnp.mean(got_e != ref_e)),
           "ref_s": t1 - t0, "program_s": time.perf_counter() - t1}
    del grads, ref_grads
    return out


def judge_compare(readings: dict, first_loss: float, limits: dict):
    """The comparison's checks, each reading beside its limit."""
    d = abs(first_loss - readings["ref_loss"])
    checks = [(d <= limits["loss_abs"],
               f"the warm-up's first loss {first_loss:.5f} against the "
               f"reference's {readings['ref_loss']:.5f}: |difference| "
               f"{d:.5f} <= {limits['loss_abs']}")]
    for group, r in readings["grads"].items():
        rel_max, cos_min = limits["grad_rel"][group], limits["grad_cos"][group]
        checks.append((r["rel"] <= rel_max and r["cos"] >= cos_min,
                       f"gradients of {group}: relative error of the norm "
                       f"{r['rel']:.5f} <= {rel_max}, cosine {r['cos']:.6f} "
                       f">= {cos_min}"))
    checks.append((readings["routing_differs"] <= limits["routing_differs"],
                   f"share of tokens whose expert differs from the "
                   f"reference's {readings['routing_differs']:.5f} <= "
                   f"{limits['routing_differs']}"))
    return checks


def routing_over_pool(params, pool, cfg):
    """``routing_stats`` summed over the pool's batches: (layers, n_experts)
    on the host."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models import hybrid

    stats = jax.jit(lambda p, x: hybrid.routing_stats(p, x, cfg))
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
        return hybrid.lm_loss_per_example(p, xb, yb, cfg)

    params = jax.jit(lambda key: hybrid.init_params(key, cfg))(
        jax.random.key(seed32(cell.seed)))
    pool = host_batches(cfg.base.vocab_size, batch, seq, w["pool_batches"],
                        cell.seed)
    x0, y0 = (jax.device_put(a) for a in pool[0])
    # which experts live here: dealt by load on the first batch, as a
    # deployment places them; the reference is given the placed weights
    params = hybrid.place_experts(params, x0, cfg)
    readings = compare(params, x0, y0, cfg, loss, reference_model(cell.config),
                       w["reference_block"])
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
    # one per layer per trace of the step: which side its attention took
    paths = {k: snap["counters"].get(f"attention.path.{k}", 0)
             - before.get(f"attention.path.{k}", 0) for k in ("kernel", "xla")}
    say(f"warm-up: {len(warm)} steps, losses "
        + " ".join(f"{v:.4f}" for v in warm)
        + f"; first dispatch {snap['timers']['train_step.compile']['max_s']:.1f}s;"
        f" train_step.recompile {compiles:g}; attention.path {paths}")
    METRICS.reset()

    setup_s = time.perf_counter() - cell.process_t0
    state, losses, wall = window(trainer, state, pool, cell.seconds,
                                 w["resolve_every"], cell.on_window)
    recompiled = METRICS.snapshot()["counters"].get("train_step.recompile", 0)

    tokens_per_s = len(losses) * batch * seq / wall
    say(f"window: {len(losses)} steps of {batch} x {seq} in {wall:.3f}s "
        f"({wall / max(1, len(losses)) * 1e3:.2f} ms/step); losses "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    # outside the window: where the final parameters send the pool's tokens
    routed = hybrid.publish_routing_stats(
        routing_over_pool(state.params, pool, cfg), cfg)
    snap = METRICS.snapshot()
    say(f"routing over the pool, final parameters: {100 * routed['local_share']:.2f}% "
        f"of tokens to the {cfg.layers[0][1].held[1]} experts held; largest "
        f"held expert's load over the mean {routed['load_max_over_mean']:.3f}")
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
               "flops_per_token": flops_moe.train_flops_per_token(
                   cell.config, seq, routed["local_share"]),
               "tokens_per_step": batch * seq,
               "device_bytes_with_program": live + program["temporaries"],
               "timers": snap["timers"], "counters": snap["counters"]})
