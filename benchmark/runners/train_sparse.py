"""Runner ``train_sparse``: a model of ``models/hybrid.py`` whose layers are a
learned sparse attention (``SparseAttention``: an indexer selects each query's
``topk`` keys and has a loss of its own) and a top-k mixture of experts behind
a linear router, through ``DataParallelTrainer.fit`` over a window of seconds,
judged against the plain reference first.

The workload file gives what ``train``'s gives (``n_dp``, ``zero_stage``,
``global_batch``, ``seq_len``, ``resolve_every``, ``pool_batches``,
``warmup_batches``, ``first_loss_band``) plus ``compare``: the limits of the
comparison with ``benchmark/reference_keye.py`` and ``reference_block``, the
queries (and positions of head logits) the reference scores at a time.  Data,
window, rate and the checks on the losses are ``train``'s own functions.

Order.  Weights from ``--seed`` on the device and the experts placed by load
(``hybrid.place_experts`` on pool batch 0); then, BEFORE the optimizer state
exists, on pool batch 0 at the timed sizes: the program's selected keys and
expert choices, the reference's objective, its two parts, gradients and own
selections and choices ALONG the program's (float32, one example and one layer
at a time), and the two parts and gradients of the trainer's own ``loss``.
Then the trainer, the warm-up (whose first step's loss is compared with the
reference's objective), the window, and after it one pass of the program's
routing and selection statistics over the pool with the final parameters:
the ``moe.*`` and ``dsa.*`` counters, and the local share of (token, choice)
pairs that ``flops_sparse`` counts the experts by.
"""

from __future__ import annotations

import gc
import math
import time

from benchmark import flops_sparse, reference_keye
from benchmark.harness import (Cell, Outcome, live_bytes, say, seed32,
                               transformer_config)
from benchmark.runners.train import (host_batches, judge, step_program_bytes,
                                     window)

#: counted while the step is traced: one per layer, which side its attention took
TRACED = ("dsa.layers", "attention.path.kernel", "attention.path.xla",
          "lm_head_loss.path.fused", "lm_head_loss.path.plain")


def hybrid_config(config: dict):
    """The program's ``HybridConfig`` from the config file's published keys;
    the trunk is its ``transformer_config`` group."""
    from deeplearning4j_tpu.models import hybrid

    sa = config["sa_config"]
    assert sa["indexer_num_kv_heads"] == 1 and config["hidden_act"] == "silu"
    assert not config["mlp_only_layers"] and config["decoder_sparse_step"] == 1
    assert not config["tie_word_embeddings"] and not config["attention_bias"]
    mixer = hybrid.SparseAttention(
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]), qk_norm=True,
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        top_k=sa["topk"], q_chunk=sa["q_chunk_size"], kv_chunk=sa["kv_chunk_size"],
        norm_eps=config["rms_norm_eps"])
    ffn = hybrid.MoE(
        n_experts=config["router_width"], held=tuple(config["experts_held"]),
        router_hidden=0, d_ff=config["moe_intermediate_size"],
        top_k=config["num_experts_per_tok"], renormalize=config["norm_topk_prob"])
    assert ffn.held[1] == config["num_experts"]
    return hybrid.HybridConfig(
        base=transformer_config(config), norm_eps=config["rms_norm_eps"],
        layers=((mixer, ffn),) * config["num_hidden_layers"])


def program_loss(cfg):
    """``(params, tokens, targets) -> (mean objective, {lm, index})``: the
    trainer's own loss with its two parts beside it."""
    from deeplearning4j_tpu.models import hybrid

    def measured(p, a, b):
        lm, own = hybrid.loss_parts(p, a, b, cfg)
        return (lm + own).mean(), {"lm": lm.mean(), "index": own.mean()}
    return measured


def program_choices(params, x, cfg):
    """What the program selects and chooses on ``x`` (B, T): ``([bool (B, T,
    T) per layer], int32 (B, layers, T, k))``."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import hybrid

    return jax.jit(lambda p, a: (                   # one program for both
        hybrid.selections(p, a, cfg),
        jnp.stack(hybrid.encode(p, a, cfg)[1], axis=1)))(params, x)


def differing_choices(got, own) -> float:
    """Share of ``got``'s (example, layer, token, choice) experts that are not
    among ``own``'s for the same token, whatever their order."""
    import jax.numpy as jnp

    return float(jnp.mean(~jnp.any(got[..., :, None] == own[..., None, :], axis=-1)))


def readings_of(loss, parts, grads, ref, ref_grads, ref_aux, routing,
                selection_of=None) -> dict:
    """One comparison's numbers on the host: what ``judge_compare`` reads.
    ``selection_of`` is the ``loss_and_grads`` result whose own selection is
    held against the one it followed (the reference's, by default)."""
    selection_of = selection_of or ref_aux
    return {"ref": {"objective": float(ref), "lm": float(ref_aux["lm"]),
                    "index": float(ref_aux["index"])},
            "program": {"objective": float(loss), "lm": float(parts["lm"]),
                        "index": float(parts["index"])},
            "grads": reference_keye.compare_grads(grads, ref_grads),
            "routing_differs": differing_choices(routing, ref_aux["choices"]),
            "selection_differs": float(selection_of["selection_differs"]),
            "selected": int(selection_of["selected"])}


def compare(params, x, y, cfg, model: dict, block: int) -> dict:
    """Program against reference on one batch (``readings_of``): the reference
    follows the program's selected keys and expert choices and reports its own
    beside them, so a near-tie decided the other way is one differing pair
    there, not a difference in every number downstream of it."""
    import jax

    # the batch goes in as arguments: as constants it would be part of the
    # programs, and every seed would compile them anew
    t0 = time.perf_counter()
    selection, routing = program_choices(params, x, cfg)
    jax.block_until_ready(routing)
    t1 = time.perf_counter()
    ref, ref_grads, ref_aux = reference_keye.loss_and_grads(
        params, x, y, model, block_rows=block, selection=selection,
        routing=routing)
    ref_grads = jax.device_get(ref_grads)   # 2.6 GB off the device: the two
    del selection                           # sets of gradients do not fit beside
    t2 = time.perf_counter()                # the program's own temporaries
    (loss, parts), grads = jax.jit(
        jax.value_and_grad(program_loss(cfg), has_aux=True))(params, x, y)
    out = dict(readings_of(loss, parts, grads, ref, ref_grads, ref_aux, routing),
               ref_s=t2 - t1, program_s=t1 - t0 + time.perf_counter() - t2)
    del grads, ref_grads
    return out


def judge_compare(readings: dict, first_loss: float, limits: dict):
    """The comparison's checks, each reading beside its limit."""
    ref, got = readings["ref"], readings["program"]
    d = abs(first_loss - ref["objective"])
    checks = [(d <= limits["loss_abs"],
               f"the warm-up's first loss {first_loss:.5f} against the "
               f"reference's objective {ref['objective']:.5f}: |difference| "
               f"{d:.5f} <= {limits['loss_abs']}")]
    for part, what in (("lm", "language-model loss"), ("index", "index loss")):
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
                   f"share of (layer, token, choice) experts outside the "
                   f"reference's own top choices {readings['routing_differs']:.5f}"
                   f" <= {limits['routing_differs']}"))
    checks.append((readings["selection_differs"] <= limits["selection_differs"],
                   f"share of the {readings['selected']} selected (layer, query, "
                   f"key) pairs outside the reference's own selection "
                   f"{readings['selection_differs']:.6f} <= "
                   f"{limits['selection_differs']}"))
    return checks


def stats_over_pool(params, pool, cfg):
    """``(routing_stats, selection_stats)`` summed over the pool's batches,
    on the host."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models import hybrid

    stats = jax.jit(lambda p, x: (hybrid.routing_stats(p, x, cfg),
                                  hybrid.selection_stats(p, x, cfg)))
    routed = chosen = 0
    for x, _ in pool:
        r, c = stats(params, jax.device_put(x))
        routed, chosen = routed + np.asarray(r), chosen + np.asarray(c, np.int64)
    return routed, chosen


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
    traced = {k: snap["counters"].get(k, 0) - before.get(k, 0) for k in TRACED}
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
    # outside the window: where the final parameters send the pool's tokens,
    # and which keys their indexers select
    routed, chosen = stats_over_pool(state.params, pool, cfg)
    local = hybrid.publish_routing_stats(routed, cfg)
    picked = hybrid.publish_selection_stats(chosen)
    snap = METRICS.snapshot()
    say(f"over the pool, final parameters: {100 * local['local_share']:.2f}% of "
        f"(token, choice) pairs to the {cfg.layers[0][1].held[1]} experts held, "
        f"largest held expert's load over the mean {local['load_max_over_mean']:.3f}; "
        f"{100 * picked['selected_share']:.3f}% of the causal pairs selected, "
        f"{100 * picked['empty_tile_share']:.3f}% of the tiles hold no selected key")
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
               "flops_per_token": flops_sparse.train_flops_per_token(
                   cell.config, seq, local["local_share"]),
               "tokens_per_step": batch * seq,
               "device_bytes_with_program": live + program["temporaries"],
               "timers": snap["timers"], "counters": snap["counters"]})
