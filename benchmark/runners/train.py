"""Runner ``train``: ``DataParallelTrainer.fit`` over a window of seconds.

The workload file gives ``n_dp``, ``zero_stage``, ``global_batch``,
``seq_len``, ``resolve_every``, ``pool_batches``, ``warmup_batches`` and
``first_loss_band``.  The optimizer is ``chip_smoke.fit_flagship``'s: AdamW
over a warmup-cosine schedule.

Data: a pool of distinct host batches made from ``--seed``, cycled by a
generator that stops at the deadline, so the real input path (host pad ->
prefetch -> jitted step) runs all through the window.  The rate is taken over
the whole of the timed ``fit``: every step it resolved, and its wall from the
call to the return of every loss as a float, drain included.
"""

from __future__ import annotations

import gc
import math
import time

from benchmark import flops
from benchmark.harness import (Cell, Outcome, init_params_on_device,
                               live_bytes, say, seed32, transformer_config)


def host_batches(vocab: int, batch: int, seq: int, n: int, seed: int):
    """``n`` seeded (tokens, next-token targets) host batches."""
    import numpy as np

    rng = np.random.default_rng(seed32(seed))
    pool = []
    for _ in range(n):
        toks = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
        pool.append((toks, np.roll(toks, -1, axis=1)))
    return pool


def until(deadline: float, pool):
    """Cycle ``pool`` until ``perf_counter()`` passes ``deadline``."""
    i = 0
    while time.perf_counter() < deadline:
        yield pool[i % len(pool)]
        i += 1


def build(cfg, w: dict, seed: int):
    """The trainer and its state on ``local_mesh(n_dp)``, built the way
    ``online/loop.py`` and ``chip_smoke.py`` build theirs."""
    import jax

    from deeplearning4j_tpu.models.transformer import lm_loss_local
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import local_mesh

    def loss(p, xb, yb, key=None):
        return lm_loss_local(p, xb, yb, cfg)

    tx = T.adamw(T.warmup_cosine(1e-4, 10, 1000), weight_decay=0.01)
    trainer = DataParallelTrainer(loss, tx, mesh=local_mesh(w["n_dp"]),
                                  zero_stage=w["zero_stage"])
    params = init_params_on_device(cfg, seed)
    state = trainer.init_state(params)
    del params                  # init_state copied them onto the mesh
    jax.block_until_ready((state.params, state.tstate))
    gc.collect()
    return trainer, state


def window(trainer, state, pool, seconds: float, resolve_every: int,
           on_window=lambda t0: None):
    """The measured window: one ``fit`` over batches cycled until the
    deadline.  Returns (state, losses, wall seconds)."""
    t0 = time.perf_counter()
    on_window(t0)
    state, losses = trainer.fit(state, until(t0 + seconds, pool),
                                resolve_every=resolve_every)
    return state, losses, time.perf_counter() - t0


def step_program_bytes(trainer, state, batch: int, seq: int) -> dict:
    """What the compiled step holds on each chip, from the trainer's own
    cached jit as ``chip_smoke.fit_flagship`` reads it (the recompile counter
    does not move).  ``memory_stats()`` counts live buffers only, so a step's
    temporaries have to come from the compiler's own account."""
    import jax
    import jax.numpy as jnp

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), tree)

    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=trainer._batch_sh)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=trainer._rep_sh)
    mem = trainer._step_for(batch).lower(
        abstract(state.params), abstract(state.tstate), tok, tok,
        abstract(state.key), i32, i32).compile().memory_analysis()
    return {"temporaries": int(mem.temp_size_in_bytes),
            "arguments": int(mem.argument_size_in_bytes)}


def judge(first_loss: float, band, losses, recompiles_in_window: float):
    """The checks behind ``correct``, each with what it read."""
    k = min(8, len(losses) // 2)
    head = sum(losses[:k]) / k if k else float("nan")
    tail = sum(losses[-k:]) / k if k else float("nan")
    return [
        (bool(losses) and all(math.isfinite(v) for v in losses),
         f"{len(losses)} losses in the window, all finite"),
        (band[0] <= first_loss <= band[1],
         f"the warm-up's first loss {first_loss:.4f} in {list(band)}"),
        (tail < head,
         f"mean of the last {k} losses {tail:.4f} below the first {k} {head:.4f}"),
        (recompiles_in_window == 0,
         f"nothing compiled inside the window (train_step.recompile moved "
         f"by {recompiles_in_window:g})"),
    ]


def run(cell: Cell) -> Outcome:
    from deeplearning4j_tpu.observability import METRICS

    w = cell.workload
    cfg = transformer_config(cell.config)
    batch, seq = w["global_batch"], w["seq_len"]
    METRICS.reset()
    trainer, state = build(cfg, w, cell.seed)
    pool = host_batches(cfg.vocab_size, batch, seq, w["pool_batches"],
                        cell.seed)
    # warm-up: the one shape the window uses; compiles or reads the cache
    state, warm = trainer.fit(state, pool[:w["warmup_batches"]],
                              resolve_every=w["resolve_every"])
    snap = METRICS.snapshot()
    compiles = snap["counters"].get("train_step.recompile", 0)
    say(f"warm-up: {len(warm)} steps, losses "
        + " ".join(f"{v:.4f}" for v in warm)
        + f"; first dispatch {snap['timers']['train_step.compile']['max_s']:.1f}s;"
        f" train_step.recompile {compiles:g}")
    METRICS.reset()             # timers and counters now cover the window only

    setup_s = time.perf_counter() - cell.process_t0
    state, losses, wall = window(trainer, state, pool, cell.seconds,
                                 w["resolve_every"], cell.on_window)
    snap = METRICS.snapshot()

    tokens_per_s = len(losses) * batch * seq / wall
    ex = snap["timers"].get("train_step.execute", {})
    say(f"window: {len(losses)} steps of {batch} x {seq} in {wall:.3f}s "
        f"({wall / max(1, len(losses)) * 1e3:.2f} ms/step; between fences "
        f"median {ex.get('p50_s', 0) * 1e3:.2f}, max {ex.get('max_s', 0) * 1e3:.2f}"
        f" ms/step); losses {losses[0]:.4f} -> {losses[-1]:.4f}")
    live = live_bytes(cell.devices)
    program = step_program_bytes(trainer, state, batch, seq)
    say(f"memory per chip: {live} B live after the window; the compiled step "
        f"holds {program} B (its temporaries are not in memory_stats())")
    checks = judge(warm[0], w["first_loss_band"], losses,
                   snap["counters"].get("train_step.recompile", 0))
    checks.append((compiles == 1, f"exactly one compile before the window "
                                  f"(train_step.recompile == {compiles:g})"))
    for ok, what in checks:
        say(f"  {'ok' if ok else 'FAILED'}: {what}")
    bad = sum(not math.isfinite(v) for v in losses)
    return Outcome(
        correct=all(ok for ok, _ in checks), attempted=len(losses), failed=bad,
        end_to_end={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        facts={"tokens_per_s": tokens_per_s, "chips": w["n_dp"],
               "flops_per_token": flops.train_flops_per_token(
                   cell.config["transformer_config"], seq),
               "tokens_per_step": batch * seq,
               "device_bytes_with_program": live + program["temporaries"],
               "timers": snap["timers"], "counters": snap["counters"]})
