"""The control behind the limits of a ``train_mla`` cell's comparison: what has
to come out as NOT correct, through the cell's own ``judge_compare`` and
limits.

    python3 benchmark/control_mla.py --workload <cell> --seed <n> [--seed <m> ...]

For each seed, on pool batch 0 at the cell's sizes with the weights the cell
would draw and the experts placed as the cell places them:

- ``float8``: the reference with every matrix product's operands, forward and
  backward, rounded to ``float8_e4m3fn`` (the nearest precision below the
  configuration's bf16), put in the program's place.  Like the program in the
  cell it is judged against the float32 reference, both FOLLOWING the
  program's expert choices; its own choices, its objective's two parts and its
  gradients are what the cell's limits read.  It has to fail at least one
  check of ``judge_compare``.  (It takes no trainer step, so the check of the
  selection biases' first update is not among its checks.)

Every reading is printed beside its limit, the readings go to
``chiprun_out/control_mla/<cell>.seed_<n>.json``, and the exit code is 0 only
if the control failed as it must.  Like ``run.py``, nothing runs without a
TPU; the tests call ``controls`` on the CPU at a tiny size.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import harness, reference_joyai  # noqa: E402
from benchmark.harness import say  # noqa: E402
from benchmark.runners import train_mla  # noqa: E402
from benchmark.runners.train import host_batches  # noqa: E402


def controls(params, x, y, cfg, model: dict, workload: dict) -> dict:
    """``{"float8": (readings, checks)}``: the control's readings in the form
    the runner's own are, and what the cell's limits make of them."""
    import jax
    import jax.numpy as jnp

    block, limits = workload["reference_block"], workload["compare"]
    routing = train_mla.program_forward(params, x, y, cfg)[1]
    ref, ref_grads, ref_aux = reference_joyai.loss_and_grads(
        params, x, y, model, block_rows=block, routing=routing)
    ref_grads = jax.device_get(ref_grads)       # as the runner: off the device
    low, low_grads, low_aux = reference_joyai.loss_and_grads(
        params, x, y, model, operand_dtype=jnp.float8_e4m3fn, block_rows=block,
        routing=routing)
    # in the program's place: its objective is also "the trainer's first loss"
    float8 = train_mla.readings_of(
        {"objective": float(low), "lm": float(low_aux["lm"]),
         "mtp": float(low_aux["mtp"])}, low_grads, ref, ref_grads, ref_aux,
        low_aux["choices"])
    del ref_grads, low_grads
    return {"float8": (float8, train_mla.judge_compare(
        float8, float8["program"]["objective"], limits))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)

    import jax

    from deeplearning4j_tpu.models import hybrid

    try:
        w = harness.load("workloads", args.workload)
        model = harness.load("configs", w["config"])
        harness.require_tpu(w["chips"])
    except harness.BenchmarkError as e:
        sys.exit(f"benchmark: {e}")
    from deeplearning4j_tpu.parallel.compile_cache import setup_compile_cache

    say(f"controls of {args.workload}: seeds {args.seed}; compile cache "
        f"{setup_compile_cache()}")
    cfg = train_mla.hybrid_config(model)
    out_dir = REPO / "chiprun_out" / "control_mla"
    out_dir.mkdir(parents=True, exist_ok=True)
    as_they_must = True
    for seed in args.seed:
        t0 = time.perf_counter()
        params = jax.jit(lambda key: hybrid.init_params(key, cfg))(
            jax.random.key(harness.seed32(seed)))
        x, y = (jax.device_put(a) for a in host_batches(
            cfg.base.vocab_size, w["global_batch"], w["seq_len"], 1, seed)[0])
        params = hybrid.place_experts(params, x, cfg, y)
        found = controls(params, x, y, cfg, model, w)
        del params
        say(f"seed {seed}: {time.perf_counter() - t0:.1f}s")
        for name, (readings, checks) in found.items():
            failed = sum(not ok for ok, _ in checks)
            as_they_must &= failed > 0
            say(f" control {name}: fails {failed} of {len(checks)} checks"
                + ("" if failed else ": NOT TOLD FROM THE PROGRAM"))
            for ok, what in checks:
                say(f"  {'passes' if ok else 'fails'}: {what}")
        (out_dir / f"{args.workload}.seed_{seed}.json").write_text(json.dumps(
            {name: {"readings": readings,
                    "checks": [[bool(ok), what] for ok, what in checks]}
             for name, (readings, checks) in found.items()}, indent=1))
    return 0 if as_they_must else 1


if __name__ == "__main__":
    sys.exit(main())
