"""Reader ``analytic_mfu``: model FLOP/s utilisation over the whole window,
in %.  The runner's tokens per second x the benchmark's own analytic FLOPs per
token (``benchmark/flops.py``; recomputed operations do not count) over chips
x the peak of ``benchmark/peaks.json``.  Not the program's ``train.mfu``
gauge, whose numerator is XLA's count of the compiled program."""


def read(args: dict, run: dict):
    f = run["facts"]
    if not all(k in f for k in ("tokens_per_s", "flops_per_token", "chips")):
        return None
    return 100.0 * (f["tokens_per_s"] * f["flops_per_token"]
                    / (f["chips"] * run["peak"]["bf16_flops_per_s"]))
