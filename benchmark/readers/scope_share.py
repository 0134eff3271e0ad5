"""Reader ``scope_share``: a sublayer's share of the device's busy time, in %.

Inside the WHOLE executions of the step program (args: ``module_prefix``), the
self time of each ``XLA Ops`` event goes to the outermost ``jax.named_scope``
sublayer on its ``op_name`` path (``SUBLAYERS``: the names the program gives
in ``models/transformer.py``, the trainer's step builders and the engine);
forward, backward (``transpose(...)``) and recomputed operations of a sublayer
add up.  The value is the share of the listed ``scopes`` (args) in the busy
time there, over every chip; ``"scopes": []`` is the share under none of the
names.  A fusion counts under its ROOT instruction's scope: a matmul fused
with the next sublayer's first elementwise operation is that sublayer's.

The whole table (every sublayer, forward and backward, the kernels named
inside them) is printed once per trace.  Nothing without a trace, without a
whole execution, or where no operation is under any of the names (a program
that has no named scopes).
"""

from collections import defaultdict

from benchmark import trace_reduce, trace_spans
from benchmark.harness import say

SUBLAYERS = ("embed", "layernorm", "qkv_proj", "attention", "attn_out", "ffn",
             "lm_head_loss", "lm_head", "kv_gather", "kv_scatter", "sample",
             "optimizer", "grad_sync")


def table(ops, runs) -> dict | None:
    """``{scope or None: {"fwd": ns, "bwd": ns, "inner": {name: ns}}}`` over
    the operations inside ``runs``; None where nothing is under a scope."""
    times = trace_spans.scope_times(trace_spans.inside(ops, runs), SUBLAYERS)
    if not any(scope for scope, _, _ in times):
        return None
    out: dict = defaultdict(lambda: {"fwd": 0.0, "bwd": 0.0,
                                     "inner": defaultdict(float)})
    for (scope, inner, backward), ns in times.items():
        out[scope]["bwd" if backward else "fwd"] += ns
        if inner:
            out[scope]["inner"][inner] += ns
    return dict(out)


def show(tab: dict, n_runs: int, prefix: str) -> None:
    total = sum(t["fwd"] + t["bwd"] for t in tab.values())
    say(f"busy time inside {n_runs} whole {prefix} executions, "
        f"{total / 1e9:.4f} s, by sublayer (a fusion counts under its root "
        f"instruction's scope):")
    for scope, t in sorted(tab.items(),
                           key=lambda kv: -(kv[1]["fwd"] + kv[1]["bwd"])):
        inner = ", ".join(f"{k} {100 * v / total:.2f}%" for k, v in sorted(
            t["inner"].items(), key=lambda kv: -kv[1]))
        say(f"  {scope or 'unattributed':<14}{100 * (t['fwd'] + t['bwd']) / total:7.2f}%"
            f"  forward {100 * t['fwd'] / total:6.2f}%  backward "
            f"{100 * t['bwd'] / total:6.2f}%" + (f"  [{inner}]" if inner else ""))


def read(args: dict, run: dict):
    parsed = trace_spans.of_run(run)
    if parsed is None:
        return None
    prefix = args["module_prefix"]

    def make():
        runs = trace_reduce.whole_runs(run["trace_rows"], prefix)
        tab = table(parsed["ops"], runs) if runs else None
        if tab is not None:
            show(tab, sum(len(v) for v in runs.values()), prefix)
        return tab

    tab = trace_spans.cached(parsed, f"scope_share:{prefix}", make)
    if tab is None:
        return None
    total = sum(t["fwd"] + t["bwd"] for t in tab.values())
    wanted = args["scopes"] or [None]
    return 100.0 * sum(tab[s]["fwd"] + tab[s]["bwd"]
                       for s in wanted if s in tab) / total
