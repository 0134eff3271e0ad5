"""Reader ``scope_split``: the share of the device's busy time, in %, under
named scopes that the caller lists (args: ``module_prefix``, ``scopes``).

``scope_share`` attributes busy time to the OUTERMOST of a fixed list of
sublayers; the parts of a sublayer (the router, dispatch and experts of an
expert layer inside ``ffn``; CCA's mixing inside ``qkv_proj``) are scopes
nested in one of those, so this reader takes its own names from ``args`` and
asks ``trace_spans.scope_times`` for them alone.  Inside the WHOLE executions
of the step program, self time of ``XLA Ops``, forward, backward and
recomputed operations added up, over the busy time there, every chip.  Nothing
without a trace, without a whole execution, or where no operation of the
trace is under any of the listed scopes (a program that lacks them).
"""

from benchmark import trace_reduce, trace_spans


def split(ops, runs, scopes) -> tuple[float, float] | None:
    """``(ns under the scopes, busy ns)`` inside ``runs``; None where
    nothing is under any of them."""
    times = trace_spans.scope_times(trace_spans.inside(ops, runs), tuple(scopes))
    under = sum(ns for (scope, _, _), ns in times.items() if scope)
    return (under, sum(times.values())) if under else None


def read(args: dict, run: dict):
    parsed = trace_spans.of_run(run)
    if parsed is None:
        return None
    runs = trace_reduce.whole_runs(run["trace_rows"], args["module_prefix"])
    found = split(parsed["ops"], runs, args["scopes"]) if runs else None
    return None if found is None else 100.0 * found[0] / found[1]
