"""Reader ``busy_roofline``: how far the device is from its compute roofline
while it is busy, in %.  Over the WHOLE executions of the step program that
the trace recorded (args: ``module_prefix``): their analytic FLOPs (each chip
does its share) over the peak, divided by the device's busy seconds from the
first one's start to the last one's end.  The worst chip.  Nothing without a
trace, or when the trace holds no whole execution under that name."""

from benchmark import trace_reduce


def read(args: dict, run: dict):
    f = run["facts"]
    rows = run.get("trace_rows")
    if rows is None or not all(k in f for k in ("flops_per_token",
                                                "tokens_per_step", "chips")):
        return None
    runs = trace_reduce.whole_runs(rows, args["module_prefix"])
    ops = trace_reduce.by_plane(rows, trace_reduce.OP_LINE)
    flops_per_run = f["flops_per_token"] * f["tokens_per_step"] / f["chips"]
    shares = []
    for plane, spans in runs.items():
        if not spans or plane not in ops:
            continue
        busy_s = trace_reduce.busy_idle(
            ops[plane], (spans[0][0], spans[-1][1]))["busy_ns"] / 1e9
        if busy_s > 0:
            shares.append(len(spans) * flops_per_run
                          / run["peak"]["bf16_flops_per_s"] / busy_s)
    return 100.0 * min(shares) if shares else None
