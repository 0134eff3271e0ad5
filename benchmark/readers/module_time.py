"""Reader ``module_time``: the median device duration, in ms, of the WHOLE
executions of the programs whose name starts with ``module_prefix`` (args),
as ``trace_reduce.whole_runs`` finds them on the ``XLA Modules`` line; over
every chip.  The program alone: no host time, no gap before the next one.
Nothing without a trace, or when it holds no whole execution of that name."""

from statistics import median

from benchmark import trace_reduce


def read(args: dict, run: dict):
    rows = run.get("trace_rows")
    if rows is None:
        return None
    runs = trace_reduce.whole_runs(rows, args["module_prefix"])
    durs = [end - start for spans in runs.values() for start, end in spans]
    return median(durs) / 1e6 if durs else None
