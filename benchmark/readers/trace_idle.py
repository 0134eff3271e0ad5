"""Reader ``trace_idle``: the device's idle share of the traced slice, in %:
1 - (union of the intervals in which an operation ran) / slice, on the worst
chip.  Nothing without a trace."""


def read(args: dict, run: dict):
    summary = run.get("trace")
    return None if summary is None else 100.0 * summary["idle_share_worst"]
