"""Reader ``metrics_timer``: one field of one of the program's ``METRICS``
timers, as the registry summarised it over the window (it is reset when the
window opens).  args: ``timer``, ``field`` (``mean_s``, ``p50_s``, ``p95_s``
...), ``scale`` (1000 for ms, 100 for a ratio in %)."""


def read(args: dict, run: dict):
    summary = run["facts"].get("timers", {}).get(args["timer"])
    if not summary or not summary.get("count"):
        return None
    return summary[args["field"]] * args.get("scale", 1.0)
