"""Reader ``moe_counters``: what the expert layers' counters say (args:
``what``).  The program publishes ``moe.tokens_total``, ``moe.tokens_local``
and ``moe.expert_load.l<layer>.e<expert>`` from its routing statistics (the
runner calls them over the pool after the window).

- ``local_share``: tokens routed to an expert held here over all tokens, in %.
- ``load_max_over_mean``: the held experts' loads summed over the layers, the
  largest over their mean (1.0 = perfectly even).

Nothing where the counters are absent (a program without expert layers).
"""

from collections import defaultdict

PREFIX = "moe.expert_load."


def read(args: dict, run: dict):
    counters = run["facts"].get("counters", {})
    if args["what"] == "local_share":
        total = counters.get("moe.tokens_total", 0)
        return 100.0 * counters.get("moe.tokens_local", 0) / total if total else None
    load = defaultdict(float)
    for name, value in counters.items():
        if name.startswith(PREFIX):
            load[name.rsplit(".", 1)[-1]] += value
    if not load or not sum(load.values()):
        return None
    return max(load.values()) / (sum(load.values()) / len(load))
