"""Reader ``loop_counters``: what a looped model's exit counters say (args:
``what``).  The program publishes ``loop.tokens_total`` and
``loop.exit_mass.t<k>`` (the exit distribution summed over tokens, loop step
``k`` from 1) from one forward pass (the runner calls it over the pool after
the window).

- ``expected_steps``: ``sum_k k x loop.exit_mass.t<k> / loop.tokens_total``,
  the loop steps a token takes on average before its gate lets it leave:
  1.875 of 4 at a gate of one half, 1.0 or 4.0 for a gate that has collapsed.
  A diagnostic: a training step runs every loop step whatever this reads, so
  in a training cell it moves no end-to-end metric.

Nothing where the counters are absent (a program without a loop).
"""

PREFIX = "loop.exit_mass.t"


def read(args: dict, run: dict):
    counters = run["facts"].get("counters", {})
    total = counters.get("loop.tokens_total", 0)
    mass = {int(name[len(PREFIX):]): value for name, value in counters.items()
            if name.startswith(PREFIX)}
    if not total or not mass or args["what"] != "expected_steps":
        return None
    return sum(k * m for k, m in mass.items()) / total
