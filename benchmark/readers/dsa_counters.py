"""Reader ``dsa_counters``: what a learned sparse attention's selection
counters say (args: ``what``).  The program publishes ``dsa.pairs_selected``,
``dsa.pairs_causal``, ``dsa.tiles_empty`` and ``dsa.tiles_total`` from one
forward pass of its indexers (the runner calls it over the pool after the
window, with the final parameters).

- ``selected_pair_share``: (query, key) pairs the layers attend over, of the
  pairs ``s <= t``, in %: 23.44 at 16,384 positions where every query keeps
  exactly ``min(t + 1, 2048)`` keys.  What the model asks for, whatever the
  implementation computes.
- ``empty_tile_share``: ``(q_chunk, kv_chunk)`` tiles on or below the diagonal
  in which no query selects any key, of all such tiles, in %: the most a
  kernel that skips empty tiles could ever save on these weights.

Nothing where the counters are absent (a program without the mixer).
"""

PAIRS = {"selected_pair_share": ("dsa.pairs_selected", "dsa.pairs_causal"),
         "empty_tile_share": ("dsa.tiles_empty", "dsa.tiles_total")}


def read(args: dict, run: dict):
    counters = run["facts"].get("counters", {})
    part, whole = PAIRS.get(args["what"], (None, None))
    if not counters.get(whole):
        return None
    return 100.0 * counters.get(part, 0) / counters[whole]
