"""Analytic model FLOPs of the sparse-attention mixture-of-experts block (a
learned top-k selection of keys, top-k routed experts behind a linear router),
kept with the yardstick: what the model needs per token, matrix products only,
forward x 3 for forward + backward.  Recomputed operations (``remat``, the
chunked loss, the chunks of queries) do not count, and WHAT THE MODEL ASKS FOR
counts, not what an implementation happens to do: attention is counted over
the SELECTED (query, key) pairs, so a masked dense implementation reads as a
low ``mfu.train`` and not as a fast model; an expert is multiplied only by the
(token, choice) pairs routed to it.

``model`` holds the published keys of ``configs/<config>.json``.  Per layer
and token, forward, at ``T`` positions (``k`` = ``sa_config.topk``, ``J`` index
heads of width ``c``):

- projections ``2 (E Hd + 2 E Gd + Hd E)``: q, k, v in, ``wo`` out;
- index projections ``2 (E Jc + E c + E J)``: the indexer's queries, its one
  key head, its head weights;
- index scores ``2 J c`` a CAUSAL pair (every key so far is scored):
  ``2 J c (T + 1) / 2`` a token;
- attention ``4 H d`` a SELECTED pair (scores and values):
  ``4 H d sum_t min(t + 1, k) / T`` a token;
- router ``2 E n_router``;
- experts ``2 * 3 E F n_tok s``: ``n_tok`` choices a token, ``s`` the share of
  (token, choice) pairs routed to an expert held here (measured by the runner;
  1/8 for uniform routing over an eighth);
and once, the head ``2 V E`` over the vocabulary held.
"""

from __future__ import annotations


def selected_pairs(seq_len: int, top_k: int) -> int:
    """``sum_t min(t + 1, top_k)`` over one sequence's queries."""
    full = min(seq_len, top_k)
    return full * (full + 1) // 2 + (seq_len - full) * top_k


def forward_flops_per_token(model: dict, seq_len: int, local_share: float) -> dict:
    """Forward matmul FLOPs per token, by part, over all layers."""
    e, h, g, d = (model["hidden_size"], model["num_attention_heads"],
                  model["num_key_value_heads"], model["head_dim"])
    sa = model["sa_config"]
    j, c = sa["indexer_num_heads"], sa["indexer_head_dim"]
    layers = model["num_hidden_layers"]
    return {
        "projections": layers * 2 * (e * h * d + 2 * e * g * d + h * d * e),
        "index_projections": layers * 2 * (e * j * c + e * c + e * j),
        "index_scores": layers * 2 * j * c * (seq_len + 1) / 2,
        "attention": layers * 4 * h * d * selected_pairs(seq_len, sa["topk"])
        / seq_len,
        "router": layers * 2 * e * model["router_width"],
        "experts": layers * 2 * 3 * e * model["moe_intermediate_size"]
        * model["num_experts_per_tok"] * local_share,
        "head": 2 * model["vocab_size"] * e,
    }


def train_flops_per_token(model: dict, seq_len: int, local_share: float) -> float:
    return 3.0 * sum(forward_flops_per_token(model, seq_len, local_share).values())
