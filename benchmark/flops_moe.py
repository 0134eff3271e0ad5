"""Analytic model FLOPs of the ZAYA1 block (CCA mixer, top-1 routed experts),
kept with the yardstick: what the model needs per token, matrix products only,
forward x 3 for forward + backward.  Recomputed operations (``remat``, the
chunked loss) do not count, and ACTIVE parameters count, not held ones: an
expert is multiplied only by the tokens routed to it.

``model`` holds the published keys of ``configs/<config>.json``.  Per layer
and token, forward:

- projections ``2 (E Hd + 2 E Gd + Hd E)``: q~, k~ and the two value halves
  (together ``E x Gd``) in, ``wo`` out;
- grouped convolution ``2 k1 (H + G) d^2``; the depthwise one is elementwise;
- attention ``2 T Hd``: causal, counted as the kernel computes it, half of the
  full ``4 T Hd``;
- router ``2 (E r + 2 r^2 + r n_router)``;
- experts ``2 * 3 E F s``: ``s`` is the share of tokens routed to an expert
  held here (measured by the runner; 0.5 for uniform routing over a half);
and once, the head ``2 V E`` over the vocabulary held.
"""

from __future__ import annotations


def forward_flops_per_token(model: dict, seq_len: int, local_share: float) -> dict:
    """Forward matmul FLOPs per token, by part, over all layers."""
    e, h, g, d = (model["hidden_size"], model["num_attention_heads"],
                  model["num_key_value_heads"], model["head_dim"])
    r, f, v = (model["router_hidden_size"], model["moe_intermediate_size"],
               model["vocab_size"])
    layers = model["num_hidden_layers"]
    return {
        "projections": layers * 2 * (e * h * d + 2 * e * g * d + h * d * e),
        "convolution": layers * 2 * model["cca_time1"] * (h + g) * d * d,
        "attention": layers * 2 * seq_len * h * d,
        "router": layers * 2 * (e * r + 2 * r * r + r * model["router_width"]),
        "experts": layers * 2 * 3 * e * f * local_share,
        "head": 2 * v * e,
    }


def train_flops_per_token(model: dict, seq_len: int, local_share: float) -> float:
    return 3.0 * sum(forward_flops_per_token(model, seq_len, local_share).values())
