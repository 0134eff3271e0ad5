"""Analytic model FLOPs of a looped stack (plain attention, a gated dense
FFN, the same layers run ``total_ut_steps`` times, a head pass and an exit
gate every loop step), kept with the yardstick: what the model needs per
token, matrix products only, forward x 3 for forward + backward.  A parameter
counts once a USE: a layer that runs four times costs four layers' FLOPs for
one layer's memory.  Recomputed operations (``remat``, the head's recomputed
chunks) do not count.

``model`` holds the published keys of ``configs/<config>.json``.  Per layer
application and token, forward:

- projections ``2 (E Hd + 2 E Gd + Hd E)``: q, k, v in, ``wo`` out;
- ffn ``2 * 3 E F``: gate, up, down;
- attention ``2 T Hd``: causal, counted as the kernel computes it, half of the
  full ``4 T Hd``;
and per loop step the head ``2 V E`` and the gate ``2 E``.
"""

from __future__ import annotations


def forward_flops_per_token(model: dict, seq_len: int) -> dict:
    """Forward matmul FLOPs per token, by part, over all layers and loop
    steps."""
    e, h, g, d = (model["hidden_size"], model["num_attention_heads"],
                  model["num_key_value_heads"], model["head_dim"])
    uses = model["total_ut_steps"] * model["num_hidden_layers"]
    return {
        "projections": uses * 2 * (e * h * d + 2 * e * g * d + h * d * e),
        "ffn": uses * 2 * 3 * e * model["intermediate_size"],
        "attention": uses * 2 * seq_len * h * d,
        "head": model["total_ut_steps"] * 2 * model["vocab_size"] * e,
        "gate": model["total_ut_steps"] * 2 * e,
    }


def train_flops_per_token(model: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(model, seq_len).values())
