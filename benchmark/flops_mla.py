"""Analytic model FLOPs of the latent-attention mixture-of-experts model (MLA,
a leading dense layer, sigmoid-routed experts beside a shared expert, one
multi-token-prediction module), kept with the yardstick: what the model needs
per token, matrix products only, forward x 3 for forward + backward.
Recomputed operations (``remat``, the chunked loss, the chunks of queries) do
not count, and ACTIVE parameters count, not held ones: an expert is multiplied
only by the (token, choice) pairs routed to it.

``model`` holds the published keys of ``configs/<config>.json``.  A block is
the mixer plus its ffn; the blocks are the ``first_k_dense_replace`` dense
layers, the other ``num_hidden_layers - first_k_dense_replace`` expert layers,
and one expert layer more for each of the ``num_nextn_predict_layers``
prediction modules.  Per token, forward, at ``T`` positions (``H`` heads,
``rq`` / ``rkv`` the latents' ranks, ``dn + dr`` a head's query and key,
``dv`` its value):

- projections, every block: ``2 (E rq + rq H (dn + dr) + E (rkv + dr) + rkv H
  (dn + dv) + H dv E)``;
- attention, every block: causal, a pair costs ``2 H (dn + dr)`` for its score
  and ``2 H dv`` for its value: ``H (dn + dr + dv) (T + 1)`` a token;
- dense ffn ``2 * 3 E F``, a dense layer;
- an expert layer: router ``2 E n_router``, shared experts ``2 * 3 E F' n_shared``,
  experts ``2 * 3 E F' n_tok s``: ``n_tok`` choices a token, ``s`` the share of
  (token, choice) pairs routed to an expert held here (measured by the runner;
  1/16 for uniform routing over a sixteenth);
- a prediction module's merge ``2 (2 E) E``;
and the head ``2 V E`` over the vocabulary held, once for the main model and
``(T - 1) / T`` times for each module (its last position has no target).
"""

from __future__ import annotations


def blocks(model: dict) -> tuple[int, int, int]:
    """``(dense layers, expert layers, prediction modules)``; every module
    holds one expert layer more."""
    dense = model["first_k_dense_replace"]
    return (dense, model["num_hidden_layers"] - dense,
            model["num_nextn_predict_layers"])


def forward_flops_per_token(model: dict, seq_len: int, local_share: float) -> dict:
    """Forward matmul FLOPs per token, by part, over all blocks."""
    e, h = model["hidden_size"], model["num_attention_heads"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    f = model["moe_intermediate_size"]
    dense, sparse, modules = blocks(model)
    sparse += modules
    return {
        "projections": (dense + sparse) * 2 * (
            e * rq + rq * h * (dn + dr) + e * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * e),
        "attention": (dense + sparse) * h * (dn + dr + dv) * (seq_len + 1),
        "dense_ffn": dense * 2 * 3 * e * model["intermediate_size"],
        "router": sparse * 2 * e * model["router_width"],
        "shared": sparse * 2 * 3 * e * f * model["n_shared_experts"],
        "experts": sparse * 2 * 3 * e * f * model["num_experts_per_tok"]
        * local_share,
        "mtp_merge": modules * 2 * 2 * e * e,
        "head": 2 * model["vocab_size"] * e
        * (1 + modules * (seq_len - 1) / seq_len),
    }


def train_flops_per_token(model: dict, seq_len: int, local_share: float) -> float:
    return 3.0 * sum(forward_flops_per_token(model, seq_len, local_share).values())
