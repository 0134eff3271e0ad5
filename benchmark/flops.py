"""Analytic model FLOPs of the dense transformer, kept with the yardstick.

Training FLOPs per token, forward + backward, counting matrix
multiplications only (6 x the parameters every token is multiplied by, plus
the attention scores and values): the arithmetic of
``TransformerConfig.flops_per_token`` with two differences.  The position
table is left out (it is added, never multiplied: 0.4% of BERT-base), and the
attention term uses the sequence length of the batch, not ``max_len``.
Recomputed operations (the compiler's rematerialisation, ``jax.checkpoint``)
do not count: this is what the model needs, not what the program does.
"""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that multiply every token: the blocks and the (tied) head."""
    d, f = model["d_model"], model["d_ff"]
    return model["n_layers"] * (4 * d * d + 2 * d * f) + model["vocab_size"] * d


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """``model`` holds TransformerConfig's size fields (a config file's
    ``transformer_config`` group).  Attention is counted in full (T keys per
    query), as the program computes it for causal and bidirectional alike."""
    attention = model["n_layers"] * 2 * seq_len * model["d_model"]
    return 6.0 * (matmul_params(model) + attention)
