"""What decides ``correct`` for served tokens: copies of ``chip_smoke.py``'s
``pad_sequences`` / ``plain_margins`` / ``judge`` (PR 21), kept here so that a
later change to the smoke script cannot move the yardstick.

Exact token parity between the engine and a plain forward pass is a CPU
property: on the chip, with random weights, bf16 near-ties flip.  So every
generated token's logit must lie within a fixed band of its position's maximum
under the plain forward, which uses no KV cache, no pages and no kernel.
"""

from __future__ import annotations


def pad_sequences(max_len: int, seqs):
    import numpy as np

    toks = np.zeros((len(seqs), max_len), np.int32)
    for r, s in enumerate(seqs):
        toks[r, :len(s)] = s
    return toks


def plain_margins(cfg):
    """``margin[r, t]`` = max logit at position t minus the logit of the token
    actually at t+1, under the plain full forward over the whole sequence."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import forward_local

    @jax.jit
    def margins(params, toks):
        logits = forward_local(params, toks, cfg)
        nxt = jnp.roll(toks, -1, axis=1)
        chosen = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        return logits.max(axis=-1) - chosen

    return margins


def worst_margin(margin_rows, prompt_lens, new_tokens) -> tuple[float, int]:
    """Worst margin over every generated token, and how many were judged.
    Generated token s of row r sits at position p_len + s and was predicted
    at position p_len + s - 1.  NaN if any margin is."""
    import numpy as np

    m = np.concatenate([np.asarray(row[p - 1:p - 1 + n], np.float64)
                        for row, p, n in zip(margin_rows, prompt_lens,
                                             new_tokens)])
    return float(m.max()), int(m.size)
