"""What the ``models/hybrid.py`` test files share: seeded parameters, a
parameter tree's leaves by their path names, and the tiny steps of the
families the benchmark trains."""

import jax

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models import transformer as tf


def seeded_params(cfg, seed=0):
    """Init, with every vector and scalar (norms, biases, temperatures, an
    exit gate) moved off its neutral value so that a dropped one would show."""
    params = hybrid.init_params(jax.random.key(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return tree.unflatten([
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim <= 1 else a
        for a, k in zip(leaves, keys)])


def leaf_names(tree):
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def named(tree):
    return dict(zip(leaf_names(tree), jax.tree_util.tree_leaves(tree)))


def objective(params, toks, tgts, cfg):
    return hybrid.lm_loss_per_example(params, toks, tgts, cfg).mean()


def tiny_base(**kw):
    return tf.TransformerConfig(**{**dict(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128, max_len=256,
        causal=True, remat=True, xent_chunk=64), **kw})


def dense_family():
    """``(config, init, loss, whether the loss is per example)`` of a tiny
    dense model; ``zaya_like_family`` and ``looped_family`` alike."""
    cfg = tiny_base(causal=False, remat=False)
    return (cfg, tf.init_params,
            lambda p, x, y, key=None: tf.lm_loss_local(p, x, y, cfg), False)


def zaya_like_family():
    cfg = hybrid.HybridConfig(base=tiny_base(n_kv_heads=2), layers=((
        hybrid.CCA(4, 2, 16), hybrid.MoE(8, (0, 4), 32, 48)),) * 2)
    return (cfg, hybrid.init_params,
            lambda p, x, y, key=None: hybrid.lm_loss_per_example(p, x, y, cfg), True)


def looped_family():
    cfg = hybrid.HybridConfig(
        base=tiny_base(tie_embeddings=False), norm_eps=1e-6, n_loops=3, exit_beta=0.1,
        layers=((hybrid.Attention(4, 4, 16), hybrid.GatedMLP(96)),) * 2)
    return (cfg, hybrid.init_params,
            lambda p, x, y, key=None: hybrid.looped_lm_loss_per_example(
                p, x, y, cfg), True)
