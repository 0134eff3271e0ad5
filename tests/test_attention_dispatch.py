"""The choice between the fused attention kernel and the XLA path, made in one
place (``ops.pallas.attention.attention_candidate``) for both block families
from what it can observe: backend, ``n_sp``, length, head width.  On the CPU
backend these tests run on, the default is the XLA path, bit for bit; the
kernel is forced (interpret mode) through ``TransformerConfig.attention``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.pallas import attention as pallas_attention
from deeplearning4j_tpu.ops.pallas.attention import (attention_candidate,
                                                     kernel_takes)


def _tiny(**kw):
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    base = dict(vocab_size=128, d_model=128, n_heads=2, n_layers=1, d_ff=64,
                max_len=128, causal=False, dtype=jnp.float32, remat=False)
    return TransformerConfig(**{**base, **kw})


def _loss_and_grad(cfg, t=128, seed=0):
    from deeplearning4j_tpu.models.transformer import (init_params,
                                                       lm_loss_local)
    params = init_params(jax.random.key(seed), cfg)
    toks = jax.random.randint(jax.random.key(seed + 1), (2, t), 0,
                              cfg.vocab_size)
    tgts = jnp.roll(toks, -1, axis=1)
    return jax.jit(jax.value_and_grad(
        lambda p: lm_loss_local(p, toks, tgts, cfg)))(params)


def _paths():
    from deeplearning4j_tpu.observability import METRICS
    c = METRICS.snapshot()["counters"]
    return (c.get("attention.path.kernel", 0), c.get("attention.path.xla", 0))


def test_default_config_keeps_the_xla_path_on_cpu_bitwise():
    """On the CPU backend the default configuration runs ``ring_attention``:
    value and gradient are bit for bit those of ``attention="ring"``, and
    the counters name the path, once per block."""
    assert _tiny().attention == "auto"
    l_auto, g_auto = _loss_and_grad(_tiny())
    assert _paths() == (0, 1)
    l_ring, g_ring = _loss_and_grad(_tiny(attention="ring"))
    assert _paths() == (0, 2)
    assert np.asarray(l_auto).tobytes() == np.asarray(l_ring).tobytes()
    for a, b in zip(jax.tree_util.tree_leaves(g_auto),
                    jax.tree_util.tree_leaves(g_ring)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("causal", [False, True],
                         ids=["bidirectional", "causal"])
def test_forced_kernel_agrees_with_the_xla_path(causal):
    """``attention="fused"`` runs the kernel (interpreted here) with the
    projections' heads merged; loss and gradients stay inside the
    candidate's declared tolerance, and far inside it in f32."""
    from deeplearning4j_tpu.ops.pallas import registry
    max_err = registry.get("attention", "fused").tolerances["max_err"]
    l_ring, g_ring = _loss_and_grad(_tiny(attention="ring", causal=causal))
    l_fused, g_fused = _loss_and_grad(_tiny(attention="fused", causal=causal))
    assert _paths() == (1, 1)
    assert abs(float(l_ring) - float(l_fused)) < 1e-5 < max_err
    for a, b in zip(jax.tree_util.tree_leaves(g_ring),
                    jax.tree_util.tree_leaves(g_fused)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_lengths_the_kernel_does_not_take_fall_back_unchanged():
    """T % 128 != 0: a forced kernel still gives way to the XLA path, and
    the result is bitwise the XLA path's."""
    l_fused, g_fused = _loss_and_grad(_tiny(attention="fused"), t=96)
    assert _paths() == (0, 1)
    l_ring, g_ring = _loss_and_grad(_tiny(attention="ring"), t=96)
    assert np.asarray(l_fused).tobytes() == np.asarray(l_ring).tobytes()
    for a, b in zip(jax.tree_util.tree_leaves(g_fused),
                    jax.tree_util.tree_leaves(g_ring)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("backend,kw,n_sp,t,want", [
    ("tpu", {}, 1, 512, "fused"),                  # the benchmark's cell
    ("tpu", {"d_model": 1024, "n_heads": 16, "causal": True}, 1, 1024,
     "fused"),                                     # gpt2_medium
    ("tpu", {"d_model": 256, "n_heads": 2}, 1, 256, "fused"),   # width 128
    ("cpu", {}, 1, 512, None),                     # the tests' backend
    ("tpu", {}, 2, 512, None),                     # the sp ring
    ("tpu", {}, 1, 448, None),                     # not whole 128-row blocks
    ("tpu", {}, 1, 4096, "fused"),                 # the longest it takes
    ("tpu", {}, 1, 8192, None),                    # does not fit in VMEM
    ("tpu", {"d_model": 96, "n_heads": 3}, 1, 512, None),       # width 32
    ("tpu", {"d_model": 192, "n_heads": 3}, 1, 512, None),      # 1.5 groups
    ("tpu", {"n_kv_heads": 4}, 1, 512, None),      # GQA
    ("tpu", {"attention": "ring"}, 1, 512, None),  # forced XLA
    ("cpu", {"attention": "fused"}, 1, 512, "fused"),           # forced
    ("cpu", {"attention": "fused"}, 2, 512, None),  # forced, but the ring
], ids=["bert-base", "gpt2-medium", "width-128", "cpu", "sp-2", "t-448",
        "t-4096", "t-8192", "width-32", "odd-heads", "gqa", "forced-ring",
        "forced-fused", "forced-fused-sp-2"])
def test_attention_candidate_from_what_block_observes(monkeypatch, backend,
                                                      kw, n_sp, t, want):
    from deeplearning4j_tpu.models import transformer as tf
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = _tiny(**{"d_model": 768, "n_heads": 12, "max_len": 8192, **kw})
    assert tf._attention_candidate(cfg, n_sp, t, cfg.n_heads) == want


@pytest.mark.parametrize("backend,t,want", [
    ("tpu", 4096, "fused"), ("cpu", 4096, None), ("tpu", 8192, None),
], ids=["tpu-4096", "cpu-4096", "tpu-8192"])
def test_attention_candidate_at_the_zaya_shapes(monkeypatch, backend, t, want):
    """``hybrid``'s question, 8 heads of width 128: the kernel on a TPU up
    to the 4096 rows that fit in VMEM, the XLA path elsewhere — and the
    answer is counted where it is given."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert attention_candidate(t, 8, 128) == want
    assert _paths() == ((1, 0) if want else (0, 1))


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_each_block_family_asks_the_one_predicate_once_a_layer(monkeypatch,
                                                               family):
    """``transformer._block`` and ``hybrid.block`` decide nothing themselves:
    tracing two layers asks ``attention_candidate`` twice, with the shapes
    the block holds."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.models import transformer as tf
    asked = []
    real = attention_candidate

    def spy(t, h, d, **kw):
        asked.append((t, h, d, kw))
        return real(t, h, d, **kw)

    monkeypatch.setattr(pallas_attention, "attention_candidate", spy)
    toks = jnp.zeros((2, 128), jnp.int32)
    if family == "dense":
        cfg = _tiny(n_layers=2)
        params = jax.eval_shape(lambda: tf.init_params(jax.random.key(0), cfg))
        jax.eval_shape(lambda p: tf.lm_loss_local(p, toks, toks, cfg), params)
        want = (128, 2, 64, {"n_sp": 1, "asked": "auto"})
    else:
        layer = (hybrid.CCA(4, 2, 16), hybrid.MoE(8, (0, 4), 32, 48))
        cfg = hybrid.HybridConfig(
            base=_tiny(d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
                       causal=True), layers=(layer,) * 2)
        params = jax.eval_shape(
            lambda: hybrid.init_params(jax.random.key(0), cfg))
        jax.eval_shape(lambda p: hybrid.lm_loss(p, toks, toks, cfg), params)
        want = (128, 4, 16, {})
    assert asked == [want, want]
    assert _paths() == (0, 2)


def test_kernel_takes():
    assert kernel_takes(512, 12, 64) and kernel_takes(1024, 3, 128)
    assert not kernel_takes(500, 12, 64) and not kernel_takes(512, 12, 80)
    assert not kernel_takes(512, 3, 64)
    assert kernel_takes(4096, 8, 128) and not kernel_takes(4224, 8, 128)


@pytest.mark.parametrize("zero_stage", [0, 1], ids=["dp4", "dp4-zero1"])
def test_forced_kernel_inside_the_trainers_step(zero_stage):
    """The kernel as the trainer reaches it: per-example ``vmap`` of batch 1
    inside the dp ``shard_map`` (the ZeRO step too), over four virtual
    devices — three steps of AdamW give the XLA path's losses."""
    from deeplearning4j_tpu.models.transformer import (init_params,
                                                       lm_loss_local)
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import local_mesh

    toks = np.random.default_rng(0).integers(0, 128, (8, 128), dtype=np.int32)
    batches = [(toks, np.roll(toks, -1, axis=1))] * 3

    def losses(attention):
        cfg = _tiny(attention=attention)
        trainer = DataParallelTrainer(
            lambda p, x, y, key=None: lm_loss_local(p, x, y, cfg),
            T.adamw(1e-3), mesh=local_mesh(4), zero_stage=zero_stage)
        state = trainer.init_state(init_params(jax.random.key(0), cfg))
        return trainer.fit(state, batches, resolve_every=3)[1]

    np.testing.assert_allclose(losses("fused"), losses("ring"), atol=1e-5)
    assert _paths() == (1, 1)
