"""Every Pallas kernel of the main path compiles for a TPU v5e at BERT-base
widths — checked here, with no chip attached, by the TPU compiler that is
installed next to JAX (on-chip-measurement guide §2).

Interpret-mode tests cannot see what Mosaic refuses (a block that breaks
the (8, 128) tiling rule, a kernel that wants too much VMEM); these can,
in a second or two each.  A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture and nowhere else:
describing it loads libtpu, which only one process may hold, so nothing
here may touch it while a module is being imported or collected.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops.pallas.attention import fused_attention
from deeplearning4j_tpu.ops.pallas.layernorm import fused_residual_layernorm
from deeplearning4j_tpu.ops.pallas.matmul_int8 import (QuantizedLinear,
                                                       int8_matmul)
from deeplearning4j_tpu.ops.pallas.paged_attention import (
    paged_attention, paged_attention_int8)
from deeplearning4j_tpu.ops.pallas.xent import blocked_cross_entropy

# BERT-base widths (__graft_entry__._flagship_cfg) at the training batch
# and at the serving engine's default page geometry
B, T, H, DH, D, F, V = 64, 512, 12, 64, 768, 3072, 32768
SLOTS, PAGE, N_PAGES = 8, 16, T // 16
POOL = SLOTS * N_PAGES + 1              # + the engine's trash page


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep it off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip from shapes alone and return
    the optimized HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _fwd_bwd(fn):
    """``fn`` plus its gradient wrt every float argument, as one program."""
    def run(*args):
        def scalar(*a):
            out = fn(*a)
            leaves = jax.tree_util.tree_leaves(out)
            return sum(l.astype(jnp.float32).sum() for l in leaves)
        idx = tuple(i for i, a in enumerate(args)
                    if jnp.issubdtype(a.dtype, jnp.floating))
        return jax.value_and_grad(scalar, argnums=idx)(*args)
    return run


BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


def _per_example(kernel):
    """The trainer's form: ``vmap`` over examples of batch 1."""
    return lambda q, k, v, **kw: jax.vmap(
        lambda a, b, c: kernel(a[None], b[None], c[None], **kw)[0])(q, k, v)


@pytest.mark.parametrize("kernel,causal,shape", [
    (fused_attention, True, (B, T, H, DH)),
    (fused_attention, False, (B, T, H, DH)),
    (fused_attention, True, (8, 1024, 16, DH)),
    (_per_example(fused_attention), False, (B, T, H, DH)),
    (fused_attention, True, (8, 2048, 8, 128)),
    (fused_attention, True, (4, 4096, 8, 128)),
    (fused_attention, False, (4, 4096, 12, 64)),
    (fused_attention, True, (2, 4096, 16, 128)),
], ids=["fused-causal", "fused-bidirectional",
        "fused-causal-gpt2-medium", "fused-per-example-vmap",
        "fused-causal-2048-width-128", "fused-causal-4096-width-128",
        "fused-bidirectional-4096-width-64",
        "fused-causal-4096-16-heads-of-128"])
def test_attention_fwd_bwd_compiles(one_chip, kernel, causal, shape):
    hlo = _compile(
        _fwd_bwd(lambda q, k, v: kernel(q, k, v, causal=causal,
                                        interpret=False)),
        one_chip, *[(shape, BF16)] * 3)
    assert "tpu_custom_call" in hlo


def test_train_step_holds_no_score_matrix(one_chip, monkeypatch):
    """``value_and_grad(lm_loss_local)`` of two BERT-base-width blocks,
    compiled for the chip with the kernel ``_block`` takes there: no array
    whose last two dimensions are both the sequence length (the scores are
    gone, forward and backward), no copy or transpose beside the kernels
    (XLA lays q, k, v and the gradients out the way they read and write),
    and two custom calls a block: forward, and one backward for dq, dk, dv."""
    import re

    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params,
                                                       lm_loss_local)
    from deeplearning4j_tpu.ops.pallas import registry

    # code that asks for the backend still sees the CPU here: steer it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(registry, "resolve_interpret",
                        lambda interpret: bool(interpret))
    cfg = TransformerConfig(vocab_size=2048, d_model=D, n_heads=H,
                            n_layers=2, d_ff=F, max_len=T, causal=False,
                            remat=False)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(jax.random.key(0), cfg)))
    tokens = jax.ShapeDtypeStruct((8, T), I32, sharding=one_chip)
    hlo = jax.jit(jax.value_and_grad(
        lambda p, x, y: lm_loss_local(p, x, y, cfg))).lower(
            params, tokens, tokens).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * 2
    assert not re.findall(rf"(?:f32|bf16)\[[0-9,]*{T},{T}\]", hlo)
    beside = [line for line in hlo.splitlines()
              if re.search(r" (copy|transpose)\(", line)
              and re.search(r'op_name="[^"]*(attention|qkv_proj|attn_out)',
                            line)]
    assert not beside, beside[:3]


def test_fused_residual_layernorm_fwd_bwd_compiles(one_chip):
    hlo = _compile(
        _fwd_bwd(lambda x, r, s, b: fused_residual_layernorm(
            x, r, s, b, interpret=False)),
        one_chip, ((B, T, D), BF16), ((B, T, D), BF16),
        ((D,), F32), ((D,), F32))
    assert "tpu_custom_call" in hlo


def test_blocked_cross_entropy_fwd_bwd_compiles(one_chip):
    hlo = _compile(
        _fwd_bwd(lambda h, head, t: blocked_cross_entropy(
            h, head, t, interpret=False)),
        one_chip, ((B * T, D), BF16), ((D, V), BF16), ((B * T,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kv,dtype", [(H, BF16), (H, F32), (4, BF16)],
                         ids=["bf16", "f32", "gqa-kv4"])
def test_paged_attention_compiles(one_chip, kv, dtype):
    hlo = _compile(
        lambda q, kp, vp, bt, ln: paged_attention(q, kp, vp, bt, ln,
                                                  interpret=False),
        one_chip, ((SLOTS, H, DH), dtype),
        ((POOL, PAGE, kv, DH), dtype), ((POOL, PAGE, kv, DH), dtype),
        ((SLOTS, N_PAGES), I32), ((SLOTS,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kv", [H, 4], ids=["mha", "gqa-kv4"])
def test_paged_attention_int8_compiles(one_chip, kv):
    hlo = _compile(
        lambda q, kp, vp, ks, vs, bt, ln: paged_attention_int8(
            q, kp, vp, ks, vs, bt, ln, interpret=False),
        one_chip, ((SLOTS, H, DH), BF16),
        ((POOL, PAGE, kv, DH), I8), ((POOL, PAGE, kv, DH), I8),
        ((POOL, kv), F32), ((POOL, kv), F32),
        ((SLOTS, N_PAGES), I32), ((SLOTS,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k,n", [(D, F), (F, D), (D, V)],
                         ids=["ffn-up", "ffn-down", "lm-head"])
def test_int8_matmul_compiles(one_chip, k, n):
    hlo = _compile(
        lambda x, q, s: int8_matmul(x, QuantizedLinear(q=q, scale=s),
                                    interpret=False),
        one_chip, ((SLOTS, k), BF16), ((k, n), I8), ((n,), F32))
    assert "tpu_custom_call" in hlo


def test_hybrid_block_takes_the_kernel_at_4096(one_chip, monkeypatch):
    """One ZAYA1-width layer (CCA at 8 query heads over 2 KV heads of 128,
    8 of 16 experts held) at 2 x 4096, forward and backward, compiled for the
    chip as ``models/hybrid.py`` runs it there: attention is the fused kernel
    (forward, backward, and the forward once more under ``remat``), no array
    holds 4096 x 4096 scores, and the grouped matmuls are the compiler's
    ragged products."""
    import re

    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.observability import METRICS
    from deeplearning4j_tpu.ops.pallas import registry

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(registry, "resolve_interpret",
                        lambda interpret: bool(interpret))
    t = 4096
    base = TransformerConfig(vocab_size=2048, d_model=2048, n_heads=8,
                             n_kv_heads=2, n_layers=1, d_ff=2048, max_len=t,
                             causal=True, remat=True, xent_chunk=2048)
    cfg = hybrid.HybridConfig(base=base, layers=((hybrid.CCA(), hybrid.MoE()),))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg)))
    tokens = jax.ShapeDtypeStruct((2, t), I32, sharding=one_chip)
    METRICS.reset()
    hlo = jax.jit(jax.value_and_grad(
        lambda p, x, y: hybrid.lm_loss(p, x, y, cfg))).lower(
            params, tokens, tokens).compile().as_text()
    counters = METRICS.snapshot()["counters"]
    assert counters.get("attention.path.kernel") == 1
    assert "attention.path.xla" not in counters
    fused = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(r'op_name="[^"]*attention\.fused', line)]
    assert len(fused) == 3, len(fused)
    assert not re.findall(rf"(?:f32|bf16)\[[0-9,]*{t},{t}\]", hlo)
    assert "ragged-dot" in hlo


def test_looped_step_compiles_each_layer_once_at_4096(one_chip, monkeypatch):
    """Two layers of the looped family at its published widths (16 heads of
    128, no KV repeat, gated FFN 5632, sandwich norms) run 4 times at 2 x 4096
    with the exit-weighted objective, forward and backward, compiled for the
    chip: each layer's attention is the fused kernel three times (forward,
    recomputed forward, backward) however many loop steps run it, no array
    holds 4096 x 4096 scores, and the head's recomputed chunks are in the
    program (compiled for a padded batch's unequal rows; the exit weights are
    inside the head's call and a full batch never runs them)."""
    import re

    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.observability import METRICS
    from deeplearning4j_tpu.ops.pallas import registry

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(registry, "resolve_interpret",
                        lambda interpret: bool(interpret))
    t = 4096
    base = TransformerConfig(vocab_size=4096, d_model=2048, n_heads=16,
                             n_kv_heads=16, n_layers=2, d_ff=5632, max_len=t,
                             causal=True, tie_embeddings=False, remat=True,
                             xent_chunk=2048)
    cfg = hybrid.HybridConfig(
        base=base, layers=((hybrid.Attention(), hybrid.GatedMLP()),) * 2,
        norm_eps=1e-6, n_loops=4, exit_beta=0.1)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg)))
    tokens = jax.ShapeDtypeStruct((2, t), I32, sharding=one_chip)
    before = METRICS.snapshot()["counters"]
    # the rows' weights arrive at run time, as the trainer's mask / n_valid
    # does: under a constant mean XLA folds the head's cond away
    rows = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)
    hlo = jax.jit(jax.value_and_grad(
        lambda p, x, y, w: jnp.sum(
            hybrid.looped_lm_loss_per_example(p, x, y, cfg) * w))
    ).lower(params, tokens, tokens, rows).compile().as_text()
    after = METRICS.snapshot()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in (
        "attention.path.kernel", "attention.path.xla", "loop.layer_applications")}
    assert moved == {"attention.path.kernel": 2, "attention.path.xla": 0,
                     "loop.layer_applications": 8}
    fused = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(r'op_name="[^"]*attention\.fused', line)]
    assert len(fused) == 2 * 3, len(fused)
    assert not re.findall(rf"(?:f32|bf16)\[[0-9,]*{t},{t}\]", hlo)
    assert re.search(r'op_name="[^"]*lm_head\.recompute', hlo)
    assert re.search(r'op_name="[^"]*loop\.exit', hlo)


def _sparse_layer(one_chip, t, **mixer):
    """One layer of the sparse-attention family at its published widths (32
    query heads over 4 KV heads of 128, a 16 x 64 indexer, top-2048; 16 of 128
    experts of width 768, top-8 behind a linear router) at 1 x ``t``, forward
    and backward, compiled for the chip: ``(text, what the trace counted)``."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.observability import METRICS

    base = TransformerConfig(vocab_size=2048, d_model=2048, n_heads=32,
                             n_kv_heads=4, n_layers=1, d_ff=768, max_len=t,
                             causal=True, tie_embeddings=False, remat=True,
                             xent_chunk=2048)
    cfg = hybrid.HybridConfig(base=base, norm_eps=1e-6, layers=((
        hybrid.SparseAttention(**mixer),
        hybrid.MoE(128, (0, 16), 0, 768, top_k=8, renormalize=True)),))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: hybrid.init_params(jax.random.key(0), cfg)))
    tokens = jax.ShapeDtypeStruct((1, t), I32, sharding=one_chip)
    before = METRICS.snapshot()["counters"]
    hlo = jax.jit(jax.value_and_grad(
        lambda p, x, y: hybrid.lm_loss_per_example(p, x, y, cfg).mean())).lower(
            params, tokens, tokens).compile().as_text()
    after = METRICS.snapshot()["counters"]
    return hlo, {k: after.get(k, 0) - before.get(k, 0) for k in (
        "dsa.layers", "attention.path.kernel", "attention.path.xla",
        "dsa.index_path.kernel", "dsa.index_path.xla")}


def _as_on_the_chip(monkeypatch):
    from deeplearning4j_tpu.ops.pallas import registry

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(registry, "resolve_interpret",
                        lambda interpret: bool(interpret))


def test_sparse_block_compiles_with_no_whole_score_matrix(one_chip, monkeypatch):
    """The sparse layer at 1 x 16,384, the cell's length, compiled as
    ``models/hybrid.py`` runs it on the chip: Mosaic takes the three kernels
    that take the selection (forward once a span of chunks and NOT again in
    the checkpointed block's backward, the backward once, the heads' mean
    twice) inside VMEM; no f32 or bf16 array has a head extent, a chunk of
    queries and a run of keys (the per-head scores are gone, forward and
    backward), and none holds 16,384 x 16,384 of anything; the exact selection
    is still a counting loop with no sort; every ``dsa.*`` scope sits under
    the sublayer the readers know with its whole path, the heads' mean under
    ``dsa.index_loss``; and the choices go through the compiler's ragged
    products inside one loop."""
    import math
    import re

    t, spans = 16384, 4
    _as_on_the_chip(monkeypatch)
    hlo, moved = _sparse_layer(one_chip, t)
    assert moved == {"dsa.layers": 1, "attention.path.kernel": 1,
                     "attention.path.xla": 0, "dsa.index_path.kernel": 1,
                     "dsa.index_path.xla": 0}
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "pallas_call" in line]
    count = {name: sum(f"jit({name})" in p for p in calls)
             for name in ("_sparse_fwd", "_sparse_bwd", "_sparse_headsum",
                          "_index_fwd", "_index_bwd")}
    assert count == {"_sparse_fwd": spans, "_sparse_bwd": spans,
                     "_sparse_headsum": 2 * spans, "_index_fwd": 2 * spans,
                     "_index_bwd": spans}, count
    assert all("attention" in p for p in calls)
    assert all(("dsa.index_loss" in p) == ("_sparse_headsum" in p) for p in calls)
    assert all(("dsa.index_scores" in p) == ("_index_" in p) for p in calls)
    # (the first span's 4096 keys are also 32 heads x 128: left out)
    keys = {t * (i + 1) // spans for i in range(1, spans)}
    for shape in set(re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", hlo)):
        dims = [int(n) for n in shape.split(",")]
        if 256 in dims and keys & set(dims):       # (.., queries, .., keys)
            rest = math.prod(dims) // 256 // max(keys & set(dims))
            # neither the layer's 32 heads (4 x 8 on the XLA path) nor,
            # since the index kernels, the indexer's 16
            assert rest % 16, shape
    assert not re.findall(rf"(?:f32|bf16|u32|pred|s8)\[[0-9,]*{t},{t}\]", hlo)
    assert not re.search(r'op_name="[^"]*attention\.fused', hlo)
    assert not re.search(r' sort\([^\n]*dsa\.', hlo) and "ragged-dot" in hlo
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    for inner, outer in (("dsa.index_proj", "qkv_proj"), ("dsa.select", "attention"),
                         ("dsa.index_scores", "attention"),
                         ("dsa.index_loss", "attention"), ("moe.dispatch", "ffn")):
        # (a scope differentiated by hand reads jvp(<scope>), as the readers
        # know: benchmark/trace_spans.py unwraps it)
        mine = [p for p in paths if p.startswith("jit(")
                and re.search(rf"[/(]{re.escape(inner)}[/)]", p)]
        assert mine and all(outer in p.split(inner)[0] for p in mine), (
            inner, mine[:2])
        assert any("transpose(" in p for p in mine) or inner == "dsa.index_proj"


def test_sparse_block_falls_back_where_the_kernel_does_not_take_the_shape(
        one_chip, monkeypatch):
    """Chunks of 192 queries are no whole blocks: the same layer at 1 x 3072
    runs the mixer's XLA path on the chip too, and counts it."""
    _as_on_the_chip(monkeypatch)
    hlo, moved = _sparse_layer(one_chip, 3072, rows=192)
    assert moved == {"dsa.layers": 1, "attention.path.kernel": 0,
                     "attention.path.xla": 1, "dsa.index_path.kernel": 0,
                     "dsa.index_path.xla": 1}
    assert "_sparse_fwd" not in hlo and "pallas_call" not in hlo
