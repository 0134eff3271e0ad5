"""The names the program gives the device side of a profiler trace (DESIGN.md
§9): every trainer step program is ``jit_step`` whichever builder made it, the
engine's programs are ``jit_decode_step`` and ``jit_prefill_b<bucket>``, every
matmul of the model lowers under one of the ``jax.named_scope`` sublayers, and
a kernel taken from ``registry.get`` lowers under its registered name.

Read from the lowered module's HLO text (``op_name`` metadata), on the CPU and
on virtual devices: names are metadata, the same on every backend.
"""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM, init_params,
                                                   lm_loss_local)
from deeplearning4j_tpu.ops.pallas import registry
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import local_mesh
from deeplearning4j_tpu.serving import InferenceEngine, ServingConfig

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# the benchmark's own reading of a path: what it cannot attribute, fails here
from benchmark.readers.scope_share import SUBLAYERS  # noqa: E402
from benchmark.trace_spans import scope_of  # noqa: E402


def hlo_text(lowered) -> str:
    """HLO text WITH metadata (``as_text(dialect="hlo")`` drops it)."""
    return lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string()


def scopes_on(path: str) -> list[str]:
    """The sublayers on a path, outermost first (at most the two the
    benchmark's table shows)."""
    scope, inner, _ = scope_of(path, SUBLAYERS)
    return [s for s in (scope, inner) if s in SUBLAYERS]


def op_names(text: str, opcode: str) -> list[str]:
    """The ``op_name`` of every ``opcode`` instruction ('' where it has none)."""
    out = []
    for line in text.splitlines():
        if re.search(rf"= \S+ {opcode}\(", line):
            m = re.search(r'op_name="([^"]*)"', line)
            out.append(m.group(1) if m else "")
    return out


def abstract(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)


def tiny_cfg(**kw):
    return TransformerConfig(**dict(dict(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32,
        remat=False), **kw))


# ------------------------------------------------------------------ trainer

def lowered_step(n_dp: int, **trainer_kw):
    """One dispatched step of the tiny BERT, then the trainer's own cached
    jit lowered from the shapes of the very arguments it was called with."""
    if len(jax.devices()) < n_dp:
        pytest.skip(f"needs {n_dp} virtual devices")
    cfg = tiny_cfg(causal=False)
    trainer = DataParallelTrainer(
        lambda p, x, y, key=None: lm_loss_local(p, x, y, cfg), T.adamw(1e-3),
        mesh=local_mesh(n_dp), **trainer_kw)
    state = trainer.init_state(init_params(jax.random.key(0), cfg))
    batch = np.zeros((8, 16), np.int32)
    state, _ = trainer.step(state, batch, batch)
    x = jax.device_put(batch, trainer._batch_sh)
    if trainer.router == "iterative_reduce":
        rest = [jax.device_put(v, trainer._rep_sh)
                for v in (state.key, np.int32(1), np.int32(8))]
    else:
        rest = [jax.device_put(v, trainer._batch_sh)
                for v in (jax.random.split(state.key, n_dp),
                          np.full((n_dp,), 1, np.int32),
                          np.full((n_dp,), 8, np.int32))]
    return trainer._step_for(8).lower(
        *abstract((state.params, state.tstate, x, x, *rest)))


STEPS = {"sync": dict(n_dp=1), "zero1": dict(n_dp=4, zero_stage=1),
         "local": dict(n_dp=4, router="hogwild")}


@pytest.fixture(scope="module", params=sorted(STEPS))
def step(request):
    return request.param, lowered_step(**STEPS[request.param])


def test_every_trainer_step_program_is_called_jit_step(step):
    _, lowered = step
    assert re.findall(r"module @(\S+)", lowered.as_text())[:1] == ["jit_step"]
    assert hlo_text(lowered).startswith("HloModule jit_step")


def test_every_matmul_of_the_step_is_under_a_sublayer(step):
    _, lowered = step
    dots = op_names(hlo_text(lowered), "dot")
    assert len(dots) >= 2 * 6 + 1          # two layers of six, and the head
    assert all(scopes_on(p) for p in dots), [p for p in dots if not scopes_on(p)]
    seen = {scopes_on(p)[0] for p in dots}
    assert seen == {"qkv_proj", "attention", "attn_out", "ffn", "lm_head_loss"}
    # forward and backward of a sublayer carry the same name
    assert any("transpose(" in p and scopes_on(p) == ["attention"] for p in dots)


def test_optimizer_and_collectives_are_named(step):
    which, lowered = step
    text = hlo_text(lowered)
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert any(scopes_on(p)[:1] == ["optimizer"] for p in paths)
    assert any(scopes_on(p)[:1] == ["embed"] for p in paths)
    assert any(scopes_on(p)[:1] == ["layernorm"] for p in paths)
    if which == "zero1":
        synced = (op_names(text, "all-reduce") + op_names(text, "all-gather"))
        named = [p for p in synced if scopes_on(p) == ["grad_sync"]]
        # the gradients' psum and the parameters' all_gather; the loss's
        # own psum is outside it
        assert len(named) >= 2 and len(named) >= len(synced) - 1, synced


# ------------------------------------------------------------------- engine

@pytest.fixture(scope="module", params=["dense", "paged"])
def engine(request):
    model = TransformerLM(tiny_cfg(dtype=jnp.float32))
    params = model.init(jax.random.key(7))
    scfg = ServingConfig(slots=2, resolve_every=2,
                         **(dict(paged=True, page_size=4)
                            if request.param == "paged" else {}))
    return request.param, InferenceEngine(model, params=params, cfg=scfg)


def test_decode_step_is_named_and_its_matmuls_are_under_sublayers(engine):
    kind, eng = engine
    text = hlo_text(eng._step_fn.lower(eng._params, eng._state))
    assert text.startswith("HloModule jit_decode_step")
    dots = op_names(text, "dot")
    assert len(dots) == 2 * 6 + 1 and all(scopes_on(p) for p in dots), dots
    assert {scopes_on(p)[0] for p in dots} == {
        "qkv_proj", "attention", "attn_out", "ffn", "lm_head"}
    paths = re.findall(r'op_name="([^"]*)"', text)
    heads = {scopes_on(p)[0] for p in paths if scopes_on(p)}
    assert {"embed", "layernorm", "sample", "kv_scatter"} <= heads
    assert ("kv_gather" in heads) == (kind == "paged")


def test_prefill_bucket_is_named_and_its_matmuls_are_under_sublayers(engine):
    _, eng = engine
    bucket = eng._bucket_ladder()[0]
    lowered = eng._admit_for(bucket).lower(
        eng._params, {}, eng._state, jnp.zeros((bucket,), jnp.int32),
        jnp.int32(1), jnp.int32(0), jnp.int32(0), jax.random.key(0),
        jnp.float32(0.0), jnp.int32(0))
    text = hlo_text(lowered)
    assert text.startswith(f"HloModule jit_prefill_b{bucket}")
    dots = op_names(text, "dot")
    assert dots and all(scopes_on(p) for p in dots), dots


# ------------------------------------------------------------------ kernels

def test_a_kernel_from_the_registry_lowers_under_its_registered_name():
    cand = registry.get("attention", "fused")
    assert cand is registry.get("attention", "fused")       # one wrapper
    bare = next(c for c in registry.candidates("attention") if c.name == "fused")
    assert cand.fn.unscoped is bare.fn and cand.tolerances == bare.tolerances
    q = jnp.ones((1, 128, 2, 16), jnp.float32)
    text = hlo_text(jax.jit(
        lambda q, k, v: cand.fn(q, k, v, causal=False)).lower(q, q, q))
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert paths and any("/attention.fused/" in p for p in paths)
    text = hlo_text(jax.jit(
        lambda q, k, v: bare.fn(q, k, v, causal=False)).lower(q, q, q))
    assert "attention.fused" not in text


def test_block_runs_a_registry_kernel_inside_the_attention_scope():
    cfg = tiny_cfg(causal=False, attention="fused", max_len=128)
    params = init_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, 128), jnp.int32)
    text = hlo_text(jax.jit(
        lambda p, t: lm_loss_local(p, t, t, cfg)).lower(params, tokens))
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert any("/attention/attention.fused/" in p for p in paths)
