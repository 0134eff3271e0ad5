"""Every operation of a training step has a name from the program (PR 36), and
the program can say what each operation of its compiled step holds.

On the CPU, at tiny sizes, for each family of model the benchmark trains
(dense, ZAYA-like, looped, sparse on its XLA path and on the kernels'), the
replicated and the ZeRO step:

- the step LOWERED with ``debug_info`` carries the new names (``residual``,
  ``loss_reduce``, and where they apply ``zero.layout``, ``dsa.attend``,
  ``moe.combine``) and no ``stablehlo.add`` / ``dot_general`` / ``reduce`` of
  it lies under no name of the program, but for what ``lax.scan`` itself emits
  around a body the program names no part of (the loop's counter and the
  transposed loop's sums over its steps, ``add_any``: ``scopemap`` names those
  by what they are fused with);
- ``scopemap.scope_map`` of the step COMPILED gives every fusion of the
  optimised module's text with its members, and the members keep their paths;
- ``fit`` registers shapes alone: it never compiles for the map, and keeps no
  array alive.
"""

import collections
import functools
import gc
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.models import transformer as tf
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.observability import scopemap
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.mesh import local_mesh

#: the sublayers ``benchmark/readers/scope_share.py`` knows, and PR 36's two
OUTER = ("embed", "layernorm", "qkv_proj", "attention", "attn_out", "ffn",
         "lm_head_loss", "lm_head", "kv_gather", "kv_scatter", "sample",
         "optimizer", "grad_sync", "residual", "loss_reduce")
#: names that only ever nest in one of those
NESTED = ("zero.layout", "cca.mix", "dsa.index_proj", "dsa.index_scores",
          "dsa.select", "dsa.index_loss", "dsa.attend", "moe.router",
          "moe.dispatch", "moe.experts", "moe.combine", "lm_head.fused",
          "lm_head.recompute", "loop.exit")
WRAPPER = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
#: what ``lax.scan`` emits for itself, under the path of the scan
SCANS_OWN = re.compile(r"(^|/)while/(body|cond)/(closed_call/add_any|[a-z_\-]+)$")


def _tiny(**kw):
    return TransformerConfig(**{**dict(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128, max_len=256,
        causal=True, remat=True, xent_chunk=64), **kw})


def _dense():
    cfg = _tiny(causal=False, remat=False)
    return (cfg, tf.init_params,
            lambda p, x, y, key=None: tf.lm_loss_local(p, x, y, cfg), False)


def _zaya_like():
    cfg = hybrid.HybridConfig(base=_tiny(n_kv_heads=2), layers=((
        hybrid.CCA(4, 2, 16), hybrid.MoE(8, (0, 4), 32, 48)),) * 2)
    return (cfg, hybrid.init_params,
            lambda p, x, y, key=None: hybrid.lm_loss_per_example(p, x, y, cfg), True)


def _looped():
    cfg = hybrid.HybridConfig(
        base=_tiny(tie_embeddings=False), norm_eps=1e-6, n_loops=3, exit_beta=0.1,
        layers=((hybrid.Attention(4, 4, 16), hybrid.GatedMLP(96)),) * 2)
    return (cfg, hybrid.init_params,
            lambda p, x, y, key=None: hybrid.looped_lm_loss_per_example(
                p, x, y, cfg), True)


def _sparse(rows=32):
    cfg = hybrid.HybridConfig(base=_tiny(tie_embeddings=False), layers=((
        hybrid.SparseAttention(n_heads=4, n_kv_heads=2, head_dim=16, index_heads=2,
                               index_dim=8, top_k=48, q_chunk=rows, kv_chunk=rows,
                               rows=rows),
        hybrid.MoE(8, (0, 4), 0, 48, top_k=2, renormalize=True)),) * 2)
    return (cfg, hybrid.init_params,
            lambda p, x, y, key=None: hybrid.lm_loss_per_example(p, x, y, cfg), True)


#: id -> (family, zero_stage, the names its step must carry, tokens a row)
CASES = {
    "dense": (_dense, 0, ("residual", "loss_reduce"), 128),
    "dense-zero1": (_dense, 1, ("residual", "loss_reduce", "zero.layout"), 128),
    "zaya-like": (_zaya_like, 0, ("residual", "loss_reduce", "moe.combine"), 128),
    "looped": (_looped, 0, ("residual", "loss_reduce"), 128),
    "sparse": (_sparse, 0, ("residual", "loss_reduce", "dsa.attend",
                            "moe.combine"), 128),
    "sparse-zero1": (_sparse, 1, ("residual", "loss_reduce", "dsa.attend",
                                  "moe.combine", "zero.layout"), 128),
    # the mixer on the kernels' path (interpreted here): their entry points
    # name dsa.attend themselves
    "sparse-kernels": (functools.partial(_sparse, rows=128), 0,
                       ("residual", "loss_reduce", "dsa.attend", "dsa.select"),
                       256),
}


def step_and_arguments(case, monkeypatch):
    """The trainer's jitted step for ``case`` and the arguments of a call."""
    family, zero, _, t = CASES[case]
    if case == "sparse-kernels":
        monkeypatch.setattr(hybrid, "sparse_attend", functools.partial(
            hybrid.sparse_attend, asked="selected"))
    jax.clear_caches()
    cfg, init, loss, per_example = family()
    trainer = DataParallelTrainer(
        loss, T.adamw(1e-3, weight_decay=0.01), mesh=local_mesh(2 if zero else 1),
        zero_stage=zero, per_example_loss=per_example)
    state = trainer.init_state(init(jax.random.key(0), cfg))
    toks = jax.device_put(jnp.zeros((2, t), jnp.int32), trainer._batch_sh)
    i32 = jax.device_put(np.int32(0), trainer._rep_sh)
    return trainer._step_for(2), (state.params, state.tstate, toks, toks,
                                  state.key, i32, i32)


# ------------------------------------------------------- the lowered step

LOC = re.compile(r"^(#loc\d+) = loc\((.*)\)$")
FUNC = re.compile(r"^\s*func\.func (?:public |private )?@([\w.\-]+)\(")
CALL = re.compile(r"(?:func\.)?call @([\w.\-]+)\(")
OP = re.compile(r'(?:= |^\s+)"?((?:stablehlo|chlo)\.[a-z_]+)"?')
REF = re.compile(r"loc\((#loc\d+)\)\s*$")
NAMED = re.compile(r'^"([^"]*)"\((#loc\d+)\)$')


def op_paths(text: str) -> list[tuple[str, str]]:
    """``(operation, its whole name-stack path)`` of every operation of a
    module lowered with ``debug_info``.  A function that is lowered once and
    called (a ``jit``, a checkpointed block, a scan's body) carries paths
    from its own start on: the path of each of its call sites goes in front."""
    table, funcs, current = {}, {}, None
    for line in text.splitlines():
        if (m := LOC.match(line)):
            table[m.group(1)] = m.group(2)
        elif (m := FUNC.match(line)):
            current = funcs.setdefault(m.group(1), [])
        elif current is not None:
            op, ref, call = OP.search(line), REF.search(line), CALL.search(line)
            if ref and (op or call):
                current.append(("func.call" if call else op.group(1),
                                ref.group(1), call.group(1) if call else None))

    def own(ref):
        m = NAMED.match(table.get(ref, ""))
        if m and m.group(1).endswith(":"):      # "jit:"("the call's path"(...))
            return own(m.group(2))
        return m.group(1) if m else ""

    callers = collections.defaultdict(list)
    for fn, ops in funcs.items():
        for _, ref, callee in ops:
            if callee:
                callers[callee].append((fn, own(ref)))
    memo: dict[str, list[str]] = {}

    def prefixes(fn):
        if fn not in memo:
            memo[fn] = [""]
            if callers[fn]:
                memo[fn] = sorted({f"{p}/{c}".strip("/") for caller, c in callers[fn]
                                   for p in prefixes(caller)})
        return memo[fn]

    return [(op, f"{p}/{own(ref)}".strip("/"))
            for fn, ops in funcs.items() for op, ref, _ in ops
            for p in prefixes(fn)]


def names_on(path: str) -> set[str]:
    out = set()
    for part in path.split("/"):
        while (m := WRAPPER.match(part)):
            part = m.group(1)
        out.add(part)
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_every_operation_of_a_lowered_step_is_under_a_name(monkeypatch, case):
    fn, args = step_and_arguments(case, monkeypatch)
    rows = op_paths(fn.lower(*args).as_text(debug_info=True))
    assert len(rows) > 1000
    seen = set().union(*(names_on(path) for _, path in rows))
    assert set(CASES[case][2]) <= seen
    heavy = ("stablehlo.add", "stablehlo.dot_general", "stablehlo.reduce")
    bare = collections.Counter(
        (op, path) for op, path in rows
        if op in heavy and not names_on(path) & set(OUTER + NESTED)
        and not SCANS_OWN.search(path))
    assert not bare, bare.most_common(10)
    if case != "looped":        # only the loop over the layers is named by nobody
        assert not [path for op, path in rows if op in heavy
                    and not names_on(path) & set(OUTER + NESTED)]
    # scope_split takes the first of its names on a path, so a sublayer's
    # parts add up only while no dsa.* / moe.* name lies inside another
    for _, path in rows:
        for kind in ("dsa.", "moe."):
            assert sum(n.startswith(kind) for n in names_on(path)) <= 1, path


# ------------------------------------------------------- the compiled step

@pytest.mark.parametrize("case", ["dense", "dense-zero1", "zaya-like", "looped",
                                  "sparse"])
def test_scope_map_gives_every_fusion_its_members(monkeypatch, case):
    fn, args = step_and_arguments(case, monkeypatch)
    scopemap.clear()
    assert scopemap.register(fn, *args) == "jit_step"
    found = scopemap.scope_map("jit_step")
    text = fn.lower(*args).compile().as_text()
    fusions = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? fusion\(.*calls=", text,
                         re.MULTILINE)
    assert len(fusions) > 50
    for name in fusions:
        assert len(found[name]) >= 1, name
        assert all(isinstance(m, scopemap.Member) and m.opcode for m in found[name])
    # the members keep their paths: every op_name of the text is some member's
    paths = {m.path for members in found.values() for m in members}
    in_text = set(re.findall(r'op_name="([^"]*)"', text))
    assert in_text and in_text <= paths
    held = set().union(*(names_on(p) for p in paths))
    assert set(CASES[case][2]) <= held
    # and a fusion's are more than its root's: somewhere a member's name
    # differs from the name the fusion itself carries
    roots = dict(re.findall(
        r'^\s+(?:ROOT )?%?([\w.\-]+) = .*? fusion\(.*op_name="([^"]*)"', text,
        re.MULTILINE))
    assert any({m.path for m in found[name]} - {root, ""}
               for name, root in roots.items())


def test_parse_reads_tuples_nested_fusions_and_instructions_without_a_path():
    text = """HloModule jit_step, is_scheduled=true

%fused_inner (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(step)/ffn/neg"}
}

%fused_outer (a: f32[4], b: bf16[8,4]) -> (f32[4], bf16[8,4]) {
  %a = f32[4]{0} parameter(0)
  %b = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(1)
  %fusion.9 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_inner
  %dot.3 = bf16[8,4]{1,0} dot(%b, %b), metadata={op_name="jit(step)/transpose(jvp(attn_out))/dot_general" source_file="x.py" source_line=3}
  ROOT %tuple.2 = (f32[4]{0}, bf16[8,4]{1,0}) tuple(%fusion.9, %dot.3)
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %copy.5 = f32[4]{0} copy(%x)
  ROOT %r = (s32[], f32[4]{0}) tuple(%i, %copy.5)
}

ENTRY %main.7 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = (f32[4]{0}, bf16[8,4]{1,0}) fusion(%x, %y), kind=kOutput, calls=%fused_outer, metadata={op_name="jit(step)/optimizer/add"}
  ROOT %while.2 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body
}
"""
    found = scopemap.parse(text)
    assert [(m.opcode, m.path, m.shape) for m in found["fusion.1"]] == [
        ("parameter", "", "f32[4]"), ("parameter", "", "bf16[8,4]"),
        ("parameter", "", "f32[4]"), ("negate", "jit(step)/ffn/neg", "f32[4]"),
        ("dot", "jit(step)/transpose(jvp(attn_out))/dot_general", "bf16[8,4]"),
        ("tuple", "", "f32[4]")]
    assert found["copy.5"] == [scopemap.Member("copy", "", "f32[4]")]
    assert found["while.2"][0].opcode == "while"
    assert "neg.1" not in found and "dot.3" not in found    # fused: not executed alone


# ------------------------------------------------------- what a run pays

def test_fit_registers_shapes_and_never_asks_for_the_map(monkeypatch):
    asked = []
    monkeypatch.setattr(scopemap, "scope_map", lambda *a: asked.append(a))
    monkeypatch.setattr(scopemap, "parse", lambda *a: asked.append(a))
    scopemap.clear()
    cfg, init, loss, _ = _dense()
    trainer = DataParallelTrainer(loss, T.adamw(1e-3), mesh=local_mesh(1))
    state = trainer.init_state(init(jax.random.key(0), cfg))
    toks = np.zeros((2, 128), np.int32)
    watched = [weakref.ref(leaf) for leaf in jax.tree_util.tree_leaves(
        (state.params, state.tstate))]
    state, losses = trainer.fit(state, [(toks, toks)] * 3)
    assert len(losses) == 3 and not asked
    assert scopemap.registered("jit_step") == 1
    (entry,) = scopemap._programs["jit_step"]
    leaves = jax.tree_util.tree_leaves(entry.args)
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)
    # the first step donated the watched arrays; nothing holds them since
    del state
    gc.collect()
    assert all(ref() is None or ref().is_deleted() for ref in watched)
    # a second bucket is kept beside the first, the same one replaces itself
    trainer._nominal = None
    trainer.fit(trainer.init_state(init(jax.random.key(1), cfg)),
                [(toks[:1], toks[:1])])
    assert scopemap.registered("jit_step") == 2
    again = DataParallelTrainer(loss, T.adamw(1e-3), mesh=local_mesh(1))
    again.fit(again.init_state(init(jax.random.key(2), cfg)), [(toks, toks)])
    assert scopemap.registered("jit_step") == 2
    # the map is there after the code that trained is gone (a reader asks
    # once the runner has returned), for a bounded number of shapes
    del trainer, again
    gc.collect()
    monkeypatch.undo()
    assert scopemap.registered("jit_step") == 2 <= scopemap.KEEP
    assert len(scopemap.scope_map("jit_step")) > 100
    scopemap.clear()
    assert scopemap.scope_map("jit_step") == {}
