"""ZeRO sharded weight update (DESIGN.md §15): parity, layout, portability.

Pins the PR's acceptance criteria:
- stage parity: zero_stage 1/2/3 produce BITWISE-equal losses and params
  to the replicated stage-0 step on the CPU mesh, same data/seed — the
  sharded update is a layout change, not a numerics change,
- memory: optimizer-state bytes/device shrink ~1/ndp vs replicated
  (within flatten-padding tolerance), visible through the
  ``train.opt_state_bytes`` gauges,
- sharded layout: state leaves are chunks along axis 0 placed with a dp
  ``NamedSharding`` — of the leaf in its own shape where the dp width
  divides its leading dimension and the chunk fills the chip's (8, 128)
  tiles, of a 1-D padded vector otherwise (PR 31);
  stage 3 additionally keeps params sharded between steps,
- portable checkpoints: a zero-2 checkpoint saved on dp=2 restores onto
  dp=1 (and vice versa) and continues like an unsharded fixed-seed
  reference — bitwise at the reference's width, to a few float32 ulps
  across widths; stages interoperate through the same natural on-disk
  layout,
- the transfer-guard contract (PR 3) holds through the sharded step.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.observability import METRICS
from deeplearning4j_tpu.optimize import transforms as T
from deeplearning4j_tpu.parallel import DataParallelTrainer
from deeplearning4j_tpu.parallel.checkpoint import CheckpointManager
from deeplearning4j_tpu.parallel.mesh import DP, MeshSpec, make_mesh
from deeplearning4j_tpu.parallel.zero import ZeroLayout, host_natural_to_flat

D = 6
SIZES = [32, 31, 17, 9, 23, 13, 32, 5, 29, 11]


def _loss(params, x, y, key=None):
    return ((x @ params["w"] + params["b"] - y) ** 2).mean()


def _params(d=D):
    rng = np.random.default_rng(42)
    return {"w": rng.normal(size=(d, 1)).astype(np.float32),
            "b": np.zeros((1,), np.float32)}


def _data(n=10, seed=0, d=D):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(s, d)).astype(np.float32),
             rng.normal(size=(s, 1)).astype(np.float32))
            for s in SIZES[:n]]


def _adam():
    return T.adam(1e-2)


def _momentum():
    return T.chain(T.momentum(0.9), T.sgd_lr(5e-2))


def _run(stage, transform, steps=8, mesh=None, d=D):
    tr = DataParallelTrainer(_loss, transform, mesh=mesh, zero_stage=stage)
    state = tr.init_state(_params(d))
    losses = []
    for x, y in _data(steps, d=d):
        state, lazy = tr.step(state, x, y)
        losses.append(float(lazy))
    return np.array(losses), jax.device_get(tr.final_params(state)), tr, state


# --------------------------------------------------------------- parity
@pytest.mark.no_implicit_transfers
@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_stage_matches_replicated_bitwise(stage):
    """Acceptance: sharded update == replicated update, bit for bit."""
    l0, p0, _, _ = _run(0, _momentum())
    ls, ps, _, _ = _run(stage, _momentum())
    np.testing.assert_array_equal(ls, l0)
    for k in p0:
        np.testing.assert_array_equal(ps[k], p0[k])


def test_zero2_adam_tuple_state_bitwise():
    """Tuple-valued optimizer state (adam's (mu, nu)) shards per leaf."""
    l0, p0, _, _ = _run(0, _adam())
    l2, p2, _, _ = _run(2, _adam())
    np.testing.assert_array_equal(l2, l0)
    np.testing.assert_array_equal(p2["w"], p0["w"])


@pytest.mark.no_implicit_transfers
def test_zero2_fit_matches_sync_fit():
    """The async fit loop (prefetch, buckets, lazy ring) rides the sharded
    step unchanged — and stays inside the hot-loop transfer guard."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    data = [DataSet(x, y) for x, y in _data(6)]
    ta = DataParallelTrainer(_loss, _momentum(), zero_stage=2)
    _, la = ta.fit(ta.init_state(_params()), data,
                   async_dispatch=True, resolve_every=3)
    ts = DataParallelTrainer(_loss, _momentum(), zero_stage=0)
    _, lsync = ts.fit(ts.init_state(_params()), data,
                      async_dispatch=False)
    np.testing.assert_array_equal(np.array(la), np.array(lsync))


# --------------------------------------------------------------- layout
def test_zero2_state_leaves_are_dp_sharded_chunks():
    tr = DataParallelTrainer(_loss, _adam(), zero_stage=2)
    state = tr.init_state(_params())
    z = tr._zero
    n_dp = tr.n_dp
    for leaf in jax.tree.leaves(state.tstate):
        assert leaf.ndim == 1
        assert leaf.shape[0] % n_dp == 0
        assert leaf.sharding.spec == P(DP)
    # params stay replicated + natural below stage 3
    for leaf in jax.tree.leaves(state.params):
        assert leaf.sharding.spec == P()
    # padded sizes match the layout's arithmetic
    flat = jax.eval_shape(z.flatten_tree, z.natural_params)
    for nat, fl in zip(jax.tree.leaves(z.natural_params),
                       jax.tree.leaves(flat)):
        assert fl.shape == (z.padded_size(int(np.prod(nat.shape))),)


def test_zero3_params_sharded_between_steps_and_final_params_natural():
    _, p3, tr, state = _run(3, _momentum(), steps=4)
    for leaf in jax.tree.leaves(state.params):
        assert leaf.ndim == 1 and leaf.sharding.spec == P(DP)
    assert p3["w"].shape == (D, 1) and p3["b"].shape == (1,)
    l0, p0, _, _ = _run(0, _momentum(), steps=4)
    np.testing.assert_array_equal(p3["w"], p0["w"])


def test_zero_rejects_hogwild_and_bad_stage():
    with pytest.raises(ValueError, match="hogwild"):
        DataParallelTrainer(_loss, _momentum(), router="hogwild",
                            zero_stage=2)
    with pytest.raises(ValueError, match="zero_stage"):
        DataParallelTrainer(_loss, _momentum(), zero_stage=5)


def test_layout_padding_arithmetic():
    mesh = make_mesh(MeshSpec(dp=8))
    z = ZeroLayout(mesh, _momentum(), _params())
    assert z.padded_size(1) == 8          # never empty
    assert z.padded_size(8) == 8          # already divisible
    assert z.padded_size(9) == 16         # round up
    assert z.chunk_size(9) == 2
    # flatten -> unflatten roundtrips the natural tree exactly
    p = _params()
    flat = z.flatten_tree(p)
    back = z.unflatten_like(flat, z.natural_params)
    for k in p:
        np.testing.assert_array_equal(np.asarray(back[k]), p[k])


# --------------------------------------------------------------- per-leaf rule
# A tree that mixes leaves sharded in their own shape along axis 0 (the dp
# width divides the leading dimension and the chunk fills (8, 128) tiles)
# with leaves that flatten + pad: a scalar, a leading 5, and ``q``, which 4
# divides but whose last dimension is half a tile (BERT's qkv weight's case).
MIXED_SHAPES = {"w": (32, 128), "t": (4, 8, 128), "b": (128,), "s": (),
                "o": (5, 128), "q": (8, 2, 64)}
SPLIT_AT_4 = {"w": True, "t": True, "b": True, "s": False, "o": False,
              "q": False}


# sharded against replicated: XLA:CPU fuses the whole-array update and the
# chunk's differently, and Adam's g / sqrt(g * g) turns an ulp of a gradient
# near zero into a visible share of lr (1e-2); the all-flat layout reads the
# same.  Sharded against sharded is held bitwise.
REPLICATED_TOL = dict(rtol=1e-5, atol=5e-6)


def _mixed_params():
    rng = np.random.default_rng(7)
    return {k: (0.1 * rng.normal(size=sh) + (k == "s")).astype(np.float32)
            for k, sh in MIXED_SHAPES.items()}


def _mixed_loss(p, x, y, key=None):
    h = (x @ p["w"] + jnp.einsum("bk,kji->bi", x[:, :4], p["t"]) + p["b"]
         + x[:, :5] @ p["o"] + x[:, :8] @ p["q"].reshape(8, 128))
    return ((p["s"] * h - y) ** 2).mean()


def _mixed_data(n=3, batch=16, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(batch, 32)).astype(np.float32),
             rng.normal(size=(batch, 128)).astype(np.float32))
            for _ in range(n)]


def _dp_mesh(width):
    return make_mesh(MeshSpec(dp=width), devices=jax.devices()[:width])


def _mixed_trainer(stage, width=4):
    return DataParallelTrainer(_mixed_loss, T.adamw(1e-2, 0.1),
                               mesh=_dp_mesh(width), zero_stage=stage)


def _natural_tstate(tr, state):
    """The optimizer state in natural shapes, on the host."""
    if tr.zero_stage == 0:
        return jax.device_get(state.tstate)
    return tr._zero.to_natural_host(state.tstate, tr._zero.natural_tstate)


def _mixed_run(stage, steps=3, width=4):
    tr = _mixed_trainer(stage, width)
    state = tr.init_state(_mixed_params())
    losses = []
    for x, y in _mixed_data(steps):
        state, lazy = tr.step(state, x, y)
        losses.append(float(lazy))
    return (np.array(losses), jax.device_get(tr.final_params(state)),
            _natural_tstate(tr, state), tr, state)


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_mixed_tree_matches_replicated_and_all_flat(stage, monkeypatch):
    """Loss, params AND optimizer state after 3 AdamW steps, on a tree where
    some leaves split in their own shape and some flatten.  Against the same
    sharded step with EVERY leaf flattened (the layout before PR 31):
    bitwise, the chunking rule is chosen per leaf and the numbers are not.
    Against the replicated step: the losses bitwise, params and state to a
    float32 rounding (XLA:CPU fuses the whole-array update and the chunk's
    differently, in the all-flat layout just the same).  Weight decay
    checks the mask: ``o`` and ``q`` decay though their chunks are 1-D,
    ``b`` does not though its chunk has its own shape."""
    l0, p0, t0, _, _ = _mixed_run(0)
    ls, ps, ts, tr, state = _mixed_run(stage)
    with monkeypatch.context() as m:
        m.setattr(ZeroLayout, "splits", lambda self, shape: False)
        lf, pf, tf, _, sf = _mixed_run(stage)
    assert all(leaf.ndim == 1 for leaf in jax.tree.leaves(sf.tstate))
    np.testing.assert_array_equal(ls, lf)
    np.testing.assert_array_equal(ls, l0)
    for k in p0:
        assert ps[k].shape == MIXED_SHAPES[k]
        np.testing.assert_array_equal(ps[k], pf[k], err_msg=k)
        np.testing.assert_allclose(ps[k], p0[k], **REPLICATED_TOL,
                                   err_msg=k)
    for a, f, r in zip(*(jax.tree.leaves(t) for t in (ts, tf, t0))):
        np.testing.assert_array_equal(a, f)
        np.testing.assert_allclose(a, r, **REPLICATED_TOL)
    # on the device: a leaf that splits keeps its shape, cut along axis 0
    z = tr._zero
    mu = state.tstate[0][0]             # adamw = chain(scale_by_adam, ...)
    for k, split in SPLIT_AT_4.items():
        assert z.splits(MIXED_SHAPES[k]) == split
        want = (MIXED_SHAPES[k] if split
                else (z.padded_size(int(np.prod(MIXED_SHAPES[k]))),))
        assert mu[k].shape == want and mu[k].sharding.spec == P(DP)
        assert mu[k].addressable_shards[0].data.shape[0] == want[0] // 4
        if stage == 3:
            assert state.params[k].shape == want


def test_zero_leaf_counters_say_which_path_each_leaf_took():
    METRICS.reset()
    ZeroLayout(_dp_mesh(4), _momentum(), _mixed_params())
    c = METRICS.snapshot()["counters"]
    assert (c["zero.leaves.natural"], c["zero.leaves.flat"]) == (3, 3)
    METRICS.reset()
    # at dp 8 ``w``'s chunk is 4 rows, half a tile, and 8 does not divide 4
    ZeroLayout(_dp_mesh(8), _momentum(), _mixed_params())
    c = METRICS.snapshot()["counters"]
    assert (c["zero.leaves.natural"], c["zero.leaves.flat"]) == (1, 5)


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (shard_map, pjit, ...) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_step_of_divisible_tree_reshapes_and_pads_nothing(stage):
    """The jaxpr of the sharded step, every leaf one that splits: no leaf is
    reshaped to 1-D, nothing is concatenated (padding) and no
    pad is sliced off — gradient sync, update and all-gather run on the
    leaves' own shapes."""
    params = {"w": np.ones((32, 128), np.float32),
              "t": np.ones((4, 8, 128), np.float32)}

    def loss(p, x, y, key=None):
        h = x @ p["w"] + jnp.einsum("bk,kji->bi", x[:, :4], p["t"])
        return ((h - y) ** 2).mean()

    tr = DataParallelTrainer(loss, _adam(), mesh=_dp_mesh(4), zero_stage=stage)
    state = tr.init_state(params)
    x, y = _mixed_data(1)[0]
    args = (state.params, state.tstate, x, y, state.key, np.int32(0),
            np.int32(16))
    eqns = list(_eqns(jax.make_jaxpr(tr._step_for(16))(*args).jaxpr))
    names = {e.primitive.name for e in eqns}
    assert {"dynamic_slice", "all_gather"} <= names or stage == 3
    assert ("psum" in names) if stage == 1 else ("reduce_scatter" in names)
    assert "concatenate" not in names and "pad" not in names
    leaf_sizes = {4096, 1024}           # whole leaves and their chunks
    flat = [e for e in eqns if e.primitive.name == "reshape"
            and len(e.outvars[0].aval.shape) == 1
            and e.outvars[0].aval.shape[0] in leaf_sizes]
    assert not flat, flat
    # the mixed tree's step, for contrast, does flatten and pad its three
    trm = _mixed_trainer(stage)
    sm = trm.init_state(_mixed_params())
    argm = (sm.params, sm.tstate, x, y, sm.key, np.int32(0), np.int32(16))
    namem = {e.primitive.name
             for e in _eqns(jax.make_jaxpr(trm._step_for(16))(*argm).jaxpr)}
    assert "concatenate" in namem


# --------------------------------------------------------------- memory
def test_zero2_opt_state_bytes_shrink_per_device():
    """Acceptance: opt-state bytes/device ~ replicated/ndp (+ padding)."""
    d = 64  # big enough that per-leaf padding is small vs the total

    def opt_bytes():
        g = METRICS.snapshot()["gauges"]
        vals = [v for k, v in g.items()
                if k.startswith("train.opt_state_bytes.device.")]
        assert vals, "state gauges missing"
        return vals

    tr0 = DataParallelTrainer(_loss, _adam(), zero_stage=0)
    tr0.init_state(_params(d))
    rep = max(opt_bytes())
    METRICS.reset()
    tr2 = DataParallelTrainer(_loss, _adam(), zero_stage=2)
    tr2.init_state(_params(d))
    shard = max(opt_bytes())
    n_dp, itemsize = tr2.n_dp, 4
    n_leaves = len(jax.tree.leaves(tr2._zero.natural_tstate))
    pad_slack = n_leaves * itemsize * n_dp  # <= one dp-row of pad per leaf
    assert shard <= rep / n_dp + pad_slack
    assert shard >= rep / n_dp  # padding only ever adds
    # params are replicated below stage 3: full bytes on every device
    g = METRICS.snapshot()["gauges"]
    pb = [v for k, v in g.items()
          if k.startswith("train.params_bytes.device.")]
    assert max(pb) == (d + 1) * itemsize


# --------------------------------------------------------------- checkpoints
def _reference_losses(steps=6, split=3):
    """Unsharded fixed-seed reference: dp=1, stage 0, straight through."""
    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tr = DataParallelTrainer(_loss, _adam(), mesh=mesh, zero_stage=0)
    s = tr.init_state(_params())
    out = []
    for x, y in _data(steps):
        s, lz = tr.step(s, x, y)
        out.append(float(lz))
    return np.array(out[split:])


def _ckpt_roundtrip(tmp_path, save_dp, load_dp, save_stage=2, load_stage=2,
                    steps=6, split=3):
    data = _data(steps)
    mgr = CheckpointManager(tmp_path / f"dp{save_dp}to{load_dp}", keep=2)
    mesh_a = make_mesh(MeshSpec(dp=save_dp), devices=jax.devices()[:save_dp])
    tra = DataParallelTrainer(_loss, _adam(), mesh=mesh_a,
                              zero_stage=save_stage)
    sa = tra.init_state(_params())
    for x, y in data[:split]:
        sa, _ = tra.step(sa, x, y)
    tra.checkpoint(sa, mgr)

    mesh_b = make_mesh(MeshSpec(dp=load_dp), devices=jax.devices()[:load_dp])
    trb = DataParallelTrainer(_loss, _adam(), mesh=mesh_b,
                              zero_stage=load_stage)
    sb = trb.init_state(_params())
    sb = trb.restore(sb, mgr)
    assert sb.step == split
    losses = []
    for x, y in data[split:]:
        sb, lz = trb.step(sb, x, y)
        losses.append(float(lz))
    return np.array(losses), mgr


def _assert_continues_like_reference(got, continued_dp):
    """The reference runs on ONE chip, where the gradient "reduction" sums
    nothing.  A continuation that also runs at dp=1 must match it bitwise.
    One at dp=2 sums two per-chip partials — an all-reduce at stage 0, a
    reduce-scatter at stage >= 2 — in an order XLA is free to choose, and
    float32 addition does not associate: those cross-width comparisons are
    held to a few float32 ulps instead (jax 0.9.0 lands them 1 ulp apart)."""
    want = _reference_losses()
    if continued_dp == 1:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_array_max_ulp(
            got.astype(np.float32), want.astype(np.float32), maxulp=4)


@pytest.mark.parametrize("save_dp,load_dp", [(2, 1), (1, 2)])
def test_zero2_checkpoint_resharding_across_dp_widths(tmp_path,
                                                      save_dp, load_dp):
    """Acceptance: a zero-2 checkpoint written at one dp width restores
    onto another and continues equal to an unsharded reference — bitwise
    where the continuation runs at the reference's width, to a few ulps
    where it crosses widths (see ``_assert_continues_like_reference``)."""
    got, mgr = _ckpt_roundtrip(tmp_path, save_dp, load_dp)
    _assert_continues_like_reference(got, load_dp)
    # the manifest records provenance for tooling/debugging
    r = mgr.restore(jax.eval_shape(lambda t: t, _params()))
    assert r["extra"] == {"zero_stage": 2, "saved_dp": save_dp}


@pytest.mark.parametrize("save_stage,load_stage", [(0, 2), (2, 0), (3, 0)])
def test_zero_checkpoints_interoperate_across_stages(tmp_path, save_stage,
                                                     load_stage):
    """Natural on-disk layout: stage-0 checkpoints load under zero and
    vice versa — sharding is a runtime property, not a disk format.  Every
    case here runs at dp=2 against the dp=1 reference AND crosses stages,
    so the comparison is to a few float32 ulps, not bitwise (see
    ``_assert_continues_like_reference``)."""
    got, _ = _ckpt_roundtrip(tmp_path, 2, 2, save_stage=save_stage,
                             load_stage=load_stage)
    _assert_continues_like_reference(got, 2)


def test_zero2_fit_resume_matches_stage0_resume(tmp_path):
    """Supervisor-style resume parity: interrupt a fit at step 4, restart
    with resume=True — the zero-2 continuation is bitwise-equal to a
    stage-0 run interrupted and resumed the same way.  (Both are compared
    post-resume: a fresh trainer re-anchors its bucket ladder on the first
    batch it sees, so interrupted-vs-straight-through runs can differ by
    reduction order within a padded bucket — a ladder property, not a
    zero property.)"""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    data = [DataSet(x, y) for x, y in _data(8)]

    def interrupted(stage, root):
        mgr = CheckpointManager(root, keep=2)
        tr1 = DataParallelTrainer(_loss, _momentum(), zero_stage=stage)
        stopped = tr1.fit(tr1.init_state(_params()), data,
                          checkpoint_manager=mgr, resume=True,
                          async_dispatch=False,
                          should_stop=lambda step: step >= 4)
        assert stopped[0].step == 4
        tr2 = DataParallelTrainer(_loss, _momentum(), zero_stage=stage)
        s2, l2 = tr2.fit(tr2.init_state(_params()), data,
                         checkpoint_manager=mgr, resume=True,
                         async_dispatch=False)
        assert s2.step == len(data)
        return np.array(l2), jax.device_get(tr2.final_params(s2))

    l_zero, p_zero = interrupted(2, tmp_path / "zero2")
    l_rep, p_rep = interrupted(0, tmp_path / "stage0")
    np.testing.assert_array_equal(l_zero, l_rep)
    for k in p_rep:
        np.testing.assert_array_equal(p_zero[k], p_rep[k])


# ------------------------------------------- checkpoints under the per-leaf rule
def _mixed_ckpt(tmp_path, name, stage, width, steps=2, layout="natural"):
    tr = _mixed_trainer(stage, width)
    state = tr.init_state(_mixed_params())
    for x, y in _mixed_data(steps):
        state, _ = tr.step(state, x, y)
    mgr = CheckpointManager(tmp_path / name, keep=2)
    tr.checkpoint(state, mgr, layout=layout)
    return tr, state, mgr


def _payload(mgr):
    d = sorted(mgr.directory.glob("ckpt_*"))[-1]
    return {f"{f}:{k}": v for f in ("params.npz", "tstate.npz")
            for k, v in np.load(d / f).items()}


def _assert_payloads_identical(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert a[k].shape == b[k].shape and a[k].dtype == b[k].dtype, k
        assert a[k].tobytes() == b[k].tobytes(), k


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_natural_payload_is_the_replicated_save_byte_for_byte(tmp_path,
                                                                   stage):
    """The on-disk format did not move: a sharded trainer's natural save has
    the keys, shapes and dtypes of a replicated trainer's (its values to the
    rounding the two steps differ by), and a replicated trainer that
    restores it writes the same bytes back."""
    _, _, rep = _mixed_ckpt(tmp_path, "rep", 0, 4)
    _, _, shd = _mixed_ckpt(tmp_path, f"zero{stage}", stage, 4)
    want, got = _payload(rep), _payload(shd)
    assert got.keys() == want.keys()
    for k, v in got.items():
        assert (v.shape, v.dtype) == (want[k].shape, want[k].dtype), k
        assert v.shape == MIXED_SHAPES[k.rsplit("'", 2)[-2]], k
        np.testing.assert_allclose(v, want[k], **REPLICATED_TOL)
    plain = _mixed_trainer(0, 4)
    restored = plain.restore(plain.init_state(_mixed_params()), shd)
    out = CheckpointManager(tmp_path / "out", keep=2)
    plain.checkpoint(restored, out)
    _assert_payloads_identical(_payload(out), got)


@pytest.mark.parametrize("save_dp,load_dp", [(4, 2), (4, 1), (2, 4), (1, 4)])
def test_zero1_mixed_checkpoint_restores_across_dp_widths(tmp_path, save_dp,
                                                          load_dp):
    """Saved at one width under stage 1, restored at another: which leaves
    split is decided anew for the restoring width, the restored state is
    the saved one."""
    _, _, mgr = _mixed_ckpt(tmp_path, "src", 1, save_dp)
    dst = _mixed_trainer(1, load_dp)
    restored = dst.restore(dst.init_state(_mixed_params()), mgr)
    out = CheckpointManager(tmp_path / "out", keep=2)
    dst.checkpoint(restored, out)
    _assert_payloads_identical(_payload(out), _payload(mgr))
    z = dst._zero
    for k, leaf in restored.tstate[0][0].items():
        shape = MIXED_SHAPES[k]
        assert leaf.shape == (shape if z.splits(shape) else
                              (z.padded_size(int(np.prod(shape))),))
        assert leaf.sharding.spec == P(DP)


@pytest.mark.parametrize("load_dp", [4, 2])
def test_zero_restores_the_parents_flat_padded_checkpoint(tmp_path, load_dp):
    """Before PR 31 ``layout="flat"`` wrote EVERY state leaf as a 1-D vector
    padded to the save-side width.  Such a checkpoint (rebuilt here from a
    natural one with the same host arithmetic) still restores, at its own
    width and across widths, to the state the natural one restores to."""
    src, state, nat = _mixed_ckpt(tmp_path, "nat", 1, 4)
    flat_t = jax.tree.map(lambda a: host_natural_to_flat(a, 4),
                          src._zero.to_natural_host(state.tstate,
                                                    src._zero.natural_tstate))
    assert all(a.ndim == 1 and a.shape[0] % 4 == 0
               for a in jax.tree.leaves(flat_t))
    old = CheckpointManager(tmp_path / "old", keep=2)
    old.save(state.step, jax.device_get(state.params), tstate=flat_t,
             key=state.key, data_cursor=state.step,
             extra={"zero_stage": 1, "saved_dp": 4}, dp_width=4,
             zero_stage=1, layout="flat")
    dst = _mixed_trainer(1, load_dp)
    restored = dst.restore(dst.init_state(_mixed_params()), old)
    out = CheckpointManager(tmp_path / "out", keep=2)
    dst.checkpoint(restored, out)
    _assert_payloads_identical(_payload(out), _payload(nat))
    # today's flat save: leaves that split are written in their own shape
    _, _, flat_now = _mixed_ckpt(tmp_path, "flatnow", 1, 4, layout="flat")
    shapes = {k: v.shape for k, v in _payload(flat_now).items()
              if k.startswith("tstate") and ("'w'" in k or "'o'" in k)}
    assert set(shapes.values()) == {(32, 128), (640,)}
    again = _mixed_trainer(1, load_dp)
    r2 = again.restore(again.init_state(_mixed_params()), flat_now)
    out2 = CheckpointManager(tmp_path / "out2", keep=2)
    again.checkpoint(r2, out2)
    _assert_payloads_identical(_payload(out2), _payload(nat))
