"""ZeRO sharded weight update (DESIGN.md §15): parity, layout, portability.

Pins the PR's acceptance criteria:
- stage parity: zero_stage 1/2/3 produce BITWISE-equal losses and params
  to the replicated stage-0 step on the CPU mesh, same data/seed — the
  sharded update is a layout change, not a numerics change,
- memory: optimizer-state bytes/device shrink ~1/ndp vs replicated
  (within flatten-padding tolerance), visible through the
  ``train.opt_state_bytes`` gauges,
- sharded layout: state leaves are 1-D chunks placed with a dp
  ``NamedSharding``; stage 3 additionally keeps params sharded between
  steps,
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
from deeplearning4j_tpu.parallel.zero import ZeroLayout

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
