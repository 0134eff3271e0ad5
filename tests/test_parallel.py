"""Distributed-layer tests on the virtual 8-device CPU mesh — the
"distributed-without-a-cluster" pattern (SURVEY.md §4 item 4,
``BaseTestDistributed``): the REAL collectives/trainer stack in one process.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet, IrisDataSetIterator
from deeplearning4j_tpu.nn import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, list_builder
from deeplearning4j_tpu.optimize import transforms as tfm
from deeplearning4j_tpu.parallel import (
    CheckpointManager,
    DataParallelTrainer,
    MeshSpec,
    local_mesh,
    make_mesh,
)
from deeplearning4j_tpu.parallel.mesh import DP, TP, batch_sharding, replicated
from deeplearning4j_tpu.parallel import collectives as coll


def test_virtual_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_mesh_spec_resolution():
    spec = MeshSpec(dp=-1, tp=2)
    sizes = spec.resolve(8)
    assert sizes["dp"] == 4 and sizes["tp"] == 2
    with pytest.raises(ValueError):
        MeshSpec(dp=3, tp=2).resolve(8)


def test_make_mesh_axes():
    mesh = make_mesh(MeshSpec(dp=4, tp=2))
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    assert mesh.axis_names == ("pp", "dp", "sp", "tp", "ep")


def test_collectives_via_shard_map():
    try:
        from jax import shard_map           # jax >= 0.8
    except ImportError:
        from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = local_mesh()

    def f(x):
        return coll.pmean(x, DP), coll.psum(x, DP)

    fm = shard_map(f, mesh=mesh, in_specs=(P(DP),), out_specs=(P(DP), P(DP)))
    x = jnp.arange(8.0)
    mean, total = fm(x)
    np.testing.assert_allclose(np.asarray(mean), np.full(8, x.mean()), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.full(8, x.sum()), rtol=1e-6)


def test_ring_shift():
    try:
        from jax import shard_map           # jax >= 0.8
    except ImportError:
        from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = local_mesh()
    fm = shard_map(lambda x: coll.ring_shift(x, DP, 8, 1), mesh=mesh,
                   in_specs=(P(DP),), out_specs=P(DP))
    x = jnp.arange(8.0)
    shifted = fm(x)
    # ppermute (i -> i+1): value from shard i lands on shard i+1
    np.testing.assert_allclose(np.asarray(shifted), np.roll(np.arange(8.0), 1))


def _iris_net():
    base = NeuralNetConfiguration(n_in=4, n_out=3, lr=0.1, use_adagrad=True,
                                  momentum=0.9, activation="tanh")
    conf = (list_builder(base, 2).hidden_layer_sizes(10)
            .override(1, kind="output", activation="softmax", loss="mcxent")
            .pretrain(False).build())
    net = MultiLayerNetwork(conf)
    net.init(jax.random.key(0))
    return net


def _iris_data():
    return (IrisDataSetIterator(batch=150).next()
            .normalize_zero_mean_unit_variance().shuffle(seed=3))


def test_data_parallel_iterative_reduce_trains():
    """Sync DP over 8 virtual chips reaches F1>=0.9 on Iris — parity with the
    reference's parameter-averaging path, but as one pjit'd step."""
    net = _iris_net()
    ds = _iris_data()
    trainer = DataParallelTrainer(
        loss_fn=lambda p, x, y, k: net.supervised_loss(p, x, y, rng=k, train=True),
        transform=tfm.from_conf(net.layers[-1].conf),
        router="iterative_reduce")
    state = trainer.init_state(net.params)
    for _ in range(150):
        state, loss = trainer.step(state, ds.features, ds.labels)
    net.params = trainer.final_params(state)
    assert net.evaluate(ds).f1() >= 0.9


def test_data_parallel_hogwild_trains():
    """Local-SGD/periodic-averaging (HogWild approximation) also converges."""
    net = _iris_net()
    ds = _iris_data()
    trainer = DataParallelTrainer(
        loss_fn=lambda p, x, y, k: net.supervised_loss(p, x, y, rng=k, train=True),
        transform=tfm.from_conf(net.layers[-1].conf),
        router="hogwild", average_every=4)
    state = trainer.init_state(net.params)
    for _ in range(150):
        state, loss = trainer.step(state, ds.features, ds.labels)
    net.params = trainer.final_params(state)
    assert net.evaluate(ds).f1() >= 0.85


@pytest.mark.strict_dtypes
def test_sync_matches_single_device_math():
    """One sync-DP step with the full batch == one single-device step on the
    same batch (parameter averaging over equal shards ≡ full-batch gradient).
    Runs under strict dtype promotion: the parity claim is about the same
    arithmetic, so no implicit widening may sneak into either side."""
    net = _iris_net()
    ds = _iris_data()
    x, y = jnp.asarray(ds.features[:64]), jnp.asarray(ds.labels[:64])
    loss_fn = lambda p, x_, y_, k: net.supervised_loss(p, x_, y_)
    transform = tfm.sgd_lr(0.1)

    trainer = DataParallelTrainer(loss_fn, transform, router="iterative_reduce")
    state = trainer.init_state(net.params)
    state, _ = trainer.step(state, x, y)

    loss, grads = jax.value_and_grad(lambda p: net.supervised_loss(p, x, y))(net.params)
    expected = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, net.params, grads)
    got_w = np.asarray(state.params[0]["W"])
    np.testing.assert_allclose(got_w, np.asarray(expected[0]["W"]), atol=1e-5)


def test_dp8_train_step_hlo_holds_the_gradient_all_reduce():
    """The transformer's train step over a dp=8 mesh, compiled: the
    gradients' all-reduce is in the program (a step that dropped it would
    still run, each shard on its own gradients)."""
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = TransformerConfig(vocab_size=512, d_model=128, n_heads=4,
                            n_layers=2, d_ff=512, max_len=128, causal=False,
                            dtype=jnp.float32, remat=False)
    model = TransformerLM(cfg, mesh=make_mesh(MeshSpec(dp=8, sp=1, tp=1),
                                              devices=jax.devices()[:8]))
    tx = tfm.chain(tfm.momentum(0.9), tfm.sgd_lr(1e-3))
    params = model.place(model.init(jax.random.key(0)))
    opt = model.init_opt(params, tx)
    tokens = jax.random.randint(jax.random.key(1), (32, 128), 0,
                                cfg.vocab_size)
    step = model.build_train_step(tx).lower(
        params, opt, tokens, jnp.roll(tokens, -1, axis=1)).compile()
    assert "all-reduce" in step.as_text()


def test_checkpoint_roundtrip(tmp_path):
    net = _iris_net()
    transform = tfm.from_conf(net.layers[-1].conf)
    tstate = transform.init(net.params)
    mgr = CheckpointManager(tmp_path, keep=2)
    key = jax.random.key(9)
    mgr.save(5, net.params, tstate, key, data_cursor=42)
    mgr.save(10, net.params, tstate, key, data_cursor=84)
    mgr.save(15, net.params, tstate, key, data_cursor=99)
    assert mgr.all_steps() == [10, 15]  # keep=2 rotation
    restored = mgr.restore(net.params, tstate)
    assert restored["step"] == 15 and restored["data_cursor"] == 99
    np.testing.assert_allclose(np.asarray(restored["params"][0]["W"]),
                               np.asarray(net.params[0]["W"]))
    assert restored["key"] is not None
    # restored tstate drives the same update math
    assert jax.tree_util.tree_structure(restored["tstate"]) == \
        jax.tree_util.tree_structure(tstate)
