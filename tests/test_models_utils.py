"""Model zoo + utility tests (mirror of LSTMTest beam search, MNIST conv
test, MathUtils/serialization/moving-window util tests)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.datasets import DataSet, DigitsDataSetIterator
from deeplearning4j_tpu.models import (
    LSTMSequenceModel,
    ResNet,
    ResNetConfig,
    dbn,
    lenet,
    mlp,
    stacked_denoising_autoencoder,
)
from deeplearning4j_tpu.utils import (
    Counter,
    CounterMap,
    DiskBasedQueue,
    Index,
    SummaryStatistics,
    Viterbi,
    viterbi_decode,
)
from deeplearning4j_tpu.utils.misc import (
    entropy,
    moving_window_matrix,
    read_object,
    save_object,
)


def digits_ds(n=500):
    it = DigitsDataSetIterator(batch=n)
    return it.next().shuffle(seed=0)


def test_mlp_on_digits():
    ds = digits_ds()
    net = mlp(64, 10, hidden=(48,), num_iterations=150)
    net.init(jax.random.key(0))
    net.fit(ds)
    assert net.evaluate(ds).f1() > 0.85


def test_lenet_trains_on_digit_images():
    it = DigitsDataSetIterator(batch=300, flatten=False)
    ds = it.next()
    net = lenet(n_classes=10, input_side=8, num_filters=4, filter_size=(3, 3),
                pool=(2, 2), num_iterations=200, lr=0.1)
    net.init(jax.random.key(0))
    net.fit(ds)
    ev = net.evaluate(ds)
    assert ev.accuracy() > 0.7, ev.stats()


def test_sda_pretrains_and_finetunes():
    ds = digits_ds(300).scale_minmax()
    net = stacked_denoising_autoencoder(
        64, 10, hidden=(32,), pretrain_iterations=40, finetune_iterations=150)
    net.init(jax.random.key(0))
    net.fit(ds)
    assert net.evaluate(ds).f1() > 0.8


def test_lstm_model_learns_and_beam_search():
    seq = np.array(([0, 1, 2, 3] * 10), np.int32)
    model = LSTMSequenceModel(vocab_size=4, hidden_size=24, lr=0.3)
    model.init()
    losses = model.fit_sequence(seq, epochs=120)
    assert losses[-1] < losses[0] * 0.4
    assert model.predict_next([0, 1, 2]) == 3
    decoded, score = model.beam_search([0, 1], length=4, beam_width=3)
    assert decoded[2:] == [2, 3, 0, 1]
    sampled = model.sample([0], length=5, temperature=0.3, seed=1)
    assert len(sampled) == 6


def test_resnet18_forward_and_grad():
    cfg = ResNetConfig.resnet18(num_classes=5, width=8, dtype=jnp.float32)
    model = ResNet(cfg)
    model.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    logits = model.predict_logits(x)
    assert logits.shape == (2, 5)
    y = jax.nn.one_hot(jnp.array([0, 3]), 5)
    g = jax.jit(jax.grad(lambda p: model.loss_fn()(p, x, y)))(model.params)
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in flat)
    assert any(float(jnp.max(jnp.abs(l))) > 0 for l in flat)


def test_viterbi_recovers_smooth_path():
    # emissions strongly favor [0,0,1,1] with one noisy step
    em = np.array([[0.9, 0.1], [0.8, 0.2], [0.45, 0.55], [0.1, 0.9]])
    v = Viterbi([0, 1], transition_prob=0.8)
    assert v.decode(em) == [0, 0, 1, 1]
    path, score = viterbi_decode(np.log(em), np.log(np.array([[0.8, 0.2], [0.2, 0.8]])))
    assert path.tolist() == [0, 0, 1, 1]


def test_counters():
    c = Counter(["a", "b", "a"])
    assert c.get_count("a") == 2
    assert c.argmax() == "a"
    c.normalize()
    assert c.total_count() == pytest.approx(1.0)
    cm = CounterMap()
    cm.increment("x", "y", 2.0)
    assert cm.get_count("x", "y") == 2.0
    idx = Index(["w1", "w2"])
    assert idx.index_of("w2") == 1
    assert idx.get(0) == "w1"
    assert idx.add("w1") == 0 and len(idx) == 2


def test_summary_statistics_and_entropy():
    s = SummaryStatistics()
    s.add_all([1.0, 2.0, 3.0, 4.0])
    assert s.mean == pytest.approx(2.5)
    assert s.std == pytest.approx(np.std([1, 2, 3, 4], ddof=1))
    assert entropy([0.5, 0.5]) == pytest.approx(np.log(2))


def test_disk_based_queue(tmp_path):
    q = DiskBasedQueue(tmp_path, memory_window=3)
    for i in range(10):
        q.add(i)
    assert len(q) == 10
    assert [q.poll() for _ in range(10)] == list(range(10))
    assert q.is_empty()


def test_moving_window_and_serialization(tmp_path):
    m = np.arange(16).reshape(4, 4)
    wins = moving_window_matrix(m, 2, 2)
    assert wins.shape == (4, 4)
    wins_rot = moving_window_matrix(m, 2, 2, add_rotations=True)
    assert wins_rot.shape == (16, 4)
    save_object({"a": 1}, tmp_path / "o.pkl")
    assert read_object(tmp_path / "o.pkl") == {"a": 1}


def test_preprocessor_serde_roundtrip():
    net = lenet(n_classes=10, input_side=8, num_filters=2, filter_size=(3, 3))
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
    back = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert back.preprocessors == {0: "flatten"}


def test_resnet_s2d_stem_matches_plain_stem():
    """The space-to-depth stem (4x4/1 conv on a 2x2-s2d input) is an exact
    rearrangement of the 7x7/2 conv — logits must match the plain stem to
    summation-order noise."""
    import dataclasses

    from deeplearning4j_tpu.models.resnet import forward, init_params

    cfg = ResNetConfig.resnet18(num_classes=5, width=8, dtype=jnp.float32)
    assert cfg.stem_space_to_depth
    params = init_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    fwd = jax.jit(forward, static_argnums=2)
    with jax.default_matmul_precision("highest"):
        y_s2d = fwd(params, x, cfg)
        y_plain = fwd(params, x, dataclasses.replace(cfg, stem_space_to_depth=False))
    # stem outputs agree to ~1e-6; batch-norm rsqrt amplifies that
    # summation-order noise through the stack, hence the loose logit atol
    np.testing.assert_allclose(np.asarray(y_s2d), np.asarray(y_plain),
                               atol=2e-3)
