"""MultiLayerNetwork — the training container.

TPU-native re-design of ``nn/multilayer/MultiLayerNetwork.java:45-1596``:
build a layer stack from a ``MultiLayerConfiguration``, greedy layerwise
``pretrain`` (``:115-199``), supervised ``finetune`` (``:996-1048``),
``feedForward``/``output``/``predict``/``score``/``evaluate``
(``:408-474,1058-1169``), parameter flatten/unflatten (``:744-788``), and
``merge`` parameter averaging (``:1302``).

Architecture notes (TPU-first, not a translation):
- params are a tuple of per-layer dicts (a pytree); the whole supervised
  train step — forward, loss, backward (autodiff), gradient post-processing,
  update — is ONE jitted function, compiled once per (shape, mesh).  The
  reference's per-iteration Java loop with hand-written deltas
  (``computeDeltas:611-670``) becomes `jax.value_and_grad` inside that step;
- pretrain steps are likewise jitted per layer (CD-k sampling runs under
  `lax.scan` with threefry keys);
- second-order finetuning (CG/LBFGS/Hessian-free) dispatches to the L2
  solvers, whose curvature products use `jax.jvp` over `jax.grad`
  (replacing ``feedForwardR/computeDeltasR/backPropGradientR:1415-1487``);
- data-parallel training over a `jax.sharding.Mesh` is available via
  ``parallel.trainer`` which shards the same step with `pjit` (parameter
  averaging ≡ gradient `pmean` implied by sharded batch + replicated params).
"""

from __future__ import annotations

import logging
import pickle
import time
from pathlib import Path
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..datasets.dataset import DataSet, to_outcome_matrix
from ..evaluation import Evaluation
from ..observability import METRICS, enabled as _obs_enabled, trace
from ..optimize import transforms as tfm
from ..parallel.compile_cache import setup_compile_cache
from ..utils import tree_math as tm
from .conf import LayerKind, MultiLayerConfiguration, OptimizationAlgorithm
from .layers import (
    BasePretrainLayer,
    Layer,
    OutputLayer,
    create_layer,
    merge_params,
)

log = logging.getLogger(__name__)

Params = tuple[dict[str, jnp.ndarray], ...]

# OutputPreProcessor registry (reference: ``nn/conf/preprocessor/
# ReshapePreProcessor`` + ``nn/layers/convolution/preprocessor/*``): named
# transforms applied to a layer's OUTPUT before the next layer.
PREPROCESSORS: dict[str, Callable] = {
    "flatten": lambda h: h.reshape(h.shape[0], -1),
    "none": lambda h: h,
}


class MultiLayerNetwork:
    """Layer stack + training orchestration."""

    def __init__(self, conf: MultiLayerConfiguration, *, score_every: int = 1):
        self.conf = conf
        self.layers: list[Layer] = [create_layer(c) for c in conf.confs]
        self.params: Params | None = None
        self._tstates: list[Any] | None = None
        self.listeners: list = []
        self._jit_cache: dict = {}
        self._score = float("nan")
        # How often pretrain/finetune sync the on-device loss into the host
        # ``_score`` float.  1 (default) keeps the reference per-iteration
        # behavior; larger values keep the hot loop asynchronous — jax only
        # runs ahead of the device if nothing forces a device->host read.
        # The final iteration always syncs, so ``score()`` stays correct.
        self.score_every = max(1, int(score_every))
        setup_compile_cache()  # persistent XLA cache (compile_cache.py)

    # ------------------------------------------------------------------ init
    def init(self, key=None) -> Params:
        """``MultiLayerNetwork.init():284-339`` — build all param tables."""
        key = key if key is not None else jax.random.key(self.conf.confs[0].seed)
        keys = jax.random.split(key, len(self.layers))
        self.params = tuple(l.init(k) for l, k in zip(self.layers, keys))
        self._tstates = None
        self._jit_cache.clear()
        return self.params

    def _ensure_init(self):
        if self.params is None:
            self.init()

    # ------------------------------------------------------------------ forward
    def _preproc(self, i: int, h):
        """Apply layer i's OutputPreProcessor (``feedForward:419-421``)."""
        name = self.conf.preprocessors.get(i)
        return PREPROCESSORS[name](h) if name else h

    def feed_forward_fn(self, params: Params, x, rng=None, train: bool = False):
        """Pure forward returning all activations (``feedForward:408-474``)."""
        acts = [x]
        rngs = (jax.random.split(rng, len(self.layers))
                if rng is not None else [None] * len(self.layers))
        h = x
        for i, (layer, p, r) in enumerate(zip(self.layers, params, rngs)):
            h = self._preproc(i, layer.activate(p, h, rng=r, train=train))
            acts.append(h)
        return acts

    def _forward(self, params: Params, x):
        h = x
        for i, (layer, p) in enumerate(zip(self.layers, params)):
            h = self._preproc(i, layer.activate(p, h))
        return h

    def feed_forward(self, x) -> list:
        self._ensure_init()
        return self.feed_forward_fn(self.params, jnp.asarray(x))

    def output(self, x) -> jnp.ndarray:
        """Probabilities/activations of the final layer (``output:1140``)."""
        self._ensure_init()
        fn = self._jit_cache.get("output")
        if fn is None:
            fn = jax.jit(self._forward)
            self._jit_cache["output"] = fn
        return fn(self.params, jnp.asarray(x))

    def predict(self, x) -> np.ndarray:
        """Argmax class per row (``predict:1058-1062``)."""
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    def label_probabilities(self, x) -> jnp.ndarray:
        return self.output(x)

    def reconstruct(self, x, layer_idx: int) -> jnp.ndarray:
        """Activations at layer ``layer_idx`` (``reconstruct:1152-1169``)."""
        acts = self.feed_forward(x)
        return acts[layer_idx]

    # ------------------------------------------------------------------ loss
    def supervised_loss(self, params: Params, x, labels, rng=None, train: bool = False):
        out_layer = self.layers[-1]
        h = x
        rngs = (jax.random.split(rng, len(self.layers))
                if rng is not None else [None] * len(self.layers))
        for i, (layer, p, r) in enumerate(zip(self.layers[:-1], params[:-1], rngs[:-1])):
            h = self._preproc(i, layer.activate(p, h, rng=r, train=train))
        if hasattr(out_layer, "loss"):  # OutputLayer, LSTM, or any loss-bearing tail
            return out_layer.loss(params[-1], h, labels)
        raise TypeError(f"final layer {type(out_layer).__name__} has no loss")

    def score(self, data: DataSet | None = None) -> float:
        """``score()`` — supervised loss on the given/last batch."""
        if data is not None:
            self._ensure_init()
            self._score = float(self.supervised_loss(
                self.params, jnp.asarray(data.features), jnp.asarray(data.labels)))
        return self._score

    # ------------------------------------------------------------------ pretrain
    def pretrain(self, data_or_iter, key=None) -> None:
        """Greedy layerwise pretraining (``pretrain:115-199``): feed inputs
        through layers [0..i-1], then run layer i's unsupervised objective.
        Each (layer, shape) gets one jitted update step; AdaGrad/momentum
        state threads through the loop on-device."""
        self._ensure_init()
        key = key if key is not None else jax.random.key(self.conf.confs[0].seed + 7)
        batches = self._as_batches(data_or_iter)
        for i, layer in enumerate(self.layers):
            if not isinstance(layer, BasePretrainLayer):
                continue
            conf = layer.conf
            with trace.span("multilayer.pretrain_layer", layer=i,
                            kind=conf.kind.value):
                transform = tfm.from_conf(conf)
                step = self._pretrain_step(i, layer, transform)
                lparams = self.params[i]
                tstate = transform.init(lparams)
                loss = None
                for b, batch in enumerate(batches):
                    x = jnp.asarray(batch.features)
                    # inputs to layer i are fixed while layer i trains
                    inp = self._forward_to(i, x)
                    for it in range(max(1, conf.num_iterations)):
                        key, sub = jax.random.split(key)
                        lparams, tstate, loss = step(lparams, tstate, inp, sub,
                                                     jnp.asarray(it))
                    # syncing the score is a device->host read; keep it off
                    # the hot loop unless asked for every batch
                    if (b + 1) % self.score_every == 0:
                        self._score = float(loss)
                if loss is not None:
                    self._score = float(loss)
                new_params = list(self.params)
                new_params[i] = lparams
                self.params = tuple(new_params)
            METRICS.increment("multilayer.pretrain_layers")
            log.info("pretrained layer %d (%s) score %.5f", i, conf.kind.value, self._score)

    def _forward_to(self, i: int, x):
        """Inputs to layer i = activations of layers [0..i-1]."""
        fn = self._jit_cache.get(("fwd_to", i))
        if fn is None:
            def forward_to(params, x):
                h = x
                for j, (layer, p) in enumerate(zip(self.layers[:i], params[:i])):
                    h = self._preproc(j, layer.activate(p, h))
                return h
            fn = jax.jit(forward_to)
            self._jit_cache[("fwd_to", i)] = fn
        return fn(self.params, x)

    def _pretrain_step(self, i: int, layer: BasePretrainLayer, transform):
        cache_key = ("pretrain_step", i)
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            def step(lparams, tstate, x, key, iteration):
                loss, grads = layer.pretrain_value_and_grad(lparams, x, key)
                updates, tstate = transform.update(grads, tstate, lparams, iteration)
                lparams = tfm.apply_updates(lparams, updates)
                return lparams, tstate, loss
            fn = jax.jit(step)
            self._jit_cache[cache_key] = fn
        return fn

    # ------------------------------------------------------------------ finetune
    def finetune(self, data_or_iter, key=None) -> None:
        """Supervised training of the whole stack (``finetune:996-1048``).

        First-order algos run the jitted minibatch step; CG/LBFGS/HF
        dispatch to the L2 solvers on the batch objective.
        """
        self._ensure_init()
        out_conf = self.layers[-1].conf
        key = key if key is not None else jax.random.key(out_conf.seed + 13)
        batches = self._as_batches(data_or_iter)
        algo = out_conf.optimization_algo
        if algo in (OptimizationAlgorithm.GRADIENT_DESCENT,
                    OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT):
            self._finetune_first_order(batches, key)
        else:
            self._finetune_solver(batches, key, algo)

    def _finetune_first_order(self, batches: Sequence[DataSet], key) -> None:
        out_conf = self.layers[-1].conf
        transform = tfm.from_conf(out_conf)
        step = self._train_step(transform)
        tstate = (self._tstates if self._tstates is not None
                  else transform.init(self.params))
        it = 0
        loss = None
        n_total = len(batches) * max(1, out_conf.num_iterations)
        for batch in batches:
            x, y = jnp.asarray(batch.features), jnp.asarray(batch.labels)
            for _ in range(max(1, out_conf.num_iterations)):
                obs = _obs_enabled()
                t0 = time.perf_counter() if obs else 0.0
                key, sub = jax.random.split(key)
                # Rebind self.params/self._tstates IMMEDIATELY: the step
                # donates its inputs, so the previous buffers are dead the
                # moment it returns — listeners (which may call output())
                # and crash recovery must see the fresh ones.
                self.params, tstate, loss = step(
                    self.params, tstate, x, y, sub, jnp.asarray(it))
                self._tstates = tstate
                it += 1
                # ``float(loss)`` is a device->host sync that stalls jax's
                # async dispatch; only pay it every ``score_every`` steps
                # (and on the last step, so ``score()``/the loss gauge end
                # correct).  score_every=1 is exactly the old behavior.
                if it % self.score_every == 0 or it == n_total:
                    self._score = float(loss)
                    if obs:
                        METRICS.gauge("multilayer.loss", self._score)
                if obs:
                    METRICS.observe_time("multilayer.fit_iteration",
                                         time.perf_counter() - t0)
                    METRICS.increment("multilayer.iterations")
                for l in self.listeners:
                    l.iteration_done(self, it)

    def _train_step(self, transform):
        fn = self._jit_cache.get("train_step")
        if fn is None:
            def step(params, tstate, x, y, key, iteration):
                loss, grads = jax.value_and_grad(self.supervised_loss)(
                    params, x, y, rng=key, train=True)
                updates, tstate = transform.update(grads, tstate, params, iteration)
                params = tfm.apply_updates(params, updates)
                return params, tstate, loss
            fn = jax.jit(step, donate_argnums=(0, 1))
            self._jit_cache["train_step"] = fn
        return fn

    def _finetune_solver(self, batches: Sequence[DataSet], key, algo) -> None:
        from ..optimize.solvers import Solver  # deferred: avoids import cycle
        # Mini-batch mode: the solver cycles batches across outer iterations
        # instead of the r4 DataSet.merge of the whole corpus — DEVICE
        # memory is bounded by one batch (batches stay host-side numpy;
        # each jitted call transfers only the iteration's batch).  Keeping
        # shapes uniform (batch_by pads nothing, so the tail batch may
        # recompile once) bounds compilation at two variants.
        data = [(np.asarray(b.features), np.asarray(b.labels))
                for b in batches]

        def objective(params, k, x, y):
            return jax.value_and_grad(self.supervised_loss)(params, x, y)

        out_conf = self.layers[-1].conf
        extra = {}
        if algo == OptimizationAlgorithm.HESSIAN_FREE:
            # Gauss-Newton split (the reference CGs on GN products,
            # StochasticHessianFree.java:27): predict = network up to the
            # final pre-activation z; loss_out = convex loss of z.
            from ..ops import activations as _act
            from ..ops import losses as _losses

            def predict(params, k, x, y):
                h = jnp.asarray(x)
                for i, (layer, p) in enumerate(zip(self.layers[:-1], params[:-1])):
                    h = self._preproc(i, layer.activate(p, h))
                return self.layers[-1].pre_output(params[-1], h)

            def loss_out(z, x, y):
                return _losses.score(out_conf.loss, y,
                                     _act.apply(out_conf.activation, z))

            extra = {"damping": self.conf.damping_factor,
                     "gauss_newton": (predict, loss_out)}
        solver = Solver(out_conf, objective, listeners=self.listeners,
                        batches=data, **extra)
        result = solver.optimize(self.params, key)
        self.params = result.params
        self._score = result.score

    # ------------------------------------------------------------------ fit
    def fit(self, data_or_iter, key=None) -> "MultiLayerNetwork":
        """``fit = pretrain + finetune`` (``fit:985-1022``)."""
        self._ensure_init()
        k_pre = k_fine = None
        if key is not None:
            k_pre, k_fine = jax.random.split(key)
        with trace.span("multilayer.fit", n_layers=len(self.layers)):
            if self.conf.pretrain:
                with trace.span("multilayer.pretrain"):
                    self.pretrain(data_or_iter, k_pre)
            if self.conf.backprop:
                with trace.span("multilayer.finetune"):
                    self.finetune(data_or_iter, k_fine)
        return self

    def fit_arrays(self, features, labels_or_idx, key=None) -> "MultiLayerNetwork":
        """Classifier.fit(x, labels) — int labels become one-hot
        (``MultiLayerNetwork.java:1127`` FeatureUtil.toOutcomeMatrix)."""
        labels = np.asarray(labels_or_idx)
        if labels.ndim == 1:
            labels = to_outcome_matrix(labels, self.layers[-1].conf.n_out)
        return self.fit(DataSet(np.asarray(features), labels), key)

    def _as_batches(self, data_or_iter) -> list[DataSet]:
        if isinstance(data_or_iter, DataSet):
            bs = self.layers[-1].conf.batch_size
            return data_or_iter.batch_by(bs) if bs > 0 else [data_or_iter]
        return list(data_or_iter)

    # ------------------------------------------------------------------ eval
    def evaluate(self, data_or_iter) -> Evaluation:
        ev = Evaluation()
        with trace.span("multilayer.evaluate"):
            for batch in self._as_batches(data_or_iter):
                ev.eval(batch.labels, np.asarray(self.output(batch.features)))
                METRICS.increment("evaluate.batches")
        return ev

    # ------------------------------------------------------------------ params plumbing
    def params_flat(self) -> jnp.ndarray:
        """Flatten all params (``params():744-788``) in layer/key order."""
        self._ensure_init()
        return jnp.concatenate([
            layer.flatten(p) for layer, p in zip(self.layers, self.params)])

    def set_params_flat(self, flat) -> None:
        self._ensure_init()
        flat = jnp.asarray(flat)
        out, off = [], 0
        for layer, p in zip(self.layers, self.params):
            n = layer.n_params(p)
            out.append(layer.unflatten(flat[off:off + n], p))
            off += n
        self.params = tuple(out)

    def num_params(self) -> int:
        self._ensure_init()
        return sum(l.n_params(p) for l, p in zip(self.layers, self.params))

    def merge(self, *others: "MultiLayerNetwork") -> None:
        """Parameter averaging with peers (``merge:1302``; DP aggregation)."""
        self._ensure_init()
        all_params = [self.params] + [o.params for o in others]
        self.params = merge_params(all_params)

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(self.conf)
        if self.params is not None:
            net.params = jax.tree_util.tree_map(lambda x: x, self.params)
        return net

    # ------------------------------------------------------------------ persistence
    def save(self, path: str | Path) -> None:
        """Config JSON + params npz in one pickle envelope (replaces the
        reference's Java serialization ``SerializationUtils``)."""
        payload = {
            "conf_json": self.conf.to_json(),
            "params": None if self.params is None else
            [{k: np.asarray(v) for k, v in p.items()} for p in self.params],
        }
        with open(path, "wb") as f:
            pickle.dump(payload, f)

    @classmethod
    def load(cls, path: str | Path) -> "MultiLayerNetwork":
        with open(path, "rb") as f:
            payload = pickle.load(f)
        net = cls(MultiLayerConfiguration.from_json(payload["conf_json"]))
        if payload["params"] is not None:
            net.params = tuple({k: jnp.asarray(v) for k, v in p.items()}
                               for p in payload["params"])
        return net
