"""ResNet — the second north-star model family (BASELINE.json: ResNet-50
ImageNet).

TPU-first: NHWC layout, bf16 MXU compute with f32 params, batch-norm with
batch statistics (training) folded next to convs for XLA fusion, and the
data-parallel path through ``parallel.trainer`` (batch sharded on dp,
XLA-inserted gradient all-reduce).  Functional init/apply like ``nn.layers``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    num_classes: int = 1000
    stage_sizes: tuple[int, ...] = (3, 4, 6, 3)   # ResNet-50
    width: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    bn_fold: bool = False  # apply BN as a folded per-channel affine in the
    #                        compute dtype (stats still f32): elementwise
    #                        reads/writes drop to bf16 and the f32 cast fuses
    #                        into the reductions — candidate for the r4
    #                        ResNet MFU gap; default off until measured
    stem_space_to_depth: bool = True  # rewrite the 7x7/2 stem conv as an
    #                                   exactly-equivalent 4x4/1 conv on a
    #                                   2x2 space-to-depth input: C_in=3 is
    #                                   MXU-hostile (contraction 7*7*3=147,
    #                                   channels padded to the 128 lane);
    #                                   the s2d form contracts over 192 with
    #                                   12 input channels (standard TPU
    #                                   ResNet optimization)

    @classmethod
    def resnet18(cls, num_classes=1000, **kw):
        return cls(num_classes=num_classes, stage_sizes=(2, 2, 2, 2), **kw)

    @classmethod
    def resnet50(cls, num_classes=1000, **kw):
        return cls(num_classes=num_classes, stage_sizes=(3, 4, 6, 3), **kw)

    def flops_per_image(self, image_size: int = 224) -> float:
        """Analytic training FLOPs per image (2*MACs forward, ×3 for
        fwd+bwd), counting convs + the classifier matmul: the 2*MACs
        convention of ``TransformerConfig.flops_per_token``."""
        def conv_flops(hw, k, cin, cout, stride):
            out_hw = hw // stride
            return 2.0 * out_hw * out_hw * k * k * cin * cout, out_hw

        total, hw = 0.0, image_size
        f, hw = conv_flops(hw, 7, 3, self.width, 2)          # stem
        total += f
        hw //= 2                                             # 3x3/2 max pool
        c_in = self.width
        for s, blocks in enumerate(self.stage_sizes):
            c_mid = self.width * (2 ** s)
            c_out = c_mid * 4
            for b in range(blocks):
                stride = 2 if (s > 0 and b == 0) else 1
                f1, _ = conv_flops(hw, 1, c_in, c_mid, 1)
                f2, hw2 = conv_flops(hw, 3, c_mid, c_mid, stride)
                f3, _ = conv_flops(hw2, 1, c_mid, c_out, 1)
                total += f1 + f2 + f3
                if c_in != c_out or stride != 1:
                    fp, _ = conv_flops(hw, 1, c_in, c_out, stride)
                    total += fp
                hw = hw2
                c_in = c_out
        total += 2.0 * c_in * self.num_classes               # head matmul
        return 3.0 * total                                   # fwd + bwd


def _conv_init(key, shape, pd):
    fan_in = shape[0] * shape[1] * shape[2]
    return (jax.random.normal(key, shape) * np.sqrt(2.0 / fan_in)).astype(pd)


def _bn_params(c, pd):
    return {"scale": jnp.ones((c,), pd), "bias": jnp.zeros((c,), pd)}


def init_params(key, cfg: ResNetConfig) -> dict:
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 2048))
    params: dict = {
        "stem": {"conv": _conv_init(next(keys), (7, 7, 3, cfg.width), pd),
                 "bn": _bn_params(cfg.width, pd)},
        "stages": [],
    }
    c_in = cfg.width
    for s, blocks in enumerate(cfg.stage_sizes):
        c_mid = cfg.width * (2 ** s)
        c_out = c_mid * 4
        stage = []
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            blk = {
                "conv1": _conv_init(next(keys), (1, 1, c_in, c_mid), pd),
                "bn1": _bn_params(c_mid, pd),
                "conv2": _conv_init(next(keys), (3, 3, c_mid, c_mid), pd),
                "bn2": _bn_params(c_mid, pd),
                "conv3": _conv_init(next(keys), (1, 1, c_mid, c_out), pd),
                "bn3": _bn_params(c_out, pd),
            }
            if c_in != c_out or stride != 1:
                blk["proj"] = _conv_init(next(keys), (1, 1, c_in, c_out), pd)
                blk["proj_bn"] = _bn_params(c_out, pd)
            stage.append(blk)
            c_in = c_out
        params["stages"].append(stage)
    params["head"] = {
        "w": (jax.random.normal(next(keys), (c_in, cfg.num_classes)) *
              np.sqrt(1.0 / c_in)).astype(pd),
        "b": jnp.zeros((cfg.num_classes,), pd),
    }
    return params


def _conv(x, w, stride=1, dtype=jnp.bfloat16):
    return lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, rs=None, train=True, momentum=0.9, eps=1e-5, fold=False):
    """BatchNorm, statistics in f32.  ``rs`` = running stats
    ``{"mean", "var"}``: train mode normalizes with batch statistics (and,
    when ``rs`` is given, returns EMA-updated running stats under
    stop_gradient); eval mode normalizes with ``rs`` so inference is
    batch-independent.  ``fold=False`` does the elementwise normalize in
    f32 (the r4 path, byte-identical); ``fold=True`` folds (mean, var,
    scale, bias) into one per-channel affine applied in the input dtype —
    same math, bf16 elementwise traffic.  Returns ``(y, new_rs)`` —
    ``new_rs`` is None when stats aren't threaded."""
    x32 = x.astype(jnp.float32)
    if train:
        mean = x32.mean(axis=(0, 1, 2))
        var = x32.var(axis=(0, 1, 2))
        new_rs = None
        if rs is not None:
            sg = lax.stop_gradient
            new_rs = {"mean": momentum * rs["mean"] + (1 - momentum) * sg(mean),
                      "var": momentum * rs["var"] + (1 - momentum) * sg(var)}
    else:
        if rs is None:
            raise ValueError("eval-mode BN needs running stats "
                             "(init_batch_stats + a training pass)")
        mean, var, new_rs = rs["mean"], rs["var"], rs
    inv = lax.rsqrt(var + eps)
    if fold:
        sc = (p["scale"] * inv).astype(x.dtype)
        bi = (p["bias"] - p["scale"] * mean * inv).astype(x.dtype)
        return x * sc + bi, new_rs
    y = (x32 - mean) * inv
    return (y * p["scale"] + p["bias"]).astype(x.dtype), new_rs


def init_batch_stats(cfg: ResNetConfig) -> dict:
    """Running-stats pytree mirroring the BN nodes of ``init_params``
    (flax-style separate collection: params stay a pure gradient target;
    stats thread through train steps as data)."""
    def node(c):
        return {"mean": jnp.zeros((c,), jnp.float32),
                "var": jnp.ones((c,), jnp.float32)}

    stats: dict = {"stem": {"bn": node(cfg.width)}, "stages": []}
    c_in = cfg.width
    for s, blocks in enumerate(cfg.stage_sizes):
        c_mid = cfg.width * (2 ** s)
        c_out = c_mid * 4
        stage = []
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            blk = {"bn1": node(c_mid), "bn2": node(c_mid), "bn3": node(c_out)}
            if c_in != c_out or stride != 1:
                blk["proj_bn"] = node(c_out)
            stage.append(blk)
            c_in = c_out
        stats["stages"].append(stage)
    return stats


def _bottleneck(x, blk, stride, dtype, rs=None, train=True, momentum=0.9,
                fold=False):
    g = lambda name: None if rs is None else rs[name]
    new_rs = {} if rs is not None else None

    def bn(name, h):
        y, n = _bn(h, blk[name], g(name), train, momentum, fold=fold)
        if new_rs is not None:
            new_rs[name] = n
        return y

    h = jax.nn.relu(bn("bn1", _conv(x, blk["conv1"], 1, dtype)))
    h = jax.nn.relu(bn("bn2", _conv(h, blk["conv2"], stride, dtype)))
    h = bn("bn3", _conv(h, blk["conv3"], 1, dtype))
    if "proj" in blk:
        x = bn("proj_bn", _conv(x, blk["proj"], stride, dtype))
    return jax.nn.relu(x + h), new_rs


def _space_to_depth(x):
    """(N, H, W, C) -> (N, H/2, W/2, 4C), channel-minor order (a, b, c)."""
    N, H, W, C = x.shape
    x = x.reshape(N, H // 2, 2, W // 2, 2, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(N, H // 2, W // 2, 4 * C)


def _stem_s2d_kernel(w):
    """Rearrange the (7, 7, C, O) stride-2 stem kernel into the (4, 4, 4C, O)
    stride-1 kernel that computes the identical map on a space-to-depth
    input: pad to 8x8 (the extra taps are zero), then space-to-depth the
    kernel itself with the same (a, b, c) channel order as the input."""
    _, _, C, O = w.shape
    wp = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    wp = wp.reshape(4, 2, 4, 2, C, O)
    return wp.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * C, O)


def forward(params, images, cfg: ResNetConfig, batch_stats=None,
            train: bool = True, momentum: float = 0.9):
    """images: (N, H, W, 3) -> logits (N, num_classes).

    Without ``batch_stats`` (the default, the benched training path) BN
    uses batch statistics and only logits return.  With ``batch_stats``
    (from :func:`init_batch_stats`) the call returns ``(logits,
    new_stats)``: train mode still normalizes by batch but EMA-updates the
    running stats; ``train=False`` normalizes by the running stats, making
    eval-mode inference batch-independent (the reference has no BN to
    match — VERDICT r4 'missing' #4, implied by the ResNet north star)."""
    dt = cfg.dtype
    N, H, W, _ = images.shape
    if cfg.stem_space_to_depth and H % 2 == 0 and W % 2 == 0:
        # SAME on the s2d conv reproduces SAME on the original exactly:
        # k=7 s=2 pads (2, 3) on 2H -> k=4 s=1 pads (1, 2) on H
        w = _stem_s2d_kernel(params["stem"]["conv"]).astype(dt)
        x = lax.conv_general_dilated(
            _space_to_depth(images).astype(dt), w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    else:
        x = _conv(images, params["stem"]["conv"], 2, dt)
    rs = batch_stats
    new_stats = None if rs is None else {"stem": {}, "stages": []}
    x, n = _bn(x, params["stem"]["bn"],
               None if rs is None else rs["stem"]["bn"], train, momentum,
               fold=cfg.bn_fold)
    if rs is not None:
        new_stats["stem"]["bn"] = n
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for s, stage in enumerate(params["stages"]):
        if rs is not None:
            new_stats["stages"].append([])
        for b, blk in enumerate(stage):
            stride = 2 if (s > 0 and b == 0) else 1
            x, n = _bottleneck(
                x, blk, stride, dt,
                None if rs is None else rs["stages"][s][b], train, momentum,
                fold=cfg.bn_fold)
            if rs is not None:
                new_stats["stages"][s].append(n)
    x = x.mean(axis=(1, 2)).astype(jnp.float32)       # global average pool
    logits = x @ params["head"]["w"].astype(jnp.float32) + params["head"]["b"]
    return logits if rs is None else (logits, new_stats)


def cross_entropy(params, images, labels, cfg: ResNetConfig) -> jnp.ndarray:
    logits = forward(params, images, cfg)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(labels * logp, axis=-1))


def cross_entropy_with_stats(params, batch_stats, images, labels,
                             cfg: ResNetConfig, momentum: float = 0.9):
    """(loss, new_batch_stats) for train loops that maintain running BN
    statistics — use with ``jax.value_and_grad(..., has_aux=True)``."""
    logits, new_stats = forward(params, images, cfg, batch_stats,
                                train=True, momentum=momentum)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(labels * logp, axis=-1)), new_stats


class ResNet:
    def __init__(self, cfg: ResNetConfig):
        self.cfg = cfg
        self.params = None
        self.batch_stats = None
        self._fwd = None
        self._fwd_eval = None

    def init(self, key=None):
        self.params = init_params(key if key is not None else jax.random.key(0),
                                  self.cfg)
        self.batch_stats = init_batch_stats(self.cfg)
        return self.params

    def predict_logits(self, images, use_running_stats: bool = False):
        """``use_running_stats=True`` gives batch-independent eval-mode
        inference (meaningful once training has populated
        ``self.batch_stats`` via ``train_step``)."""
        if use_running_stats:
            if self._fwd_eval is None:
                self._fwd_eval = jax.jit(partial(
                    forward, cfg=self.cfg, train=False))
            logits, _ = self._fwd_eval(self.params, jnp.asarray(images),
                                       batch_stats=self.batch_stats)
            return logits
        if self._fwd is None:
            self._fwd = jax.jit(partial(forward, cfg=self.cfg))
        return self._fwd(self.params, jnp.asarray(images))

    def loss_fn(self):
        """(params, x, y, key) -> scalar, pluggable into parallel.trainer.
        Note: this path trains with batch statistics only; loops that need
        eval-mode inference maintain running stats via
        ``cross_entropy_with_stats`` (see ``train_step``)."""
        cfg = self.cfg
        return lambda p, x, y, k=None: cross_entropy(p, x, y, cfg)

    def train_step(self, tx):
        """Jitted ``(params, stats, opt, x, y) -> (params, stats, opt,
        loss)`` that maintains running BN statistics alongside training."""
        from ..optimize.transforms import apply_updates
        cfg = self.cfg

        def step(params, stats, opt, x, y):
            count, st = opt
            (loss, new_stats), g = jax.value_and_grad(
                cross_entropy_with_stats, has_aux=True)(
                    params, stats, x, y, cfg)
            updates, st = tx.update(g, st, params, count)
            return (apply_updates(params, updates), new_stats,
                    (count + 1, st), loss)

        return jax.jit(step, donate_argnums=(0, 1, 2))
