"""Pipeline parallelism over the ``pp`` mesh axis — GPipe-style micro-batch
fill/drain, TPU-first.

No reference analog (the v0 reference tops out at parameter-averaging data
parallelism, ``IterativeReduceWorkRouter.java:16``); the spec is the
BASELINE.json north star (multi-axis sharding on a pod).  The design is the
idiomatic JAX/XLA one, NOT a port of torch-style stage processes:

- The transformer blocks are **stacked on a leading layer axis** and that
  axis is sharded over ``pp``: each pp rank holds ``n_layers / pp``
  contiguous blocks (a *stage*) as one pytree of ``(L_loc, ...)`` leaves.
- ONE SPMD program runs on every rank under ``shard_map``.  A ``lax.scan``
  over ``M + S - 1`` ticks implements fill/drain: at each tick a rank
  applies its stage to its current activation and hands the result to the
  next rank via ``lax.ppermute``.  Rank 0 feeds micro-batch ``t`` in; the
  last rank collects finished micro-batches from tick ``S-1`` on.
- **Backward needs no schedule of its own**: the VJP of ``ppermute`` is the
  reverse rotation, so differentiating the scan yields the drain-ordered
  backward pipeline automatically.
- Embedding/final-LN/head are replicated over ``pp`` but *used* only on the
  first/last rank; their local gradients are partial contributions (zero on
  unused ranks), so the pp gradient sync is ``psum`` — unlike dp/sp where
  replicas hold full per-shard gradients and the sync is ``pmean``.

Composes with the existing axes: dp (batch shard + grad pmean), sp (ring
attention inside each block), tp (Megatron psum boundaries inside each
block) — all in the same mesh, same shard_map.

Bubble fraction is ``(S-1)/(M+S-1)``; pick ``n_micro >= 2*S`` (GPipe's
guidance is ~4x) to keep it small.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DP, PP, SP, TP
from .transformer import (
    TransformerConfig,
    TransformerLM,
    _block,
    _layernorm,
    embed_local,
    lm_head_loss,
    param_specs,
)


# --------------------------------------------------------------------- layout

def stack_layers(params):
    """List-of-layer-dicts -> single stacked pytree with leading layer axis
    (the axis ``pp`` shards).  Non-layer leaves pass through."""
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *params["layers"])
    return out


def unstack_layers(params, n_layers: int):
    """Inverse of :func:`stack_layers` (checkpoint interchange with the
    list-layout ``TransformerLM``)."""
    out = {k: v for k, v in params.items() if k != "layers"}
    st = params["layers"]
    out["layers"] = [jax.tree_util.tree_map(lambda x: x[i], st)
                     for i in range(n_layers)]
    return out


def pipeline_param_specs(cfg: TransformerConfig):
    """Stacked-layout PartitionSpecs: the stacked layer axis is sharded over
    pp; inner axes keep their tp sharding; everything else replicated."""
    base = param_specs(cfg)
    specs = {k: v for k, v in base.items() if k != "layers"}
    specs["layers"] = jax.tree_util.tree_map(
        lambda s: P(PP, *s), base["layers"][0],
        is_leaf=lambda x: isinstance(x, P))
    return specs


# --------------------------------------------------------------------- schedule

def pipelined_encode_local(params, tokens, cfg: TransformerConfig, *,
                           n_pp: int, n_micro: int, n_sp: int = 1,
                           sp_axis=None, tp_axis=None):
    """Final hidden states for the local (dp/sp-sharded) token block, the
    layer stack executed as an ``n_pp``-stage, ``n_micro``-micro-batch
    pipeline.  Runs inside shard_map.  Every rank returns the same-shaped
    output; only the LAST rank's is the real sequence encoding (callers
    mask with ``lax.axis_index(PP)``)."""
    B, T = tokens.shape
    assert B % n_micro == 0, f"local batch {B} % n_micro {n_micro}"
    stage = lax.axis_index(PP)

    # Embedding on every rank (SPMD; a gather — cheap), used only by rank 0.
    x = embed_local(params, tokens, cfg, sp_axis)

    bm = B // n_micro
    micro = x.reshape(n_micro, bm, T, x.shape[-1])

    stacked = params["layers"]                    # (L_loc, ...) leaves

    def apply_stage(h):
        def body(carry, lp):
            out = _block(lp, carry, cfg, n_sp, sp_axis, tp_axis, T)
            return out, None
        body_fn = jax.checkpoint(body) if cfg.remat else body
        h, _ = lax.scan(body_fn, h, stacked)
        return h

    n_ticks = n_micro + n_pp - 1
    right = [(i, (i + 1) % n_pp) for i in range(n_pp)]

    def tick(carry, t):
        recv, outs = carry
        x0 = lax.dynamic_index_in_dim(micro, jnp.clip(t, 0, n_micro - 1), 0,
                                      keepdims=False)
        xin = jnp.where(stage == 0, x0, recv)
        # Zero the garbage lane (fill/drain bubble ticks) BEFORE the stage
        # runs: a masked-out lane that went non-finite (bf16 overflow) would
        # poison real gradients through the jnp.where backward (0 * inf =
        # nan).  Zeros stay finite through the block, so the trap can't arm.
        valid = (t >= stage) & (t < n_micro + stage)
        xin = jnp.where(valid, xin, jnp.zeros_like(xin))
        y = apply_stage(xin)
        out_idx = jnp.clip(t - (n_pp - 1), 0, n_micro - 1)
        updated = lax.dynamic_update_index_in_dim(outs, y, out_idx, 0)
        outs = jnp.where(t >= n_pp - 1, updated, outs)
        recv = lax.ppermute(y, PP, right)
        return (recv, outs), None

    outs0 = jnp.zeros_like(micro)
    recv0 = jnp.zeros_like(micro[0])
    (_, outs), _ = lax.scan(tick, (recv0, outs0), jnp.arange(n_ticks))

    h = outs.reshape(B, T, x.shape[-1])
    return _layernorm(h, params["final_ln_scale"], params["final_ln_bias"])


def pipelined_lm_loss_local(params, tokens, targets, cfg: TransformerConfig,
                            *, n_pp: int, n_micro: int, **axes):
    """Local masked LM loss: real on the last pp rank, 0 elsewhere; callers
    ``psum`` over pp (exactly one rank contributes) then pmean over dp/sp."""
    h = pipelined_encode_local(params, tokens, cfg, n_pp=n_pp,
                               n_micro=n_micro, **axes)
    loss = lm_head_loss(params, h, targets, cfg)
    is_last = lax.axis_index(PP) == n_pp - 1
    return jnp.where(is_last, loss, 0.0)


def pipelined_cls_loss_local(backbone, head, tokens, labels,
                             cfg: TransformerConfig, *, n_pp: int,
                             n_micro: int, n_sp: int = 1, sp_axis=None,
                             tp_axis=None):
    """Classifier fine-tune loss through the pipeline (the BERT-fine-tune
    north star composed with pp): mean-pool the last rank's encoding, dense
    head, cross entropy — real on the last pp rank, 0 elsewhere (callers
    psum over pp, as with the LM loss)."""
    h = pipelined_encode_local(backbone, tokens, cfg, n_pp=n_pp,
                               n_micro=n_micro, n_sp=n_sp, sp_axis=sp_axis,
                               tp_axis=tp_axis)
    pooled = h.astype(jnp.float32).mean(axis=1)
    if sp_axis:
        pooled = lax.pmean(pooled, sp_axis)
    logits = pooled @ head["w_cls"].astype(jnp.float32) + head["b_cls"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    is_last = lax.axis_index(PP) == n_pp - 1
    return jnp.where(is_last, loss, 0.0)


# --------------------------------------------------------------------- facade

class PipelinedTransformerLM(TransformerLM):
    """Flagship trainer with the pp axis live: (dp, pp, sp, tp) explicit
    SPMD.  Param layout is the STACKED one (use :func:`stack_layers` /
    :func:`unstack_layers` to interchange with ``TransformerLM``)."""

    def __init__(self, cfg: TransformerConfig, mesh, n_micro: int | None = None):
        super().__init__(cfg, mesh)
        s = mesh.shape
        self.n_pp = s.get(PP, 1)
        assert self.n_pp > 1, "use TransformerLM when pp == 1"
        assert cfg.n_layers % self.n_pp == 0, (
            f"n_layers {cfg.n_layers} % pp {self.n_pp}")
        self.n_micro = n_micro if n_micro is not None else 2 * self.n_pp

    def init(self, key=None) -> dict:
        return stack_layers(super().init(key))

    def _unstacked_only(name):
        def guard(self, *a, **kw):
            raise NotImplementedError(
                f"{name} runs on the unstacked single-device layout: "
                f"TransformerLM(cfg).{name}(unstack_layers("
                "jax.device_get(params), cfg.n_layers), ...)")
        guard.__name__ = name
        return guard

    sample = _unstacked_only("sample")
    beam_search = _unstacked_only("beam_search")
    score = _unstacked_only("score")
    del _unstacked_only

    def _specs(self):
        return pipeline_param_specs(self.cfg)


    def _grad_sync(self, specs, sp_axis, tp_axis, include_dp: bool = True):
        """dp/sp replicas hold full per-shard grads -> pmean; pp holds
        PARTIAL contributions on pp-replicated leaves -> psum (stage-sharded
        leaves already have their full grad locally).  ``include_dp=False``
        is the ZeRO-1 path: dp handled by the caller's reduce-scatter."""
        base = super()._grad_sync(specs, sp_axis, tp_axis, include_dp)

        def sync(grads):
            grads = base(grads)

            def pp_fix(g, spec):
                if any(ax == PP for ax in spec if ax is not None):
                    return g
                return lax.psum(g, PP)

            return jax.tree_util.tree_map(
                pp_fix, grads, specs, is_leaf=lambda x: isinstance(x, P))

        return sync

    def _loss_reduce(self, loss, sp_axis):
        """Exactly one pp rank (the last) holds the real loss; psum over pp
        recovers it, then the usual dp/sp pmean applies."""
        return super()._loss_reduce(lax.psum(loss, PP), sp_axis)

    # -- ZeRO-1 over dp, composed with pp -------------------------------
    #
    # Stage-sharded leaves (the stacked ``layers`` subtree, spec
    # ``P(PP, ...)``) hold a DIFFERENT local chunk per pp rank, exactly as
    # tp-sharded leaves do per tp rank — so their dp-sharded optimizer
    # state grows a pp row dimension: state leaves are encoded globally as
    # ``(rows, n_dp * k)`` with ``rows = n_pp·[n_tp]`` and spec
    # ``P((PP[, TP]), DP)``.  Inside shard_map every rank still sees a
    # ``(1, k)`` local leaf, so the parent's scatter/update/gather local
    # step needs no change at all.

    def _decay_mask(self, tree):
        """Stacking grafts a leading layer axis onto every per-layer leaf,
        so the ndim >= 2 weight-class default misfires there (a (D,) LN
        scale becomes (L, D)): stacked leaves are weight-class iff their
        UNstacked form is, i.e. ndim >= 3."""
        def mask(path, w):
            stacked = any(getattr(k, "key", None) == "layers" for k in path)
            return w.ndim >= (3 if stacked else 2)
        return jax.tree_util.tree_map_with_path(mask, tree)

    def _z1_leaf_is_pp_sharded(self, spec) -> bool:
        return any(ax == PP for ax in spec if ax is not None)

    def _z1_row_layout(self, spec):
        """(row count multiplier axes, row PartitionSpec entry) for a leaf."""
        _, _, n_tp = self._axes()
        axes = []
        if self._z1_leaf_is_pp_sharded(spec):
            axes.append((PP, self.n_pp))
        if self._z1_leaf_is_tp_sharded(spec) and n_tp > 1:
            axes.append((TP, n_tp))
        names = tuple(a for a, _ in axes)
        row_spec = names if len(names) > 1 else (names[0] if names else None)
        rows = 1
        for _, n in axes:
            rows *= n
        return rows, row_spec

    def _z1_template_and_specs(self, params, specs):
        n_dp = self._axes()[0]

        def template(p, spec):
            rows, _ = self._z1_row_layout(spec)
            local_size = int(np.prod(p.shape)) // rows
            k = self._z1_chunk(local_size, n_dp)
            return jnp.zeros((rows, n_dp * k), p.dtype)

        def spec_of(p, spec):
            return P(self._z1_row_layout(spec)[1], DP)

        is_p = lambda x: isinstance(x, P)
        tmpl = jax.tree_util.tree_map(template, params, specs, is_leaf=is_p)
        tspec = jax.tree_util.tree_map(spec_of, params, specs, is_leaf=is_p)
        return tmpl, tspec

    def _z1_state_specs(self, specs):
        return jax.tree_util.tree_map(
            lambda spec: P(self._z1_row_layout(spec)[1], DP), specs,
            is_leaf=lambda x: isinstance(x, P))

    def build_train_step(self, tx=None, lr: float = 1e-3, zero1: bool = False):
        """``step(params, opt, tokens, targets) -> (params, opt, loss)``
        with the layer stack pipelined over pp (shared ``_build_step``
        wiring; only the loss fn, specs, and reductions differ).
        ``zero1=True`` shards optimizer state over dp, including the
        pp-stage-sharded leaves (pair with ``init_opt_zero1``)."""
        cfg = self.cfg
        tx = tx if tx is not None else self._default_tx(lr)
        n_pp, n_micro = self.n_pp, self.n_micro

        def loss_of(params, tokens, targets, axes):
            return pipelined_lm_loss_local(params, tokens, targets, cfg,
                                           n_pp=n_pp, n_micro=n_micro, **axes)

        return self._build_step(tx, loss_of, self._specs(),
                                (P(DP, SP), P(DP, SP)), zero1=zero1)

    def _pipeline_axes(self):
        s = self.mesh.shape
        n_sp, n_tp = s.get(SP, 1), s.get(TP, 1)
        return dict(n_sp=n_sp, sp_axis=SP if n_sp > 1 else None,
                    tp_axis=TP if n_tp > 1 else None)

    def forward(self, params, tokens):
        """Vocabulary logits through the pipeline.  The last pp rank holds
        the real logits; a pp psum of the masked value replicates them so
        every rank returns the same (global) array."""
        if self._fwd is None:
            cfg, n_pp, n_micro = self.cfg, self.n_pp, self.n_micro
            axes = self._pipeline_axes()

            def local_fwd(params, tokens):
                h = pipelined_encode_local(params, tokens, cfg, n_pp=n_pp,
                                           n_micro=n_micro, **axes)
                logits = jnp.einsum(
                    "btd,dv->btv", h.astype(cfg.dtype),
                    (params["tok_embed"].T if cfg.tie_embeddings
                     else params["lm_head"]).astype(cfg.dtype)
                ).astype(jnp.float32)
                is_last = lax.axis_index(PP) == n_pp - 1
                return lax.psum(jnp.where(is_last, logits, 0.0), PP)

            self._fwd = jax.jit(shard_map(
                local_fwd, mesh=self.mesh,
                in_specs=(self._specs(), P(DP, SP)),
                out_specs=P(DP, SP), check_vma=False))
        return self._fwd(params, tokens)

    def init_finetune(self, key, n_classes, params=None):
        """Stacked-layout ``{"backbone", "head"}`` tree (inherits the parent
        wiring: ``init`` already stacks, ``finetune_specs`` routes through
        ``_specs``)."""
        return super().init_finetune(key, n_classes, params)

    def build_finetune_step(self, tx=None, lr: float = 2e-5):
        """Classifier fine-tune step with the layer stack pipelined over pp
        (the BERT-fine-tune north star composed with pipeline parallelism)."""
        cfg = self.cfg
        tx = tx if tx is not None else self._default_tx(lr)
        n_pp, n_micro = self.n_pp, self.n_micro

        def loss_of(tree, tokens, labels, axes):
            return pipelined_cls_loss_local(
                tree["backbone"], tree["head"], tokens, labels, cfg,
                n_pp=n_pp, n_micro=n_micro, **axes)

        return self._build_step(tx, loss_of, self.finetune_specs(),
                                (P(DP, SP), P(DP)))
