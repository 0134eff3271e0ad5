"""SPMD data-parallel trainer with an asynchronous, pipelined hot loop.

TPU-native replacement for the reference's scaleout training loop
(master/worker actors + StateTracker + WorkRouter policy, SURVEY.md §3.3):
ONE jitted train step over a `jax.sharding.Mesh`, batch sharded on the
``dp`` axis.  Both of the reference's routing policies exist:

- **iterative-reduce** (``IterativeReduceWorkRouter.java:16,30``): replicated
  params + dp-sharded batch — XLA inserts the gradient all-reduce (the
  `pmean`) into the compiled step, so 'wait for all workers, average,
  rebroadcast' is a single fused collective per step on ICI.
- **hogwild** (``HogWildWorkRouter.java``, async always-send): TPUs are
  lockstep, so the idiomatic approximation is *local SGD / periodic
  averaging*: per-worker parameter replicas (leading dp-sharded axis) take
  K local steps with NO cross-device traffic, then average with one
  in-compiled `pmean` (``shard_map``).  K=1 degenerates to iterative-reduce.
  Deviation documented per SURVEY.md §7 hard-part #5.

Async execution model (DESIGN.md §10): JAX dispatch is asynchronous, so the
Python driver only stays ahead of the device if nothing on the hot loop
forces a device->host read.  Three rules enforce that here:

1. ``step`` never calls ``float(loss)`` — it returns a :class:`LazyLoss`
   handle and parks the device scalar on a bounded pending ring; ``fit``
   resolves the ring in batches (every ``resolve_every`` steps and at the
   end) behind one explicit ``block_until_ready`` fence.  Loss/throughput
   gauges move to the resolution point so metrics stay correct without
   re-introducing the per-step sync.
2. Ragged batches pad to a small powers-of-two bucket ladder (capped at
   the nominal batch) with one jitted step per bucket and a
   ``train_step.recompile`` counter — bounded compilation instead of one
   recompile per odd shape.  A validity mask keeps the loss/gradient
   average EXACT under padding (padded rows contribute zero).
3. ``fit`` streams any iterable (no ``list(data)`` materialization) and
   routes host batches through ``prefetch_to_device`` with the trainer's
   ``NamedSharding``, so H2D transfer overlaps device compute.

Checkpoints fence the ring before reading params (``checkpoint``), so a
snapshot never races in-flight steps.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis.runtime import allow_transfers, hot_loop_guard
from ..analysis.shardguard import SHARDGUARD
from ..datasets.dataset import DataSet
from ..resilience.faults import FAULTS, DeviceLossError, DivergenceError
from ..observability import COSTS, METRICS, NOOP_SPAN, enabled as _obs_enabled
from ..observability import sample_device_memory, sample_state_bytes
from ..observability import scopemap, trace
from ..optimize import transforms as tfm
from . import collectives as clv
from .compile_cache import setup_compile_cache
from .mesh import DP, local_mesh, mesh_devices
from .zero import ZeroLayout

LossFn = Callable[..., jnp.ndarray]  # (params, x, y, key) -> scalar


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _jit_step(fn, **jit_kwargs):
    """``jax.jit(fn)`` under the one program name every trainer step has in
    a profiler trace, ``jit_step``: the benchmark's readers find the step
    program by that prefix whichever builder made it."""
    fn.__name__ = fn.__qualname__ = "step"
    return jax.jit(fn, **jit_kwargs)


class LazyLoss:
    """Lazy handle to a device-resident loss.

    ``step`` returns one of these instead of a synced float: the scalar
    stays on device until ``float(handle)`` / ``handle.value()`` forces
    the device->host read, so the dispatch loop never blocks on it.
    ``block()`` waits for the device value without converting it (the
    fence primitive ``fit`` uses).  Hogwild steps carry a per-replica
    loss vector; ``value()`` reduces it to the replica mean.
    """

    __slots__ = ("_dev", "_value")

    def __init__(self, dev):
        self._dev = dev
        self._value: float | None = None

    @property
    def resolved(self) -> bool:
        return self._value is not None

    def block(self) -> "LazyLoss":
        if self._value is None:
            jax.block_until_ready(self._dev)
        return self

    def value(self) -> float:
        if self._value is None:
            self._value = float(np.mean(jax.device_get(self._dev)))
            self._dev = None
        return self._value

    __float__ = value

    def __format__(self, spec: str) -> str:
        return format(self.value(), spec)

    def __repr__(self) -> str:
        return (f"LazyLoss({self._value!r})" if self.resolved
                else "LazyLoss(<pending>)")


@dataclasses.dataclass
class TrainState:
    params: Any
    tstate: Any
    step: int
    key: Any


class DataParallelTrainer:
    """Shard a supervised train step over the ``dp`` axis of a mesh.

    ``max_pending`` bounds the ring of unresolved losses: when a caller
    drives ``step`` directly and never resolves, the trainer fences
    itself every ``max_pending`` dispatches so the device queue cannot
    grow without bound.
    """

    def __init__(self, loss_fn: LossFn, transform: tfm.GradientTransform,
                 mesh: Mesh | None = None, router: str = "iterative_reduce",
                 average_every: int = 8, max_pending: int = 64,
                 zero_stage: int = 0, per_example_loss: bool = False):
        if router not in ("iterative_reduce", "hogwild"):
            raise ValueError(f"unknown router {router!r}")
        if zero_stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_stage must be 0..3, got {zero_stage!r}")
        if zero_stage and router != "iterative_reduce":
            raise ValueError(
                "zero_stage requires the iterative_reduce router — hogwild "
                "keeps independent per-replica optimizer state by design, "
                "so there is no shared state to shard")
        self.loss_fn = loss_fn
        # True: ``loss_fn(params, x, y, key)`` returns the rows' losses (B,)
        # for the whole local batch; False: a mean, taken row by row
        self.per_example_loss = per_example_loss
        self.transform = transform
        self.mesh = mesh if mesh is not None else local_mesh()
        self.router = router
        self.average_every = average_every
        self.max_pending = max(1, max_pending)
        self.n_dp = self.mesh.shape[DP]
        # ZeRO stage (DESIGN.md §15): 0 replicates grads + optimizer state
        # (the classic path); 1 shards optimizer state (all-reduce grads,
        # update this chip's chunk, all-gather params); 2 reduce-scatters
        # grads so full gradients never materialize; 3 additionally keeps
        # PARAMS sharded between steps, gathering per microbatch.
        self.zero_stage = int(zero_stage)
        self._zero: ZeroLayout | None = None  # built at init_state
        # canonical placements for step arguments: batches split over dp,
        # scalars replicated.  Dispatch device_puts EVERY argument against
        # these (a no-op for already-placed arrays), so nothing reaches the
        # jitted step via an implicit transfer/reshard — the invariant the
        # hot-loop transfer guard enforces.
        self._batch_sh = NamedSharding(self.mesh, P(DP))
        self._rep_sh = NamedSharding(self.mesh, P())
        self._avg_fn = None
        # bucketed jit cache: one compiled step per padded batch size
        self._step_cache: dict[int, Any] = {}
        self._nominal: int | None = None
        # pending-loss ring: (LazyLoss, n_real_samples, post-dispatch step)
        # awaiting resolution; the step rides along so the NaN guard can
        # report exactly which step diverged
        self._pending: list[tuple[LazyLoss, int, int]] = []
        self._window_t0: float | None = None
        self._nan_guard = False  # set per-fit; checked at resolution
        # XLA cost of the most recent bucket's dispatch (captured at first
        # compile) — feeds the live train.mfu gauge at resolution fences
        self._step_cost = None
        setup_compile_cache()  # persistent XLA cache (compile_cache.py)

    # ------------------------------------------------------------------ state
    def init_state(self, params, key=None) -> TrainState:
        if key is None:
            # seed from an explicitly-placed scalar: works under a caller's
            # transfer guard (jax.random.key(0) implicitly uploads the int)
            key = jax.random.key(jax.device_put(np.uint32(0)))
        key = jax.device_put(key, self._rep_sh)  # replicate once, up front
        # Copy before placement: device_put may alias the caller's buffers as
        # mesh shards, and the jitted step donates its inputs — without this
        # copy the caller's params would be deleted by the first step.  Host
        # leaves cross over via an EXPLICIT device_put (itself a fresh
        # buffer), so initializing from numpy works under a transfer guard.
        params = jax.tree_util.tree_map(
            lambda a: (jnp.array(a) if isinstance(a, jax.Array)
                       else jax.device_put(np.asarray(a))), params)
        if self.router == "hogwild":
            # per-worker replicas: stack along a leading dp axis
            params = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (self.n_dp,) + x.shape), params)
            params = jax.device_put(
                params, NamedSharding(self.mesh, P(DP)))
        else:
            params = jax.device_put(params, NamedSharding(self.mesh, P()))
        # transform.init (and the hogwild x[0] slice) build setup-time
        # constants — zero buffers, gather indices — that a surrounding
        # transfer guard would reject.  This is one-shot setup, not the hot
        # loop, and every leaf is explicitly re-placed below, so the
        # documented escape hatch applies here.
        with allow_transfers():
            if self.router == "hogwild":
                tstate = self.transform.init(
                    jax.tree_util.tree_map(lambda x: x[0], params))
                tstate = jax.tree_util.tree_map(
                    lambda x: (jnp.broadcast_to(x[None],
                                                (self.n_dp,) + x.shape)
                               if isinstance(x, jnp.ndarray) else x), tstate)
                tstate = jax.device_put(tstate, NamedSharding(self.mesh, P(DP)))
            elif self.zero_stage >= 1:
                # ZeRO: optimizer state is born shard-local — init runs
                # jitted over the flat param view (zero.py) with
                # out_shardings from state_spec, so each chip materializes
                # only its 1/ndp chunk of every state leaf
                z = self._zero_layout(params)
                flat_params = z.place_flat(params, z.flat_sharding)
                tstate = tfm.init_sharded(self.transform, flat_params,
                                          P(DP), self.mesh)
                if self.zero_stage >= 3:
                    params = flat_params  # params stay sharded between steps
            else:
                tstate = self.transform.init(params)
                # transform.init builds its buffers eagerly on one device;
                # replicate them NOW so the first step's call needs no
                # implicit reshard (the hot-loop transfer guard rejects it)
                tstate = jax.tree_util.tree_map(
                    lambda x: (jax.device_put(x, self._rep_sh)
                               if isinstance(x, jnp.ndarray) else x), tstate)
        state = TrainState(params=params, tstate=tstate, step=0, key=key)
        sample_state_bytes(state.params, state.tstate)  # ZeRO memory gauges
        return state

    def _zero_layout(self, params) -> ZeroLayout:
        """Build (once) the per-leaf shard metadata for zero_stage >= 1.
        Pure shape metadata — safe under a transfer guard."""
        if self._zero is None:
            self._zero = ZeroLayout(self.mesh, self.transform, params)
        return self._zero

    # ------------------------------------------------------------------ buckets
    def _bucket_size(self, n: int) -> int:
        """Padded size for a batch of ``n``: powers-of-two ladder rounded to
        the dp width, capped at the nominal (first-seen) batch size.  Bounds
        the number of compiled step variants at ~log2(nominal)."""
        if self._nominal is None:
            self._nominal = _round_up(n, self.n_dp)
        cap = self._nominal
        if n >= cap:
            return _round_up(n, self.n_dp)
        b = 1
        while b < n:
            b <<= 1
        return min(_round_up(b, self.n_dp), cap)

    def _pad_to_bucket(self, x, y):
        """Host-side pad to the bucket size.  Returns (x, y, n_valid, bucket).

        Wrap indices are built with ``np.arange`` — constructing padding
        indices must not launch a device computation.  The padded rows are
        masked out inside the jitted step, so the loss/gradient average
        stays exact regardless of what the pad rows contain.
        """
        n = int(np.shape(x)[0])
        bucket = self._bucket_size(n)
        pad = bucket - n
        if pad:
            if _obs_enabled():
                METRICS.increment("train_step.pad_batch")
                METRICS.increment("train_step.padded_samples", pad)
            idx = np.arange(pad) % n  # wrap: pad may exceed batch
            lib = jnp if isinstance(x, jnp.ndarray) else np
            if lib is jnp:
                # indexing a device array with a host index vector is an
                # implicit H2D transfer — spell it out (transfer-guard safe)
                idx = jax.device_put(idx)
            x = lib.concatenate([x, x[idx]])
            y = lib.concatenate([y, y[idx]])
        return x, y, n, bucket

    # ------------------------------------------------------------------ steps
    def _per_example(self, params, x, y, key):
        """Each row's loss, ``(B,)``: through a singleton-batch vmap of a
        ``loss_fn`` that returns a mean, or straight from one that returns
        the rows' losses itself (``per_example_loss=True``) and so sees the
        whole batch at once — what an expert layer that groups tokens and a
        chunked head loss need."""
        return self._per_example_and_moves(params, x, y, key, moved=False)[0]

    def _per_example_and_moves(self, params, x, y, key, moved: bool = True):
        """``_per_example`` and, second, what the step adds to leaves the
        gradient does not reach: a ``per_example_loss`` function may return
        ``(rows' losses, {leaf path: array})`` (the path the leaf's keys
        joined by ``/``, as ``layers/1/moe/router/bias``; in an array's place
        a function of the rows' validity ``(B,)`` bool, for a rule that
        counts rows: a padded row then counts for nothing, as in the loss),
        and None from one that returns the losses alone.  ``moved=False`` is a step program
        that cannot apply them (the shard-local ones, which would see one
        chip's rows where the rule wants the batch's) and says so."""
        loss_fn = self.loss_fn
        moves = None
        if self.per_example_loss:
            per = loss_fn(params, x, y, key)
            if isinstance(per, tuple):
                per, moves = per
                if not moved:
                    raise NotImplementedError(
                        "a loss that moves leaves without a gradient needs the "
                        "replicated step (router='iterative_reduce', "
                        "zero_stage=0): it sees the whole batch there")
        else:
            per = jax.vmap(
                lambda xi, yi: loss_fn(params, xi[None], yi[None], key))(x, y)
        with jax.named_scope("loss_reduce"):
            return per.reshape((x.shape[0],)), moves

    def _masked_mean_loss(self, key_select, with_moves: bool = False):
        """Wrap ``loss_fn`` (a per-sample mean) into an exact masked mean:
        per-example losses via a singleton-batch vmap, zero weight for
        padded rows, normalized by the REAL sample count (``with_moves``:
        ``(that mean, _per_example_and_moves' second)``, for a step that
        differentiates it with ``has_aux``).  Decomposable
        (per-row) losses — every loss in this repo — are exact under this
        rewrite; batch-coupled losses (cross-batch statistics) are not and
        should avoid ragged batches."""
        def masked(params, x, y, key, mask, denom):
            per, moves = self._per_example_and_moves(
                params, x, y, key_select(key), moved=with_moves)
            with jax.named_scope("loss_reduce"):
                loss = (jnp.sum(per * mask.astype(per.dtype))
                        / denom.astype(per.dtype))
            if moves:
                moves = {leaf: move(mask) if callable(move) else move
                         for leaf, move in moves.items()}
            return (loss, moves) if with_moves else loss

        return masked

    @staticmethod
    def _moved(before, after, moves):
        """``after`` (the parameters the optimizer made of ``before``) with
        the leaves ``moves`` names ({leaf path: array}) set to their value
        ``before`` plus what the loss handed back for them: such a leaf takes
        its own rule's step in place of the optimizer's (whose moments, fed a
        zero gradient, stay where they are, and whose decay never reaches
        it).  A path that names no leaf is an error, not a no-op."""
        if not moves:
            return after
        left = dict(moves)

        def one(path, old, new):
            name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
            return old + left.pop(name).astype(old.dtype) if name in left else new

        after = jax.tree_util.tree_map_with_path(one, before, after)
        if left:
            raise KeyError(f"the loss moves leaves the parameters lack: "
                           f"{sorted(left)}")
        return after

    def _build_sync_step(self):
        mesh = self.mesh
        batch_sh = NamedSharding(mesh, P(DP))
        rep = NamedSharding(mesh, P())
        masked = self._masked_mean_loss(lambda k: k, with_moves=True)

        def step(params, tstate, x, y, key, iteration, n_valid):
            with jax.named_scope("loss_reduce"):
                mask = jnp.arange(x.shape[0]) < n_valid
            (loss, moves), grads = jax.value_and_grad(masked, has_aux=True)(
                params, x, y, key, mask, n_valid)
            with jax.named_scope("optimizer"):
                updates, tstate = self.transform.update(
                    grads, tstate, params, iteration)
                params = self._moved(
                    params, tfm.apply_updates(params, updates), moves)
            return params, tstate, loss

        # shardguard (off by default: one flag check per dispatch) diffs
        # the arrays crossing this boundary against the very shardings the
        # jit declares — a drifted device_put upstream means XLA reshards
        # on every step instead of failing loudly
        return SHARDGUARD.wrap(
            "train.sync_step",
            _jit_step(
                step,
                in_shardings=(rep, rep, batch_sh, batch_sh, rep, rep, rep),
                out_shardings=(rep, rep, rep),
                donate_argnums=(0, 1),
            ),
            in_shardings=(rep, rep, batch_sh, batch_sh, rep, rep, rep),
            out_shardings=(rep, rep, rep),
        )

    def _build_zero_step(self):
        """ZeRO sharded weight update (zero_stage >= 1), one shard_map'd
        program per bucket:

        local grads -> stage 1: all-reduce + slice this chip's chunk
                       stage >= 2: reduce-scatter (full grads never land)
        -> ``transform.update`` on this chip's chunk only
        -> stage <= 2: all-gather updated params, rebuild natural shapes
           stage 3: params stay sharded; the NEXT step gathers them.

        Chunks are cut along axis 0 of each leaf's flat view, which for a
        leaf that ``ZeroLayout.splits`` (the dp width divides its leading
        dimension, its chunk fills the chip's tiles) is the leaf itself:
        collectives, slices and the update run on the natural shape and
        nothing is reshaped or padded.

        Numerics match the replicated step bitwise on the CPU mesh: the
        per-row losses, the 1/n_valid cotangent, and the elementwise
        transform are the same programs, and the cross-chip sum reduces
        the same per-chip partials (psum / psum_scatter are the same
        reduction, differently placed).  Norm-coupled transforms
        (clip_unit_norm, clip_by_global_norm) would see shard-local norms
        and are NOT exact under zero_stage >= 1 — documented in §15.
        """
        mesh, n_dp, stage = self.mesh, self.n_dp, self.zero_stage
        z = self._zero
        if z is None:
            raise RuntimeError("zero step built before init_state — the "
                               "layout comes from the param shapes")

        def local(params, tstate, x, y, key, iteration, n_valid):
            if stage >= 3:
                with jax.named_scope("grad_sync"):
                    flat_full = jax.tree_util.tree_map(
                        lambda c: clv.all_gather_or_identity(c, DP, n_dp),
                        params)
                    nat = z.unflatten_like(flat_full, z.natural_params)
            else:
                nat = params
            with jax.named_scope("loss_reduce"):
                idx = clv.axis_index(DP)
                rows = idx * x.shape[0] + jnp.arange(x.shape[0])
                mask = rows < n_valid

            def local_sum(p):
                per = self._per_example(p, x, y, key)
                with jax.named_scope("loss_reduce"):
                    return jnp.sum(per * mask.astype(per.dtype))

            # vjp with a 1/n_valid cotangent == grad of the GLOBAL masked
            # mean: the division folds into the backward seed exactly where
            # pjit's autodiff puts it, so per-chip partial grads are the
            # same floats as the replicated step's pre-psum partials
            lsum, vjp_fn = jax.vjp(local_sum, nat)
            with jax.named_scope("loss_reduce"):
                denom = n_valid.astype(lsum.dtype)
                seed = jnp.ones((), lsum.dtype) / denom
            (grads,) = vjp_fn(seed)
            with jax.named_scope("loss_reduce"):
                loss = clv.psum(lsum, DP) / denom
            # the layout's reshapes, pads and slices are the exchange's own
            # (zero.layout, ZeroLayout's name for them, nests in grad_sync)
            with jax.named_scope("grad_sync"):
                gflat = z.flatten_tree(grads)
                if stage == 1:
                    gfull = jax.tree_util.tree_map(
                        lambda g: clv.psum(g, DP), gflat)
                    gchunk = z.chunk_tree(gfull, idx)
                else:
                    gchunk = jax.tree_util.tree_map(
                        lambda g: clv.reduce_scatter_or_psum(g, DP, n_dp),
                        gflat)
            if stage >= 3:
                pchunk = params  # already this chip's chunks
            else:
                with jax.named_scope("grad_sync"):
                    pchunk = z.chunk_tree(z.flatten_tree(nat), idx)
            # decay classification must come from the NATURAL shapes — on
            # the 1-D chunks of a leaf that flattens, the ndim >= 2
            # heuristic would decay nothing
            with tfm.decay_mask_override(z.decay_mask), \
                    jax.named_scope("optimizer"):
                updates, tstate = self.transform.update(
                    gchunk, tstate, pchunk, iteration)
                pchunk = tfm.apply_updates(pchunk, updates)
            if stage >= 3:
                return pchunk, tstate, loss
            with jax.named_scope("grad_sync"):
                pfull = jax.tree_util.tree_map(
                    lambda c: clv.all_gather_or_identity(c, DP, n_dp), pchunk)
                return z.unflatten_like(pfull, z.natural_params), tstate, loss

        param_spec = P(DP) if stage >= 3 else P()
        smapped = shard_map(
            local, mesh=mesh,
            in_specs=(param_spec, P(DP), P(DP), P(DP), P(), P(), P()),
            out_specs=(param_spec, P(DP), P()),
            check_vma=False,
        )
        # baseline mode: the ZeRO placements are emergent (stage-dependent
        # param spec), so the first dispatch captures them and later drift
        # — not the initial layout — is the violation
        return SHARDGUARD.wrap(
            "train.zero_step", _jit_step(smapped, donate_argnums=(0, 1)))

    def _build_local_step(self):
        """HogWild-approx local step: runs independently per dp shard."""
        mesh = self.mesh
        masked = self._masked_mean_loss(lambda k: k[0])

        def local(params, tstate, x, y, key, iteration, n_valid):
            # leading dp axis stripped by shard_map (shard size 1) -> squeeze
            params = jax.tree_util.tree_map(lambda a: a[0], params)
            tstate = jax.tree_util.tree_map(
                lambda a: a[0] if isinstance(a, jnp.ndarray) else a, tstate)
            # global row ids of this shard's slice -> local validity mask
            with jax.named_scope("loss_reduce"):
                rows = (jax.lax.axis_index(DP) * x.shape[0]
                        + jnp.arange(x.shape[0]))
                mask = rows < n_valid[0]
                denom = jnp.maximum(jnp.sum(mask), 1)  # all-pad shard guard
            loss, grads = jax.value_and_grad(masked)(
                params, x, y, key, mask, denom)
            with jax.named_scope("optimizer"):
                updates, tstate = self.transform.update(
                    grads, tstate, params, iteration[0])
                params = tfm.apply_updates(params, updates)
            expand = lambda a: a[None] if isinstance(a, jnp.ndarray) else a
            return (jax.tree_util.tree_map(expand, params),
                    jax.tree_util.tree_map(expand, tstate), loss[None])

        smapped = shard_map(
            local, mesh=mesh,
            in_specs=(P(DP), P(DP), P(DP), P(DP), P(DP), P(DP), P(DP)),
            out_specs=(P(DP), P(DP), P(DP)),
            check_vma=False,
        )
        return _jit_step(smapped, donate_argnums=(0, 1))

    def _build_average(self):
        """Periodic parameter averaging: one pmean inside shard_map."""
        mesh = self.mesh

        def avg(params):
            local = jax.tree_util.tree_map(lambda a: a[0], params)
            meaned = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, DP), local)
            return jax.tree_util.tree_map(lambda a: a[None], meaned)

        return jax.jit(shard_map(
            avg, mesh=mesh, in_specs=(P(DP),), out_specs=P(DP),
            check_vma=False))

    def _step_for(self, bucket: int):
        fn = self._step_cache.get(bucket)
        if fn is None:
            # one compiled variant per bucket — the counter the perf smoke
            # asserts on: steady-state recompiles == buckets used
            METRICS.increment("train_step.recompile")
            if self.router == "iterative_reduce":
                fn = (self._build_zero_step() if self.zero_stage
                      else self._build_sync_step())
            else:
                fn = self._build_local_step()
                if self._avg_fn is None:
                    self._avg_fn = self._build_average()
            self._step_cache[bucket] = fn
        return fn

    # ------------------------------------------------------------------ api
    def step(self, state: TrainState, x, y) -> tuple[TrainState, LazyLoss]:
        """Dispatch one step; returns the new state and a :class:`LazyLoss`.

        The loss handle is float-compatible (``float(loss)`` forces the
        device->host sync) but the hot loop should leave resolution to
        ``fit``'s batched fences.
        """
        x, y, n_valid, bucket = self._pad_to_bucket(x, y)
        return self._dispatch(state, x, y, n_valid, bucket)

    def _dispatch(self, state: TrainState, x, y, n_valid: int,
                  bucket: int) -> tuple[TrainState, LazyLoss]:
        # chaos seam: transient step failure (disarmed cost: one attr test)
        FAULTS.maybe_fire("train.step", state.step + 1)
        # chaos seam: device loss — ``kind`` is the number of chips that
        # "die" (default 1, always leaving at least one survivor).  Raises
        # DeviceLossError so the supervisor can rebuild the mesh from the
        # survivors instead of retrying onto dead hardware.
        spec = FAULTS.check("mesh.shrink", state.step + 1)
        if spec is not None:
            devs = mesh_devices(self.mesh)
            k = int(spec.kind) if str(spec.kind or "").isdigit() else 1
            k = max(1, min(k, len(devs) - 1)) if len(devs) > 1 else 1
            raise DeviceLossError(state.step + 1, devs[-k:])
        # Observability is gated on one flag check: when disabled, no span
        # object, no perf_counter read, no registry lock on this path.
        obs = _obs_enabled()
        first = bucket not in self._step_cache  # first call pays trace+compile
        t0 = time.perf_counter() if obs else 0.0
        cm = trace.span("train_step.compile" if first else "train_step",
                        step=state.step, router=self.router) if obs else NOOP_SPAN
        with cm:
            step_fn = self._step_for(bucket)
            state.key, sub = jax.random.split(state.key)
            # every argument crosses to its device placement EXPLICITLY
            # (device_put, a no-op when already placed): under the hot-loop
            # transfer guard an implicit jnp.asarray(int) or a numpy batch
            # leaking into the jitted call would raise on every step
            x = jax.device_put(x, self._batch_sh)
            y = jax.device_put(y, self._batch_sh)
            if self.router == "iterative_reduce":
                args = (state.params, state.tstate, x, y,
                        jax.device_put(sub, self._rep_sh),
                        jax.device_put(np.int32(state.step), self._rep_sh),
                        jax.device_put(np.int32(n_valid), self._rep_sh))
            else:
                keys = jax.device_put(jax.random.split(sub, self.n_dp),
                                      self._batch_sh)
                iters = jax.device_put(
                    np.full((self.n_dp,), state.step, np.int32),
                    self._batch_sh)
                nv = jax.device_put(
                    np.full((self.n_dp,), n_valid, np.int32), self._batch_sh)
                args = (state.params, state.tstate, x, y, keys, iters, nv)
            if first and obs:
                # XLA cost per dispatch for this bucket (lower() reads
                # avals only — safe before the donating call); feeds the
                # live train.mfu gauge at every resolution fence
                self._step_cost = COSTS.capture(
                    f"train_step.b{bucket}", step_fn, *args)
                # the shapes a reader of a trace compiles again to ask what
                # each fusion of this step holds (nothing is compiled here)
                scopemap.register(step_fn, *args)
            params, tstate, loss = step_fn(*args)
            if self.router != "iterative_reduce" \
                    and (state.step + 1) % self.average_every == 0:
                params = self._avg_fn(params)
                if obs:
                    METRICS.increment("train_step.periodic_average")
        lazy = LazyLoss(loss)
        if obs:
            dt = time.perf_counter() - t0
            # compile-vs-execute split: the first call's wall time is
            # dominated by trace+lower+compile — keep it out of the steady
            # state histogram so p99 means what a dashboard thinks it means.
            # Steady-state entries time DISPATCH only (the loop is async);
            # execution time lands in train_step.execute at resolution.
            METRICS.observe_time("train_step.compile" if first else "train_step", dt)
            METRICS.increment("train_step.iterations")
        if not self._pending:
            self._window_t0 = t0 if obs else time.perf_counter()
        self._pending.append((lazy, n_valid, state.step + 1))
        if len(self._pending) >= self.max_pending:
            self._resolve_pending()  # ring full: self-fence (bounded queue)
        return TrainState(params, tstate, state.step + 1, state.key), lazy

    def _resolve_pending(self) -> list[float]:
        """Fence: block until every pending loss is on host, then publish
        the window's metrics in one batch (gauges/histograms move HERE so
        the dispatch loop never syncs)."""
        if not self._pending:
            return []
        entries, self._pending = self._pending, []
        obs = _obs_enabled()
        with trace.span("trainer.fence", n=len(entries)):
            wait0 = time.perf_counter() if obs else 0.0
            # Device programs execute in dispatch order, so the losses become
            # ready in order and the last one's wait is the fence.  Each is
            # waited for under a span of its own: a profiler trace keeps a
            # span only if it starts and ends inside the traced slice, and
            # one wait for a whole window (seconds) seldom does.  fence.wait:
            # the device is still working; fence.read (values to floats,
            # metrics published): it has nothing queued
            for lazy, _n, _s in entries:
                with trace.span("trainer.fence.wait"):
                    lazy.block()
            with trace.span("trainer.fence.read"):
                vals = [lazy.value() for lazy, _n, _s in entries]
                if obs:
                    self._publish_window(entries, vals, wait0)
        self._window_t0 = None
        if self._nan_guard:
            # divergence detection lives at the resolution point — the one
            # place losses are host floats anyway, so the guard adds no sync
            for (_lazy, _n, s), v in zip(entries, vals):
                if not np.isfinite(v):
                    METRICS.increment("resilience.nan_detected")
                    raise DivergenceError(s, v)
        return vals

    def _publish_window(self, entries, vals, wait0: float) -> None:
        """The resolved window's gauges and histograms, at the fence."""
        now = time.perf_counter()
        METRICS.observe_time("train_step.resolve_wait", now - wait0)
        METRICS.increment("train_step.losses_resolved", len(vals))
        METRICS.gauge("train_step.loss", vals[-1])
        t0 = self._window_t0
        if t0 is not None and now > t0:
            window = now - t0
            n_samples = sum(n for _, n, _s in entries)
            METRICS.gauge("train_step.samples_per_sec", n_samples / window)
            # amortized per-step execution time over the async window —
            # the steady-state throughput histogram (dispatch times in
            # `train_step` no longer measure execution)
            METRICS.observe_many(
                "train_step.execute", [window / len(entries)] * len(entries))
            # live MFU/MBU from the same cost_analysis() accounting
            # bench reports: one dispatch's flops over the amortized
            # per-step execution time
            COSTS.publish_utilization(
                self._step_cost, window / len(entries),
                "train.mfu", "train.mbu")

    def abort(self) -> None:
        """Drop the pending-loss ring without resolving — the supervisor's
        retry path discards the in-flight window along with the state that
        produced it, then resumes from the last checkpoint."""
        self._pending.clear()
        self._window_t0 = None
        METRICS.increment("resilience.aborts")

    # ------------------------------------------------------------------ fit
    def _host_stream(self, data, epochs: int, skip: int, prefetch_size: int):
        """Stream (x, y, n_valid, bucket) tuples: host-side bucket padding,
        then double-buffered device transfer via ``prefetch_to_device``
        with this trainer's batch sharding — H2D overlaps compute on the
        production path, not just in bench.  Accepts a DataSet, a sequence,
        a DataSetIterator, or a one-shot generator (no ``list(data)``);
        re-iterable inputs replay for ``epochs``, one-shot generators
        stream a single pass."""
        if isinstance(data, DataSet):
            data = (data,)

        def batches():
            idx = 0
            for _ in range(max(1, int(epochs))):
                for b in iter(data):
                    if idx < skip:  # checkpoint-resume cursor
                        idx += 1
                        continue
                    idx += 1
                    # chaos seam: input-pipeline failure mid-stream
                    FAULTS.maybe_fire("data.next", idx)
                    x, y = ((b.features, b.labels)
                            if hasattr(b, "features") else (b[0], b[1]))
                    if not isinstance(x, jnp.ndarray):
                        x, y = np.asarray(x), np.asarray(y)
                    yield self._pad_to_bucket(x, y)

        if prefetch_size <= 0:
            return batches()
        from ..datasets.iterator import prefetch_to_device
        return prefetch_to_device(batches(), size=prefetch_size,
                                  sharding=NamedSharding(self.mesh, P(DP)))

    def fit(self, state: TrainState, data: Iterable[DataSet] | DataSet,
            epochs: int = 1, *, checkpoint_manager=None,
            checkpoint_every: int = 0, resume: bool = True,
            async_dispatch: bool = True, resolve_every: int = 32,
            prefetch_size: int = 2, nan_guard: bool = False,
            should_stop: Callable[[int], bool] | None = None,
            extra_skip: int = 0, goodput=None,
            ) -> tuple[TrainState, list[float]]:
        """Run ``epochs`` passes over ``data``, counting steps from
        ``state.step`` — so a state restored from a checkpoint continues
        where it left off (the elastic-recovery resume path; the reference
        only ever re-loaded bare params, ``ModelSavingActor.java:75-79``).

        ``data`` may be any iterable of batches and is never materialized;
        batches flow host-pad -> prefetch double-buffer -> jitted step.
        With ``async_dispatch`` (default) losses resolve in batches every
        ``resolve_every`` steps behind one ``block_until_ready`` fence;
        ``async_dispatch=False`` is the synchronous per-step reference
        path (same compiled steps, same numbers — used by the parity
        tests).  Returned losses are resolved floats either way.

        With ``checkpoint_manager`` set, auto-saves params + transform state
        + RNG key + data cursor every ``checkpoint_every`` steps (and at the
        end) — each save fences pending steps first; with ``resume``
        (default) restores the latest checkpoint before training.

        Supervisor hooks (all default-off; see ``resilience/``):
        ``nan_guard`` raises :class:`~..resilience.faults.DivergenceError`
        when a resolved loss is non-finite; ``should_stop(step)`` is polled
        after every dispatch — True drains the ring, writes an emergency
        checkpoint and returns (preemption handling); ``extra_skip`` drops
        that many additional stream batches past the resume cursor (the
        supervisor's divergence batch-window skip); ``goodput`` is an
        optional :class:`~..observability.goodput.GoodputTracker` the loop
        marks with restore/checkpoint/stall/drain intervals (``None`` —
        the default, and always the case when observability is off — adds
        zero per-step work: no clock reads, no allocations)."""
        n_known = len(data) if hasattr(data, "__len__") else -1
        self._nan_guard = nan_guard
        with trace.span("trainer.fit", epochs=epochs, n_batches=n_known,
                        router=self.router):
            if checkpoint_manager is not None and resume \
                    and checkpoint_manager.latest_step() is not None:
                if goodput is not None:
                    goodput.transition("restore")
                try:
                    state = self.restore(state, checkpoint_manager)
                except FileNotFoundError:
                    # every on-disk checkpoint failed verification — train
                    # from scratch rather than load corrupt state
                    METRICS.increment("checkpoint.no_valid_restore")
            if goodput is not None:
                # whatever the caller left us in (rollback backoff, resize
                # restore, drain), dispatching steps is productive time
                goodput.transition("productive")
            handles: list[LazyLoss] = []
            draining = False
            # steady state runs under the transfer guard: every host<->device
            # crossing in the loop must be an explicit device_put/device_get
            # (opt out via DL4J_TPU_TRANSFER_GUARD=0; see analysis.runtime)
            with hot_loop_guard():
                stream = iter(self._host_stream(
                    data, epochs, state.step + extra_skip, prefetch_size))
                while True:
                    if goodput is not None:
                        t_fetch = time.perf_counter()
                    with trace.span("trainer.data_wait"):
                        item = next(stream, None)
                    if item is None:
                        break
                    x, y, n_valid, bucket = item
                    if goodput is not None:
                        goodput.data_wait(t_fetch, time.perf_counter())
                    state, lazy = self._dispatch(state, x, y, n_valid, bucket)
                    handles.append(lazy)
                    if not async_dispatch:
                        self._resolve_pending()  # sync reference path
                    elif resolve_every and len(self._pending) >= resolve_every:
                        self._resolve_pending()
                    if should_stop is not None and should_stop(state.step):
                        # preemption: drain in-flight steps, snapshot, leave
                        # (goodput: everything from the stop signal to the
                        # return — including the emergency save — is drain)
                        if goodput is not None:
                            goodput.transition("drain")
                        draining = True
                        self._resolve_pending()
                        if checkpoint_manager is not None:
                            self.checkpoint(state, checkpoint_manager)
                        METRICS.increment("resilience.emergency_checkpoints")
                        break
                    if (checkpoint_manager is not None and checkpoint_every > 0
                            and state.step % checkpoint_every == 0):
                        if goodput is not None:
                            with goodput.phase("checkpoint"):
                                self.checkpoint(state, checkpoint_manager)
                        else:
                            self.checkpoint(state, checkpoint_manager)
                self._resolve_pending()
            losses = [h.value() for h in handles]
            if checkpoint_manager is not None and losses:
                if goodput is not None and not draining:
                    with goodput.phase("checkpoint"):
                        self.checkpoint(state, checkpoint_manager)
                else:
                    self.checkpoint(state, checkpoint_manager)
        sample_device_memory()  # HBM gauges; no-op on CPU / when disabled
        return state, losses

    # ------------------------------------------------------------------ ckpt
    def checkpoint(self, state: TrainState, manager,
                   layout: str = "natural") -> None:
        """Fence-then-save: resolve the pending-loss ring and block on the
        state itself so the snapshot cannot race in-flight steps.

        ``layout="natural"`` (default) gathers ZeRO state back to natural
        shapes — the width-agnostic on-disk format.  ``layout="flat"``
        writes the on-device flat ``P('dp')`` leaves as-is (skipping the
        unflatten: natural shapes for leaves that split, 1-D padded vectors
        for the rest); the manager stamps the save-side width so a restore
        at any other width re-splits those host-side, exactly."""
        self._resolve_pending()
        jax.block_until_ready((state.params, state.tstate))
        METRICS.increment("checkpoint.fences")
        flat = layout == "flat" and self.zero_stage >= 1
        # the save pulls every leaf to host: a sanctioned sync point, so it
        # re-allows transfers even when called inside the guarded fit loop
        with allow_transfers():
            params, tstate, extra = state.params, state.tstate, None
            if self.zero_stage >= 1:
                # gather shard-local leaves and write the NATURAL layout:
                # the on-disk format is identical across stages and dp
                # widths, so restore can reshard onto any current mesh
                # (np.asarray on a dp-sharded leaf assembles the full
                # array from its chunks — single-host gather)
                z = self._zero
                if not flat:
                    tstate = z.to_natural_host(tstate, z.natural_tstate)
                    if self.zero_stage >= 3:
                        params = z.to_natural_host(params, z.natural_params)
                extra = {"zero_stage": self.zero_stage,
                         "saved_dp": int(self.n_dp)}
            manager.save(state.step, params, tstate=tstate,
                         key=state.key, data_cursor=state.step, extra=extra,
                         dp_width=int(self.n_dp), zero_stage=self.zero_stage,
                         layout="flat" if flat else "natural")

    def restore(self, template: TrainState, manager,
                reshard: bool = True) -> TrainState:
        """Restore the latest checkpoint into a state shaped like
        ``template`` (fresh ``init_state`` output), re-placed on the mesh.

        Under zero_stage >= 1 the checkpoint holds either the NATURAL
        layout (see :meth:`checkpoint`) or the flat save-side layout the
        manager re-splits; restoring re-flattens and re-shards onto THIS
        trainer's mesh — a checkpoint written at dp=2 restores onto dp=1
        (and vice versa) bit-for-bit.  The trainer's contract IS
        resharding, so ``reshard`` defaults to True; pass False to get the
        strict ``MeshMismatchError`` behavior across widths."""
        if self.zero_stage >= 1:
            state = self._restore_zero(template, manager, reshard=reshard)
        else:
            r = manager.restore(template.params,
                                tstate_template=template.tstate,
                                reshard=reshard, dp_width=int(self.n_dp))
            params = jax.tree_util.tree_map(
                lambda t, a: jax.device_put(jnp.asarray(a), t.sharding),
                template.params, r["params"])
            tstate = template.tstate
            if r["tstate"] is not None:
                tstate = jax.tree_util.tree_map(
                    lambda t, a: (jax.device_put(jnp.asarray(a), t.sharding)
                                  if isinstance(t, jnp.ndarray) else a),
                    template.tstate, r["tstate"])
            key = r["key"] if r["key"] is not None else template.key
            state = TrainState(params=params, tstate=tstate,
                               step=r["step"], key=key)
        sample_state_bytes(state.params, state.tstate)  # ZeRO memory gauges
        return state

    def _restore_zero(self, template: TrainState, manager,
                      reshard: bool = True) -> TrainState:
        """Reshard a natural-layout checkpoint onto the current mesh: load
        against abstract natural templates, then jit-flatten each tree
        straight into its cached dp sharding (no replicated intermediate)."""
        z = self._zero
        if z is None:
            # templates normally come from init_state (which builds the
            # layout); under stage 3 they MUST — template params are
            # already flat there, so natural shapes are unrecoverable
            if self.zero_stage >= 3:
                raise RuntimeError(
                    "zero_stage=3 restore needs a template from init_state")
            z = self._zero_layout(template.params)
        # restore is a sanctioned sync point like save: loading npz leaves
        # and re-placing them is setup, not the hot loop
        with allow_transfers():
            r = manager.restore(z.natural_params,
                                tstate_template=z.natural_tstate,
                                reshard=reshard, dp_width=int(self.n_dp))
            nat_params = jax.tree_util.tree_map(jnp.asarray, r["params"])
            if self.zero_stage >= 3:
                params = z.place_flat(nat_params, z.flat_sharding)
            else:
                params = jax.device_put(nat_params, self._rep_sh)
            tstate = template.tstate
            if r["tstate"] is not None:
                nat_t = jax.tree_util.tree_map(
                    lambda a: (jnp.asarray(a)
                               if isinstance(a, (jnp.ndarray, np.ndarray))
                               else a), r["tstate"])
                tstate = z.place_flat(nat_t, z.state_shardings)
        key = r["key"] if r["key"] is not None else template.key
        return TrainState(params=params, tstate=tstate,
                          step=r["step"], key=key)

    def final_params(self, state: TrainState):
        """Collapse to a single param set (average replicas for hogwild;
        gather + unflatten the sharded chunks for zero_stage 3)."""
        if self.zero_stage >= 3:
            z = self._zero
            return jax.jit(
                lambda t: z.unflatten_like(t, z.natural_params),
                out_shardings=self._rep_sh)(state.params)
        if self.router == "hogwild":
            # one-shot post-fit collapse; the x[0] gather index is a
            # setup-style constant a surrounding guard would reject
            with allow_transfers():
                avgd = (self._avg_fn(state.params) if self._avg_fn
                        else state.params)
                return jax.tree_util.tree_map(lambda a: a[0], avgd)
        return state.params
