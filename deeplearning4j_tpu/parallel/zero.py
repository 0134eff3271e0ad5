"""ZeRO layout: the sharded view of a param tree that the sharded weight
update (DESIGN.md §15, arXiv:2004.13336) trains in.

Every leaf of the natural param tree has one "flat" view whose axis 0 a
``NamedSharding(mesh, P('dp'))`` cuts into one contiguous, equal-size chunk
a chip.  Which view is chosen per leaf, from its shape alone:

- a leaf whose leading dimension is a positive multiple of the dp width and
  whose chunk fills the chip's tiles (``ZeroLayout.splits``) is sharded IN
  ITS OWN SHAPE along axis 0: the flat view is the natural view, a chunk is
  ``(d0 // n_dp, *rest)``, and flatten, unflatten and chunk do no reshape, no
  pad and no slice of padding.  A TPU keeps an f32 array in (8, 128) tiles
  over its last two dimensions, so a reshape of a matrix to 1-D and back is
  a physical relayout, a pass over the leaf that computes nothing;
- any other leaf maps to a 1-D vector zero-padded to a multiple of the dp
  width: a scalar, a leading dimension the width does not divide, and a leaf
  whose last two dimensions do not fill those tiles — BERT's ``(768, 3, 12,
  64)`` query-key-value weight pads 12 x 64 to 16 x 128, and sharded in its
  own shape its all-gather and copies cost the four-chip step 1.1 ms more
  than the 1-D vector's (PERF.md §6, PR 31).

Optimizer-state leaves mirror the flat tree (the ``state_spec`` contract in
``optimize/transforms``), which is what makes the shard-local
``transform.update`` exact: every transform in this repo is elementwise
over its leaves, so updating 1/ndp of the elements on each chip computes
the same numbers the replicated update would — padding carries zero
gradients and is sliced off before the natural view is rebuilt.

The layout is pure metadata (``ShapeDtypeStruct`` trees + cached
shardings): flatten/unflatten are trace-safe and appear both inside the
jitted step (grads, param chunks) and on the host checkpoint path
(``to_natural_host`` gathers shard-local leaves and restores natural
shapes, so the on-disk format is identical across stages and dp widths —
the portable-restore requirement of arXiv:2112.01075).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observability import METRICS
from ..optimize import transforms as tfm
from .mesh import DP

tree_map = jax.tree_util.tree_map


# an f32 array's tile on a TPU, over its last two dimensions (gradients, the
# optimizer state and the collectives are f32 whatever the model computes in)
_SUBLANES, _LANES = 8, 128


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _size(shape) -> int:
    return int(np.prod(shape, dtype=np.int64)) if shape else 1


def flat_padded_size(size: int, n_dp: int) -> int:
    """Length of a natural leaf of ``size`` elements in the flat padded
    ``P('dp')`` layout at width ``n_dp`` — dp-divisible, never empty.
    Module-level twin of ``ZeroLayout.padded_size`` for host code that has
    only the checkpoint metadata, not a live mesh."""
    return max(_round_up(size, n_dp), n_dp)


def host_flat_to_natural(arr: np.ndarray, shape, saved_dp: int) -> np.ndarray:
    """Exact host-side re-split of one flat padded leaf back to its natural
    shape (arXiv:2112.01075 portable redistribution, degenerate host case:
    the padding is zeros by construction, so slicing it off loses nothing
    and no renormalization happens).  Raises ValueError when the length is
    not the padded length of ``shape`` at ``saved_dp``."""
    arr = np.asarray(arr)
    size = _size(shape)
    want = flat_padded_size(size, saved_dp)
    if arr.ndim != 1 or arr.shape[0] != want:
        raise ValueError(
            f"flat leaf has shape {arr.shape}, expected ({want},) for "
            f"natural shape {tuple(shape)} at saved dp={saved_dp}")
    return arr[:size].reshape(shape)


def host_natural_to_flat(arr: np.ndarray, n_dp: int) -> np.ndarray:
    """Exact host-side flatten+pad of one natural leaf for width ``n_dp``."""
    flat = np.asarray(arr).reshape(-1)
    pad = flat_padded_size(flat.shape[0], n_dp) - flat.shape[0]
    if pad:
        flat = np.concatenate([flat, np.zeros((pad,), flat.dtype)])
    return flat


class ZeroLayout:
    """Static shard metadata for one (mesh, transform, params): which leaves
    split in their own shape and which flatten + pad.

    Built once at ``init_state`` from abstract shapes only — nothing here
    touches device memory, so constructing a layout is transfer-guard safe.
    """

    def __init__(self, mesh, transform: tfm.GradientTransform, params):
        self.mesh = mesh
        self.n_dp = int(mesh.shape[DP])
        self.transform = transform
        self.natural_params = jax.eval_shape(lambda t: t, params)
        self.natural_tstate = jax.eval_shape(transform.init,
                                             self.natural_params)
        self.flat_sharding = NamedSharding(mesh, P(DP))
        flat_params = jax.eval_shape(self.flatten_tree, self.natural_params)
        self.state_shardings = tfm.state_shardings(
            transform, flat_params, P(DP), mesh)
        # weight-decay classification comes from the NATURAL layout: the
        # ndim >= 2 heuristic is meaningless on the 1-D chunks of a leaf
        # that flattens, so the sharded step pushes this mask through
        # decay_mask_override
        self.decay_mask = tree_map(lambda a: a.ndim >= 2, self.natural_params)
        # which path each leaf took, once per layout (as attention.path.*
        # is counted once per trace)
        split = [self.splits(a.shape)
                 for a in jax.tree_util.tree_leaves(self.natural_params)]
        METRICS.increment("zero.leaves.natural", sum(split))
        METRICS.increment("zero.leaves.flat", len(split) - sum(split))

    # ---------------------------------------------------------- per-leaf ops
    def splits(self, shape) -> bool:
        """True for a leaf that is sharded in its own shape along axis 0:
        its leading dimension is a positive multiple of the dp width, and
        its chunk's last two dimensions are whole (8, 128) tiles (a 1-D
        leaf's chunk is contiguous either way)."""
        if not shape or shape[0] <= 0 or shape[0] % self.n_dp:
            return False
        if len(shape) == 1:
            return True
        rows = shape[0] // self.n_dp if len(shape) == 2 else shape[-2]
        return shape[-1] % _LANES == 0 and rows % _SUBLANES == 0

    def padded_size(self, size: int) -> int:
        """Length of a leaf that flattens, after zero-padding: dp-divisible,
        never empty."""
        return flat_padded_size(size, self.n_dp)

    def chunk_size(self, size: int) -> int:
        return self.padded_size(size) // self.n_dp

    def _flatten_leaf(self, x):
        if self.splits(x.shape):
            return x
        flat = jnp.reshape(x, (-1,))
        pad = self.padded_size(flat.shape[0]) - flat.shape[0]
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return flat

    def _unflatten_leaf(self, v, shape):
        """``v`` (a jax or a numpy array) back in its natural ``shape``."""
        if self.splits(shape):
            return v
        return v[:_size(shape)].reshape(shape)

    # ---------------------------------------------------------- tree ops
    # In a trace the three views below are ``zero.layout``: reshapes, pads
    # and slices that compute nothing.  The sharded step calls them inside
    # ``grad_sync``, whose share of a step they are part of.
    @jax.named_scope("zero.layout")
    def flatten_tree(self, tree):
        """Natural -> flat view, leaf by leaf (trace-safe).  Works on any
        tree whose array leaves carry natural shapes — params and the
        optimizer state both, since state leaves mirror param shapes."""
        return tree_map(self._flatten_leaf, tree)

    @jax.named_scope("zero.layout")
    def unflatten_like(self, flat_tree, natural_template):
        """Flat view -> natural shapes (trace-safe): a leaf that split is
        already there; one that flattened has its pad sliced off and is
        reshaped to the template leaf's shape."""
        return tree_map(lambda v, t: self._unflatten_leaf(v, t.shape),
                        flat_tree, natural_template)

    @jax.named_scope("zero.layout")
    def chunk_tree(self, flat_tree, idx):
        """This chip's contiguous chunk of every flat leaf along axis 0
        (inside shard_map: ``idx = lax.axis_index(dp)``)."""
        def chunk(v):
            rows = v.shape[0] // self.n_dp
            return lax.dynamic_slice_in_dim(v, idx * rows, rows, axis=0)

        return tree_map(chunk, flat_tree)

    # ---------------------------------------------------------- host ops
    def to_natural_host(self, flat_tree, natural_template):
        """Gather shard-local flat leaves to host numpy and rebuild the
        natural layout — the mesh-agnostic checkpoint payload (a zero-N
        checkpoint is byte-compatible with a replicated one, and restores
        onto any dp width)."""
        return tree_map(
            lambda v, t: (self._unflatten_leaf(np.asarray(v), t.shape)
                          if isinstance(v, (jnp.ndarray, np.ndarray)) else v),
            flat_tree, natural_template)

    def place_flat(self, natural_tree, out_shardings):
        """Natural-layout host/device arrays -> flat leaves placed per
        ``out_shardings`` (restore path: reshard onto the CURRENT mesh,
        whatever dp width wrote the checkpoint)."""
        return jax.jit(self.flatten_tree,
                       out_shardings=out_shardings)(natural_tree)
