"""The collectives layer — the reference's "NCCL"/transport, TPU-native.

The reference moves parameters/updates through Akka remote messages +
Hazelcast IMaps/ILists + Avro RPC (SURVEY.md §2.3 backend table).  On TPU the
entire data plane is XLA collectives compiled into the step function and
riding ICI (intra-slice) / DCN (inter-slice).  These wrappers name that
surface explicitly — use them inside ``shard_map``-ped functions; under plain
``pjit`` sharding propagation inserts the same collectives automatically.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def psum(x, axis: str):
    """All-reduce sum over a mesh axis (≡ parameter-averaging numerator,
    ``INDArrayAggregator.accumulate``)."""
    return lax.psum(x, axis)


def pmean(x, axis: str):
    """All-reduce mean (≡ ``IterativeReduceWorkRouter`` averaging in one op)."""
    return lax.pmean(x, axis)


def all_gather(x, axis: str, *, tiled: bool = False):
    return lax.all_gather(x, axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_dimension: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension, tiled=True)


# --------------------------------------------------------------- ZeRO helpers
#
# The sharded weight update (trainer zero_stage >= 1) communicates gradient
# and param chunks cut along axis 0 (zero.py: a leaf in its own shape, or a
# flattened 1-D vector).  On a 1-member dp axis the tiled collectives
# degenerate — the "scatter" of one tile is the whole array and the "gather"
# of one shard is the input — so these wrappers take the axis size explicitly
# and fall back to a plain psum / identity, keeping the dp=1 step the same
# compiled program shape as the replicated path.

def reduce_scatter_or_psum(x, axis: str, axis_size: int):
    """Reduce-scatter ``x`` (axis 0 divisible by ``axis_size``) into
    per-member contiguous tiles; psum fallback when the axis has one member
    (sum of one shard = the shard, and the tile IS the array)."""
    if axis_size == 1:
        return lax.psum(x, axis)
    return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)


def all_gather_or_identity(x, axis: str, axis_size: int):
    """Tiled all-gather of per-member chunks along axis 0 back to the
    full leaf; identity when the axis has one member."""
    if axis_size == 1:
        return x
    return lax.all_gather(x, axis, tiled=True)


def ppermute(x, axis: str, perm):
    """Neighbor exchange — the ring primitive under ring attention /
    pipeline micro-batch handoff."""
    return lax.ppermute(x, axis, perm)


def ring_shift(x, axis: str, axis_size: int, shift: int = 1):
    """Shift values around the ring by ``shift`` positions."""
    perm = [(i, (i + shift) % axis_size) for i in range(axis_size)]
    return lax.ppermute(x, axis, perm)


def axis_index(axis: str):
    return lax.axis_index(axis)


def barrier_sum(axis: str):
    """Cheap cross-device barrier: psum of a scalar 1 (control-plane sync;
    replaces the reference's 'wait for N worker updates' poll loop)."""
    return lax.psum(jnp.ones(()), axis)
