"""Mesh-sharded embedding tables — distributed Word2Vec/GloVe training.

TPU-native equivalent of the reference's distributed sparse-embedding
strategy (``scaleout/perform/models/word2vec/Word2VecWork.java`` +
``Word2VecPerformer.java:72-137``, GloVe mirror ``GlovePerformer.java``):
there, workers receive only the *rows* of syn0/syn1 their sentences touch
and return per-row deltas the master applies.  On a TPU mesh the same
semantics become SPMD primitives over the ``ep`` (embedding-parallel) axis:

- **tables row-sharded**: syn0/syn1/syn1neg live as ``P(ep, None)`` shards —
  each device owns ``rows/ep`` rows, so vocab size scales with the mesh.
- **row shipping = masked gather + psum**: every device materializes the
  batch's rows by gathering the ones it owns (others contribute zeros) and
  ``psum``-ing over ``ep`` — the collective IS the row shipment.
- **per-row deltas = masked scatter-add**: after the (identical, replicated)
  delta computation, each device applies only the rows it owns.  Duplicate
  indices within a batch accumulate exactly (XLA scatter-add), matching the
  reference's sequential per-pair ``axpy`` application order-independently.

The batch (center/context/path indices) is replicated across ``ep`` —
compute is tiny next to HBM for realistic tables, and replication keeps the
update equivalent to the single-device schedule bit-for-bit (tested).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import EP
from .glove import Glove
from .word2vec import Word2Vec


def pad_rows(n: int, n_shards: int) -> int:
    """Rows padded up so each shard owns an equal slice."""
    return ((max(n, 1) + n_shards - 1) // n_shards) * n_shards


def gather_rows(table, idx, axis: str, rows_per: int):
    """Full rows for global indices from a row-sharded table: local masked
    gather + psum over the shard axis (the 'row shipping' collective)."""
    my = lax.axis_index(axis)
    loc = idx - my * rows_per
    valid = (loc >= 0) & (loc < rows_per)
    rows = table[jnp.clip(loc, 0, rows_per - 1)]
    rows = jnp.where(valid[..., None], rows, 0)
    return lax.psum(rows, axis)


def scatter_add_rows(table, idx, upd, axis: str, rows_per: int):
    """Apply per-row deltas to the locally-owned slice only."""
    my = lax.axis_index(axis)
    loc = idx - my * rows_per
    valid = (loc >= 0) & (loc < rows_per)
    upd = jnp.where(valid[..., None], upd, 0)
    return table.at[jnp.clip(loc, 0, rows_per - 1)].add(upd)


def _gather_vec(vec, idx, axis: str, rows_per: int):
    """gather_rows for 1-d tables (GloVe biases)."""
    my = lax.axis_index(axis)
    loc = idx - my * rows_per
    valid = (loc >= 0) & (loc < rows_per)
    vals = vec[jnp.clip(loc, 0, rows_per - 1)]
    return lax.psum(jnp.where(valid, vals, 0), axis)


def _scatter_add_vec(vec, idx, upd, axis: str, rows_per: int):
    my = lax.axis_index(axis)
    loc = idx - my * rows_per
    valid = (loc >= 0) & (loc < rows_per)
    return vec.at[jnp.clip(loc, 0, rows_per - 1)].add(jnp.where(valid, upd, 0))


# --------------------------------------------------------------------------- step builders

def build_hs_step(mesh: Mesh, rows0: int, rows1: int):
    """Sharded hierarchical-softmax skip-gram step (semantics of
    ``InMemoryLookupTable.java:182-222`` at batch granularity)."""
    n_ep = mesh.shape[EP]
    r0, r1 = rows0 // n_ep, rows1 // n_ep

    def local(syn0, syn1, centers, points, codes, mask, alpha):
        h = gather_rows(syn0, centers, EP, r0)             # (B, D)
        w = gather_rows(syn1, points, EP, r1)              # (B, L, D)
        u = jnp.einsum("bd,bld->bl", h, w)
        p = jax.nn.sigmoid(u)
        g = (1.0 - codes - p) * alpha * mask
        dh = jnp.einsum("bl,bld->bd", g, w)
        dw = g[:, :, None] * h[:, None, :]
        syn1 = scatter_add_rows(syn1, points, dw, EP, r1)
        syn0 = scatter_add_rows(syn0, centers, dh, EP, r0)
        return syn0, syn1

    t = P(EP, None)
    sm = shard_map(local, mesh=mesh,
                   in_specs=(t, t, P(), P(), P(), P(), P()),
                   out_specs=(t, t), check_vma=False)
    return jax.jit(sm, donate_argnums=(0, 1))


def build_ns_step(mesh: Mesh, rows0: int, rows1: int):
    """Sharded negative-sampling step (``InMemoryLookupTable.java:225-266``)."""
    n_ep = mesh.shape[EP]
    r0, r1 = rows0 // n_ep, rows1 // n_ep

    def local(syn0, syn1neg, centers, targets, labels, alpha):
        h = gather_rows(syn0, centers, EP, r0)
        w = gather_rows(syn1neg, targets, EP, r1)
        u = jnp.einsum("bd,bkd->bk", h, w)
        p = jax.nn.sigmoid(u)
        g = (labels - p) * alpha
        dh = jnp.einsum("bk,bkd->bd", g, w)
        dw = g[:, :, None] * h[:, None, :]
        syn1neg = scatter_add_rows(syn1neg, targets, dw, EP, r1)
        syn0 = scatter_add_rows(syn0, centers, dh, EP, r0)
        return syn0, syn1neg

    t = P(EP, None)
    sm = shard_map(local, mesh=mesh,
                   in_specs=(t, t, P(), P(), P(), P()),
                   out_specs=(t, t), check_vma=False)
    return jax.jit(sm, donate_argnums=(0, 1))


def build_glove_step(mesh: Mesh, rows: int, lr: float):
    """Sharded GloVe AdaGrad step (``GloveWeightLookupTable.java`` WLS)."""
    n_ep = mesh.shape[EP]
    r = rows // n_ep

    def local(w, wc, b, bc, hw, hwc, hb, hbc, rows_i, cols_i, logx, fx):
        wi = gather_rows(w, rows_i, EP, r)
        wj = gather_rows(wc, cols_i, EP, r)
        bi = _gather_vec(b, rows_i, EP, r)
        bj = _gather_vec(bc, cols_i, EP, r)
        diff = jnp.einsum("bd,bd->b", wi, wj) + bi + bj - logx
        wdiff = fx * diff
        gw = wdiff[:, None] * wj
        gwc = wdiff[:, None] * wi
        gb = wdiff
        hw = scatter_add_rows(hw, rows_i, gw * gw, EP, r)
        hwc = scatter_add_rows(hwc, cols_i, gwc * gwc, EP, r)
        hb = _scatter_add_vec(hb, rows_i, gb * gb, EP, r)
        hbc = _scatter_add_vec(hbc, cols_i, gb * gb, EP, r)
        hw_g = gather_rows(hw, rows_i, EP, r)
        hwc_g = gather_rows(hwc, cols_i, EP, r)
        hb_g = _gather_vec(hb, rows_i, EP, r)
        hbc_g = _gather_vec(hbc, cols_i, EP, r)
        w = scatter_add_rows(w, rows_i, -lr * gw * lax.rsqrt(hw_g + 1e-8), EP, r)
        wc = scatter_add_rows(wc, cols_i, -lr * gwc * lax.rsqrt(hwc_g + 1e-8), EP, r)
        b = _scatter_add_vec(b, rows_i, -lr * gb * lax.rsqrt(hb_g + 1e-8), EP, r)
        bc = _scatter_add_vec(bc, cols_i, -lr * gb * lax.rsqrt(hbc_g + 1e-8), EP, r)
        loss = 0.5 * jnp.mean(fx * diff * diff)
        return w, wc, b, bc, hw, hwc, hb, hbc, loss

    t, v = P(EP, None), P(EP)
    sm = shard_map(local, mesh=mesh,
                   in_specs=(t, t, v, v, t, t, v, v, P(), P(), P(), P()),
                   out_specs=(t, t, v, v, t, t, v, v, P()),
                   check_vma=False)
    return jax.jit(sm, donate_argnums=tuple(range(8)))


# --------------------------------------------------------------------------- models

class ShardedWord2Vec(Word2Vec):
    """Word2Vec with tables row-sharded over the mesh's ``ep`` axis.

    Same schedule, vocab, Huffman tree and hyperparameters as the
    single-device model — only the table placement and update kernels
    change, so results match ``Word2Vec`` exactly (tested)."""

    def __init__(self, sentences=None, *, mesh: Mesh, **kw):
        super().__init__(sentences, **kw)
        if EP not in mesh.shape or mesh.shape[EP] < 1:
            raise ValueError(f"mesh must carry an {EP!r} axis")
        self.mesh = mesh
        self._hs_fn = self._ns_fn = None

    def reset_weights(self) -> None:
        n_ep = self.mesh.shape[EP]
        n, d = len(self.vocab), self.layer_size
        n0, n1 = pad_rows(n, n_ep), pad_rows(max(n - 1, 1), n_ep)
        rng = np.random.default_rng(self.seed)
        syn0 = np.zeros((n0, d), np.float32)
        syn0[:n] = (rng.random((n, d), np.float32) - 0.5) / d
        sh = NamedSharding(self.mesh, P(EP, None))
        self.syn0 = jax.device_put(jnp.asarray(syn0), sh)
        self.syn1 = jax.device_put(jnp.zeros((n1, d), jnp.float32), sh)
        self._hs_fn = build_hs_step(self.mesh, n0, n1)
        if self.negative > 0:
            n1n = pad_rows(n, n_ep)
            self.syn1neg = jax.device_put(jnp.zeros((n1n, d), jnp.float32), sh)
            counts = self.vocab.counts_array() ** 0.75
            self._unigram_log = jnp.asarray(
                np.log(counts / counts.sum()), dtype=jnp.float32)
            self._ns_fn = build_ns_step(self.mesh, n0, n1n)

    def _apply_hs(self, cb, pts, cds, msk, alpha):
        self.syn0, self.syn1 = self._hs_fn(self.syn0, self.syn1, cb, pts,
                                           cds, msk, alpha)

    def _apply_ns(self, cb, targets, labels, alpha):
        self.syn0, self.syn1neg = self._ns_fn(self.syn0, self.syn1neg, cb,
                                              targets, labels, alpha)


class ShardedGlove(Glove):
    """GloVe with all six tables row-sharded over ``ep``."""

    def __init__(self, sentences=None, *, mesh: Mesh, **kw):
        super().__init__(sentences, **kw)
        if EP not in mesh.shape or mesh.shape[EP] < 1:
            raise ValueError(f"mesh must carry an {EP!r} axis")
        self.mesh = mesh
        self._step_fn = None
        self._n_pad = 0

    def _init_tables(self, n: int, d: int, rng) -> None:
        n_ep = self.mesh.shape[EP]
        self._n_pad = pad_rows(n, n_ep)
        w = np.zeros((self._n_pad, d), np.float32)
        wc = np.zeros((self._n_pad, d), np.float32)
        w[:n] = (rng.random((n, d), np.float32) - 0.5) / d
        wc[:n] = (rng.random((n, d), np.float32) - 0.5) / d
        t = NamedSharding(self.mesh, P(EP, None))
        v = NamedSharding(self.mesh, P(EP))
        zt = lambda: jax.device_put(
            jnp.zeros((self._n_pad, d), jnp.float32), t)
        zv = lambda: jax.device_put(jnp.zeros((self._n_pad,), jnp.float32), v)
        self._tables = [jax.device_put(jnp.asarray(w), t),
                        jax.device_put(jnp.asarray(wc), t),
                        zv(), zv(), zt(), zt(), zv(), zv()]
        self._step_fn = build_glove_step(self.mesh, self._n_pad,
                                         self.learning_rate)

    def _apply_step(self, rows, cols, logx, fx):
        """One sharded AdaGrad batch; returns the DEVICE loss so ``fit``
        resolves it at its own fence instead of draining the dispatch
        queue here (the mesh version pays a cross-device gather per sync,
        so the per-batch ``float(loss)`` this replaces was the single
        largest stall in the sharded GloVe hot loop)."""
        *self._tables, loss = self._step_fn(*self._tables, rows, cols, logx, fx)
        return loss
