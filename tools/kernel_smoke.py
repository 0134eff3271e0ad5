"""Kernel smoke: per-kernel microbench of the ops/pallas tier.

Times every registered kernel candidate at a small fixed shape (warmup
dispatch excluded, ``block_until_ready`` fences each timed call), records
µs/call and a naive bytes-moved estimate through the observability
layer's ``record_kernel_time`` (``kernel.<kind>.<name>`` histograms +
bytes/GB-s gauges), and prints one JSON line.

On CPU the kernels run in Pallas interpret mode, so the numbers are a
SANITY signal (does the kernel dispatch, is nothing pathologically
slow), NOT a perf claim — on-chip claims come only from the benchmark's
cells (``benchmark/run.py``, ``PERF_LEDGER.jsonl``).

Wired as a fast tier-1 test (``tests/test_kernel_smoke.py``); also
runnable standalone: ``python tools/kernel_smoke.py``.
"""

from __future__ import annotations

import json
import sys
import time

_SHAPES = {"B": 2, "T": 128, "H": 2, "D": 32, "N": 101, "V": 77, "K": 64}


def _bytes(*arrays) -> int:
    """Naive bytes-moved estimate: every input read once + output written
    once (ignores VMEM reuse — a deliberate upper-bound convention)."""
    return sum(int(a.size) * a.dtype.itemsize for a in arrays)


def _cases():
    """(kind, name, thunk, io_arrays) for one small call per candidate."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import registry
    from deeplearning4j_tpu.ops.pallas.matmul_int8 import quantize

    s = _SHAPES
    k = jax.random.PRNGKey(0)
    q, kk, v = (jax.random.normal(jax.random.fold_in(k, i),
                                  (s["B"], s["T"], s["H"], s["D"]),
                                  jnp.float32) for i in range(3))
    x = jax.random.normal(jax.random.fold_in(k, 3), (s["N"], s["K"]))
    r = jax.random.normal(jax.random.fold_in(k, 4), (s["N"], s["K"]))
    scale = jnp.ones((s["K"],))
    bias = jnp.zeros((s["K"],))
    head = jax.random.normal(jax.random.fold_in(k, 5), (s["K"], s["V"])) * 0.1
    tgt = jax.random.randint(jax.random.fold_in(k, 6), (s["N"],), 0, s["V"])
    qw = quantize(jax.random.normal(jax.random.fold_in(k, 7),
                                    (s["K"], s["V"])) * 0.05)
    n_phys, ps = 9, 16                          # paged decode: T = 4 pages
    pq = jax.random.normal(jax.random.fold_in(k, 8),
                           (s["B"], s["H"], s["D"]), jnp.float32)
    pk, pv = (jax.random.normal(jax.random.fold_in(k, 9 + i),
                                (n_phys, ps, s["H"], s["D"]), jnp.float32)
              for i in range(2))
    bt = jax.random.permutation(
        jax.random.fold_in(k, 11),
        jnp.arange(n_phys, dtype=jnp.int32))[: s["B"] * 4].reshape(s["B"], 4)
    lens = jnp.asarray([ps * 4, ps * 2 + 3], jnp.int32)
    from deeplearning4j_tpu.ops.pallas import kv_quant as kvq
    s0 = jnp.full((n_phys, s["H"]), kvq.neutral_scale(jnp.int8), jnp.float32)
    pkq, pks = kvq.requantize_pool(pk, s0, jnp.int8)
    pvq, pvs = kvq.requantize_pool(pv, s0, jnp.int8)

    chosen = jnp.tril(jnp.ones((s["T"], s["T"]), bool))
    calls = {
        ("attention", None): (lambda fn: fn(q, kk, v, causal=True),
                              (q, kk, v, q)),
        ("sparse_attention", None): (lambda fn: fn(q[0], kk[0], v[0], chosen),
                                     (q[0], kk[0], v[0], chosen, q[0])),
        ("layernorm_residual", None): (lambda fn: fn(x, r, scale, bias),
                                       (x, r, x, x)),
        ("xent", None): (lambda fn: fn(x, head, tgt), (x, head, tgt)),
        ("int8_matmul", None): (lambda fn: fn(x[:, :s["K"]], qw),
                                (x, qw.q, qw.scale)),
        ("paged_attention", None): (lambda fn: fn(pq, pk, pv, bt, lens),
                                    (pq, pk, pv, bt, lens, pq)),
        ("paged_attention_int8", None): (
            lambda fn: fn(pq, pkq, pvq, pks, pvs, bt, lens),
            (pq, pkq, pvq, pks, pvs, bt, lens, pq)),
    }
    for kind in registry.kinds():
        call, io = calls[(kind, None)]
        for cand in registry.candidates(kind):
            yield kind, cand.name, (lambda c=cand, call=call: call(c.fn)), io


def run() -> dict:
    import jax

    from deeplearning4j_tpu.observability.kernels import record_kernel_time

    results = {}
    for kind, name, thunk, io in _cases():
        jax.block_until_ready(thunk())          # warmup (trace + compile)
        n_iters, t0 = 3, time.perf_counter()
        for _ in range(n_iters):
            jax.block_until_ready(thunk())
        per_call = (time.perf_counter() - t0) / n_iters
        moved = _bytes(*io)
        record_kernel_time(kind, name, per_call, bytes_moved=moved)
        results[f"{kind}.{name}"] = {
            "us_per_call": round(per_call * 1e6, 1),
            "bytes_moved_est": moved,
        }
    return {
        "backend": jax.default_backend(),
        "perf_claim": False,                    # interpret-mode numbers
        "kernels": results,
    }


def main() -> int:
    out = run()
    print(json.dumps(out))
    return 0 if out["kernels"] else 1


if __name__ == "__main__":
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    sys.exit(main())
