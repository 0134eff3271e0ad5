"""Runner ``serve``: ``InferenceEngine.submit`` in-process, closed loop.

The workload file gives the engine (``slots``, ``paged``, ``prefix_cache``,
``paged_attention_impl``), the callers (``callers``, ``ramp_s``) and the
traffic: lognormal prompt and output lengths (``prompt_len``, ``output_len``:
``median``, ``sigma``, ``min``, ``max``), ``size_block``, ``sizes_seed``,
``n_requests``; and the tie band of the check (``eps``, ``judged``).

Each caller submits its next request only when the last one has come back
(greedy, no EOS: every request ends by ``length``).  The callers start
staggered over ``ramp_s`` seconds, which is set-up the traffic needs: the
window then opens on slots in mixed phases.  The window counts the requests
that COMPLETE inside it; what is in flight at the deadline is cancelled after
the clock has stopped and counts for nothing.

Every seed sees the same request sizes: one block of ``size_block`` (prompt,
output) lengths, taken at the quantiles of the two lognormals and paired by
``sizes_seed``, and the stream is that block over and over, each time in
another order drawn from ``--seed``.  Prompt tokens are uniform over
the vocabulary, from ``--seed``, so no two prompts share a prefix.
"""

from __future__ import annotations

import gc
import itertools
import math
import statistics
import threading
import time

from benchmark import check
from benchmark.harness import (Cell, Outcome, init_params_on_device,
                               percentile, say, seed32, transformer_config)


def lognormal_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of a lognormal
    (``median``, ``sigma``), clipped to ``[min, max]``: the same set for every
    seed, and representative even when ``n`` is small."""
    normal = statistics.NormalDist()
    return [int(min(spec["max"], max(spec["min"], round(
        spec["median"] * math.exp(spec["sigma"] * normal.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def make_requests(w: dict, vocab: int, seed: int) -> list[tuple[list[int], int]]:
    """``n_requests`` seeded (prompt tokens, new tokens) requests."""
    import numpy as np

    n = w["size_block"]
    pairing = np.random.default_rng(w["sizes_seed"]).permutation(n)
    outputs = lognormal_lengths(w["output_len"], n)
    block = [(p_len, outputs[j])
             for p_len, j in zip(lognormal_lengths(w["prompt_len"], n), pairing)]
    rng = np.random.default_rng(seed32(seed))
    out = []
    while len(out) < w["n_requests"]:
        for j in rng.permutation(n):
            p_len, new = block[j]
            out.append((rng.integers(0, vocab, p_len).tolist(), int(new)))
    return out[:w["n_requests"]]


class Callers:
    """``n`` threads, each in a closed loop ``submit(...).result()`` over one
    shared stream of requests.  ``records`` holds, per request that came back
    or failed: (index, submit time, done time, Completion or None, error)."""

    def __init__(self, engine, requests, n: int, ramp_s: float,
                 timeout_s: float):
        self.engine, self.requests = engine, requests
        self.n, self.ramp_s, self.timeout_s = n, ramp_s, timeout_s
        self.records: list[tuple] = []          # list.append is atomic
        self.stop = threading.Event()
        self._next = itertools.count()          # next() is atomic in CPython
        self._threads = [threading.Thread(target=self._loop, args=(i,),
                                          name=f"caller-{i}", daemon=True)
                         for i in range(n)]

    def start(self) -> None:
        self._t0 = time.perf_counter()
        for t in self._threads:
            t.start()

    def _loop(self, k: int) -> None:
        if self.stop.wait(max(0.0, self._t0 + k * self.ramp_s / self.n
                              - time.perf_counter())):
            return
        while not self.stop.is_set():
            i = next(self._next)
            if i >= len(self.requests):
                return                          # run() reports the shortfall
            prompt, new = self.requests[i]
            t_sub = time.perf_counter()
            done, err = None, None
            try:
                done = self.engine.submit(prompt, new, temperature=0.0,
                                          seed=i).result(self.timeout_s)
            except Exception as e:  # refused, failed or timed out: a failed request
                err = e
            self.records.append((i, t_sub, time.perf_counter(), done, err))
            if err is not None:
                self.stop.wait(0.05)            # a refusing engine is not spun on

    def join(self) -> None:
        self.stop.set()
        for t in self._threads:
            t.join(timeout=60.0)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"callers did not end: {alive}")


def serving_metrics(records, requests, t0: float, t1: float) -> dict:
    """The serving metrics from what came back inside ``[t0, t1)``.
    ``records`` as ``Callers.records``; ``requests`` gives what was asked."""
    inside = [r for r in records if t0 <= r[2] < t1]
    ok = [r for r in inside if r[4] is None]
    tokens = sum(len(c.tokens) for _, _, _, c, _ in ok)
    ttft = [c.ttft_s * 1e3 for _, _, _, c, _ in ok if c.ttft_s is not None]
    tpot = [(t_done - t_sub - c.ttft_s) / (len(c.tokens) - 1) * 1e3
            for _, t_sub, t_done, c, _ in ok
            if c.ttft_s is not None and len(c.tokens) > 1]
    as_asked = all(len(c.tokens) == requests[i][1]
                   and c.finish_reason == "length" for i, _, _, c, _ in ok)
    out = {"attempted": len(inside), "failed": len(inside) - len(ok),
           "completed": ok, "as_asked": as_asked, "output_tokens": tokens,
           "n_ttft": len(ttft), "n_tpot": len(tpot),
           "serve_tokens_per_s": tokens / (t1 - t0)}
    if ttft and tpot:
        out.update(ttft_p95_ms=percentile(ttft, 0.95),
                   ttft_p50_ms=percentile(ttft, 0.50),
                   tpot_p95_ms=percentile(tpot, 0.95),
                   tpot_p50_ms=percentile(tpot, 0.50))
    return out


def judged_margin(cfg, params, requests, completed, n: int, seed: int):
    """Worst margin of every generated token of a seeded sample of ``n``
    completed requests against the plain cache-free forward."""
    import numpy as np

    rng = np.random.default_rng(seed32(seed) + 1)
    picks = [completed[j] for j in rng.choice(len(completed),
                                              min(n, len(completed)), False)]
    seqs = [requests[i][0] + list(c.tokens) for i, _, _, c, _ in picks]
    m = np.asarray(check.plain_margins(cfg)(
        params, check.pad_sequences(cfg.max_len, seqs)))
    return check.worst_margin(list(m), [len(requests[i][0]) for i, *_ in picks],
                              [len(c.tokens) for *_, c, _ in picks])


def run(cell: Cell) -> Outcome:
    from deeplearning4j_tpu.models.transformer import TransformerLM
    from deeplearning4j_tpu.observability import METRICS
    from deeplearning4j_tpu.serving import InferenceEngine, ServingConfig

    w = cell.workload
    cfg = transformer_config(cell.config)
    METRICS.reset()
    params = init_params_on_device(cfg, cell.seed)
    requests = make_requests(w, cfg.vocab_size, cell.seed)
    engine = InferenceEngine(TransformerLM(cfg), params=params, cfg=ServingConfig(
        slots=w["slots"], paged=w["paged"], prefix_cache=w["prefix_cache"],
        paged_attention_impl=w["paged_attention_impl"]))
    t_warm = time.perf_counter()
    engine.start()              # its own warm-up: the step and every prefill bucket
    buckets = engine.stats()["prefill_buckets"]
    say(f"engine warm in {time.perf_counter() - t_warm:.1f}s: {w['slots']} slots, "
        f"prefill buckets {buckets}, serving.prefill.recompile "
        f"{METRICS.snapshot()['counters'].get('serving.prefill.recompile', 0):g}")
    callers = Callers(engine, requests, w["callers"], w["ramp_s"],
                      w["request_timeout_s"])
    try:
        callers.start()
        time.sleep(w["ramp_s"])
        METRICS.reset()         # timers and counters now cover the window only
        t0 = time.perf_counter()
        setup_s = t0 - cell.process_t0
        cell.on_window(t0)
        time.sleep(cell.seconds)
        t1 = time.perf_counter()
        snap = METRICS.snapshot()
        stats = engine.stats()
    finally:
        callers.stop.set()
        engine.stop()           # fails what is in flight; the clock has stopped
        callers.join()
    del engine
    gc.collect()

    m = serving_metrics(callers.records, requests, t0, t1)
    say(f"window: {t1 - t0:.3f}s, {len(m['completed'])} requests completed "
        f"({m['output_tokens']} output tokens), {m['failed']} failed; "
        f"{stats['admitted']} admitted since start, prefix hits "
        f"{stats.get('prefix_hits')}")
    for name in ("ttft", "tpot"):
        if f"{name}_p95_ms" in m:
            say(f"  {name}: p95 {m[f'{name}_p95_ms']:.3f} ms, median "
                f"{m[f'{name}_p50_ms']:.3f} ms over {m[f'n_{name}']} requests")
    errors = [repr(r[4]) for r in callers.records if r[4] is not None
              and t0 <= r[2] < t1]
    counters = snap["counters"]
    checks = [
        (len(m["completed"]) > 0 and "ttft_p95_ms" in m,
         f"{len(m['completed'])} requests completed inside the window"),
        (m["failed"] == 0, f"no request failed or was refused {errors[:3]}"),
        (m["as_asked"], "every counted request returned the tokens asked for, "
                        "finish_reason 'length'"),
        (not counters.get("serving.engine.errors"), "no serving.engine.errors"),
        (not counters.get("serving.prefill.recompile"),
         "nothing compiled inside the window (serving.prefill.recompile "
         f"moved by {counters.get('serving.prefill.recompile', 0):g})"),
        (len(callers.records) < len(requests), "the request stream was not "
                                               "exhausted"),
    ]
    if m["completed"]:
        worst, n_tok = judged_margin(cfg, params, requests, m["completed"],
                                     w["judged"], cell.seed)
        checks.append((worst <= w["eps"],
                       f"all {n_tok} generated tokens of {w['judged']} sampled "
                       f"requests within eps={w['eps']} of the plain forward's "
                       f"maximum (worst margin {worst:.4f})"))
    for ok, what in checks:
        say(f"  {'ok' if ok else 'FAILED'}: {what}")
    e2e = {k: m[k] for k in ("serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")
           if k in m}
    e2e["setup_s"] = setup_s
    return Outcome(correct=all(ok for ok, _ in checks), attempted=m["attempted"],
                   failed=m["failed"], end_to_end=e2e,
                   facts={"timers": snap["timers"], "counters": counters})
