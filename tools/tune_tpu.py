"""One-off TPU tuning sweep: measure BERT/ResNet leg variants on the real
chip to pick bench.py's config (batch size, attention path).  Not part of
the benchmark contract — bench.py remains the single source of truth; this
script only informs which knobs bench.py should default to.

Usage: python tools/tune_tpu.py
           post|pallas|zero|kv|elastic|ablate|resnet_ablate|resnet_trace|
           bert|resnet|flash
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _median(ts):
    ts = sorted(ts)
    return ts[len(ts) // 2]


def _peak_flops():
    """The chip's row of the repo's one peak table (KeyError off-table)."""
    import jax
    from deeplearning4j_tpu.observability.cost import PEAKS

    return PEAKS[jax.devices()[0].device_kind].flops


def bert_variant(batch, seq, attention, remat=False, iters=8):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    from deeplearning4j_tpu.optimize import transforms as T

    cfg = TransformerConfig(vocab_size=32768, d_model=768, n_heads=12,
                            n_layers=12, d_ff=3072, max_len=seq,
                            causal=False, dtype=jnp.bfloat16, remat=remat,
                            attention=attention)
    model = TransformerLM(cfg)
    tx = T.adamw(T.warmup_cosine(1e-4, 10, 1000), weight_decay=0.01)
    params = model.init(jax.random.key(0))
    opt = model.init_opt(params, tx)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    a, b = jax.device_put(toks), jax.device_put(np.roll(toks, -1, 1))
    step = model.build_train_step(tx)
    params, opt, loss = step(params, opt, a, b)
    float(np.asarray(loss))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, a, b)
        float(np.asarray(loss))
        times.append(time.perf_counter() - t0)
    med = _median(times)
    flops = cfg.flops_per_token() * batch * seq
    return {"batch": batch, "seq": seq, "attention": attention,
            "remat": remat, "median_ms": round(med * 1e3, 2),
            "tokens_per_sec": round(batch * seq / med, 1),
            "mfu": round(flops / (med * _peak_flops()), 4)}


def resnet_variant(batch, iters=8, bn_fold=False):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.resnet import (ResNetConfig, cross_entropy,
                                                  init_params)
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.optimize.transforms import apply_updates

    cfg = ResNetConfig.resnet50(bn_fold=bn_fold)
    tx = T.chain(T.momentum(0.9), T.sgd_lr(1e-2))

    def step(params, opt, images, labels):
        count, st = opt
        loss, g = jax.value_and_grad(cross_entropy)(params, images, labels, cfg)
        updates, st = tx.update(g, st, params, count)
        return apply_updates(params, updates), (count + 1, st), loss

    params = init_params(jax.random.key(0), cfg)
    opt = (jnp.zeros((), jnp.int32), tx.init(params))
    rng = np.random.default_rng(1)
    imgs = rng.standard_normal((batch, 224, 224, 3), dtype=np.float32)
    onehot = np.eye(cfg.num_classes, dtype=np.float32)[
        rng.integers(0, cfg.num_classes, batch)]
    a, b = jax.device_put(imgs), jax.device_put(onehot)
    jstep = jax.jit(step, donate_argnums=(0, 1))
    params, opt, loss = jstep(params, opt, a, b)
    float(np.asarray(loss))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        params, opt, loss = jstep(params, opt, a, b)
        float(np.asarray(loss))
        times.append(time.perf_counter() - t0)
    med = _median(times)
    flops = cfg.flops_per_image(224) * batch
    return {"batch": batch, "bn_fold": bn_fold,
            "median_ms": round(med * 1e3, 2),
            "images_per_sec": round(batch / med, 1),
            "mfu": round(flops / (med * _peak_flops()), 4)}


def bert_ablate(batch=64, seq=512, iters=8):
    """Attribute step time: full train step vs fwd+bwd without optimizer vs
    encoder-only (no LM head) — the deltas localize optimizer and loss cost."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM,
                                                       encode_local,
                                                       lm_loss_local)
    from deeplearning4j_tpu.optimize import transforms as T

    cfg = TransformerConfig(vocab_size=32768, d_model=768, n_heads=12,
                            n_layers=12, d_ff=3072, max_len=seq,
                            causal=False, dtype=jnp.bfloat16, remat=False)
    model = TransformerLM(cfg)
    tx = T.adamw(T.warmup_cosine(1e-4, 10, 1000), weight_decay=0.01)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    a = jax.device_put(toks)
    b = jax.device_put(np.roll(toks, -1, 1))

    def time_fn(fn, *args):
        r = fn(*args)
        jax.block_until_ready(r)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return round(_median(ts) * 1e3, 2)

    out = {}
    opt = model.init_opt(params, tx)
    step = model.build_train_step(tx)
    r = step(params, opt, a, b)          # compile; donation -> rebuild below
    jax.block_until_ready(r)
    params2, opt2, _ = r
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        params2, opt2, loss = step(params2, opt2, a, b)
        float(np.asarray(loss))
        ts.append(time.perf_counter() - t0)
    out["full_step_ms"] = round(_median(ts) * 1e3, 2)

    grad_fn = jax.jit(jax.grad(lambda p: lm_loss_local(p, a, b, cfg)))
    out["grad_only_ms"] = time_fn(grad_fn, params2)
    loss_fn = jax.jit(lambda p: lm_loss_local(p, a, b, cfg))
    out["fwd_loss_ms"] = time_fn(loss_fn, params2)
    enc_fn = jax.jit(lambda p: encode_local(p, a, cfg).mean())
    out["fwd_encode_ms"] = time_fn(enc_fn, params2)
    return out


def resnet_ablate(batch=256, iters=6):
    """Localize ResNet's missing MFU (r4: 16.4% at batch 256): time the
    full step vs grad-only vs fwd-only, and the same fwd with BN reductions
    in bf16 instead of f32 — the VERDICT's named suspects."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import resnet as R
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.optimize.transforms import apply_updates

    cfg = R.ResNetConfig.resnet50()
    tx = T.chain(T.momentum(0.9), T.sgd_lr(1e-2))
    params = R.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(1)
    imgs = rng.standard_normal((batch, 224, 224, 3), dtype=np.float32)
    onehot = np.eye(cfg.num_classes, dtype=np.float32)[
        rng.integers(0, cfg.num_classes, batch)]
    a, b = jax.device_put(imgs), jax.device_put(onehot)

    def time_fn(fn, *args):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return round(_median(ts) * 1e3, 2)

    out = {"batch": batch}

    def step(params, opt, images, labels):
        count, st = opt
        loss, g = jax.value_and_grad(R.cross_entropy)(params, images, labels, cfg)
        updates, st = tx.update(g, st, params, count)
        return apply_updates(params, updates), (count + 1, st), loss

    opt = (jnp.zeros((), jnp.int32), tx.init(params))
    jstep = jax.jit(step)                          # no donation: params reused
    out["full_step_ms"] = time_fn(lambda: jstep(params, opt, a, b))
    out["grad_only_ms"] = time_fn(jax.jit(
        jax.grad(lambda p: R.cross_entropy(p, a, b, cfg))), params)
    out["fwd_only_ms"] = time_fn(jax.jit(
        lambda p: R.cross_entropy(p, a, b, cfg)), params)

    # the shippable bf16-apply path: bn_fold=True (stats stay f32, the
    # elementwise normalize becomes a folded per-channel bf16 affine)
    import dataclasses
    fcfg = dataclasses.replace(cfg, bn_fold=True)
    try:
        out["fwd_bnfold_ms"] = time_fn(jax.jit(
            lambda p: R.cross_entropy(p, a, b, fcfg)), params)
        out["grad_bnfold_ms"] = time_fn(jax.jit(
            jax.grad(lambda p: R.cross_entropy(p, a, b, fcfg))), params)
    except Exception as e:
        out["bnfold_error"] = repr(e)[:200]
    return out


def _xplane_top_ops(log_dir, n=12):
    """Sum device-plane event durations per op from the .xplane.pb trace —
    the top-N table VERDICT item 2 asks to commit."""
    from pathlib import Path

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not paths:
        return {"error": f"no xplane.pb under {log_dir}"}
    xspace = xplane_pb2.XSpace()
    xspace.ParseFromString(paths[-1].read_bytes())
    device = [pl for pl in xspace.planes
              if "TPU" in pl.name or "/device" in pl.name.lower()]
    if not device:                 # CPU run: fall back to the host plane
        device = [pl for pl in xspace.planes if "/host:" in pl.name]
    totals = {}
    for plane in device:
        meta = {m_id: m.name for m_id, m in plane.event_metadata.items()}
        for line in plane.lines:
            for ev in line.events:
                name = meta.get(ev.metadata_id, str(ev.metadata_id))
                totals[name] = totals.get(name, 0) + ev.duration_ps
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    total_ps = sum(totals.values()) or 1
    return {"plane_total_ms": round(total_ps / 1e9, 2),
            "top_ops": [{"op": k[:80], "ms": round(v / 1e9, 3),
                         "pct": round(100 * v / total_ps, 1)}
                        for k, v in top]}


def resnet_trace(batch=256, steps=3, log_dir="xplane_resnet"):
    """Capture an XPlane trace of the ResNet-50 train step and print the
    top-op table (parsed in-container via the TF xplane proto)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.resnet import (ResNetConfig, cross_entropy,
                                                  init_params)
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.optimize.transforms import apply_updates
    from deeplearning4j_tpu.parallel.observe import profiler_trace

    cfg = ResNetConfig.resnet50()
    tx = T.chain(T.momentum(0.9), T.sgd_lr(1e-2))

    def step(params, opt, images, labels):
        count, st = opt
        loss, g = jax.value_and_grad(cross_entropy)(params, images, labels, cfg)
        updates, st = tx.update(g, st, params, count)
        return apply_updates(params, updates), (count + 1, st), loss

    params = init_params(jax.random.key(0), cfg)
    opt = (jnp.zeros((), jnp.int32), tx.init(params))
    rng = np.random.default_rng(1)
    imgs = rng.standard_normal((batch, 224, 224, 3), dtype=np.float32)
    onehot = np.eye(cfg.num_classes, dtype=np.float32)[
        rng.integers(0, cfg.num_classes, batch)]
    a, b = jax.device_put(imgs), jax.device_put(onehot)
    jstep = jax.jit(step, donate_argnums=(0, 1))
    params, opt, loss = jstep(params, opt, a, b)     # compile outside trace
    float(np.asarray(loss))
    with profiler_trace(log_dir):
        for _ in range(steps):
            params, opt, loss = jstep(params, opt, a, b)
            float(np.asarray(loss))
    try:
        return {"batch": batch, "steps": steps, "log_dir": log_dir,
                **_xplane_top_ops(log_dir)}
    except Exception as e:
        return {"batch": batch, "log_dir": log_dir,
                "parse_error": repr(e)[:300]}


def flash_check():
    """Correctness of the Pallas kernel vs the XLA ring path on-chip, then
    its speed inside the full model."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.transformer import ring_attention
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    B, T, H, D = 4, 512, 12, 64
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
               for _ in range(3))
    res = {}
    for causal in (False, True):
        f = jax.jit(lambda q, k, v, c=causal: flash_attention(q, k, v, causal=c))
        r = jax.jit(lambda q, k, v, c=causal: ring_attention(
            q, k, v, n_sp=1, sp_axis=None, causal=c, t_local=T))
        err = float(np.max(np.abs(np.asarray(f(q, k, v), np.float32)
                                  - np.asarray(r(q, k, v), np.float32))))
        res[f"fwd_err_causal_{causal}"] = round(err, 5)

    def loss_f(q, k, v):
        return (flash_attention(q, k, v, causal=False).astype(jnp.float32) ** 2).mean()

    def loss_r(q, k, v):
        return (ring_attention(q, k, v, n_sp=1, sp_axis=None, causal=False,
                               t_local=T).astype(jnp.float32) ** 2).mean()

    gf = jax.jit(jax.grad(loss_f))(q, k, v)
    gr = jax.jit(jax.grad(loss_r))(q, k, v)
    res["grad_err"] = round(float(np.max(np.abs(
        np.asarray(gf, np.float32) - np.asarray(gr, np.float32)))), 5)
    return res


def _timed(fn, *args, iters=8):
    import jax
    jax.block_until_ready(fn(*args))            # compile outside the timing
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return _median(times)


def pallas_battery(iters=8, shapes=None):
    """Generic TUNE rows for the ops/pallas kernel tier, one row per
    (kernel, candidate, block config) plus a correctness row per
    candidate — the schema ``bench.py``'s registry auto-pick consumes
    (``{"kernel", "candidate", "block", "tokens_per_sec"}`` /
    ``{"kernel", "candidate", "check"}``).  Yields dicts; the caller
    prints them as JSONL."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import registry
    from deeplearning4j_tpu.ops.pallas.matmul_int8 import (quantize,
                                                           top1_agreement)

    rng = np.random.default_rng(0)
    # shapes override exists so a CPU smoke can exercise every code path
    # at toy sizes; the on-chip battery always runs the real ones
    B, T, H, D, N, K, V = shapes or (4, 512, 12, 64, 4096, 768, 32768)
    qkv = tuple(jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
                for _ in range(3))
    x = jnp.asarray(rng.standard_normal((N, K)), jnp.bfloat16)
    r = jnp.asarray(rng.standard_normal((N, K)), jnp.bfloat16)
    scale = jnp.ones((K,), jnp.float32)
    bias = jnp.zeros((K,), jnp.float32)
    head = jnp.asarray(rng.standard_normal((K, V)) * 0.05, jnp.bfloat16)
    tgt = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    qw = quantize(jnp.asarray(rng.standard_normal((K, V)) * 0.05))
    # paged decode read: one query position per row over T-token pages
    ps_pg = 16 if T >= 128 else 4
    npg = -(-T // ps_pg)
    n_phys = B * npg + 1
    pg_q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    pg_k, pg_v = (jnp.asarray(rng.standard_normal((n_phys, ps_pg, H, D)),
                              jnp.bfloat16) for _ in range(2))
    pg_bt = jnp.asarray(rng.permutation(n_phys)[: B * npg].reshape(B, npg),
                        jnp.int32)
    pg_len = jnp.asarray(rng.integers(1, npg * ps_pg + 1, B), jnp.int32)

    def grad_err(fn, ref, *args):
        def loss(f):
            def l(*a):
                out = f(*a)
                if isinstance(out, tuple):
                    out = out[1]
                return (out.astype(jnp.float32) ** 2).mean()
            return l
        ga = jax.jit(jax.grad(loss(fn)))(*args)
        gb = jax.jit(jax.grad(loss(ref)))(*args)
        return float(np.max(np.abs(np.asarray(ga, np.float32)
                                   - np.asarray(gb, np.float32))))

    # (kind, tokens-per-call, call(fn, **block), check(cand))
    def attention_check(cand):
        o = cand.fn(*qkv)
        ref = cand.reference(*qkv)
        return {"max_err": float(np.max(np.abs(
                    np.asarray(o, np.float32) - np.asarray(ref, np.float32)))),
                "grad_err": grad_err(cand.fn, cand.reference, *qkv)}

    def ln_check(cand):
        _, h = cand.fn(x, r, scale, bias)
        _, hr = cand.reference(x, r, scale, bias)
        return {"max_err": float(np.max(np.abs(
            np.asarray(h, np.float32) - np.asarray(hr, np.float32))))}

    def xent_check(cand):
        a = float(cand.fn(x, head, tgt))
        b = float(cand.reference(x, head, tgt))
        return {"max_err": abs(a - b) / max(abs(b), 1e-9)}

    def paged_check(cand):
        o = cand.fn(pg_q, pg_k, pg_v, pg_bt, pg_len)
        ref = cand.reference(pg_q, pg_k, pg_v, pg_bt, pg_len)
        return {"max_err": float(np.max(np.abs(
            np.asarray(o, np.float32) - np.asarray(ref, np.float32))))}

    def int8_check(cand):
        o = cand.fn(x, qw)
        ref = cand.reference(x, qw)
        return {"max_err": float(np.max(np.abs(
                    np.asarray(o) - np.asarray(ref)))),
                "top1_agree": float(top1_agreement(o, ref))}

    suites = (
        ("attention", B * T, lambda fn, **blk: fn(*qkv, **blk),
         attention_check),
        ("layernorm_residual", N, lambda fn, **blk: fn(x, r, scale, bias,
                                                       **blk), ln_check),
        ("xent", N, lambda fn, **blk: fn(x, head, tgt, **blk), xent_check),
        ("int8_matmul", N, lambda fn, **blk: fn(x, qw, **blk), int8_check),
        ("paged_attention", B,
         lambda fn, **blk: fn(pg_q, pg_k, pg_v, pg_bt, pg_len, **blk),
         paged_check),
    )
    for kind, tokens, call, check in suites:
        for cand in registry.candidates(kind):
            try:
                yield {"kernel": kind, "candidate": cand.name,
                       "check": check(cand)}
            except Exception as e:
                yield {"kernel": kind, "candidate": cand.name,
                       "check_error": repr(e)[:300]}
            for blk in (cand.blocks or ({},)):
                try:
                    med = _timed(jax.jit(lambda *a, c=cand, b=dict(blk):
                                         call(c.fn, **b)), iters=iters)
                    yield {"kernel": kind, "candidate": cand.name,
                           "block": dict(blk), "median_ms": round(med * 1e3, 3),
                           "tokens_per_sec": round(tokens / med, 1)}
                except Exception as e:
                    yield {"kernel": kind, "candidate": cand.name,
                           "block": dict(blk), "error": repr(e)[:300]}


def kv_battery(iters=8, shapes=None):
    """KV-precision rows for the serving decode read (DESIGN.md §20):
    every ``paged_attention_int8`` candidate checked against the FLOAT
    pool's reference — ``max_err`` is the quantization band and
    ``top1_agree`` the adoption statistic the registry gate floors at
    0.999 — plus timing rows, a GQA (n_kv_heads < n_heads) geometry
    for each, and the per-page byte accounting behind the capacity
    table in ``tools/metrics_dump.py``.  Same JSONL schema as
    ``pallas_battery``."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import registry
    from deeplearning4j_tpu.ops.pallas import kv_quant as kvq
    from deeplearning4j_tpu.ops.pallas.matmul_int8 import top1_agreement
    from deeplearning4j_tpu.ops.pallas.paged_attention import \
        reference_paged_attention

    rng = np.random.default_rng(0)
    B, H, D, ps, npg = shapes or (8, 16, 128, 16, 32)
    n_phys = B * npg + 1

    def geometry(kv_heads):
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        kf, vf = (jnp.asarray(rng.standard_normal((n_phys, ps, kv_heads, D)),
                              jnp.float32) for _ in range(2))
        bt = jnp.asarray(rng.permutation(n_phys)[: B * npg].reshape(B, npg),
                         jnp.int32)
        ln = jnp.asarray(rng.integers(1, npg * ps + 1, B), jnp.int32)
        s0 = jnp.full((n_phys, kv_heads), kvq.neutral_scale(jnp.int8),
                      jnp.float32)
        kq, ks = kvq.requantize_pool(kf, s0, jnp.int8)
        vq, vs = kvq.requantize_pool(vf, s0, jnp.int8)
        return q, kf, vf, kq, vq, ks, vs, bt, ln

    for kv_heads in (H, H // 4):                 # MHA and 4-way GQA reads
        q, kf, vf, kq, vq, ks, vs, bt, ln = geometry(kv_heads)
        want = reference_paged_attention(q, kf, vf, bt, ln)
        for cand in registry.candidates("paged_attention_int8"):
            try:
                got = cand.fn(q, kq, vq, ks, vs, bt, ln)
                yield {"kernel": "paged_attention_int8",
                       "candidate": cand.name, "kv_heads": kv_heads,
                       "check": {
                           "max_err": float(np.max(np.abs(
                               np.asarray(got, np.float32)
                               - np.asarray(want, np.float32)))),
                           "top1_agree": float(top1_agreement(got, want))}}
            except Exception as e:
                yield {"kernel": "paged_attention_int8",
                       "candidate": cand.name, "kv_heads": kv_heads,
                       "check_error": repr(e)[:300]}
            try:
                med = _timed(jax.jit(lambda c=cand:
                                     c.fn(q, kq, vq, ks, vs, bt, ln)),
                             iters=iters)
                yield {"kernel": "paged_attention_int8",
                       "candidate": cand.name, "kv_heads": kv_heads,
                       "block": {}, "median_ms": round(med * 1e3, 3),
                       "tokens_per_sec": round(B / med, 1)}
            except Exception as e:
                yield {"kernel": "paged_attention_int8",
                       "candidate": cand.name, "kv_heads": kv_heads,
                       "block": {}, "error": repr(e)[:300]}
    # the capacity arithmetic the serving gauges report, per storage mode
    import dataclasses as _dc

    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.serving.engine import kv_page_bytes
    mcfg = TransformerConfig(vocab_size=32768, d_model=H * D, n_heads=H,
                             n_layers=24, d_ff=4 * H * D, max_len=ps * npg)
    for kv_heads in (H, H // 4):
        cfg = _dc.replace(mcfg, n_kv_heads=kv_heads)
        fp = kv_page_bytes(cfg, ps, None)
        for mode in (None,) + kvq.KV_QUANT_MODES:
            yield {"battery": "kv_capacity", "kv_heads": kv_heads,
                   "kv_quant": mode, "page_bytes": kv_page_bytes(cfg, ps, mode),
                   "bytes_vs_float": round(kv_page_bytes(cfg, ps, mode) / fp, 4)}


def zero_battery(iters=12, d=4096, batch=64):
    """ZeRO rows: one per stage — step time plus the per-device
    params/opt-state bytes from the trainer's gauges.  On real chips this
    is the stage-selection table DESIGN.md §15 owes its numbers to; on
    CPU the byte columns are still exact (they come from shard metadata,
    not timing).  Yields JSONL row dicts like ``pallas_battery``."""
    import jax
    from deeplearning4j_tpu import observability
    from deeplearning4j_tpu.observability import METRICS
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.parallel import DataParallelTrainer

    observability.enable()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, d)).astype(np.float32)
    y = rng.normal(size=(batch, 1)).astype(np.float32)

    def loss_fn(p, xb, yb, key=None):
        return ((xb @ p["w"] - yb) ** 2).mean()

    for stage in (0, 1, 2, 3):
        METRICS.reset()
        tr = DataParallelTrainer(loss_fn, T.adam(1e-3), zero_stage=stage)
        state = tr.init_state({"w": np.zeros((d, 1), np.float32)})
        state, lazy = tr.step(state, x, y)  # compile + settle placements
        lazy.block()
        tr._resolve_pending()
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            state, lazy = tr.step(state, x, y)
            lazy.block()
            times.append(time.perf_counter() - t0)
        tr._resolve_pending()
        g = METRICS.snapshot()["gauges"]

        def per_dev(prefix):
            vals = [v for k, v in g.items() if k.startswith(prefix)]
            return max(vals) if vals else None

        yield {"battery": "zero", "zero_stage": stage, "n_dp": tr.n_dp,
               "d": d, "batch": batch,
               "median_ms": round(_median(times) * 1e3, 3),
               "params_bytes_per_device": per_dev(
                   "train.params_bytes.device."),
               "opt_state_bytes_per_device": per_dev(
                   "train.opt_state_bytes.device.")}


def elastic_battery(iters=5, d=4096, steps=3):
    """Elasticity rows (ISSUE 13): reshard wall-clock per zero stage and
    (save_dp -> restore_dp) direction — save a checkpoint at one dp width,
    restore it at another through the resharding path, and time the
    restore.  On CPU the widths are virtual-device halves of the host
    mesh; on real chips this is the battery the owed ROADMAP-item-2
    hardware run measures resharding cost with (the number that prices a
    live shrink/grow against simply restarting).  Yields JSONL row dicts
    like ``zero_battery``."""
    import tempfile

    import jax
    from deeplearning4j_tpu import observability
    from deeplearning4j_tpu.observability import METRICS
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.parallel import (CheckpointManager,
                                             DataParallelTrainer, elastic_mesh)

    observability.enable()
    n_dev = len(jax.devices())
    if n_dev < 2:
        yield {"battery": "elastic", "skipped": f"{n_dev} device(s)"}
        return
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_dev * 8, d)).astype(np.float32)
    y = rng.normal(size=(n_dev * 8, 1)).astype(np.float32)

    def loss_fn(p, xb, yb, key=None):
        return ((xb @ p["w"] - yb) ** 2).mean()

    def mk(width, stage):
        return DataParallelTrainer(
            loss_fn, T.adam(1e-3),
            mesh=elastic_mesh(jax.devices()[:width]), zero_stage=stage)

    params = {"w": np.zeros((d, 1), np.float32)}
    for stage in (0, 1, 2, 3):
        for save_dp, restore_dp in ((n_dev, n_dev // 2), (n_dev // 2, n_dev)):
            with tempfile.TemporaryDirectory() as ckpt_dir:
                mgr = CheckpointManager(ckpt_dir)
                src = mk(save_dp, stage)
                state = src.init_state(params)
                for _ in range(steps):
                    state, lazy = src.step(state, x, y)
                src.checkpoint(state, mgr)
                dst = mk(restore_dp, stage)
                tmpl = dst.init_state(params)
                times = []
                for _ in range(iters):
                    METRICS.reset()
                    t0 = time.perf_counter()
                    restored = dst.restore(tmpl, mgr)
                    jax.block_until_ready((restored.params, restored.tstate))
                    times.append(time.perf_counter() - t0)
                g = METRICS.snapshot()["gauges"]
                yield {"battery": "elastic", "zero_stage": stage,
                       "save_dp": save_dp, "restore_dp": restore_dp, "d": d,
                       "median_ms": round(_median(times) * 1e3, 3),
                       "reshard_seconds_gauge": g.get("elastic.reshard_seconds")}


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "bert"
    out = []
    if which == "elastic":
        # reshard cost battery: wall-clock to restore a checkpoint across
        # dp widths, per zero stage (the elastic tier's hardware row)
        for row in elastic_battery():
            print(json.dumps(row), flush=True)
        return
    if which == "zero":
        for row in zero_battery():
            print(json.dumps(row), flush=True)
        return
    if which == "kv":
        # KV-precision battery: quantized paged-read candidates vs the
        # float reference + page-byte capacity rows
        for row in kv_battery():
            print(json.dumps(row), flush=True)
        return
    if which == "pallas":
        # the kernel-tier battery alone: one generic row per (kernel,
        # candidate, block) + a check row per candidate, straight into
        # the registry auto-pick's schema
        for row in pallas_battery():
            print(json.dumps(row), flush=True)
        return
    if which == "post":
        # post-change battery: chunked-xent BERT (ring + flash) and the
        # space-to-depth ResNet at growing batch
        try:
            print(json.dumps({"flash_check": flash_check()}), flush=True)
        except Exception as e:
            print(json.dumps({"flash_check_error": repr(e)[:300]}), flush=True)
        try:
            for row in pallas_battery():
                print(json.dumps(row), flush=True)
        except Exception as e:
            print(json.dumps({"pallas_battery_error": repr(e)[:300]}),
                  flush=True)
        for fn, args, kw in ((bert_variant, (64, 512, "ring"), {}),
                             (bert_variant, (64, 512, "flash"), {}),
                             (bert_variant, (128, 512, "ring"), {}),
                             (bert_variant, (128, 512, "flash"), {}),
                             (resnet_variant, (256,), {}),
                             (resnet_variant, (256,), {"bn_fold": True}),
                             (resnet_variant, (512,), {}),
                             (resnet_variant, (512,), {"bn_fold": True})):
            try:
                print(json.dumps(fn(*args, **kw)), flush=True)
            except Exception as e:
                print(json.dumps({"args": str(args) + str(kw),
                                  "error": repr(e)[:300]}), flush=True)
        return
    if which == "ablate":
        print(json.dumps(bert_ablate()), flush=True)
        return
    if which == "resnet_ablate":
        try:
            print(json.dumps({"resnet_ablate": resnet_ablate()}), flush=True)
        except Exception as e:
            print(json.dumps({"resnet_ablate_error": repr(e)[:300]}), flush=True)
        return
    if which == "resnet_trace":
        try:
            print(json.dumps({"resnet_trace": resnet_trace()}), flush=True)
        except Exception as e:
            print(json.dumps({"resnet_trace_error": repr(e)[:300]}), flush=True)
        return
    if which == "bert":
        for batch in (64, 128, 256):
            try:
                out.append(bert_variant(batch, 512, "ring"))
            except Exception as e:
                out.append({"batch": batch, "error": repr(e)[:200]})
            print(json.dumps(out[-1]), flush=True)
    elif which == "flash":
        for batch in (64, 128):
            try:
                out.append(bert_variant(batch, 512, "flash"))
            except Exception as e:
                out.append({"batch": batch, "error": repr(e)[:200]})
            print(json.dumps(out[-1]), flush=True)
    elif which == "resnet":
        for batch in (128, 256):
            try:
                out.append(resnet_variant(batch))
            except Exception as e:
                out.append({"batch": batch, "error": repr(e)[:200]})
            print(json.dumps(out[-1]), flush=True)


if __name__ == "__main__":
    main()
