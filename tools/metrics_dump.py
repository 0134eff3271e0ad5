#!/usr/bin/env python
"""Fetch /metrics + /status from a running job's StatusServer and render a
human-readable table.

Usage:
    python tools/metrics_dump.py --port 8787 [--host 127.0.0.1]
    python tools/metrics_dump.py --url http://10.0.0.3:8787
    python tools/metrics_dump.py --port 8787 --prom   # raw Prometheus text
    python tools/metrics_dump.py --timeline ts_dir/   # offline: sparklines
                                                      # from TimeSeriesStore
                                                      # JSONL exports

``--timeline`` takes a ``timeseries-*.jsonl`` file (or a directory of
them, as written under ``DL4J_TPU_TS_DIR``) and needs no live server:
each series renders as min/last/max plus a unicode sparkline of its
recent samples.  ``--series SUBSTR`` filters the set.

No dependencies beyond stdlib: talks to the endpoints
``deeplearning4j_tpu.observability.StatusServer`` serves, and reads the
``TimeSeriesStore`` JSONL format directly (torn final lines from a
killed process are skipped, matching ``timeseries.read_back``).
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.error
import urllib.request


def _fetch(base: str, path: str, timeout: float):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            body = r.read()
    except (urllib.error.URLError, OSError) as e:
        return None, f"{path}: {e}"
    if path.endswith(".prom"):
        return body.decode(), None
    return json.loads(body), None


def _rows(title: str, rows: list[tuple], headers: tuple) -> str:
    """Plain-text table: header + aligned columns."""
    out = [title]
    if not rows:
        out.append("  (none)")
        return "\n".join(out)
    cells = [tuple(str(c) for c in r) for r in rows]
    widths = [max(len(h), *(len(r[i]) for r in cells))
              for i, h in enumerate(headers)]
    fmt = "  " + "  ".join(f"{{:<{w}}}" for w in widths)
    out.append(fmt.format(*headers))
    out.append("  " + "-" * (sum(widths) + 2 * (len(widths) - 1)))
    out.extend(fmt.format(*r) for r in cells)
    return "\n".join(out)


def _fmt_s(v: float) -> str:
    if v != v:
        return "nan"
    if v >= 1.0:
        return f"{v:.3f}s"
    return f"{v * 1e3:.2f}ms"


def _fmt_bytes(v: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if v < 1024 or unit == "GiB":
            return f"{v:.0f}{unit}" if unit == "B" else f"{v:.2f}{unit}"
        v /= 1024
    return f"{v:.2f}GiB"


def render_state_memory(snap: dict) -> str | None:
    """Per-device params / optimizer-state footprint table, from the
    ``train.params_bytes`` / ``train.opt_state_bytes`` gauges the trainer
    publishes at init and after restore.  Under ZeRO (zero_stage >= 1)
    the opt-state column shows the ~1/ndp shrink directly.  Returns None
    when the job published no state gauges (pre-ZeRO jobs)."""
    gauges = snap.get("gauges", {})
    devs: dict[str, dict[str, float]] = {}
    for prefix, col in (("train.params_bytes.device.", "params"),
                        ("train.opt_state_bytes.device.", "opt_state")):
        for k, v in gauges.items():
            if k.startswith(prefix):
                devs.setdefault(k[len(prefix):], {})[col] = v
    if not devs:
        return None
    rows = [(d, _fmt_bytes(c.get("params", 0.0)),
             _fmt_bytes(c.get("opt_state", 0.0)))
            for d, c in sorted(devs.items(), key=lambda kv: kv[0])]
    return _rows("state memory (per device)", rows,
                 ("device", "params", "opt_state"))


def render_serving(snap: dict) -> str | None:
    """Paged-KV / prefix-cache / speculative serving gauges (PR-9), plus
    the speculative accepted-prefix histogram.  Returns None when the job
    published none of them (non-serving jobs, dense engines)."""
    gauges = snap.get("gauges", {})
    rows = []
    if "serving.kv_pages_in_use" in gauges:
        rows.append(("kv_pages_in_use", f"{gauges['serving.kv_pages_in_use']:.0f}"))
    if "serving.prefix_hit_rate" in gauges:
        rows.append(("prefix_hit_rate",
                     f"{gauges['serving.prefix_hit_rate'] * 100:.1f}%"))
    if "serving.kv_bytes_per_slot" in gauges:
        rows.append(("kv_bytes_per_slot",
                     _fmt_bytes(gauges["serving.kv_bytes_per_slot"])))
    accept = snap.get("timers", {}).get("serving.spec_accept_len")
    if accept:
        rows.append(("spec_accept_len(mean)",
                     f"{accept['mean_s']:.2f} tok over {accept['count']} windows"))
    if not rows:
        return None
    return _rows("serving (paged KV / prefix cache / speculative)", rows,
                 ("metric", "value"))


def render_kv_capacity(snap: dict) -> str | None:
    """The users-per-chip ledger (ISSUE 12): derive how many concurrent
    slots the KV page pool can hold from the gauges the engine publishes
    — ``serving.kv_page_bytes`` x ``kv_pages_total`` is the pool's byte
    budget, ``kv_bytes_per_slot`` is one user's share at the current
    sequence budget, and their ratio is the capacity the quant mode +
    head layout bought.  Returns None without a paged engine's gauges."""
    gauges = snap.get("gauges", {})
    page_bytes = gauges.get("serving.kv_page_bytes")
    pages_total = gauges.get("serving.kv_pages_total")
    per_slot = gauges.get("serving.kv_bytes_per_slot")
    if page_bytes is None or pages_total is None:
        return None
    pool_bytes = page_bytes * pages_total
    bits = gauges.get("serving.kv_quant_bits")
    rows = [
        ("kv_storage_bits", "?" if bits is None else f"{bits:.0f}"),
        ("pool_pages", f"{pages_total:.0f}"),
        ("page_bytes", _fmt_bytes(page_bytes)),
        ("pool_bytes", _fmt_bytes(pool_bytes)),
    ]
    if "serving.kv_pages_in_use" in gauges:
        used = gauges["serving.kv_pages_in_use"]
        rows.append(("pages_in_use",
                     f"{used:.0f} ({used / max(pages_total, 1) * 100:.1f}%)"))
    if per_slot:
        rows.append(("bytes_per_slot", _fmt_bytes(per_slot)))
        rows.append(("slots_per_pool (users/chip)",
                     f"{pool_bytes / per_slot:.1f}"))
    return _rows("kv capacity (users per page pool)", rows,
                 ("metric", "value"))


def render_router(snap: dict) -> str | None:
    """Multi-replica router tier (PR 11): per-replica breaker state /
    in-flight load / queue depth, plus the aggregate affinity, spillover
    and quarantine story.  Returns None when the job published no
    ``router.*`` gauges (single-replica or non-serving jobs)."""
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    names: set[str] = set()
    for prefix in ("router.replica_state.", "router.replica_load.",
                   "router.replica_queue_depth."):
        for k in gauges:
            if k.startswith(prefix):
                names.add(k[len(prefix):])
    if not names and not any(k.startswith("router.") for k in counters):
        return None
    rows = []
    for n in sorted(names):
        state = gauges.get(f"router.replica_state.{n}")
        rows.append((
            n,
            "?" if state is None else ("active" if state else "quarantined"),
            f"{gauges.get(f'router.replica_load.{n}', 0.0):.0f}",
            f"{gauges.get(f'router.replica_queue_depth.{n}', 0.0):.0f}"))
    out = [_rows("router (per replica)", rows,
                 ("replica", "state", "inflight", "queue_depth"))]
    summary = []
    reqs = counters.get("router.requests")
    if reqs:
        summary.append(("requests", f"{reqs:.0f}"))
        hits = counters.get("router.prefix_affinity_hit", 0.0)
        summary.append(("prefix_affinity", f"{hits / reqs * 100:.1f}%"))
    if "router.prefix_hit_rate" in gauges:
        summary.append(("aggregate_prefix_hit_rate",
                        f"{gauges['router.prefix_hit_rate'] * 100:.1f}%"))
    for name in ("router.spillover", "router.quarantines",
                 "router.readmissions", "router.replica_errors"):
        if name in counters:
            summary.append((name.split(".", 1)[1],
                            f"{counters[name]:.0f}"))
    if summary:
        out.append(_rows("router (aggregate)", summary, ("metric", "value")))
    return "\n\n".join(out)


def render_elasticity(snap: dict) -> str | None:
    """Elastic-training tier (ISSUE 13): current mesh width, topology
    resize count, last reshard wall-clock, scaleout wave size, and the
    injected shrink/grow chaos fires that exercised them.  Returns None
    when the job published no ``elastic.*`` gauges (fixed-topology jobs)."""
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    rows = []
    if "elastic.mesh_size" in gauges:
        rows.append(("mesh_size", f"{gauges['elastic.mesh_size']:.0f} chips"))
    if "elastic.wave_size" in gauges:
        rows.append(("wave_size", f"{gauges['elastic.wave_size']:.0f} workers"))
    if "elastic.resizes_total" in gauges:
        rows.append(("resizes_total", f"{gauges['elastic.resizes_total']:.0f}"))
    if "elastic.reshard_seconds" in gauges:
        rows.append(("reshard_seconds", _fmt_s(gauges["elastic.reshard_seconds"])))
    for name, label in (("checkpoint.reshards", "reshard_restores"),
                        ("resilience.device_losses", "device_losses"),
                        ("scaleout.wave_shrinks", "wave_shrinks"),
                        ("scaleout.wave_grows", "wave_grows"),
                        ("faults.injected.mesh.shrink", "injected mesh.shrink"),
                        ("faults.injected.mesh.grow", "injected mesh.grow")):
        if name in counters:
            rows.append((label, f"{counters[name]:.0f}"))
    if not rows:
        return None
    return _rows("elasticity (topology changes)", rows, ("metric", "value"))


def render_online(snap: dict) -> str | None:
    """Online learning loop tier (ISSUE 15): live weight generation,
    reload/rollback counts, captured-traffic volume, and the last hot
    reload's wall-clock.  Returns None when the process published no
    ``online.*`` state (offline-only jobs)."""
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    rows = []
    if "online.generation" in gauges:
        rows.append(("generation", f"{gauges['online.generation']:.0f}"))
    for name, label in (("online.reloads", "reloads"),
                        ("online.rollbacks", "rollbacks"),
                        ("online.rounds", "rounds"),
                        ("online.captured_records", "captured_records"),
                        ("capture.corrupt_records", "corrupt_records"),
                        ("checkpoint.quarantined", "quarantined_ckpts")):
        if name in counters:
            rows.append((label, f"{counters[name]:.0f}"))
    if "capture.bytes" in gauges:
        rows.append(("capture.bytes", f"{gauges['capture.bytes']:.0f} B"))
    if "online.reload_seconds" in gauges:
        rows.append(("reload_seconds", _fmt_s(gauges["online.reload_seconds"])))
    if "online.canary_loss" in gauges:
        rows.append(("canary_loss", f"{gauges['online.canary_loss']:.4f}"))
    if not rows:
        return None
    return _rows("online loop (serve → capture → fine-tune → reload)",
                 rows, ("metric", "value"))


def render_fleet(snap: dict) -> str | None:
    """Fleet federation plane (ISSUE 16): the ``fleet.*`` rollups the
    scraper publishes — fleet-wide sums plus the per-replica min/med/max
    spread of each rolled-up series, and the scrape health counters.
    Returns None when no :class:`FleetScraper` ran in this process."""
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    if not any(k.startswith("fleet.") for k in gauges) and \
            not any(k.startswith("fleet.") for k in counters):
        return None
    rows = []
    for name, label in (("fleet.replicas", "replicas"),
                        ("fleet.stale_replicas", "stale_replicas"),
                        ("fleet.tokens_per_sec", "tokens_per_sec"),
                        ("fleet.kv_pages_in_use", "kv_pages_in_use"),
                        ("fleet.queue_depth", "queue_depth"),
                        ("fleet.tokens_total", "tokens_total")):
        if name in gauges:
            rows.append((label, f"{gauges[name]:.6g}", "", "", ""))
    # spread rows: fleet.spread.<series>.{min,med,max}
    spreads: dict[str, dict[str, float]] = {}
    prefix = "fleet.spread."
    for k, v in gauges.items():
        if k.startswith(prefix):
            base, _, stat = k[len(prefix):].rpartition(".")
            if stat in ("min", "med", "max"):
                spreads.setdefault(base, {})[stat] = v
    for base, s in sorted(spreads.items()):
        rows.append((f"spread {base}", "",
                     f"{s.get('min', 0.0):.6g}", f"{s.get('med', 0.0):.6g}",
                     f"{s.get('max', 0.0):.6g}"))
    for name, label in (("fleet.scrapes", "scrapes"),
                        ("fleet.scrape_errors", "scrape_errors"),
                        ("fleet.tenant_overflow", "tenant_overflow")):
        if name in counters:
            rows.append((label, f"{counters[name]:.0f}", "", "", ""))
    if not rows:
        return None
    return _rows("fleet (federated rollups + spread)", rows,
                 ("metric", "value", "min", "med", "max"))


def render_control(snap: dict) -> str | None:
    """Control plane (DESIGN.md §26): autoscaler liveness + actions,
    brownout level and what it currently costs callers, overload
    verdicts.  Returns None when no controller ran in this process."""
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    if not any(k.startswith("control.") for k in gauges) and \
            not any(k.startswith("control.") for k in counters):
        return None
    rows = []
    for name, label in (("control.autoscaler_alive", "autoscaler_alive"),
                        ("control.pool_size", "pool_size"),
                        ("control.brownout_level", "brownout_level"),
                        ("serving.speculative_enabled", "speculative_enabled"),
                        ("serving.max_new_cap", "max_new_cap")):
        if name in gauges:
            rows.append((label, f"{gauges[name]:.6g}"))
    for name, label in (("control.scale_up", "scale_ups"),
                        ("control.scale_down", "scale_downs"),
                        ("control.scale_errors", "scale_errors"),
                        ("control.errors", "loop_errors"),
                        ("control.autoscaler_killed", "autoscaler_killed"),
                        ("control.brownout_transitions",
                         "brownout_transitions"),
                        ("control.throttled", "throttled"),
                        ("control.shed", "background_shed"),
                        ("serving.preempted", "preempted"),
                        ("serving.max_new_clamped", "max_new_clamped")):
        if name in counters:
            rows.append((label, f"{counters[name]:.0f}"))
    if not rows:
        return None
    return _rows("control (autoscaler + overload)", rows, ("metric", "value"))


def render_tenants(snap: dict, top_k: int = 10) -> str | None:
    """Per-tenant accounting (ISSUE 16): the ``tenant.<label>.*``
    counters fed through the bounded :class:`TenantLabels` fold, ranked
    by tokens generated; the ``__other__`` overflow bucket renders like
    any tenant so folded traffic stays visible.  Returns None when no
    tenant traffic was accounted."""
    counters = snap.get("counters", {})
    tenants: dict[str, dict[str, float]] = {}
    for k, v in counters.items():
        if not k.startswith("tenant."):
            continue
        label, _, field = k[len("tenant."):].rpartition(".")
        if label:
            tenants.setdefault(label, {})[field] = v
    if not tenants:
        return None
    ranked = sorted(tenants.items(),
                    key=lambda kv: -(kv[1].get("generated_tokens", 0.0) +
                                     kv[1].get("prompt_tokens", 0.0)))
    rows = []
    for label, c in ranked[:top_k]:
        rows.append((label,
                     f"{c.get('prompt_tokens', 0.0):.0f}",
                     f"{c.get('generated_tokens', 0.0):.0f}",
                     _fmt_s(c.get("queue_wait_s", 0.0)),
                     f"{c.get('rejected', 0.0):.0f}",
                     f"{c.get('deadline_dropped', 0.0):.0f}"))
    title = f"tenants (top {min(top_k, len(ranked))} of {len(ranked)} by tokens)"
    if len(ranked) > top_k:
        title += f" [+{len(ranked) - top_k} not shown]"
    return _rows(title, rows,
                 ("tenant", "prompt_tok", "gen_tok", "queue_wait",
                  "rejected", "deadline_dropped"))


def render_forecast(snap: dict) -> str | None:
    """Trend forecasts (ISSUE 16): predicted seconds until each SLO
    objective breaches (``+Inf`` = flat/receding/noisy — no forecast),
    plus the warning count.  Returns None without a ForecastEvaluator."""
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    prefix = "forecast.time_to_breach."
    rows = [(k[len(prefix):],
             "inf" if v == float("inf") else _fmt_s(v))
            for k, v in sorted(gauges.items()) if k.startswith(prefix)]
    if "forecast.breach_warnings" in counters:
        rows.append(("breach_warnings",
                     f"{counters['forecast.breach_warnings']:.0f}"))
    if not rows:
        return None
    return _rows("forecast (time to SLO breach)", rows,
                 ("objective", "time_to_breach"))


def render_utilization(snap: dict) -> str | None:
    """MFU / memory-bandwidth gauges from the analytic cost model
    (``observability.cost``): published by the trainer and the decode loop
    from the same ``cost_analysis()``-derived FLOPs."""
    gauges = snap.get("gauges", {})
    rows = [(name, f"{gauges[name] * 100:.2f}%")
            for name in ("train.mfu", "train.mbu",
                         "serving.decode_mfu", "serving.decode_mbu")
            if name in gauges]
    if not rows:
        return None
    return _rows("utilization (analytic cost model)", rows,
                 ("gauge", "value"))


def render_goodput(snap: dict) -> str | None:
    """Goodput accounting + SLO burn rates (ISSUE 14): the wall-clock
    split a finished ``GoodputTracker`` published, each state as seconds
    and share-of-wall, plus every ``slo.burn_rate.*`` gauge the SLO
    evaluator keeps live (>= 1.0 means the error budget burns faster
    than the objective allows).  Returns None when the job published
    neither (unsupervised or pre-goodput jobs)."""
    gauges = snap.get("gauges", {})
    counters = snap.get("counters", {})
    out = []
    wall = gauges.get("goodput.wall_seconds")
    if wall is not None:
        rows = [("fraction", f"{gauges.get('goodput.fraction', 0.0) * 100:.1f}%"),
                ("wall", _fmt_s(wall))]
        prefix = "goodput.seconds."
        for k, v in sorted(gauges.items()):
            if k.startswith(prefix):
                share = v / wall * 100 if wall else 0.0
                rows.append((k[len(prefix):], f"{_fmt_s(v)} ({share:.1f}%)"))
        out.append(_rows("goodput (wall-clock accounting)", rows,
                         ("state", "value")))
    slo_rows = [(k[len("slo.burn_rate."):],
                 f"{v:.2f}x" + ("  << BURNING" if v >= 1.0 else ""))
                for k, v in sorted(gauges.items())
                if k.startswith("slo.burn_rate.")]
    if "slo.breaches" in counters:
        slo_rows.append(("breaches", f"{counters['slo.breaches']:.0f}"))
    if slo_rows:
        out.append(_rows("slo (error-budget burn rates)", slo_rows,
                         ("objective", "burn")))
    return "\n\n".join(out) if out else None


def render_metrics(snap: dict) -> str:
    parts = []
    state_mem = render_state_memory(snap)
    if state_mem is not None:
        parts.append(state_mem)
    for section in (render_serving(snap), render_kv_capacity(snap),
                    render_router(snap), render_fleet(snap),
                    render_control(snap),
                    render_tenants(snap), render_elasticity(snap),
                    render_online(snap), render_goodput(snap),
                    render_forecast(snap), render_utilization(snap)):
        if section is not None:
            parts.append(section)
    parts.append(_rows(
        "counters", sorted(snap.get("counters", {}).items()),
        ("name", "value")))
    parts.append(_rows(
        "gauges",
        [(k, f"{v:.6g}") for k, v in sorted(snap.get("gauges", {}).items())],
        ("name", "value")))
    timer_rows = [
        (name, s["count"], _fmt_s(s["mean_s"]), _fmt_s(s["p50_s"]),
         _fmt_s(s["p95_s"]), _fmt_s(s["p99_s"]), _fmt_s(s["total_s"]))
        for name, s in sorted(snap.get("timers", {}).items())]
    parts.append(_rows(
        "timers", timer_rows,
        ("name", "count", "mean", "p50", "p95", "p99", "total")))
    return "\n\n".join(parts)


def render_status(status: dict) -> str:
    if not status:
        return "status: (no tracker attached)"
    lines = ["status"]
    hb = status.get("heartbeats_age_s", {})
    enabled = status.get("enabled", {})
    worker_rows = [(w, enabled.get(w, "?"), hb.get(w, "?"))
                   for w in status.get("workers", [])]
    lines.append(_rows("  workers", worker_rows,
                       ("worker", "enabled", "heartbeat_age_s")))
    for k in ("current_jobs", "pending_updates", "done"):
        if k in status:
            lines.append(f"  {k}: {status[k]}")
    counters = status.get("counters", {})
    if counters:
        lines.append(_rows("  tracker counters", sorted(counters.items()),
                           ("name", "value")))
    for e in status.get("errors", []):
        lines.append(f"  partial: {e}")
    return "\n".join(lines)


_SPARK = " ▁▂▃▄▅▆▇█"


def _sparkline(values: list[float], width: int = 40) -> str:
    vals = [v for v in values if v == v][-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK[4] * len(vals)
    return "".join(
        _SPARK[min(8, int((v - lo) / (hi - lo) * 8.999))] for v in vals)


def _read_timeline(path: str) -> dict[str, list[tuple[float, float]]]:
    """Merge ``timeseries-*.jsonl`` exports (one file or a directory)
    into ``{series: [(t, value), ...]}``, skipping torn/partial lines —
    the stdlib twin of ``timeseries.read_back_series``."""
    import os
    paths = ([os.path.join(path, f) for f in sorted(os.listdir(path))
              if f.endswith(".jsonl")] if os.path.isdir(path) else [path])
    series: dict[str, list[tuple[float, float]]] = {}
    for p in paths:
        with open(p, errors="replace") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue            # torn tail from a killed process
                if not isinstance(rec, dict) or "series" not in rec:
                    continue
                t = float(rec.get("t", 0.0))
                for name, v in rec["series"].items():
                    series.setdefault(name, []).append((t, float(v)))
    for rows in series.values():
        rows.sort(key=lambda tv: tv[0])
    return series


def render_timeline(path: str, pattern: str = "") -> str:
    series = _read_timeline(path)
    names = sorted(n for n in series if pattern in n)
    if not names:
        return f"timeline: no series matching {pattern!r} in {path}"
    rows = []
    for name in names:
        vals = [v for _, v in series[name]]
        rows.append((name, len(vals), f"{min(vals):.6g}", f"{vals[-1]:.6g}",
                     f"{max(vals):.6g}", _sparkline(vals)))
    span = max(t for rs in series.values() for t, _ in rs) - \
        min(t for rs in series.values() for t, _ in rs)
    return _rows(f"timeline ({len(names)} series over {span:.1f}s)", rows,
                 ("series", "n", "min", "last", "max", "recent"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int)
    ap.add_argument("--url", help="full base URL (overrides --host/--port)")
    ap.add_argument("--prom", action="store_true",
                    help="dump raw Prometheus text exposition instead")
    ap.add_argument("--timeline", metavar="PATH",
                    help="render TimeSeriesStore JSONL (file or dir) "
                         "offline instead of scraping a server")
    ap.add_argument("--series", default="",
                    help="substring filter for --timeline series names")
    ap.add_argument("--timeout", type=float, default=5.0)
    args = ap.parse_args(argv)
    if args.timeline:
        print(render_timeline(args.timeline, args.series))
        return 0
    if args.url:
        base = args.url.rstrip("/")
    elif args.port:
        base = f"http://{args.host}:{args.port}"
    else:
        ap.error("need --port or --url")

    if args.prom:
        body, err = _fetch(base, "/metrics.prom", args.timeout)
        if err:
            print(err, file=sys.stderr)
            return 1
        print(body, end="")
        return 0

    snap, err = _fetch(base, "/metrics", args.timeout)
    if err:
        print(err, file=sys.stderr)
        return 1
    print(render_metrics(snap))
    status, err = _fetch(base, "/status", args.timeout)
    print()
    if err:
        print(f"status unavailable ({err})")
    else:
        print(render_status(status))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
