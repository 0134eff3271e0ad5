"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the machine it is started on and prints, as the last line of
its standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` the profiler
runs over a slice of the window and the metrics are the cell's per-layer
metrics.  Everything else goes on earlier lines.

Nothing is run without a TPU, with fewer chips than the cell asks for, or on a
device kind that ``peaks.json`` does not have: the exit code is non-zero and
no result line is printed.  There is no CPU branch.

This file holds no list of cells, configurations, metrics or readers.  The
cell is ``workloads/<cell>.json``; it names its configuration
(``configs/<config>.json``) and its runner (``runners/<runner>.py``); which
metrics the cell reports is read from ``BENCHMARK.json``; a per-layer metric is
``layer_metrics/<name>.json``, which names its reader (``readers/<reader>.py``).
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()      # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import harness, trace_reduce  # noqa: E402
from benchmark.harness import BenchmarkError, say  # noqa: E402


def device_report(devs, with_program: int = 0) -> dict:
    """``memory_peak_bytes``: the peak on the fullest chip.  On this runtime
    ``peak_bytes_in_use`` counts live buffers only, so a runner that knows
    what its largest program holds besides (the compiler's own account of its
    temporaries) gives live bytes + that, and the larger of the two counts."""
    peak = max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peak, with_program)}


def layer_metrics(man: dict, cell: str, run: dict) -> dict:
    """Every per-layer metric of the cell that its reader finds something for."""
    out = {}
    for m in harness.cell_metrics(man, "per_layer", cell):
        spec = harness.load("layer_metrics", m["name"])
        value = harness.load_module("readers", spec["reader"]).read(
            spec.get("args", {}), run)
        if value is None:
            say(f"  per-layer {m['name']}: its reader found nothing to read")
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        man = harness.manifest()
        workload = harness.load("workloads", args.workload)
        config = harness.load("configs", workload["config"])
        runner = harness.load_module("runners", workload["runner"])
        devs = harness.require_tpu(workload["chips"])
        peak = harness.load_peak(devs[0].device_kind)
    except BenchmarkError as e:
        sys.exit(f"benchmark: {e}")

    from deeplearning4j_tpu.parallel.compile_cache import setup_compile_cache

    events = harness.CacheEvents()
    say(f"cell {args.workload}: seed {args.seed}, {args.seconds:g}s, trace "
        f"{args.trace}; device {devs[0].device_kind} x {len(devs)}, bytes_limit "
        f"{devs[0].memory_stats().get('bytes_limit')}; compile cache "
        f"{setup_compile_cache()}")

    cell = harness.Cell(workload=workload, config=config, seed=args.seed,
                        seconds=args.seconds, devices=devs,
                        process_t0=PROCESS_T0)
    tracer = None
    if args.trace:
        # a fixed, gitignored place inside the checkout; emptied every run
        trace_dir = REPO / ".cache" / "benchmark_trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        a, b = workload["trace_slice_s"]
        scale = min(1.0, args.seconds / (b + 1.0))   # short rehearsal windows
        tracer = harness.TraceSlice(trace_dir, a * scale, b * scale)
        cell.on_window = tracer.arm

    outcome = runner.run(cell)
    say(f"compile cache events: {events.counts}")

    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    device = device_report(devs, outcome.facts.get("device_bytes_with_program", 0))
    if tracer is None:
        wanted = harness.cell_metrics(man, "end_to_end", args.workload)
        missing = [m["name"] for m in wanted if m["name"] not in outcome.end_to_end]
        if missing:     # e.g. no request completed: nothing to take a tail of
            say(f"  FAILED: the runner could not measure {missing}")
            result["correct"] = False
        result["metrics"] = {
            m["name"]: {"value": outcome.end_to_end[m["name"]], "unit": m["unit"]}
            for m in wanted if m["name"] not in missing}
    else:
        say("end to end in this traced run (not reported): " + ", ".join(
            f"{k} {v:.6g}" for k, v in outcome.end_to_end.items()))
        rows = trace_reduce.read_xspace(tracer.join())
        summary = trace_reduce.reduce(rows)
        say(f"trace: {len(rows)} device events; "
            + "; ".join(f"{p}: busy {v['busy_ns'] / 1e9:.4f}s of "
                        f"{v['window_ns'] / 1e9:.4f}s"
                        for p, v in summary["planes"].items()))
        result["metrics"] = layer_metrics(
            man, args.workload, {"facts": outcome.facts, "peak": peak,
                                 "trace": summary, "trace_rows": rows})
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
