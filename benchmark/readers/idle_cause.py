"""Reader ``idle_cause``: the part of the device's idle share that fell while
the host was inside one of the program's spans, in % of the traced slice.

Each idle interval of the device (the complement of the union of its ``XLA
Ops`` events between the first one's start and the last one's end, as
``trace_reduce.busy_idle`` has it) is cut by the program's spans on the host
plane of the same trace (``observability/tracing.py`` writes each as a
``TraceAnnotation``), moved onto the device's clock and the innermost span
winning.  The value is the idle time under the spans named in ``spans`` (args;
a name stands for its children too: ``trainer.fence`` for
``trainer.fence.wait``) over the slice, on the chip with the largest idle
share, so that the causes add up to ``device_idle_share``.
``"spans": []`` is the idle time under no span: the whole-run spans
(``WHOLE_RUN``) name no cause and count as none.

The whole table (every span that held idle time) is printed once per trace.
Nothing without a trace, or where the trace holds none of the program's spans
(a program that writes no annotations).
"""

from benchmark import trace_reduce, trace_spans
from benchmark.harness import say

WHOLE_RUN = ("trainer.fit",)


def table(rows, parsed: dict) -> tuple[dict, float] | None:
    """``({span or None: idle ns}, window ns)`` of the chip with the largest
    idle share in ``rows`` (``run["trace_rows"]``: the very intervals
    ``device_idle_share`` is made of); None where the trace has no program
    span.  The host's spans are moved onto that chip's clock first
    (``trace_spans.clock_offsets``)."""
    if not parsed["host"]:
        return None
    worst = None
    for plane, ops in trace_reduce.by_plane(rows, trace_reduce.OP_LINE).items():
        gaps, (t0, t1) = trace_spans.idle_intervals(
            [(r[3], r[3] + r[4]) for r in ops])
        share = sum(b - a for a, b in gaps) / (t1 - t0)
        if worst is None or share > worst[0]:
            worst = (share, plane, gaps, t1 - t0)
    _, plane, gaps, window = worst
    ahead, lo, hi = trace_spans.clock_offsets(
        parsed["modules"], parsed["launches"]).get(plane, (0.0, None, None))
    say(f"host plane ahead of {plane} by {ahead / 1e3:.0f} us"
        + (f" (between {lo / 1e3:.0f} and {hi / 1e3:.0f} us by the runtime's "
           f"enqueue and completion events)" if lo is not None else
           " (no launch of a program bounds it: spans are not shifted)"))
    host = [(name, start - ahead, end - ahead, thread)
            for name, start, end, thread in parsed["host"]]
    by = trace_spans.idle_by_span(gaps, host)
    for name in WHOLE_RUN:
        by[None] = by.get(None, 0.0) + by.pop(name, 0.0)
    return by, window


def under(by: dict, spans) -> float:
    """Idle ns under ``spans`` and their children; under no span for ``[]``."""
    if not spans:
        return by.get(None, 0.0)
    return sum(ns for name, ns in by.items() if name is not None and any(
        name == s or name.startswith(s + ".") for s in spans))


def show(by: dict, window: float) -> None:
    idle = sum(by.values())
    say(f"device idle {idle / 1e6:.3f} ms of a {window / 1e9:.4f} s slice "
        f"({100 * idle / window:.4f}%), by the host's phase:")
    for name, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        say(f"  {name or 'unattributed':<28}{ns / 1e6:9.3f} ms"
            f"{100 * ns / window:9.4f}% of the slice"
            f"{100 * ns / idle if idle else 0:8.2f}% of idle")


def read(args: dict, run: dict):
    parsed = trace_spans.of_run(run)
    if parsed is None:
        return None

    def make():
        found = table(run["trace_rows"], parsed)
        if found is not None:
            show(*found)
        return found

    found = trace_spans.cached(parsed, "idle_cause", make)
    if found is None:
        return None
    by, window = found
    return 100.0 * under(by, args["spans"]) / window
