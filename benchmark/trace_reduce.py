"""From a profiler trace to numbers: device busy and idle time, the operations
that took most of it, the longest idle gaps, and whole executions of a program.

Everything but ``read_xspace`` is plain arithmetic on rows
``(plane, line, name, start_ns, dur_ns)``, so it is tested on hand-written
rows with no chip.  A device plane is ``/device:TPU:<n>``; on it the line
``XLA Ops`` holds one event per executed HLO operation (nested where an
operation has a body: a ``while`` and the operations inside it), and
``XLA Modules`` one event per executed program.  Busy time is the UNION of
the operation intervals, never their sum.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

Row = tuple[str, str, str, float, float]   # plane, line, name, start_ns, dur_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
EDGE_NS = 1000.0            # an event this close to the trace's end was clipped
# "%fusion.3 = (f32[64,512]{1,0:T(8,128)}, ...) fusion(...), kind=kLoop, ..."
HLO_TEXT = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?")


class TraceError(RuntimeError):
    """The trace cannot be reduced (no device plane, no operation line)."""


def read_xspace(path: Path) -> list[Row]:
    """Rows of the device planes of an ``.xplane.pb`` (host planes are left
    out: nothing here reads them, and they hold most of the events)."""
    from jax.profiler import ProfileData

    return rows_of(ProfileData.from_file(str(path)))


def rows_of(data) -> list[Row]:
    return [(plane.name, line.name, ev.name, float(ev.start_ns),
             float(ev.duration_ns))
            for plane in data.planes if DEVICE_PLANE.match(plane.name)
            for line in plane.lines for ev in line.events]


def by_plane(rows, line: str) -> dict[str, list[Row]]:
    """The rows of ``line`` on each device plane.  No device plane, or a
    device plane without that line, is an error: a host-only trace must not
    read as a device that was idle all the time."""
    planes: dict[str, list[Row]] = defaultdict(list)
    lines_seen: dict[str, set] = defaultdict(set)
    for r in rows:
        if DEVICE_PLANE.match(r[0]):
            lines_seen[r[0]].add(r[1])
            if r[1] == line:
                planes[r[0]].append(r)
    if not lines_seen:
        raise TraceError("the trace has no /device:TPU:<n> plane: nothing ran "
                         "on the device, or it was not traced")
    for name, seen in lines_seen.items():
        if not planes.get(name):
            raise TraceError(f"{name} has no {line!r} events (lines: "
                             f"{sorted(seen)})")
    return dict(planes)


def busy_idle(ops: list[Row], span: tuple[float, float] | None = None) -> dict:
    """Busy and idle time of one plane's operation rows inside ``span``
    (default: first operation's start to last operation's end), and the idle
    gaps, each named by the operation before and after it."""
    ivs = sorted((r[3], r[3] + r[4], r[2]) for r in ops)
    t0, t1 = span or (ivs[0][0], max(e for _, e, _ in ivs))
    busy = 0.0
    gaps = []                      # (dur_ns, "prev -> next")
    end, last = t0, "(start)"
    for s, e, name in ivs:
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if s > end:
            gaps.append((s - end, f"{last} -> {name}"))
        if e > end:
            busy += e - max(s, end)
            end, last = e, name
    if t1 > end:
        gaps.append((t1 - end, f"{last} -> (end)"))
    return {"window_ns": t1 - t0, "busy_ns": busy,
            "idle_share": 1.0 - busy / (t1 - t0) if t1 > t0 else 0.0,
            "gaps": gaps}


def self_times(ops: list[Row]) -> dict[str, float]:
    """Total SELF time per operation name: an operation's duration less what
    the operations nested inside it cover, so that a ``while`` is not counted
    on top of its body."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []         # [end, name, self_ns]
    for s, e, name in sorted(((r[3], r[3] + r[4], r[2]) for r in ops),
                             key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, self_ns = stack.pop()
            out[n] += self_ns
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for end, n, self_ns in stack:
        out[n] += self_ns
    return dict(out)


def short(name: str) -> str:
    """An operation's event name is its whole HLO text: keep the instruction's
    name without its number and its (first) result shape, ``fusion
    f32[64,512]``, so that the copies of one operation in every layer of a
    model add up under one name."""
    m = HLO_TEXT.match(name)
    if not m:
        return name[:80]
    base = re.sub(r"\.\d+", "", m.group(1))      # fusion.12.remat -> fusion.remat
    return f"{base} {m.group(2)}" if m.group(2) else base


def top(pairs, n: int = 10) -> list[list]:
    """``[[name, seconds], ...]``, the ``n`` largest, from (name, ns) pairs
    summed by name."""
    total: dict[str, float] = defaultdict(float)
    for name, ns in pairs:
        total[name] += ns
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def whole_runs(rows, prefix: str) -> dict[str, list[tuple[float, float]]]:
    """Per device plane, the ``(start, end)`` of every WHOLE execution of the
    programs whose name starts with ``prefix`` (line ``XLA Modules``).  The
    profiler clips the execution that is running when the trace starts or
    stops, so one that touches either end of the plane's events is left out."""
    edges: dict[str, tuple[float, float]] = {}
    for plane, _, _, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            lo, hi = edges.get(plane, (start, start + dur))
            edges[plane] = (min(lo, start), max(hi, start + dur))
    out: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for plane, line, name, start, dur in rows:
        if (DEVICE_PLANE.match(plane) and line == MODULE_LINE
                and name.startswith(prefix)
                and start > edges[plane][0] + EDGE_NS
                and start + dur < edges[plane][1] - EDGE_NS):
            out[plane].append((start, start + dur))
    return {p: sorted(v) for p, v in out.items()}


def reduce(rows) -> dict:
    """The summary the harness reports: per plane and overall."""
    planes = {}
    pairs_ops, pairs_gaps = [], []
    for name, ops in sorted(by_plane(rows, OP_LINE).items()):
        ops = [(p, ln, short(op), start, dur) for p, ln, op, start, dur in ops]
        b = busy_idle(ops)
        planes[name] = {k: b[k] for k in ("window_ns", "busy_ns", "idle_share")}
        pairs_ops += self_times(ops).items()
        pairs_gaps += [(what, ns) for ns, what in b["gaps"]]
    n = len(planes)
    return {
        "planes": planes,
        "busy_s": sum(p["busy_ns"] for p in planes.values()) / n / 1e9,
        "window_s": sum(p["window_ns"] for p in planes.values()) / n / 1e9,
        "idle_share_worst": max(p["idle_share"] for p in planes.values()),
        # seconds summed over the chips used
        "device_ops": top(pairs_ops),
        "idle_gaps": top(pairs_gaps),
    }
