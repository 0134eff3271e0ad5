"""What the PROGRAM wrote into the profiler's trace, beside what the device
did: the ``jax.named_scope`` path of every ``XLA Ops`` event, and the
program's own spans (``observability/tracing.py`` enters a
``jax.profiler.TraceAnnotation`` for each) on the host plane, on the clock of
the device operations.

``parse`` is the only part that touches a file; everything else is plain
arithmetic on rows, tested on hand-written rows with no chip:

- an operation row is ``(plane, name, start_ns, dur_ns, path)``, ``path`` its
  ``op_name`` (``jit(step)/jvp(vmap(attention))/.../dot_general``), ``""``
  where the trace has none;
- a host row is ``(name, start_ns, end_ns, thread)``.

``jax.profiler.ProfileData`` shows an event's own stats, and the scope path is
a stat of the event's METADATA, so ``parse`` reads the ``.xplane.pb`` wire
format itself: the seven messages of ``tsl/profiler/protobuf/xplane.proto``,
varints and length-delimited fields, with the standard library alone.
"""

from __future__ import annotations

import re
import traceback
from collections import defaultdict
from pathlib import Path

from benchmark import trace_reduce
from benchmark.harness import REPO, say

OpRow = tuple[str, str, float, float, str]    # plane, name, start, dur, path
HostRow = tuple[str, float, float, str]       # name, start, end, thread

TRACE_ROOT = REPO / ".cache" / "benchmark_trace"     # where run.py traces to
HOST_PLANE = re.compile(r"^/host:")
# the program's spans are dotted lower-case names (``trainer.fence.wait``) or
# ``train_step``; the runtime's own TraceMes have capitals, spaces, colons or
# parentheses, and the Python tracer's start with ``$``
PROGRAM_SPAN = re.compile(r"^(train_step|[a-z_]+(\.[a-z_0-9]+)+)$")
PATH_STAT = "tf_op"      # the metadata stat that holds an operation's op_name
WRAPPER = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")        # jvp(x), transpose(x) ...
# the TPU runtime's own host events around one execution of a program; each
# carries the ``run_id`` of the device's ``XLA Modules`` event
ENQUEUED, COMPLETED = "DoEnqueueProgram", "CompleteCallbacks"
LAUNCH_EVENTS = (ENQUEUED, COMPLETED)
KERNEL = re.compile(r"^[a-z_0-9]+\.[a-z_0-9]+$")       # registry.get: <kind>.<name>


# ------------------------------------------------------------ the wire format

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} is not in xplane.proto")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _stat(buf, stat_names: dict[int, str]):
    """``(name, value)`` of one XStat: a string, a reference to a string
    (``ref_value``) or an unsigned integer (a scope path is one of the first
    two, a ``run_id`` any of the three); other kinds read as None."""
    name, value = None, None
    for no, v in _fields(buf):
        if no == 1:
            name = stat_names.get(v)
        elif no in (3, 4):
            value = v
        elif no == 5:
            value = _text(v)
        elif no == 7:
            value = stat_names.get(v)
    return name, value


def _plane(buf) -> tuple[str, list, dict, dict]:
    """One XPlane: its name, its raw lines, and its two metadata maps with
    the stat names resolved."""
    name, lines, raw_events, stat_names = "", [], {}, {}
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            key, value = _map_entry(v)
            raw_events[key] = value
        elif no == 5:
            key, value = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, x in _fields(value) if n == 2), "")
    return name, lines, raw_events, stat_names


def _event_metadata(buf, stat_names) -> tuple[str, dict]:
    name, stats = "", {}
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 5:
            key, value = _stat(v, stat_names)
            if key and value is not None:
                stats[key] = value
    return name, stats


def _line(buf) -> tuple[str, int, list]:
    name, t0_ns, events = "", 0, []
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            t0_ns = v
        elif no == 4:
            events.append(v)
    return name, t0_ns, events


def _event(buf, stat_names) -> tuple[int, float, float, dict]:
    meta = offset_ps = dur_ps = 0
    stats = {}
    for no, v in _fields(buf):
        if no == 1:
            meta = v
        elif no == 2:
            offset_ps = v
        elif no == 3:
            dur_ps = v
        elif no == 4:
            key, value = _stat(v, stat_names)
            if key and value is not None:
                stats[key] = value
    return meta, offset_ps / 1e3, dur_ps / 1e3, stats


_parsed: dict[tuple, dict] = {}      # (path, mtime, size) -> parse()'s result


def cached(parsed: dict, key: str, make):
    """``make()`` once for each parsed trace: a table that several metrics
    read is computed, and printed, once."""
    if key not in parsed:
        parsed[key] = make()
    return parsed[key]


def parse(path: Path) -> dict:
    """One ``.xplane.pb`` as rows: ``ops`` (``OpRow``: the ``XLA Ops`` events
    of the device planes with their scope paths), ``host`` (``HostRow``: the
    program's spans on the host planes), and what ``clock_offsets`` aligns
    the two with: ``modules`` (``(plane, run_id, start_ns, end_ns)`` of the
    ``XLA Modules`` events) and ``launches`` (``(name, chip, run_id,
    start_ns)`` of the runtime's own host events in ``LAUNCH_EVENTS``).  A
    file is parsed once however many metrics read it."""
    path = Path(path)
    stamp = path.stat()
    key = (str(path), stamp.st_mtime_ns, stamp.st_size)
    if key in _parsed:
        return _parsed[key]
    out: dict = {"ops": [], "host": [], "modules": [], "launches": []}
    for no, plane_buf in _fields(memoryview(path.read_bytes())):
        if no != 1:
            continue
        plane, lines, raw_events, stat_names = _plane(plane_buf)
        device = bool(trace_reduce.DEVICE_PLANE.match(plane))
        if not device and not HOST_PLANE.match(plane):
            continue
        metadata: dict[int, tuple[str, dict]] = {}
        for line_buf in lines:
            line, t0_ns, events = _line(line_buf)
            if device and line not in (trace_reduce.OP_LINE,
                                       trace_reduce.MODULE_LINE):
                continue
            for event_buf in events:
                meta, offset_ns, dur_ns, stats = _event(event_buf, stat_names)
                if meta not in metadata:
                    metadata[meta] = _event_metadata(
                        raw_events.get(meta, b""), stat_names)
                name, meta_stats = metadata[meta]
                start = t0_ns + offset_ns
                if device and line == trace_reduce.OP_LINE:
                    out["ops"].append((plane, name, start, dur_ns,
                                       meta_stats.get(PATH_STAT, "")))
                elif device:
                    if "run_id" in stats:
                        out["modules"].append((plane, int(stats["run_id"]),
                                               start, start + dur_ns))
                elif PROGRAM_SPAN.match(name):
                    out["host"].append((name, start, start + dur_ns, line))
                elif name in LAUNCH_EVENTS and "run_id" in stats:
                    out["launches"].append(
                        (name, int(stats.get("device_ordinal", 0)),
                         int(stats["run_id"]), start))
    _parsed.clear()                  # one trace at a time is enough
    _parsed[key] = out
    return out


def of_run(run: dict, root: Path | None = None) -> dict | None:
    """The parsed trace of this run: the newest ``.xplane.pb`` under the
    directory ``run.py`` traces into, which this process has just written.
    ``run["trace_rows"]`` has the device rows without their stats and no host
    rows, so the file is opened again.  None where there is no trace, or the
    newest file is not the one ``trace_rows`` came from."""
    rows = run.get("trace_rows")
    if rows is None:
        return None
    found = sorted((root or TRACE_ROOT).glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not found:
        return None
    try:
        parsed = parse(found[-1])
    except Exception:    # a reader that cannot read finds nothing; the run goes on
        say(f"trace_spans: could not read {found[-1]}:\n{traceback.format_exc()}")
        return None
    n_ops = sum(1 for r in rows if r[1] == trace_reduce.OP_LINE)
    return parsed if len(parsed["ops"]) == n_ops else None


# ------------------------------------------------------- busy time, by scope

def scope_of(path: str, scopes) -> tuple[str | None, str | None, bool]:
    """``(scope, inner, backward)`` of one operation's path: the OUTERMOST of
    ``scopes`` on it, the next name inside that one (another of ``scopes``,
    or a kernel's registered ``<kind>.<name>``, as ``attention.flash`` nested
    in ``attention``), and whether the operation belongs to the backward pass
    (a ``transpose(...)`` wrapper anywhere on the path).
    ``jvp(...)``, ``vmap(...)``, ``transpose(...)``, ``checkpoint(...)``,
    ``rematted_computation(...)`` count as the scope they wrap, so forward,
    backward and recomputation of a sublayer add up."""
    scope = inner = None
    for part in path.split("/"):
        while (m := WRAPPER.match(part)):
            part = m.group(1)
        if scope is None and part in scopes:
            scope = part
        elif inner is None and (part in scopes or KERNEL.match(part)):
            inner = part
    return scope, inner, "transpose(" in path


def inside(ops, runs: dict[str, list[tuple[float, float]]]) -> list[OpRow]:
    """The operation rows that lie inside a whole execution (``runs``:
    ``trace_reduce.whole_runs``) on their plane."""
    out = []
    for r in ops:
        start, end = r[2], r[2] + r[3]
        if any(a <= start and end <= b for a, b in runs.get(r[0], ())):
            out.append(r)
    return out


def scope_times(ops, scopes) -> dict[tuple, float]:
    """Self time, ns, by ``(scope, inner, backward)``: an operation's duration
    less what the operations nested in it cover (``trace_reduce.self_times``),
    so the values add up to the device's busy time over ``ops``."""
    labelled = [(r[0], trace_reduce.OP_LINE, scope_of(r[4], scopes), r[2], r[3])
                for r in ops]
    out: dict[tuple, float] = defaultdict(float)
    by_plane = defaultdict(list)
    for r in labelled:
        by_plane[r[0]].append(r)
    for rows in by_plane.values():
        for label, ns in trace_reduce.self_times(rows).items():
            out[label] += ns
    return dict(out)


# ------------------------------------------------------ idle time, by cause

def clock_offsets(modules, launches) -> dict[str, tuple[float, float, float]]:
    """How far the host plane's clock is AHEAD of each device plane's, ns, as
    ``(estimate, lowest, highest)``: the two are written on one time axis,
    but the device's timestamps are converted from its own counter, and a
    millisecond of error matters to an idle gap of a few.  Causality bounds
    it: a program cannot start on the device before the runtime enqueued it
    (``ENQUEUED``), nor end after the runtime saw it complete (``COMPLETED``).
    The estimate is the middle of the tightest pair of bounds; a plane with
    no bound on either side is left out (its spans are then not shifted)."""
    runs = {(plane, run_id): (start, end)
            for plane, run_id, start, end in modules}
    bounds: dict[str, list[float]] = {}
    for name, chip, run_id, at in launches:
        plane = f"/device:TPU:{chip}"
        if (plane, run_id) not in runs:
            continue
        start, end = runs[(plane, run_id)]
        lo, hi = bounds.setdefault(plane, [float("-inf"), float("inf")])
        if name == ENQUEUED:
            bounds[plane][0] = max(lo, at - start)
        else:
            bounds[plane][1] = min(hi, at - end)
    return {plane: ((lo + hi) / 2, lo, hi) for plane, (lo, hi) in bounds.items()
            if lo > float("-inf") and hi < float("inf")}


def idle_intervals(busy) -> tuple[list[tuple[float, float]], tuple[float, float]]:
    """The intervals in which none of the ``(start, end)`` intervals ``busy``
    of ONE plane's operations ran, between the first one's start and the last
    one's end (``trace_reduce.busy_idle``'s window), and that window."""
    ivs = sorted(busy)
    t0, t1 = ivs[0][0], max(e for _, e in ivs)
    gaps, end = [], t0
    for s, e in ivs:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    return gaps, (t0, t1)


def idle_by_span(gaps, host) -> dict[str | None, float]:
    """Idle ns by the program span that was open on the host, the innermost
    winning: of the spans that cover an instant, the one that started last
    (on one thread that is the innermost; a tie goes to the shorter).  None
    holds what no span covered."""
    out: dict[str | None, float] = defaultdict(float)
    spans = sorted(host, key=lambda h: h[1])
    active: list[HostRow] = []       # started before this gap's end, not over
    nxt = 0
    for g0, g1 in sorted(gaps):
        while nxt < len(spans) and spans[nxt][1] < g1:
            active.append(spans[nxt])
            nxt += 1
        active = [h for h in active if h[2] > g0]
        cuts = sorted({g0, g1, *(t for h in active for t in h[1:3]
                                 if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            cover = [h for h in active if h[1] <= a and h[2] >= b]
            best = max(cover, key=lambda h: (h[1], -h[2]), default=None)
            out[best[0] if best else None] += b - a
    return dict(out)
