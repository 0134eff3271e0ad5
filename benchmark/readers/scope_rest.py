"""Reader ``scope_rest``: the share of the device's busy time, in %, that NO
name of the program accounts for (args: ``module_prefix``, ``scopes``,
optional ``within``), and a table of what that time is.

``scope_share`` and ``scope_split`` give an executed operation to the scope on
its own ``op_name`` path, and a fusion carries its ROOT instruction's path
only: a root XLA made itself (a copy, a bitcast, a tuple), or one emitted by a
loop's own bookkeeping, makes the whole fusion nobody's.  This reader asks the
program what the operation HOLDS (``observability/scopemap.scope_map`` of the
step program: the instructions fused in it, each with its own path; the map
is compiled for, once per trace, in this process, where the trainer registered
its step) and calls an operation NAMED

- if its own path holds one of ``scopes`` (through ``jvp(...)``,
  ``transpose(...)`` and the other wrappers, anywhere on the path), or
- where it holds none and the map knows the operation, if the path of the
  member that does most of its work does.  That member is, among the fused
  instructions that have a path: a convolution, dot or custom call before
  anything else (the largest result among several); else the instruction
  that COMPUTES the largest result, in bytes; an instruction that only moves
  data (``MOVES``: a slice, an update in place, a copy, a reshape) only where
  nothing computes (``dominant``).  A loop's stacking of its outputs fused
  with the norm that made them is the norm's; the stacking alone is nobody's.

Over the WHOLE executions of the step program in the slice, as ``scope_share``
takes them, the value is the self time of the operations that are not named,
over the busy time there, every chip.  With ``within``, only operations whose
own path holds that scope count (over the same busy time): what a sublayer's
parts leave of it.  So ``value + the parts' scope_split values`` differs from
the sublayer's ``scope_share`` by what the map named through a member, which
the printed line gives.

Once per trace and set of arguments the fifteen costliest operations left are
printed: HLO name and shape, calls a step, ms a step, own path, and the scopes
its members are under.  A program without ``scopemap`` (an older checkout) is
read by the operations' own paths alone.  Nothing without a trace or without a
whole execution.
"""

import re
import time
import traceback
from collections import Counter, defaultdict

from benchmark import trace_reduce, trace_spans
from benchmark.harness import say

ROWS = 15
MATMULS = ("convolution", "dot", "custom-call")
MOVES = ("dynamic-update-slice", "dynamic-slice", "slice", "copy", "bitcast",
         "reshape", "transpose", "broadcast", "concatenate", "pad", "tuple",
         "get-tuple-element", "parameter", "constant", "iota")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2,
         "u16": 2, "f16": 2, "bf16": 2, "s64": 8, "u64": 8, "f64": 8}
SHAPE = re.compile(r"^([a-z][a-z0-9]*)\[([0-9,]*)\]$")


def holds(path: str, scopes) -> str | None:
    """The first of ``scopes`` on ``path``, wrappers taken off (as
    ``scope_share`` and ``scope_split`` read a path); else None."""
    return trace_spans.scope_of(path, scopes)[0]


def result_bytes(shape: str) -> int:
    m = SHAPE.match(shape)
    if not m:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n * BYTES.get(m.group(1), 4)


def dominant(members):
    """The member that does most of the operation's work (the docstring's
    rule), among those with a path; None where none has one."""
    with_path = [m for m in members if m[1]]
    heavy = ([m for m in with_path if m[0] in MATMULS]
             or [m for m in with_path if m[0] not in MOVES] or with_path)
    return max(heavy, key=lambda m: result_bytes(m[2]), default=None)


def program_map(prefix: str) -> dict:
    """The step program's own account of its operations; ``{}`` from a
    program that cannot give one."""
    try:
        from deeplearning4j_tpu.observability import scopemap
    except ImportError:
        say("scope_rest: the program has no observability/scopemap: "
            "operations are named by their own paths alone")
        return {}
    t0 = time.perf_counter()
    try:
        found = scopemap.scope_map(prefix)
    except Exception:    # a reader that cannot read finds less; the run goes on
        say(f"scope_rest: scope_map({prefix!r}) failed:\n{traceback.format_exc()}")
        return {}
    say(f"scope_rest: scope_map({prefix!r}) gave {len(found)} operations, "
        f"{sum(len(v) > 1 for v in found.values())} of them fusions, in "
        f"{time.perf_counter() - t0:.1f} s")
    return found


def self_times(ops, runs) -> dict[tuple[str, str], list[float]]:
    """``{(event name, path): [self ns, calls]}`` of the operations inside
    ``runs``, every plane added up."""
    by_plane = defaultdict(list)
    for r in trace_spans.inside(ops, runs):
        by_plane[r[0]].append((r[0], trace_reduce.OP_LINE, (r[1], r[4]), r[2], r[3]))
    out: dict = defaultdict(lambda: [0.0, 0])
    for rows in by_plane.values():
        for label, ns in trace_reduce.self_times(rows).items():
            out[label][0] += ns
        for r in rows:
            out[r[2]][1] += 1
    return dict(out)


def rest(times: dict, members_of: dict, scopes, within=None) -> dict:
    """``times`` (``self_times``) sorted into ``busy`` (all of it), ``own``
    and ``member`` (named by the operation's own path; by its dominant
    member's, and ``member_by`` which of ``scopes`` that was), ``unnamed``,
    all ns, and ``rows``: the unnamed operations grouped by short name and
    path, ``[ns, calls, an instruction's name, its members]``."""
    scopes = tuple(scopes)
    out = {"busy": 0.0, "own": 0.0, "member": 0.0, "unnamed": 0.0,
           "member_by": defaultdict(float),
           "rows": defaultdict(lambda: [0.0, 0, "", ()])}
    for (event, path), (ns, calls) in times.items():
        out["busy"] += ns
        if within and not holds(path, (within,)):
            continue
        if holds(path, scopes):
            out["own"] += ns
            continue
        m = trace_reduce.HLO_TEXT.match(event)
        instruction = m.group(1) if m else event
        members = members_of.get(instruction, ())
        lead = dominant(members)
        named_by = holds(lead[1], scopes) if lead is not None else None
        if named_by:
            out["member"] += ns
            out["member_by"][named_by] += ns
            continue
        out["unnamed"] += ns
        row = out["rows"][(trace_reduce.short(event), path)]
        row[0] += ns
        row[1] += calls
        row[2], row[3] = row[2] or instruction, row[3] or tuple(members)
    out["rows"], out["member_by"] = dict(out["rows"]), dict(out["member_by"])
    return out


def show(found: dict, n_runs: int, prefix: str, scopes, within) -> None:
    busy = found["busy"]
    where = f"under {within}, " if within else ""
    say(f"busy time inside {n_runs} whole {prefix} executions, {where}under "
        f"none of {len(scopes)} names ({', '.join(scopes)}): "
        f"{100 * found['unnamed'] / busy:.3f}% of {busy / 1e9:.4f} s; named by "
        f"the operation's own path {100 * found['own'] / busy:.3f}%, by the "
        f"member that does most of its work {100 * found['member'] / busy:.3f}%"
        + "".join(f", {k} {100 * v / busy:.3f}" for k, v in sorted(
            found["member_by"].items(), key=lambda kv: -kv[1]))
        + f".  The {ROWS} costliest operations left:")
    ranked = sorted(found["rows"].items(), key=lambda kv: -kv[1][0])[:ROWS]
    for (short, path), (ns, calls, instruction, members) in ranked:
        under = Counter(holds(m[1], scopes) or ("other path" if m[1] else "no path")
                        for m in members if m[0] not in ("parameter", "constant"))
        lead = dominant(members)
        say(f"  {short} ({instruction}): {calls / n_runs:.1f} calls, "
            f"{ns / n_runs / 1e6:.3f} ms a step ({100 * ns / busy:.3f}%); "
            f"path {path or 'no path'}; members "
            + (", ".join(f"{k} x{v}" for k, v in under.most_common()) or
               "not in the map")
            + (f"; most work: {lead[0]} {lead[2]} {lead[1]}" if lead else ""))


def read(args: dict, run: dict):
    parsed = trace_spans.of_run(run)
    if parsed is None:
        return None
    prefix, scopes = args["module_prefix"], args["scopes"]
    within = args.get("within")
    runs = trace_reduce.whole_runs(run["trace_rows"], prefix)
    if not runs:
        return None
    def timed():
        t0 = time.perf_counter()
        out = self_times(parsed["ops"], runs)
        say(f"scope_rest: self times of {len(out)} distinct operations among "
            f"{len(parsed['ops'])} events in {time.perf_counter() - t0:.1f} s")
        return out

    times = trace_spans.cached(parsed, f"scope_rest:times:{prefix}", timed)
    if not times:
        return None
    members_of = trace_spans.cached(parsed, f"scope_rest:map:{prefix}",
                                    lambda: program_map(prefix))

    def make():
        found = rest(times, members_of, scopes, within)
        show(found, sum(len(v) for v in runs.values()), prefix, scopes, within)
        return found

    found = trace_spans.cached(
        parsed, f"scope_rest:{prefix}:{within}:{','.join(scopes)}", make)
    return 100.0 * found["unnamed"] / found["busy"]
