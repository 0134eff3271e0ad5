"""What each operation of a compiled program holds, by ``jax.named_scope`` path.

A profiler trace names an executed operation by its HLO instruction and gives
it ONE ``op_name`` path: a fusion carries its root instruction's, and a root
XLA made itself (a tuple, a copy, a bitcast) carries none.  A weight-gradient
product fused with the optimizer's update, or with the sum the transposed loop
keeps over its steps, then reads as nobody's.  The compiled module knows
better: every instruction fused into the operation still has the path of the
line of the program it came from.

``register`` is what a hot path pays: at the first dispatch of a jitted
program it keeps the function and the SHAPES of its arguments
(``ShapeDtypeStruct`` with each argument's sharding, so that a program over
several chips compiles to the program that ran).  No array is kept alive,
nothing is lowered, compiled or parsed.  The function itself is kept (a
trace is read after the code that trained has returned, and its trainer with
it), the newest ``KEEP`` shapes a program name.

``scope_map(program)`` is asked for by whoever reads a trace, and only then
compiles those shapes again (a hit in the persistent cache where the process
has one: ``parallel/compile_cache.py``), reads the optimised module's text
and returns ``{instruction name: [Member, ...]}`` for every instruction of the
entry computation, of every loop body and branch: the names a trace's
``XLA Ops`` line prints.  A fusion's members are the instructions fused in it;
any other instruction is its own one member.
"""

from __future__ import annotations

import re
import threading
from typing import Any, NamedTuple

KEEP = 4        # shapes remembered under one program name, the newest


class Member(NamedTuple):
    """One instruction inside an executed operation."""
    opcode: str          # "dot", "add", "custom-call", ...
    path: str            # its op_name, "" where XLA made the instruction
    shape: str           # its result, "f32[16384,2048]"; a tuple's first part


class _Program(NamedTuple):
    fn: Any              # the jitted function (it has ``.lower``)
    args: tuple          # its arguments as ShapeDtypeStructs
    signature: tuple


_lock = threading.Lock()
_programs: dict[str, list[_Program]] = {}


def _abstract(leaf):
    import jax

    if not hasattr(leaf, "shape") or not hasattr(leaf, "dtype"):
        return leaf                     # a static Python value: kept as it is
    return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                sharding=getattr(leaf, "sharding", None))


def register(fn, *args) -> str:
    """Remember the jitted ``fn`` and the shapes of ``args`` under the name
    its compiled program has in a trace (``jit_<fn.__name__>``), which is
    returned.  The same name with the same shapes again replaces the entry
    (a rebuilt trainer); other shapes of the name (another batch bucket) are
    kept beside it, ``KEEP`` in all."""
    import jax

    name = f"jit_{fn.__name__}"
    shapes = jax.tree_util.tree_map(_abstract, args)
    signature = tuple((str(getattr(a, "shape", a)), str(getattr(a, "dtype", "")))
                      for a in jax.tree_util.tree_leaves(shapes))
    entry = _Program(fn, shapes, signature)
    with _lock:
        kept = [p for p in _programs.get(name, ()) if p.signature != signature]
        _programs[name] = (kept + [entry])[-KEEP:]
    return name


def registered(program: str) -> int:
    """How many shapes of ``program`` are registered."""
    with _lock:
        return len(_programs.get(program, ()))


def clear() -> None:
    with _lock:
        _programs.clear()


def scope_map(program: str) -> dict[str, list[Member]]:
    """``{instruction name: members}`` of every registered shape of
    ``program`` (``"jit_step"``); where two shapes' modules both have an
    instruction of one name, the first registered keeps it.  Empty where
    nothing is registered under the name.  This compiles: call it beside a
    trace, never in a loop that trains or serves."""
    with _lock:
        entries = list(_programs.get(program, ()))
    out: dict[str, list[Member]] = {}
    for entry in entries:
        text = entry.fn.lower(*entry.args).compile().as_text()
        for name, members in parse(text).items():
            out.setdefault(name, members)
    return out


# ------------------------------------------------------- the module's text
#
#   %fused_computation.3 (param_0.7: f32[64,128]) -> f32[64,128] {
#     %param_0.7 = f32[64,128]{1,0} parameter(0)
#     ROOT %add.9 = f32[64,128]{1,0} add(...), metadata={op_name="jit(step)/..."}
#   }
#   ENTRY %main.42 (...) -> (...) {
#     %fusion.3 = f32[64,128]{1,0} fusion(%p), kind=kLoop,
#         calls=%fused_computation.3, metadata={op_name="..."}
#   }

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"^([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")


def _split_result(rest: str) -> tuple[str, str]:
    """``"(f32[2]{0}, s32[]) fusion(...)..."`` -> (first result shape, the
    text from the opcode on)."""
    if rest.startswith("("):            # a tuple: to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, tail = rest.partition(" ")
    shape = _SHAPE.search(result)
    return (shape.group(0) if shape else ""), tail


def parse(text: str) -> dict[str, list[Member]]:
    """The map of one optimised HLO module's text (``Compiled.as_text()``)."""
    computations: dict[str, list[tuple[str, Member, str | None]]] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and not line.startswith("HloModule"):
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        shape, tail = _split_result(m.group(2))
        op = _OPCODE.match(tail)
        if not op:
            continue
        path = _OP_NAME.search(tail)
        fused = _FUSED.search(tail) if op.group(1) == "fusion" else None
        current.append((m.group(1),
                        Member(op.group(1), path.group(1) if path else "", shape),
                        fused.group(1) if fused else None))

    fused_names = {callee for rows in computations.values()
                   for _, _, callee in rows if callee}

    def members(callee: str, depth: int = 0) -> list[Member]:
        out = []
        for _, member, inner in computations.get(callee, ()):
            if inner and depth < 8:     # a fusion inside a fusion
                out += members(inner, depth + 1)
            else:
                out.append(member)
        return out

    out: dict[str, list[Member]] = {}
    for name, rows in computations.items():
        if name in fused_names:
            continue
        for instruction, member, callee in rows:
            out[instruction] = members(callee) if callee else [member]
    return out
