"""The graftlint rule set — twenty-seven hazard classes from this repo's
history.

| rule  | hazard                                                           |
|-------|------------------------------------------------------------------|
| HS01  | host sync (`float`/`.item()`/`np.asarray`/`device_get`) on a     |
|       | jit-produced value in a hot path                                 |
| RC01  | recompile hazard: Python-value-dependent shapes inside a traced  |
|       | function; non-hashable literals in static arg positions          |
| RNG01 | PRNG key reuse: same key fed to two `jax.random.*` calls without |
|       | a `split`/reassignment between them                              |
| DON01 | use-after-donate: an argument at a `donate_argnums` position     |
|       | read again after the jitted call                                 |
| TB01  | Python `if`/`while` branching on a traced value inside a jitted  |
|       | function                                                         |
| HOT02 | loop dispatching device work with no `trace.span`/`METRICS`      |
|       | instrumentation anywhere in reach (bypasses the PR 1 layer)      |
| EXC01 | bare `except:` — catches SystemExit/KeyboardInterrupt, so a      |
|       | retry/supervision loop becomes unkillable and every failure      |
|       | signal is swallowed untyped                                      |
| PL01  | `pallas_call` without an `interpret=` keyword — the kernel body  |
|       | can only execute on TPU, so CPU tier-1 tests never run it        |
| ZR01  | replicated `device_put` of optimizer-state trees in ZeRO-aware   |
|       | code with no `zero_stage` gate — silently re-replicates the      |
|       | state ZeRO sharded, undoing the 1/ndp memory win                 |
| LK01  | unguarded write to a lock-guarded / thread-shared attribute      |
|       | (explicit `# guarded-by:` contract, majority-guarded inference,  |
|       | or written from two thread contexts with no lock ever held)      |
| LK02  | inconsistent lock-acquisition order: the static lock-order       |
|       | graph (nested `with` + helper-call propagation) has a cycle —    |
|       | a deadlock schedule, incl. non-reentrant self-re-acquisition     |
| LK03  | blocking call while holding a lock (`block_until_ready`,         |
|       | untimed `.wait()`/`.join()`/`.get()`, socket/HTTP I/O,           |
|       | `time.sleep`) — a convoy or deadlock under contention            |
| TH01  | `threading.Thread` created with neither `daemon=True` nor a      |
|       | visible `join()`/daemon-flag lifecycle — leaks a thread that     |
|       | can hang interpreter shutdown                                    |
| PG01  | KV page acquire (`alloc`/`incref`/`lookup_prefix` on a page      |
|       | pool, `serving/` modules) with no `decref`-style release on the  |
|       | exceptional exit paths — leaked pinned pages 429 the pool        |
| OB01  | direct `time.monotonic()`/`perf_counter()` timing of dispatch    |
|       | in `serving/`/`parallel/` with no registry/tracer call in reach  |
|       | — the measurement exists nowhere a scrape or trace can see       |
| QT01  | raw `.astype(jnp.int8)`/`.astype(jnp.float8_*)` in `serving/`    |
|       | or `models/` outside the quant helpers — an unscaled,            |
|       | unsaturated cast that silently wraps/overflows instead of going  |
|       | through `kv_quant.cast_to`/`matmul_int8.quantize`                |
| EL01  | mesh/topology construction outside the `parallel/mesh.py`        |
|       | helpers in trainer/supervisor code — a raw `Mesh(...)` or a      |
|       | `jax.devices()[<literal>]` slice hard-codes a device set the     |
|       | elastic resize path (shrink/grow/reshard) cannot rebuild         |
| OB02  | literal metric name passed to `METRICS.increment/gauge/          |
|       | observe_time/time` that is missing from the documented metrics   |
|       | tables (README.md / DESIGN.md) — undocumented names drift and    |
|       | dashboards silently scrape nothing                               |
| OB03  | request-derived data (tenant/request/session/user ids, prompt    |
|       | text) interpolated into a metric name outside the bounded        |
|       | tenant-label helper — unbounded label cardinality is a memory    |
|       | leak with a dashboard                                            |
| OL01  | non-durable file rewrite on the online-loop / checkpoint publish |
|       | path: `open("w")`/`write_text`/`write_bytes` in `online/` or     |
|       | `parallel/checkpoint.py` outside the unique-tempfile + fsync +   |
|       | `os.replace` idiom — a crash mid-write publishes a torn file     |
| SH01  | collective (`psum`/`pmean`/`all_gather`/`ppermute`/`axis_index`) |
|       | over an axis name no enclosing `shard_map`/`pmap` context binds  |
|       | (resolved through the analysis/sharding.py mesh-axis pass)       |
| SH02  | `PartitionSpec` naming an axis absent from the canonical axis    |
|       | registry (`parallel/mesh.py` `AXES`) — a typo'd axis fails the   |
|       | trace on device, or silently replicates                          |
| SH03  | `shard_map` `in_specs`/`out_specs` arity mismatch against the    |
|       | wrapped function's signature / literal-tuple returns             |
| SH04  | argument donated to a jit whose declared `in_shardings` differ   |
|       | from the sharding the caller placed it with — the implicit       |
|       | reshard copies, the donation frees the copy source, the aliasing |
|       | win is silently lost (DON01 with sharding awareness)             |
| NM01  | hand-rolled softmax/logsumexp in `ops/`/`models/` without max    |
|       | subtraction (`log(sum(exp))`, `exp/sum(exp)` shapes) — the       |
|       | blocked-xent and online-softmax kernels are the sanctioned forms |
| CT01  | raw ring/pool mutation in `control/` — the control plane must    |
|       | scale through the `ReplicaPool`/`PrefixRouter` quarantine-drain  |
|       | seams (`scale_up`/`scale_down`/`drain_replica`); touching a      |
|       | `HashRing` or the pool's internals directly skips the warmed     |
|       | gate and the drain state machine                                 |
| DG01  | page accounting or block-table write in `serving/disagg/`        |
|       | outside the `KVMigrator` export/import seams — migration's       |
|       | refcount-handoff invariant (PG01 extended across the process     |
|       | boundary) holds only because every acquire/release funnels       |
|       | through the migrator                                             |

Each rule documents its known blind spots; deliberate hits are silenced
inline with ``# graftlint: disable=<RULE>`` plus a reason, or carried in
the committed baseline with a justification.
"""

from __future__ import annotations

import ast
import pathlib
import re
from collections import Counter
from typing import Iterator

from .concurrency import _INIT_METHODS, find_cycles, module_concurrency
from .core import (
    Finding,
    Rule,
    assigned_names,
    body_statements,
    dotted_name,
    last_segment,
    literal_int_tuple,
    names_read,
    register,
    statement_targets,
)
from .jitinfo import ModuleInfo
from .sharding import axis_registry, sharding_info

#: callables whose canonical name forces a device->host read of their arg
_SYNC_CALLS = {
    "float", "int", "bool",
    "numpy.asarray", "numpy.array",
    "jax.device_get",
}
#: method names that force a device->host read of their receiver
_SYNC_METHODS = {"item", "tolist"}

#: jnp constructors whose first argument fixes an output shape
_SHAPE_CONSTRUCTORS = {
    "jax.numpy.arange", "jax.numpy.zeros", "jax.numpy.ones",
    "jax.numpy.full", "jax.numpy.empty", "jax.numpy.eye",
    "jax.numpy.linspace", "jax.numpy.tri",
}

#: attributes of a traced array that are static at trace time
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}

#: observability markers — any of these in reach means the loop reports
#: through the PR 1 layer
_OBS_MARKERS = ("span", "observe_time", "observe_many", "increment",
                "gauge", "time", "iteration_done", "record_span")
_OBS_BASES = ("trace", "METRICS", "TRACER", "registry", "self.registry")


def _function_loops(fn: ast.FunctionDef) -> list[ast.stmt]:
    """Top-to-bottom list of loop statements in ``fn`` (not nested defs)."""
    loops = []
    for stmt in body_statements(fn.body):
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            loops.append(stmt)
    return loops


def _calls_in(node: ast.AST) -> Iterator[ast.Call]:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            yield n


def _is_sync_call(module: ModuleInfo, call: ast.Call) -> ast.AST | None:
    """The expression being synced to host, or None."""
    canon = module.canonical(call.func)
    if canon in _SYNC_CALLS and call.args:
        return call.args[0]
    if (isinstance(call.func, ast.Attribute)
            and call.func.attr in _SYNC_METHODS and not call.args):
        return call.func.value
    return None


@register
class HostSyncRule(Rule):
    """HS01 — device->host sync of a jit-produced value in a hot path.

    Taint: names bound from a call to a known-jitted callable inside the
    same function.  A sync call (``float``/``int``/``.item()``/
    ``np.asarray``/``jax.device_get``) whose argument reads a tainted name
    fires when it happens (a) inside a loop, or (b) anywhere in a
    loop-free function — the ``_apply_step``-style per-call method whose
    *caller* is the loop.  Syncs after a loop in a loop-containing
    function are treated as deliberate fences and left alone.
    """

    id = "HS01"
    title = "host sync on jit-produced value in hot path"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_function(module, node)

    def _check_function(self, module: ModuleInfo,
                        fn: ast.FunctionDef) -> Iterator[Finding]:
        tainted: set[str] = set()
        for stmt in body_statements(fn.body):
            if isinstance(stmt, ast.Assign) and isinstance(
                    stmt.value, (ast.Call, ast.Tuple)):
                for call in _calls_in(stmt.value):
                    callee = dotted_name(call.func)
                    if callee and module.is_jitted_call(callee):
                        for t in stmt.targets:
                            tainted.update(assigned_names(t))
                        break
        if not tainted:
            return
        has_loop = bool(_function_loops(fn))
        loop_nodes = set()
        for loop in _function_loops(fn):
            for n in ast.walk(loop):
                loop_nodes.add(id(n))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            arg = _is_sync_call(module, node)
            if arg is None:
                continue
            hit = tainted & names_read(arg)
            # direct form: float(self._step_fn(...))
            if not hit:
                inner = [c for c in _calls_in(arg)
                         if (dotted_name(c.func)
                             and module.is_jitted_call(dotted_name(c.func)))]
                if inner:
                    hit = {dotted_name(inner[0].func)}
            if not hit:
                continue
            in_loop = id(node) in loop_nodes
            if in_loop or not has_loop:
                where = ("inside a loop" if in_loop
                         else "in a loop-free per-call function")
                yield self.finding(
                    module, node,
                    f"host sync of jit-produced value {sorted(hit)[0]!r} "
                    f"{where}: forces the async dispatch queue to drain "
                    "every call — return the device value and resolve at "
                    "the caller's fence (LazyLoss pattern, DESIGN.md §10)")


@register
class RecompileRule(Rule):
    """RC01 — shapes that depend on Python values inside traced code.

    Fires on ``jnp.arange(n)``-style constructors whose size argument
    reads a *parameter* of the traced function (``x.shape[0]`` is fine —
    static under bucketing), and on list/dict/set literals passed at a
    known ``static_argnums`` position (unhashable -> TypeError at call
    time; hashable-but-fresh objects recompile every call).
    """

    id = "RC01"
    title = "recompile hazard in traced function"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for fn, _info in module.traced_defs.items():
            params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                      + fn.args.kwonlyargs)} - {"self"}
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                canon = module.canonical(node.func)
                if canon not in _SHAPE_CONSTRUCTORS or not node.args:
                    continue
                size_args = node.args[:1]
                for arg in size_args:
                    bare = _bare_param_reads(arg, params)
                    if bare:
                        yield self.finding(
                            module, node,
                            f"shape of {canon.rsplit('.', 1)[-1]}() depends "
                            f"on traced/python parameter {sorted(bare)[0]!r} "
                            "inside a jitted function — each distinct value "
                            "recompiles (or fails to trace); derive sizes "
                            "from .shape or hoist to the host")
        # static-position literal check at call sites of known jitted fns
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            if callee is None:
                continue
            info = module.jit_info_for_call(callee)
            if info is None or not info.static_argnums:
                continue
            for pos in info.static_argnums:
                if pos < len(node.args) and isinstance(
                        node.args[pos], (ast.List, ast.Dict, ast.Set)):
                    yield self.finding(
                        module, node.args[pos],
                        f"non-hashable literal at static_argnums position "
                        f"{pos} of {callee!r} — static args must be "
                        "hashable (use a tuple)")


def _bare_param_reads(node: ast.AST, params: set[str]) -> set[str]:
    """Parameter names read under ``node`` EXCLUDING reads through static
    attributes (``x.shape[0]`` does not count as a bare read of ``x``)."""
    out: set[str] = set()

    def visit(n: ast.AST) -> None:
        if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
            return  # x.shape / x.ndim / x.dtype are static at trace time
        if isinstance(n, ast.Call):
            canon_last = last_segment(dotted_name(n.func) or "")
            if canon_last in ("len", "isinstance", "type", "getattr",
                              "hasattr"):
                return
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                and n.id in params:
            out.add(n.id)
        for child in ast.iter_child_nodes(n):
            visit(child)

    visit(node)
    return out


@register
class KeyReuseRule(Rule):
    """RNG01 — the same PRNG key consumed twice.

    Linear scan per function: every ``jax.random.<draw>(key, ...)`` call
    consumes its key; a second consumption of the same (dotted) name with
    no reassignment in between fires.  A draw inside a loop whose key is
    never reassigned in that loop body fires too (silent reuse across
    iterations — identical "randomness" every step).
    """

    id = "RNG01"
    title = "PRNG key reuse without split"

    #: jax.random callables that CONSUME a key (split/fold_in produce
    #: fresh ones but still consume their input)
    _NON_DRAWS = {"key", "PRNGKey", "key_data", "wrap_key_data"}

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_function(module, node)

    def _key_arg(self, module: ModuleInfo, call: ast.Call) -> str | None:
        canon = module.canonical(call.func) or ""
        if not canon.startswith("jax.random."):
            return None
        if canon.rsplit(".", 1)[-1] in self._NON_DRAWS:
            return None
        if not call.args:
            return None
        return dotted_name(call.args[0])

    def _check_function(self, module: ModuleInfo,
                        fn: ast.FunctionDef) -> Iterator[Finding]:
        yield from self._scan(module, fn.body, {}, frozenset())

    @staticmethod
    def _terminates(body: list[ast.stmt]) -> bool:
        """Whether control never falls past the end of ``body``."""
        return bool(body) and isinstance(
            body[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))

    def _draws(self, module: ModuleInfo, node: ast.AST,
               used_once: dict[str, int],
               skip: frozenset) -> Iterator[Finding]:
        """Register/flag key consumptions in one expression or simple
        statement (no statement-level branching below this node)."""
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            key = self._key_arg(module, n)
            if key is None or key in skip:
                continue
            if key in used_once:
                yield self.finding(
                    module, n,
                    f"PRNG key {key!r} already consumed at line "
                    f"{used_once[key]} with no split/reassign since — two "
                    "draws from one key produce correlated streams")
            else:
                used_once[key] = n.lineno

    def _scan(self, module: ModuleInfo, body: list[ast.stmt],
              used_once: dict[str, int],
              skip: frozenset) -> Iterator[Finding]:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                loop_assigned: set[str] = set()
                for s in body_statements(stmt.body):
                    loop_assigned.update(statement_targets(s))
                if isinstance(stmt, (ast.For, ast.AsyncFor)):
                    loop_assigned.update(assigned_names(stmt.target))
                flagged: set[str] = set()
                for s in stmt.body:
                    for n in ast.walk(s):
                        if isinstance(n, ast.Call):
                            key = self._key_arg(module, n)
                            if (key is not None and key not in loop_assigned
                                    and key not in skip
                                    and key not in flagged):
                                flagged.add(key)
                                yield self.finding(
                                    module, n,
                                    f"PRNG key {key!r} is consumed every "
                                    "loop iteration but never split/"
                                    "reassigned in the loop — identical "
                                    "random draws each step")
                # intra-iteration reuse of keys that ARE rebound per step
                yield from self._scan(module, stmt.body, {},
                                      skip | flagged)
                used_once.clear()
                continue
            if isinstance(stmt, ast.If):
                yield from self._draws(module, stmt.test, used_once, skip)
                # branches are mutually exclusive: scan each from a copy of
                # the current state, then merge the states that fall through
                states: list[dict[str, int]] = []
                for branch in (stmt.body, stmt.orelse):
                    st = dict(used_once)
                    if branch:
                        yield from self._scan(module, branch, st, skip)
                    if not branch or not self._terminates(branch):
                        states.append(st)
                used_once.clear()
                for st in states:
                    used_once.update(st)
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    yield from self._draws(module, item.context_expr,
                                           used_once, skip)
                for t in statement_targets(stmt):
                    used_once.pop(t, None)
                yield from self._scan(module, stmt.body, used_once, skip)
                continue
            if isinstance(stmt, ast.Try):
                merged = dict(used_once)
                yield from self._scan(module, stmt.body, merged, skip)
                for handler in stmt.handlers:
                    hs = dict(used_once)
                    yield from self._scan(module, handler.body, hs, skip)
                    merged.update(hs)
                if stmt.orelse:
                    yield from self._scan(module, stmt.orelse, merged, skip)
                if stmt.finalbody:
                    yield from self._scan(module, stmt.finalbody, merged,
                                          skip)
                used_once.clear()
                used_once.update(merged)
                continue
            # simple statement: uses first, then (re)binds
            yield from self._draws(module, stmt, used_once, skip)
            for t in statement_targets(stmt):
                used_once.pop(t, None)


@register
class UseAfterDonateRule(Rule):
    """DON01 — reading a buffer after donating it to a jitted call.

    For calls to callables with known ``donate_argnums``, the (dotted)
    names passed at donated positions are dead afterwards unless the same
    statement rebinds them.  A later read before a rebind fires; a call
    inside a loop whose donated names are never rebound anywhere in the
    loop body fires at the call (next iteration reuses the corpse).
    """

    id = "DON01"
    title = "use after donate_argnums donation"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_function(module, node)

    def _donations(self, module: ModuleInfo,
                   stmt: ast.stmt) -> list[tuple[ast.Call, str]]:
        out = []
        for call in _calls_in(stmt):
            callee = dotted_name(call.func)
            if callee is None:
                continue
            info = module.jit_info_for_call(callee)
            if info is None or not info.donate_argnums:
                continue
            for pos in info.donate_argnums:
                if pos < len(call.args):
                    arg = call.args[pos]
                    if isinstance(arg, ast.Starred):
                        continue  # *tables style: rebinding checked coarsely
                    name = dotted_name(arg)
                    if name is not None:
                        out.append((call, name))
        return out

    def _check_function(self, module: ModuleInfo,
                        fn: ast.FunctionDef) -> Iterator[Finding]:
        yield from self._scan(module, fn.body, in_loop=False)

    def _scan(self, module: ModuleInfo, body: list[ast.stmt],
              in_loop: bool) -> Iterator[Finding]:
        dead: dict[str, int] = {}       # donated name -> donation line
        for i, stmt in enumerate(body):
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            donations = self._donations(module, stmt)
            rebound = statement_targets(stmt)
            # reads in this statement happen before its own donation kills
            # anything, but after PREVIOUS statements' donations
            reads = names_read(stmt)
            for name, line in list(dead.items()):
                if name in reads:
                    yield Finding(
                        rule=self.id, path=module.path, line=stmt.lineno,
                        col=stmt.col_offset + 1,
                        message=(f"{name!r} was donated to a jitted call at "
                                 f"line {line} (donate_argnums) and read "
                                 "again here — the buffer is deleted after "
                                 "donation; copy first (jnp.array) or "
                                 "rebind from the call's result"),
                        code=module.line(stmt.lineno))
                    dead.pop(name, None)
            for name in rebound:
                dead.pop(name, None)
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                loop_assigned: set[str] = set()
                for s in body_statements(stmt.body):
                    loop_assigned.update(statement_targets(s))
                for call, name in [d for s in body_statements(stmt.body)
                                   for d in self._donations(module, s)]:
                    if name not in loop_assigned:
                        yield self.finding(
                            module, call,
                            f"{name!r} is donated inside a loop but never "
                            "rebound in the loop body — the next iteration "
                            "passes a deleted buffer")
                yield from self._scan(module, stmt.body, in_loop=True)
                continue
            for name, line in [(n, c.lineno) for c, n in donations
                               if n not in rebound]:
                dead[name] = line
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, field, None)
                if sub:
                    yield from self._scan(module, sub, in_loop)
            for handler in getattr(stmt, "handlers", []) or []:
                yield from self._scan(module, handler.body, in_loop)


@register
class TracedBranchRule(Rule):
    """TB01 — Python control flow on traced values.

    Inside a traced function body, ``if``/``while`` tests that read a
    parameter of that function concretize a tracer (ConcretizationTypeError
    at best, value-dependent retraces at worst).  ``is``/``is not`` tests,
    reads through static attributes (``x.shape``), and ``isinstance``/
    ``len`` calls are allowed — those are static at trace time.
    """

    id = "TB01"
    title = "python branch on traced value"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for fn, info in module.traced_defs.items():
            static = set(info.static_argnums) if info else set()
            ordered = [a.arg for a in (fn.args.posonlyargs + fn.args.args)]
            params = ({a for i, a in enumerate(ordered) if i not in static}
                      | {a.arg for a in fn.args.kwonlyargs}) - {"self"}
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                test = node.test
                if isinstance(test, ast.Compare) and all(
                        isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops):
                    continue
                bare = _bare_param_reads(test, params)
                if bare:
                    kind = "while" if isinstance(node, ast.While) else "if"
                    yield self.finding(
                        module, node,
                        f"python `{kind}` on traced parameter "
                        f"{sorted(bare)[0]!r} inside a jitted function — "
                        "use jnp.where/lax.cond/lax.while_loop (or mark "
                        "the argument static)")


@register
class UninstrumentedHotLoopRule(Rule):
    """HOT02 — device-dispatching loops invisible to observability.

    A loop that calls a jitted callable (directly, or through a local
    helper that does) with no ``trace.span``/``METRICS``/timer call
    anywhere in the loop body or its enclosing function bypasses the PR 1
    metrics layer: its steps appear in no histogram, no trace, no
    ``/metrics.prom`` scrape.  One span or counter anywhere in reach —
    even per-epoch around the loop — satisfies the rule.
    """

    id = "HOT02"
    title = "uninstrumented device-dispatching loop"

    @staticmethod
    def _has_obs(node: ast.AST, module: ModuleInfo) -> bool:
        for call in _calls_in(node):
            name = dotted_name(call.func) or ""
            base, _, attr = name.rpartition(".")
            if attr in _OBS_MARKERS and (
                    last_segment(base) in ("trace", "METRICS", "TRACER",
                                           "registry")
                    or base.endswith("METRICS") or "observ" in base):
                return True
            canon = module.canonical(call.func) or ""
            if "observability" in canon or canon.endswith(".span"):
                return True
        return False

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            fn_has_obs = self._has_obs(node, module)
            if fn_has_obs:
                continue
            for loop in _function_loops(node):
                dispatches = None
                for call in _calls_in(loop):
                    callee = dotted_name(call.func)
                    if callee and module.is_dispatching_call(callee):
                        dispatches = callee
                        break
                if dispatches is None:
                    continue
                yield self.finding(
                    module, loop,
                    f"loop dispatches device work ({dispatches!r}) with no "
                    "trace.span/METRICS instrumentation in reach — add a "
                    "span or counter (per-epoch is enough) so the PR 1 "
                    "observability layer sees this hot path")
                break  # one finding per function is enough signal


@register
class BareExceptRule(Rule):
    """EXC01 — bare ``except:`` clauses.

    A bare handler catches ``SystemExit``, ``KeyboardInterrupt``, and
    ``GeneratorExit`` along with everything else.  In this codebase's
    retry/supervision paths (resilience supervisor, scaleout worker
    loops) that is exactly wrong twice over: the process becomes
    unkillable under retry, and the retry policy's ``retry_on`` typing is
    bypassed — every failure looks retryable.  Catch ``Exception`` (or
    narrower) instead; if the broad catch is deliberate, re-raise the
    exit exceptions first.
    """

    id = "EXC01"
    title = "bare except swallows exit signals"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                yield self.finding(
                    module, node,
                    "bare `except:` catches SystemExit/KeyboardInterrupt — "
                    "a retry loop built on this cannot be killed and treats "
                    "every failure as retryable; catch Exception (or the "
                    "policy's retry_on tuple) instead")


@register
class PallasInterpretRule(Rule):
    """PL01 — ``pallas_call`` without an ``interpret`` keyword.

    The kernel tier's contract (DESIGN.md §14) is that every Pallas
    kernel runs its REAL body in tier-1 CPU tests via interpret mode —
    a ``pl.pallas_call`` with no ``interpret=`` keyword can only ever
    execute on a TPU, so its kernel body is dead code to the test suite
    and every bug in it ships untested.  Wrappers must thread an
    ``interpret`` flag down to the call and resolve ``None`` through
    ``ops.pallas.registry.resolve_interpret``: compiled on a TPU backend,
    interpreted on the CPU backend, an error on any other — never
    interpreted on the chip path unless the caller asked for it.

    Blind spot: a call aliased through a variable
    (``f = pl.pallas_call; f(...)``) is not seen; none exist in-tree.
    """

    id = "PL01"
    title = "pallas_call without interpret keyword"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = module.canonical(node.func) or dotted_name(node.func) or ""
            if not name.endswith("pallas_call"):
                continue
            if any(kw.arg == "interpret" for kw in node.keywords):
                continue
            yield self.finding(
                module, node,
                "`pallas_call` without an `interpret=` keyword compiles "
                "only on TPU — CPU tier-1 tests can never execute the "
                "kernel body; thread an interpret flag through the "
                "wrapper, resolved by registry.resolve_interpret")


#: identifier fragments naming an optimizer-state tree
_ZR_STATE_TOKENS = ("tstate", "opt_state")


def _mentions_token(node: ast.AST, tokens) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and any(t in n.id.lower() for t in tokens):
            return True
        if isinstance(n, ast.Attribute) \
                and any(t in n.attr.lower() for t in tokens):
            return True
    return False


@register
class ZeroReplicateRule(Rule):
    """ZR01 — un-gated replicated placement of optimizer-state trees in
    ZeRO-aware code.

    Under ``zero_stage >= 2`` the optimizer state lives shard-local
    (``NamedSharding(mesh, P('dp'))`` over the flattened layout, DESIGN.md
    §15) — a ``jax.device_put`` of a tstate/opt_state tree with a
    *replicated* sharding (``P()`` / ``NamedSharding(_, P())`` / a
    ``*rep*``-named cached sharding) silently re-materializes the full
    state on every chip, undoing the 1/ndp memory win without failing any
    numerics test.  The rule scopes itself to functions that read
    ``zero_stage`` (the code that KNOWS sharded state exists) and stays
    quiet when the placement is gated by a ``zero_stage`` conditional:
    inside any branch of an ``if``/``elif`` chain whose test mentions
    ``zero_stage``, or after a ``zero_stage`` guard that early-returns.
    Both the direct form and the ``tree_map(lambda ...: device_put(...),
    tstate)`` form are caught.

    Blind spots (documented, not accidental): placements routed through a
    helper the AST can't see into, shardings aliased to names without a
    ``rep`` fragment, and state trees not named ``*tstate*``/
    ``*opt_state*`` — naming IS the contract in this tree.
    """

    id = "ZR01"
    title = "replicated device_put of sharded optimizer state"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if _mentions_token(node, ("zero_stage",)):
                    yield from self._check_function(module, node)

    # ------------------------------------------------------------- gating
    def _gated_ids(self, fn: ast.AST) -> set[int]:
        """ids of AST nodes covered by a ``zero_stage`` conditional: every
        descendant of any branch of an If whose test reads zero_stage,
        plus statements that only execute after such an If whose taken
        branch leaves the block (early return/raise/continue/break)."""
        gated: set[int] = set()

        def mark(node: ast.AST):
            for n in ast.walk(node):
                gated.add(id(n))

        # every statement list anywhere in the function is one block; a
        # zero_stage If gates its own branches, and (when its taken branch
        # leaves the block) everything after it in the same list
        for n in ast.walk(fn):
            for field in ("body", "orelse", "finalbody"):
                stmts = getattr(n, field, None)
                if isinstance(stmts, list) and stmts \
                        and all(isinstance(s, ast.stmt) for s in stmts):
                    behind = False
                    for s in stmts:
                        if behind:
                            mark(s)
                            continue
                        if isinstance(s, ast.If) and _mentions_token(
                                s.test, ("zero_stage",)):
                            for sub in s.body + s.orelse:
                                mark(sub)
                            if s.body and isinstance(
                                    s.body[-1], (ast.Return, ast.Raise,
                                                 ast.Continue, ast.Break)):
                                behind = True
        return gated

    # ------------------------------------------------------------- shardings
    def _is_replicated(self, module: ModuleInfo, node: ast.AST) -> bool:
        if isinstance(node, ast.Call):
            canon = module.canonical(node.func) or dotted_name(node.func) or ""
            seg = last_segment(canon) or canon
            if seg in ("P", "PartitionSpec") \
                    and not node.args and not node.keywords:
                return True  # bare P(): fully replicated spec
            if seg == "NamedSharding" and len(node.args) >= 2:
                return self._is_replicated(module, node.args[1])
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "replicated":
                return True
            return False
        name = dotted_name(node) or ""
        seg = (last_segment(name) or name).lower()
        return seg == "rep" or "rep_sh" in seg or "replicated" in seg

    def _check_function(self, module: ModuleInfo,
                        fn: ast.FunctionDef) -> Iterator[Finding]:
        gated = self._gated_ids(fn)
        for call in _calls_in(fn):
            if id(call) in gated:
                continue
            canon = module.canonical(call.func) or dotted_name(call.func) or ""
            seg = last_segment(canon) or canon
            if seg == "device_put" and len(call.args) >= 2:
                tree, sharding = call.args[0], call.args[1]
                if _mentions_token(tree, _ZR_STATE_TOKENS) \
                        and self._is_replicated(module, sharding):
                    yield self._fire(module, call)
            elif seg == "tree_map" and len(call.args) >= 2:
                # tree_map(lambda x: device_put(x, rep), tstate): the
                # device_put's first arg is the lambda var, so the state
                # name lives on the mapped TREE argument instead
                if not any(_mentions_token(a, _ZR_STATE_TOKENS)
                           for a in call.args[1:]):
                    continue
                for inner in _calls_in(call.args[0]):
                    iseg = last_segment(
                        module.canonical(inner.func)
                        or dotted_name(inner.func) or "") or ""
                    if iseg == "device_put" and len(inner.args) >= 2 \
                            and self._is_replicated(module, inner.args[1]):
                        yield self._fire(module, inner)

    def _fire(self, module: ModuleInfo, node: ast.AST) -> Finding:
        return self.finding(
            module, node,
            "replicated `device_put` of an optimizer-state tree in "
            "zero_stage-aware code with no `zero_stage` gate — under "
            "zero_stage >= 2 this re-materializes the full state on every "
            "chip, silently undoing the 1/ndp ZeRO memory win; branch on "
            "`zero_stage` (replicate only when it is 0) or place with the "
            "layout's dp shardings")


# ------------------------------------------------------------------ LK01-TH01

@register
class UnguardedSharedWriteRule(Rule):
    """LK01: an attribute the class treats as lock-guarded is written
    without the lock — or is written from two thread contexts with no
    lock at all.

    Three triggers, in priority order:

    1. **declared contract**: any assignment line carrying a
       ``# guarded-by: self._lock`` comment makes every later write of
       that attribute outside ``with self._lock:`` a finding;
    2. **majority inference**: when at least half of an attribute's
       non-``__init__`` writes hold some lock, the unlocked minority are
       the bug (PR 1's StepTimer race was exactly this shape);
    3. **shared-context inference**: in a class that spawns threads or
       handles HTTP, an attribute written both from a thread-entry
       context (``Thread(target=...)`` closure, ``do_GET``) and from
       caller-facing methods, with no write ever locked, is a data race
       waiting for load.  One finding per attribute, anchored at the
       first unlocked write.

    Blind spots: reads are not tracked; ``acquire()``/``release()``
    pairs are invisible (use ``with``); aliasing (``s = self.slots``)
    hides writes; happens-before edges that are real but invisible to
    the AST (warmup-before-start) need an inline suppression with the
    reason spelled out.
    """

    id = "LK01"
    title = "unguarded write to lock-guarded/thread-shared attribute"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        model = module_concurrency(module)
        for cls in model.classes:
            yield from self._check_class(module, cls)

    def _check_class(self, module: ModuleInfo,
                     cls) -> Iterator[Finding]:
        for attr in sorted(cls.writes):
            body = [w for w in cls.writes[attr]
                    if w.method not in _INIT_METHODS]
            if not body:
                continue
            body.sort(key=lambda w: getattr(w.node, "lineno", 0))
            lock = cls.guarded_by.get(attr)
            if lock is not None:
                for w in body:
                    if lock not in w.held:
                        yield self.finding(
                            module, w.node,
                            f"write to `self.{attr}` in `{cls.name}."
                            f"{w.method}` without holding `self.{lock}` "
                            f"(declared `# guarded-by: self.{lock}`)")
                continue
            unlocked = [w for w in body if not w.held]
            locked = [w for w in body if w.held]
            if not unlocked:
                continue
            if locked and len(locked) >= len(unlocked):
                guard = Counter(
                    l for w in locked for l in w.held).most_common(1)[0][0]
                others = ", ".join(
                    f"{w.method}:{getattr(w.node, 'lineno', '?')}"
                    for w in unlocked[1:]) or "none"
                yield self.finding(
                    module, unlocked[0].node,
                    f"`self.{attr}` is written under `self.{guard}` in "
                    f"{len(locked)} of {len(body)} sites but not in "
                    f"`{cls.name}.{unlocked[0].method}` (other unlocked "
                    f"sites: {others}) — take the lock, or annotate the "
                    f"deliberate exception with a reason")
            elif cls.threaded:
                ctxs = set()
                for w in body:
                    ctxs |= cls.contexts(w.method)
                if len(ctxs) >= 2:
                    roots = ", ".join(sorted(ctxs))
                    sites = ", ".join(sorted(
                        {f"{w.method}:{getattr(w.node, 'lineno', '?')}"
                         for w in body}))
                    yield self.finding(
                        module, unlocked[0].node,
                        f"`self.{attr}` is written from multiple thread "
                        f"contexts ({roots}; sites {sites}) with no lock "
                        f"ever held in `{cls.name}` — guard it (declare "
                        f"`# guarded-by: self._lock` and wrap writes in "
                        f"`with self._lock:`) or suppress with the "
                        f"happens-before argument spelled out")


@register
class LockOrderRule(Rule):
    """LK02: the module's static lock-order graph has a cycle.

    Nested ``with`` acquisitions and one level of ``self.m()`` helper
    propagation yield ``held -> acquired`` edges; any cycle is a
    schedule where two threads deadlock (or, for a length-1 cycle on a
    non-reentrant ``threading.Lock``, one thread deadlocks itself
    through a helper that re-takes the lock it already holds).

    Blind spots: cross-module cycles (lock identities are
    ``Class.attr``-scoped per module), ``acquire()`` call pairs, and
    locks passed as arguments.
    """

    id = "LK02"
    title = "inconsistent lock-acquisition order (deadlock schedule)"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        model = module_concurrency(module)
        for cyc in find_cycles(model.edges):
            e = cyc[0]
            if len(cyc) == 1 and e.held == e.acquired:
                yield self.finding(
                    module, e.node,
                    f"`{e.acquired}` is a non-reentrant Lock already held "
                    f"in `{e.func}` when it is re-acquired — guaranteed "
                    f"self-deadlock; use RLock or hoist the helper's "
                    f"locking to the caller")
                continue
            path = " -> ".join([c.held for c in cyc] + [cyc[0].held])
            where = "; ".join(
                f"{c.held}->{c.acquired} in {c.func}:"
                f"{getattr(c.node, 'lineno', '?')}" for c in cyc)
            yield self.finding(
                module, e.node,
                f"lock-order cycle {path} ({where}) — two threads taking "
                f"these paths concurrently deadlock; pick one global "
                f"order and re-nest the minority site")


@register
class BlockingUnderLockRule(Rule):
    """LK03: a call that can block indefinitely runs while a lock is
    held — every other thread needing that lock convoys behind device
    work, socket I/O, or an untimed wait (and if the blocked operation
    itself needs the lock to make progress, it is a deadlock).

    Condition-variable waits on the *same* lock being held are exempt
    (``wait`` releases its own lock); timed waits/joins/gets are exempt
    (bounded convoy).  Blind spots: blocking hidden behind helper
    functions, and ``dict.get(key)``-vs-``queue.get()`` is told apart
    only by argument count.
    """

    id = "LK03"
    title = "blocking call while holding a lock"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        model = module_concurrency(module)
        for node, why, func in model.blocking:
            yield self.finding(
                module, node,
                f"{why} while holding a lock in `{func}` — threads "
                f"contending for the lock convoy behind this call (a "
                f"deadlock if the blocked work needs the same lock); "
                f"move it outside the `with`, or bound it with a timeout")


@register
class ThreadLifecycleRule(Rule):
    """TH01: a ``threading.Thread`` is created with neither
    ``daemon=True`` nor any visible join/daemon lifecycle.

    A non-daemon thread with no ``join()`` keeps the interpreter alive
    after ``main`` returns — test runs and CLI tools hang on exit, and
    there is no orderly shutdown path.  Accepted lifecycles: a
    ``daemon=True`` kwarg, a later ``<name>.daemon = True`` assignment
    or ``setDaemon(True)`` call, or a ``.join(...)`` on the variable (or
    attribute basename) the thread was assigned to — including threads
    built in a comprehension bound to a container that is then joined
    through a loop variable (``ts = [Thread(...) ...]`` /
    ``for t in ts: t.join()``).

    Blind spots: ``Thread`` subclasses instantiated by their own name,
    and joins that live in another module.
    """

    id = "TH01"
    title = "thread without daemon flag or join lifecycle"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        joined: set[str] = set()
        daemonized: set[str] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute):
                recv = dotted_name(node.func.value)
                if recv and node.func.attr == "join":
                    joined.add(last_segment(recv))
                if recv and node.func.attr == "setDaemon":
                    daemonized.add(last_segment(recv))
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    name = dotted_name(t)
                    if name and last_segment(name) == "daemon":
                        owner = name.rsplit(".", 2)
                        if len(owner) >= 2:
                            daemonized.add(owner[-2])
        # a container joined through a loop variable counts: the loop var
        # landed in `joined` above, so lift that onto the iterated name
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.For, ast.AsyncFor)) \
                    and isinstance(node.target, ast.Name):
                src = dotted_name(node.iter)
                if src:
                    if node.target.id in joined:
                        joined.add(last_segment(src))
                    if node.target.id in daemonized:
                        daemonized.add(last_segment(src))
        compound = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef,
                    ast.For, ast.AsyncFor, ast.While, ast.If, ast.Try,
                    ast.With, ast.AsyncWith)
        for stmt in body_statements(module.tree.body, into_defs=True):
            if isinstance(stmt, compound):
                continue       # its simple statements are enumerated anyway
            for call, bound in self._thread_calls(module, stmt):
                kw = {k.arg: k.value for k in call.keywords}
                d = kw.get("daemon")
                if d is not None and not (
                        isinstance(d, ast.Constant) and d.value is False):
                    continue
                base = last_segment(bound) if bound else None
                if base and (base in joined or base in daemonized):
                    continue
                held = f"bound to `{bound}`" if bound else "never bound"
                yield self.finding(
                    module, call,
                    f"thread created without `daemon=True` and with no "
                    f"visible `join()`/daemon lifecycle ({held}) — it "
                    f"outlives main and hangs interpreter shutdown; pass "
                    f"`daemon=True` or join it on the shutdown path")

    @staticmethod
    def _thread_calls(module: ModuleInfo, stmt: ast.stmt):
        """(Thread(...) call, dotted name it is assigned to | None)."""
        bound_ids: dict[int, str] = {}
        if isinstance(stmt, ast.Assign):
            names = [n for t in stmt.targets for n in assigned_names(t)]
            if names and isinstance(stmt.value, ast.Call):
                bound_ids[id(stmt.value)] = names[0]
            elif names and isinstance(stmt.value, (ast.ListComp, ast.SetComp,
                                                   ast.GeneratorExp)):
                # threads built in a comprehension are "bound to" the
                # container the comprehension is assigned to
                for sub in ast.walk(stmt.value):
                    if isinstance(sub, ast.Call):
                        bound_ids[id(sub)] = names[0]
        for node in ast.walk(stmt):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)) and node is not stmt:
                continue
            if isinstance(node, ast.Call):
                canon = module.canonical(node.func) or ""
                if canon == "threading.Thread" or canon.endswith(".Thread"):
                    yield node, bound_ids.get(id(node))


#: PagePool methods that hand the caller page references it must release
_PG_ACQUIRE = {"alloc", "incref", "lookup_prefix"}
#: methods that give references back (any one on an exit path clears PG01)
#: — decref_quarantine is the off-serve-thread release (migration abort):
#: it drops the reference without making the page allocatable, which is
#: still a release for leak purposes
_PG_RELEASE = {"decref", "decref_quarantine", "free", "release", "reset"}


@register
class PageLeakRule(Rule):
    """PG01: KV pages acquired from a page pool with no release on the
    failure exit paths.

    The paged serving engine's pages are refcounted host-side
    (serving/paging.py): every ``alloc``/``lookup_prefix``/``incref``
    hands the caller references it MUST give back with ``decref`` on
    every exit path — including the exceptional ones.  A bare acquire
    that can unwind past its caller leaks pinned pages: the pool's free
    list shrinks permanently and admission starts 429ing long before the
    device pool is actually full (the refcount twin of a file-descriptor
    leak).  The engine's own discipline is acquire-inside-``try`` with
    ``decref`` in the handler or ``finally`` (see ``_admit``/``warmup``).

    Fires on an acquire-method call whose receiver looks pool-ish (its
    dotted name mentions ``pool``/``paging``) when no enclosing ``try``
    has a release call in its handlers or ``finally``.  Scoped to
    ``serving/`` modules — that is where the pool contract lives.
    ``self.<acquire>`` is exempt: those are the pool's own internals,
    whose invariants the pool lock already owns.

    Blind spots: a pool aliased to a name without ``pool`` in it; a
    release performed by a callee the handler delegates to (name the
    release in the handler, or silence with a reason).
    """

    id = "PG01"
    title = "KV page acquire without release on exit paths"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if "serving/" not in module.path.replace("\\", "/"):
            return
        parents: dict[int, ast.AST] = {}
        for node in ast.walk(module.tree):
            for child in ast.iter_child_nodes(node):
                parents[id(child)] = node
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _PG_ACQUIRE):
                continue
            recv = dotted_name(node.func.value) or ""
            low = recv.lower()
            if recv == "self" or not ("pool" in low or "paging" in low):
                continue
            if self._released_on_unwind(node, parents):
                continue
            yield self.finding(
                module, node,
                f"`{recv}.{node.func.attr}` acquires KV page references "
                "with no release on the exceptional exit path — an "
                "unwind here leaks pinned pages and the pool 429s "
                "forever after; wrap in try/except-or-finally that "
                "`decref`s what was acquired")

    @staticmethod
    def _released_on_unwind(call: ast.Call, parents) -> bool:
        """True when an enclosing ``try`` releases pages in a handler or
        ``finally`` (walking out stops at the enclosing function)."""
        node: ast.AST = call
        while True:
            parent = parents.get(id(node))
            if parent is None or isinstance(
                    parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
                return False
            if isinstance(parent, ast.Try):
                cleanup = list(parent.finalbody)
                for h in parent.handlers:
                    cleanup.extend(h.body)
                for stmt in cleanup:
                    for sub in ast.walk(stmt):
                        if isinstance(sub, ast.Call) \
                                and isinstance(sub.func, ast.Attribute) \
                                and sub.func.attr in _PG_RELEASE:
                            return True
            node = parent


@register
class UnregisteredTimingRule(Rule):
    """OB01 — hand-rolled dispatch timing that bypasses the registry.

    A function in ``serving/`` or ``parallel/`` that reads
    ``time.monotonic()``/``time.perf_counter()`` around a device-
    dispatching call but never reports through the observability layer
    (``METRICS``/``trace``/``record_span``) produces a measurement that
    exists nowhere: no histogram, no ``/metrics.prom`` scrape, no trace
    event.  PR 10's tracing/MFU accounting derives everything from
    registry observations — a private clock read next to a dispatch is
    the sign a hot path grew its own timing instead of feeding the
    registry (how the pre-PR-1 hot loops went dark).  One registry or
    tracer call anywhere in the function satisfies the rule, exactly
    like HOT02.

    Blind spots: a clock read in one function passed to a helper that
    times/dispatches in another; a dispatch hidden behind an attribute
    the jit-facts pass cannot resolve.  Silence deliberate raw timing
    with ``# graftlint: disable=OB01`` plus the reason.
    """

    id = "OB01"
    title = "dispatch timing bypasses the observability registry"

    _CLOCKS = {"time.monotonic", "time.monotonic_ns",
               "time.perf_counter", "time.perf_counter_ns"}

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if "serving/" not in path and "parallel/" not in path:
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if UninstrumentedHotLoopRule._has_obs(node, module):
                continue
            clock = None
            for call in _calls_in(node):
                name = (module.canonical(call.func)
                        or dotted_name(call.func) or "")
                if name in self._CLOCKS:
                    clock = call
                    break
            if clock is None:
                continue
            dispatches = None
            for call in _calls_in(node):
                callee = dotted_name(call.func)
                if callee and module.is_dispatching_call(callee):
                    dispatches = callee
                    break
            if dispatches is None:
                continue
            yield self.finding(
                module, clock,
                f"function times device dispatch ({dispatches!r}) with a "
                "raw monotonic/perf_counter read and never reports through "
                "METRICS/trace — the measurement is invisible to scrapes "
                "and traces; record it via METRICS.observe_time/time() or "
                "trace.record_span (or silence with a reason)")


@register
class RawQuantCastRule(Rule):
    """QT01 — ad-hoc KV/weight precision casts outside the quant helpers.

    ``x.astype(jnp.int8)`` wraps on overflow (numpy semantics: 300 →
    44) and ``.astype(jnp.float8_*)`` rounds with no absmax scaling —
    neither is a quantization.  Every sound low-precision write in this
    tree goes through a helper that scales THEN saturates
    (``ops/pallas/kv_quant.cast_to`` for cache pages,
    ``ops/pallas/matmul_int8.quantize`` for weights), which is also
    where the paired scale tensor is produced.  A raw cast in
    ``serving/`` or ``models/`` means a value reached storage precision
    without a scale beside it — the bug class where a page quantizes
    fine on small activations and silently wraps on the first outlier.
    Scoped to those two trees; the helpers themselves (``ops/pallas/``)
    are the one place a raw cast is the point.

    Blind spots: a dtype smuggled through a variable
    (``dt = jnp.int8; x.astype(dt)``); ``jnp.asarray(x, jnp.int8)``.
    Silence a deliberate storage-layer cast with
    ``# graftlint: disable=QT01`` plus the reason.
    """

    id = "QT01"
    title = "raw int8/fp8 cast outside the quant helpers"

    _QUANT_DTYPES = {"jax.numpy.int8", "jnp.int8", "numpy.int8"}

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if "serving/" not in path and "models/" not in path:
            return
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"):
                continue
            dtype_arg = None
            if node.args:
                dtype_arg = node.args[0]
            else:
                for kw in node.keywords:
                    if kw.arg == "dtype":
                        dtype_arg = kw.value
            if dtype_arg is None:
                continue
            name = (module.canonical(dtype_arg)
                    or dotted_name(dtype_arg) or "")
            seg = last_segment(name) or ""
            if not (name in self._QUANT_DTYPES
                    or seg.startswith("float8_")):
                continue
            yield self.finding(
                module, node,
                f"raw `.astype({seg})` — an unscaled, unsaturated cast "
                "to storage precision (int8 wraps on overflow, fp8 "
                "rounds with no absmax); quantize through "
                "`kv_quant.cast_to`/`requantize_pool` or "
                "`matmul_int8.quantize` so a scale rides beside the "
                "bytes (or silence with a reason)")


@register
class ElasticMeshConstructionRule(Rule):
    """EL01 — mesh/topology construction outside the mesh helpers.

    Elastic training (DESIGN.md §21) rebuilds the mesh at runtime: a
    device loss shrinks it, a re-registration grows it, and a resharding
    restore re-splits state onto whatever width came out.  That only
    works when every mesh in ``parallel/``/``resilience/`` flows through
    the ``parallel/mesh.py`` helpers (``make_mesh``/``local_mesh``/
    ``elastic_mesh``/``shrink_mesh``/``grow_mesh``), which keep the
    device list explicit and the axis layout canonical.  A raw
    ``jax.sharding.Mesh(...)`` call, or a ``jax.devices()`` /
    ``jax.local_devices()`` subscript with *integer-literal* bounds
    (``jax.devices()[:8]``), hard-codes a topology the resize path can
    neither rebuild nor verify — it is exactly the frozen-device-set bug
    a shrink turns into a crash.  Variable-bounded slices
    (``jax.devices()[:n]``) are fine: the width is a parameter the
    caller can re-derive after a resize.  Scoped to ``parallel/`` and
    ``resilience/`` excluding ``mesh.py`` itself (the one sanctioned
    construction site); ``NamedSharding`` over an existing mesh is not
    construction and is not flagged.

    Blind spots: a ``Mesh`` aliased through a variable
    (``M = Mesh; M(...)``), and device lists materialized in another
    module and passed in.  Silence a deliberate fixed topology with
    ``# graftlint: disable=EL01`` plus the reason.
    """

    id = "EL01"
    title = "raw mesh construction outside parallel/mesh.py helpers"

    _DEVICE_ENUMS = {"jax.devices", "jax.local_devices"}

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if "parallel/" not in path and "resilience/" not in path:
            return
        if path.endswith("parallel/mesh.py"):
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                canon = (module.canonical(node.func)
                         or dotted_name(node.func) or "")
                if (last_segment(canon) or canon) == "Mesh":
                    yield self.finding(
                        module, node,
                        "raw `Mesh(...)` constructor outside "
                        "`parallel/mesh.py` — the elastic resize path "
                        "(shrink/grow/reshard, DESIGN.md §21) can only "
                        "rebuild meshes made by the helpers; use "
                        "`make_mesh`/`local_mesh`/`elastic_mesh` (or "
                        "silence with a reason)")
            elif isinstance(node, ast.Subscript):
                v = node.value
                if not isinstance(v, ast.Call):
                    continue
                canon = (module.canonical(v.func)
                         or dotted_name(v.func) or "")
                if canon not in self._DEVICE_ENUMS:
                    continue
                if self._literal_bounds(node.slice):
                    yield self.finding(
                        module, node,
                        f"`{canon}()` subscripted with integer-literal "
                        "bounds hard-codes a device set — after a "
                        "shrink/grow the literal is stale and the slice "
                        "silently picks the wrong chips; derive the "
                        "width from the mesh (or a parameter) and build "
                        "through `elastic_mesh`/`make_mesh`")

    @staticmethod
    def _literal_bounds(sl: ast.AST) -> bool:
        """True for ``[3]`` / ``[:8]`` / ``[2:6]``; False when every
        bound is a name/expression the caller computes (``[:n]``)."""
        if isinstance(sl, ast.Constant) and isinstance(sl.value, int):
            return True
        if isinstance(sl, ast.Slice):
            return any(isinstance(b, ast.Constant)
                       and isinstance(b.value, int)
                       for b in (sl.lower, sl.upper))
        return False


@register
class UndocumentedMetricNameRule(Rule):
    """OB02 — a metric name absent from the documented metrics tables.

    Every scrape consumer (``metrics_dump``, the perf gate, the SLO
    evaluator, dashboards) binds to metric names by string; PRs 9-13
    each hand-patched a name that drifted from the docs after the fact.
    This rule closes the loop at lint time: a literal first argument to
    ``METRICS.increment/gauge/observe_time/observe_many/time`` (or the
    same mutators on a ``registry``) must appear in a metrics table row
    of ``README.md``/``DESIGN.md`` — rows shaped
    ``| `name` | counter/gauge/timer | description |``.  Documented rows
    may carry ``<placeholder>``/``{placeholder}``/``*`` suffixes
    (``faults.injected.<site>``): they match any name sharing the
    literal prefix.  F-strings and string concatenations are checked by
    their leading literal against those wildcard rows; names with no
    leading literal at all are runtime-composed and out of scope.

    Blind spots: names built through variables or ``str.join``; a
    mutator reached through a receiver not named ``METRICS``/
    ``registry``; a too-short f-string prefix that several wildcard
    rows cover.  Silence a deliberately undocumented (e.g. test-only)
    name with ``# graftlint: disable=OB02`` plus the reason.
    """

    id = "OB02"
    title = "metric name missing from the documented metrics tables"

    _MUTATORS = {"increment", "gauge", "observe_time", "observe_many",
                 "time"}
    _RECEIVERS = {"METRICS", "registry"}
    _DOC_FILES = ("README.md", "DESIGN.md")
    _ROW = re.compile(
        r"\s*\|\s*`([^`]+)`\s*\|\s*(?:counter|gauge|timer|histogram)s?\b")
    _cache: tuple[frozenset, tuple] | None = None
    _override: tuple[frozenset, tuple] | None = None

    # ------------------------------------------------------- documented set
    @classmethod
    def set_documented(cls, names) -> None:
        """Test hook: replace the parsed doc tables (None restores)."""
        cls._override = None if names is None else cls._split(names)

    @staticmethod
    def _split(names) -> tuple[frozenset, tuple]:
        exact, prefixes = set(), []
        for n in names:
            m = re.search(r"[<{*]", n)
            if m:
                prefixes.append(n[:m.start()])
            else:
                exact.add(n)
        return frozenset(exact), tuple(prefixes)

    @classmethod
    def documented(cls) -> tuple[frozenset, tuple]:
        if cls._override is not None:
            return cls._override
        if cls._cache is None:
            root = pathlib.Path(__file__).resolve().parents[2]
            names: list[str] = []
            for fn in cls._DOC_FILES:
                p = root / fn
                if p.exists():
                    for line in p.read_text().splitlines():
                        m = cls._ROW.match(line)
                        if m:
                            names.append(m.group(1))
            cls._cache = cls._split(names)
        return cls._cache

    # --------------------------------------------------------------- check
    @staticmethod
    def _literal_name(arg) -> tuple[str | None, bool]:
        """(name, is_prefix_only): a Constant is the full name; an
        f-string / ``"lit" + var`` concat yields its leading literal."""
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value, False
        if isinstance(arg, ast.JoinedStr) and arg.values:
            head = arg.values[0]
            if isinstance(head, ast.Constant) and isinstance(head.value, str) \
                    and head.value:
                return head.value, True
        if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add) \
                and isinstance(arg.left, ast.Constant) \
                and isinstance(arg.left.value, str) and arg.left.value:
            return arg.left.value, True
        return None, False

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        exact, prefixes = self.documented()
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self._MUTATORS):
                continue
            recv = dotted_name(node.func.value) or ""
            if (last_segment(recv) or recv) not in self._RECEIVERS:
                continue
            arg = node.args[0] if node.args else None
            if arg is None:
                for kw in node.keywords:
                    if kw.arg == "name":
                        arg = kw.value
            if arg is None:
                continue
            name, prefix_only = self._literal_name(arg)
            if name is None:
                continue
            if prefix_only:
                if any(name.startswith(p) or p.startswith(name)
                       for p in prefixes):
                    continue
            elif name in exact or any(name.startswith(p) for p in prefixes):
                continue
            yield self.finding(
                module, node,
                f"metric name `{name}{'…' if prefix_only else ''}` is not "
                "in the documented metrics tables (README.md/DESIGN.md) — "
                "scrape consumers bind to names by string, so undocumented "
                "names drift silently; add a "
                "`| `name` | kind | description |` row (wildcard "
                "placeholders allowed) or silence with a reason")


@register
class UnboundedMetricCardinalityRule(Rule):
    """OB03 — request-derived data interpolated into a metric name.

    The registry keys counters/gauges/histograms by name forever: a
    metric name built from a tenant id, request id, session id, or
    prompt-derived string mints one immortal series per distinct value —
    unbounded cardinality, i.e. a memory leak the dashboard renders
    proudly.  The ONE sanctioned path from request-derived strings to
    metric names is ``observability/fleet.py``'s ``TenantLabels``: it
    folds everything beyond the tracked top-K into ``__other__``, so the
    series set stays bounded by construction.  That module is exempt;
    everywhere else, an f-string or concatenation passed to
    ``METRICS.increment/gauge/observe_time/observe_many/time`` (or the
    same mutators on a ``registry``) whose interpolated parts reference
    a request-derived identifier — a name, attribute, subscript key, or
    ``.get("...")`` key in the tenant/request/session/user/prompt
    family — fails here.

    Blind spots: names composed through intermediate variables
    (``n = f"x.{tenant}"; METRICS.increment(n)``), identifiers renamed
    before interpolation (``t = req.tenant``... ``f"x.{t}"``), and
    ``str.join``/``%``/``.format`` composition.  Silence a
    deliberately-bounded interpolation (e.g. a fixed enum) with
    ``# graftlint: disable=OB03`` plus the reason.
    """

    id = "OB03"
    title = "request-derived data interpolated into a metric name"

    _MUTATORS = UndocumentedMetricNameRule._MUTATORS
    _RECEIVERS = UndocumentedMetricNameRule._RECEIVERS
    _REQUEST_DERIVED = frozenset({
        "tenant", "tenant_id", "tenants", "request_id", "req_id",
        "trace_id", "prompt", "user", "user_id", "session", "session_id"})
    _EXEMPT_SUFFIX = "observability/fleet.py"  # the bounded label helper

    @classmethod
    def _dynamic_identifiers(cls, arg) -> set[str]:
        """Lower-cased identifiers referenced by the NON-literal parts
        of an interpolated metric-name expression."""
        dyn: list[ast.AST] = []
        if isinstance(arg, ast.JoinedStr):
            dyn = [v.value for v in arg.values
                   if isinstance(v, ast.FormattedValue)]
        elif isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
            stack: list[ast.AST] = [arg]
            while stack:
                n = stack.pop()
                if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Add):
                    stack.extend((n.left, n.right))
                elif not isinstance(n, ast.Constant):
                    dyn.append(n)
        out: set[str] = set()
        for d in dyn:
            for sub in ast.walk(d):
                if isinstance(sub, ast.Name):
                    out.add(sub.id.lower())
                elif isinstance(sub, ast.Attribute):
                    out.add(sub.attr.lower())
                elif isinstance(sub, ast.Subscript):
                    sl = sub.slice
                    if isinstance(sl, ast.Constant) \
                            and isinstance(sl.value, str):
                        out.add(sl.value.lower())
                elif isinstance(sub, ast.Call):
                    # payload.get("tenant") — the key names the data
                    for a in sub.args:
                        if isinstance(a, ast.Constant) \
                                and isinstance(a.value, str):
                            out.add(a.value.lower())
        return out

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if module.path.replace("\\", "/").endswith(self._EXEMPT_SUFFIX):
            return
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self._MUTATORS):
                continue
            recv = dotted_name(node.func.value) or ""
            if (last_segment(recv) or recv) not in self._RECEIVERS:
                continue
            arg = node.args[0] if node.args else None
            if arg is None:
                for kw in node.keywords:
                    if kw.arg == "name":
                        arg = kw.value
            if arg is None:
                continue
            hits = sorted(self._dynamic_identifiers(arg)
                          & self._REQUEST_DERIVED)
            if hits:
                yield self.finding(
                    module, node,
                    f"metric name interpolates request-derived data "
                    f"({', '.join(hits)}) — every distinct value mints an "
                    "immortal registry series (unbounded cardinality); "
                    "route per-tenant accounting through "
                    "`observability.fleet.TenantLabels` (top-K exact, "
                    "`__other__` fold) instead of building the name here")


@register
class OnlineDurableWriteRule(Rule):
    """OL01 — non-durable rewrite on the online-loop / publish path.

    The online learning loop's durability story (DESIGN.md §23) has
    exactly two sanctioned write shapes: *append-only fsync'd logs* (the
    capture store — ``open(..., "a")`` plus ``os.fsync``, where a crash
    costs at most the torn tail replay already tolerates) and
    *unique-tempfile + fsync + atomic ``os.replace``* for anything
    rewritten in place (checkpoint payloads, manifests, poison/repair
    tooling).  A bare ``open(path, "w")`` / ``write_text`` /
    ``write_bytes`` on these paths is a torn-file publisher: a crash (or
    injected ``corrupt_file``) mid-write leaves a half-written file at
    the FINAL name, where a concurrent reader — the serving reload, the
    replay, ``latest_valid_step()`` — picks it up as truth.

    Fires on truncating opens (mode containing ``w`` or ``x``, incl.
    ``os.fdopen``) and ``write_text``/``write_bytes`` calls in modules
    under ``online/`` or in ``parallel/checkpoint.py``, unless the
    enclosing function visibly carries the idiom: a call to
    ``os.replace`` AND durability evidence (``os.fsync``, an
    ``*fsync*``-named helper, or a ``tempfile.mkstemp``/``mkdtemp``/
    ``NamedTemporaryFile`` unique target).  Append-mode opens are exempt
    (the log-structured contract).

    Blind spots: writers behind helpers in other modules (``np.savez``
    onto a final path — route it at a tempfile), modes built at runtime,
    and idiom halves split across functions (keep open→fsync→replace in
    ONE function so the reviewer — and this rule — can see the whole
    contract).  Silence a deliberate non-durable write with
    ``# graftlint: disable=OL01`` plus the reason.
    """

    id = "OL01"
    title = "non-durable rewrite on the online/checkpoint publish path"

    _WRITE_ATTRS = {"write_text", "write_bytes"}
    _TMP_CALLS = {"tempfile.mkstemp", "tempfile.mkdtemp",
                  "tempfile.NamedTemporaryFile", "mkstemp", "mkdtemp",
                  "NamedTemporaryFile"}

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if "/online/" not in path and not path.startswith("online/") \
                and not path.endswith("parallel/checkpoint.py"):
            return
        parents: dict[int, ast.AST] = {}
        for node in ast.walk(module.tree):
            for child in ast.iter_child_nodes(node):
                parents[id(child)] = node
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            label = self._rewrite_label(module, node)
            if label is None:
                continue
            fn = self._enclosing_function(node, parents)
            if fn is not None and self._has_idiom(module, fn):
                continue
            yield self.finding(
                module, node,
                f"`{label}` rewrites a file on the online/checkpoint "
                "publish path without the unique-tempfile + fsync + "
                "`os.replace` idiom — a crash mid-write publishes a torn "
                "file under the final name; write to a `tempfile` "
                "sibling, fsync it, then `os.replace` onto the target "
                "(appends to fsync'd logs are the one exemption)")

    def _rewrite_label(self, module: ModuleInfo, call: ast.Call) -> str | None:
        """A display label when ``call`` truncates/rewrites a file."""
        canon = module.canonical(call.func) or dotted_name(call.func) or ""
        if canon in ("open", "os.fdopen"):
            mode = None
            if len(call.args) >= 2:
                mode = call.args[1]
            for kw in call.keywords:
                if kw.arg == "mode":
                    mode = kw.value
            if (isinstance(mode, ast.Constant) and isinstance(mode.value, str)
                    and any(c in mode.value for c in "wx")):
                return f'{canon}(..., "{mode.value}")'
            return None
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr in self._WRITE_ATTRS):
            recv = dotted_name(call.func.value) or "<expr>"
            return f"{recv}.{call.func.attr}"
        return None

    @staticmethod
    def _enclosing_function(node: ast.AST, parents) -> ast.AST | None:
        while node is not None:
            node = parents.get(id(node))
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return node
        return None

    def _has_idiom(self, module: ModuleInfo, fn: ast.AST) -> bool:
        """True when ``fn`` visibly replaces atomically AND shows
        durability evidence (fsync or a unique tempfile target)."""
        has_replace = False
        has_durable = False
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            canon = module.canonical(sub.func) or dotted_name(sub.func) or ""
            name = last_segment(canon) or canon
            if canon == "os.replace" or name == "replace" and \
                    canon.startswith("os."):
                has_replace = True
            if canon == "os.fsync" or "fsync" in name.lower() \
                    or canon in self._TMP_CALLS:
                has_durable = True
            if has_replace and has_durable:
                return True
        return False


# ------------------------------------------------------------- sharding tier
#
# SH01-SH04 + NM01 consume the analysis/sharding.py mesh-axis pass: axis
# bindings resolved interprocedurally from Mesh construction through
# shard_map/pmap wrap sites, the canonical axis registry parsed out of
# parallel/mesh.py, and literal PartitionSpec signatures.  The runtime
# twin is analysis/shardguard.py (implicit-reshard detection on live
# executables) — same split as the concurrency tier's LK rules/lockguard.


@register
class UnboundCollectiveAxisRule(Rule):
    """SH01 — collective over an axis no enclosing mesh context binds.

    ``lax.psum(x, 'tp')`` inside a function that is only ever
    ``shard_map``-ed over a ``('dp',)`` mesh cannot succeed: the trace
    fails with an unbound axis name on device — or, when an outer
    context happens to bind a same-named axis of different extent, the
    collective silently reduces over the wrong device group.  The
    sharding pass resolves which axes each function body is bound under
    (through ``Mesh``/``make_mesh``/``local_mesh``/``elastic_mesh``,
    ``shard_map`` and ``pmap(axis_name=...)``, plus one module-internal
    call level of propagation) and this rule fires when a collective's
    literal/constant axis argument is missing from that KNOWN set.

    Deliberately confidence-ranked: an axis arriving as a function
    parameter (the ``parallel/collectives.py`` wrappers), a mesh the
    pass cannot resolve, or a function never visibly wrapped all leave
    the binding unknown and keep the rule silent — cross-module wrap
    sites are the blind spot, and why suppressions exist.
    """

    id = "SH01"
    title = "collective over an axis not bound by the enclosing mesh context"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        info = sharding_info(module)
        for call, chain in info.collective_chains.items():
            axis_arg = info.collective_axis_arg(call)
            if axis_arg is None:
                continue
            axes_named = info.resolve_axis_tuple(axis_arg)
            if axes_named is None:
                continue
            bound = info.axes_for_chain(chain)
            if bound is None:
                continue
            missing = [a for a in axes_named if a not in bound]
            if missing:
                op = last_segment(module.canonical(call.func) or "") or "?"
                yield self.finding(
                    module, call,
                    f"collective `{op}` over axis {missing[0]!r} but the "
                    f"enclosing shard_map/pmap context only binds "
                    f"{sorted(bound)} — an unbound axis name fails the "
                    "trace on device (or reduces over the wrong device "
                    "group); bind the axis in the mesh or fix the name")


@register
class UnknownAxisNameRule(Rule):
    """SH02 — ``PartitionSpec`` naming an axis outside the registry.

    Every axis name in this repo comes from ONE table —
    ``parallel/mesh.py``'s ``DP/TP/PP/SP/EP`` constants and the ``AXES``
    tuple — which the sharding pass parses directly, so the linter and
    the runtime can never disagree about which axes exist.  A literal
    axis string in a ``PartitionSpec``/``P(...)`` call that is not in
    that table is a typo ('dpx'), a stale rename, or an axis the mesh
    builder will never create: placement either fails the trace or
    silently replicates where the author meant to shard.

    The fix for a true finding is the registry hoist: import the
    constant (``P(DP)``) instead of repeating the string.  Blind spots:
    names built at runtime, specs threaded through variables, and
    constants shadowed locally with non-registry values.
    """

    id = "SH02"
    title = "PartitionSpec axis name absent from the canonical axis registry"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        registry = axis_registry()
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            canon = module.canonical(node.func) or ""
            if last_segment(canon) != "PartitionSpec":
                continue
            for arg in node.args:
                elts = (arg.elts if isinstance(arg, (ast.Tuple, ast.List))
                        else [arg])
                for elt in elts:
                    if not (isinstance(elt, ast.Constant)
                            and isinstance(elt.value, str)):
                        continue
                    if elt.value not in registry:
                        yield self.finding(
                            module, node,
                            f"PartitionSpec axis {elt.value!r} is not in "
                            "the canonical axis registry "
                            f"({', '.join(sorted(registry))}; "
                            "parallel/mesh.py AXES) — no mesh builder "
                            "creates this axis, so placement fails the "
                            "trace or silently replicates; use the mesh.py "
                            "constants instead of string literals")


@register
class ShardMapSpecArityRule(Rule):
    """SH03 — ``shard_map`` specs that cannot match the wrapped function.

    ``in_specs`` is zipped positionally against the wrapped function's
    arguments and ``out_specs`` against its returned tuple; an arity
    mismatch is a guaranteed trace-time pytree error — but one that only
    surfaces when the wrap site finally executes, typically deep inside
    a trainer build.  When the wrapped callable is a module-local def or
    lambda and the specs are literal tuples, both arities are checkable
    at lint time; functions with ``*args``, specs threaded through
    variables, and cross-module targets stay out of scope.  The return
    check only fires when every return statement is a literal tuple of
    one consistent length (anything else — a returned variable, a
    single-value return — is unknowable statically).
    """

    id = "SH03"
    title = "shard_map in_specs/out_specs arity mismatch with wrapped fn"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        info = sharding_info(module)
        for site in info.shard_map_sites:
            target = site.target
            if target is None:
                continue
            args = target.args
            if args.vararg is not None:
                continue
            params = [a.arg for a in (args.posonlyargs + args.args)]
            if params and params[0] == "self":
                params = params[1:]
            name = getattr(target, "name", "<lambda>")
            if isinstance(site.in_specs, (ast.Tuple, ast.List)):
                n_in = len(site.in_specs.elts)
                lo = len(params) - len(args.defaults or [])
                if not (lo <= n_in <= len(params)):
                    yield self.finding(
                        module, site.call,
                        f"shard_map in_specs has {n_in} entries but "
                        f"`{name}` takes {len(params)} positional "
                        "argument(s) — the spec/argument zip fails at "
                        "trace time; make the arities match")
            if isinstance(site.out_specs, (ast.Tuple, ast.List)) \
                    and isinstance(target, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)):
                lens = set()
                literal = True
                returns = [s for s in body_statements(target.body)
                           if isinstance(s, ast.Return) and s.value is not None]
                for ret in returns:
                    if isinstance(ret.value, ast.Tuple):
                        lens.add(len(ret.value.elts))
                    else:
                        literal = False
                        break
                if literal and len(lens) == 1:
                    n_ret = lens.pop()
                    if n_ret != len(site.out_specs.elts):
                        yield self.finding(
                            module, site.call,
                            f"shard_map out_specs has "
                            f"{len(site.out_specs.elts)} entries but "
                            f"`{name}` returns a {n_ret}-tuple — the "
                            "output pytree/spec zip fails at trace time")


@register
class DonatedReshardRule(Rule):
    """SH04 — donation through a sharding mismatch (DON01, shard-aware).

    When an argument reaches a ``donate_argnums`` position of a jit
    whose declared ``in_shardings`` differ from the sharding the caller
    placed the array with (``jax.device_put(x, NamedSharding(...))``),
    XLA inserts an implicit reshard copy at the boundary: the donation
    then aliases the *copy*, the caller's original buffer is still freed
    — so the memory win the donation promised is silently lost on every
    step, and any post-call read of the name is use-after-free exactly
    as in DON01.  Fires at the call site when both shardings are
    statically literal (``NamedSharding(mesh, P(...))`` placement in the
    same function, literal ``in_shardings`` tuple on the jit); either
    side arriving through a variable keeps the rule silent.
    """

    id = "SH04"
    title = "donated argument placed with a sharding the jit reshards"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        info = sharding_info(module)
        jits = self._declared_jits(module, info)
        if not jits:
            return
        for fn in ast.walk(module.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_function(module, info, jits, fn)

    def _declared_jits(self, module: ModuleInfo, info) -> dict:
        """basename -> (donate positions, tuple of spec signatures)."""
        out: dict[str, tuple] = {}
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            canon = module.canonical(call.func) or ""
            if not (canon in ("jit", "jax.jit") or canon.endswith(".jit")):
                continue
            donate = in_sh = None
            for kw in call.keywords:
                if kw.arg in ("donate_argnums", "donate_argnames"):
                    donate = literal_int_tuple(kw.value)
                elif kw.arg == "in_shardings":
                    in_sh = kw.value
            if not donate or not isinstance(in_sh, (ast.Tuple, ast.List)):
                continue
            sigs = tuple(info.spec_signature(e) for e in in_sh.elts)
            for target in node.targets:
                tname = dotted_name(target)
                if tname is not None:
                    out[last_segment(tname)] = (donate, sigs)
        return out

    def _check_function(self, module: ModuleInfo, info, jits,
                        fn) -> Iterator[Finding]:
        placed: dict[str, tuple] = {}     # name -> placed spec signature
        for stmt in body_statements(fn.body):
            if isinstance(stmt, ast.Assign) \
                    and isinstance(stmt.value, ast.Call):
                sig = self._device_put_sig(module, info, stmt.value)
                for target in stmt.targets:
                    tname = dotted_name(target)
                    if tname is None:
                        continue
                    if sig is not None:
                        placed[tname] = sig
                    else:
                        placed.pop(tname, None)   # rebound: stale signature
            for call in _calls_in(stmt):
                callee = dotted_name(call.func)
                if callee is None:
                    continue
                hit = jits.get(last_segment(callee))
                if hit is None:
                    continue
                donate, sigs = hit
                for pos in donate:
                    if pos >= len(call.args):
                        continue
                    aname = dotted_name(call.args[pos])
                    if aname is None:
                        continue
                    declared = sigs[pos] if pos < len(sigs) else None
                    got = placed.get(aname)
                    if declared is not None and got is not None \
                            and declared != got:
                        yield self.finding(
                            module, call,
                            f"{aname!r} was placed with sharding "
                            f"P{got!r} but is donated at position {pos} "
                            f"of a jit declaring in_shardings P"
                            f"{declared!r} — the implicit reshard copies "
                            "and the donation frees the original without "
                            "aliasing it: the memory win is lost and any "
                            "later read is use-after-free; place with the "
                            "jit's sharding (or fix the declaration)")

    def _device_put_sig(self, module: ModuleInfo, info, call: ast.Call):
        canon = module.canonical(call.func) or ""
        if last_segment(canon) != "device_put":
            return None
        sh = call.args[1] if len(call.args) > 1 else None
        for kw in call.keywords:
            if kw.arg in ("device", "sharding"):
                sh = kw.value
        return None if sh is None else info.spec_signature(sh)


@register
class UnstableReductionRule(Rule):
    """NM01 — hand-rolled softmax/logsumexp without max subtraction.

    ``log(sum(exp(x)))`` and ``exp(x)/sum(exp(x))`` overflow to inf the
    moment one logit exceeds ~88 (f32) or ~11 (bf16) — which real logits
    do.  The sanctioned implementations in this tree are the blocked-
    xent kernel (``ops/pallas/xent.py``), the online-softmax attention
    kernels (``ops/pallas/attention.py``, ``ops/pallas/paged_attention.py``)
    and ``jax.scipy.special.logsumexp`` / ``jax.nn.softmax`` — all of
    which subtract a running or global max first.  Scoped to ``ops/``
    and ``models/``, the rule fires on three shapes: direct
    ``log(...sum(exp(...))...)`` nesting, a division whose numerator
    holds ``exp`` and denominator a ``sum`` of ``exp``, and a ``log``/
    division of a local name bound from a sum-of-exp — in each case
    only when the enclosing function shows NO max/clip evidence at all
    (any ``max``/``maximum``/``clip``/``logsumexp``/``softmax`` call
    quiets it, which is what makes the online-softmax kernels pass).

    Blind spots: guards living in a helper the function calls, and
    reductions split across functions.  A deliberately unguarded form
    (inputs bounded by construction) gets ``# graftlint: disable=NM01``
    with the bound stated.
    """

    id = "NM01"
    title = "numerically unstable reduction (softmax/logsumexp w/o max)"

    _GUARDS = {"max", "maximum", "clip", "pmax", "logsumexp", "softmax",
               "log_softmax", "amax", "nanmax"}

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if not any(seg in path for seg in ("ops/", "models/")):
            return
        for fn in ast.walk(module.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if self._has_guard(module, fn):
                    continue
                yield from self._check_function(module, fn)

    def _has_guard(self, module: ModuleInfo, fn) -> bool:
        for call in _calls_in(fn):
            canon = module.canonical(call.func) or dotted_name(call.func) or ""
            base = (last_segment(canon) or canon).lstrip("_")
            if base in self._GUARDS:
                return True
        return False

    def _is_exp(self, module: ModuleInfo, call: ast.Call) -> bool:
        canon = module.canonical(call.func) or ""
        return last_segment(canon) == "exp"

    def _contains_exp(self, module: ModuleInfo, node: ast.AST) -> bool:
        return any(self._is_exp(module, c) for c in _calls_in(node))

    def _is_sum_of_exp(self, module: ModuleInfo, node: ast.AST,
                       exp_names=frozenset()) -> bool:
        """``sum(..exp..)`` call or ``(..exp..).sum()`` method call,
        where "exp" is a literal exp call or a name bound from one."""
        if not isinstance(node, ast.Call):
            return False
        canon = module.canonical(node.func) or ""
        if last_segment(canon) != "sum":
            return False

        def exppy(n: ast.AST) -> bool:
            return (self._contains_exp(module, n)
                    or bool(names_read(n) & exp_names))

        scope = (node.func.value if isinstance(node.func, ast.Attribute)
                 else None)
        return any(exppy(a) for a in node.args) \
            or (scope is not None and exppy(scope))

    def _contains_sum_of_exp(self, module: ModuleInfo, node: ast.AST,
                             exp_names=frozenset()) -> bool:
        return any(self._is_sum_of_exp(module, n, exp_names)
                   for n in ast.walk(node) if isinstance(n, ast.Call))

    def _check_function(self, module: ModuleInfo, fn) -> Iterator[Finding]:
        # names bound (in this function) to an exp / to a sum-of-exp
        exp_names: set[str] = set()
        sumexp_names: set[str] = set()
        for stmt in body_statements(fn.body):
            if not isinstance(stmt, ast.Assign):
                continue
            if self._contains_sum_of_exp(module, stmt.value, exp_names):
                for target in stmt.targets:
                    sumexp_names.update(assigned_names(target))
            elif self._contains_exp(module, stmt.value):
                for target in stmt.targets:
                    exp_names.update(assigned_names(target))

        def holds_exp(node: ast.AST) -> bool:
            return (self._contains_exp(module, node)
                    or bool(names_read(node) & exp_names))

        def holds_sumexp(node: ast.AST) -> bool:
            return (self._contains_sum_of_exp(module, node, exp_names)
                    or bool(names_read(node) & sumexp_names))

        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                canon = module.canonical(node.func) or ""
                if last_segment(canon) == "log" and node.args \
                        and holds_sumexp(node.args[0]):
                    yield self.finding(
                        module, node,
                        "hand-rolled logsumexp: log of a sum of exp "
                        "with no max subtraction in reach — overflows "
                        "to inf on realistic logits; use "
                        "jax.scipy.special.logsumexp (or subtract the "
                        "max first, like the blocked-xent kernel)")
            elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Div):
                if holds_sumexp(node.right) and holds_exp(node.left):
                    yield self.finding(
                        module, node,
                        "hand-rolled softmax: exp(x) divided by a sum of "
                        "exp with no max subtraction in reach — overflows "
                        "to inf on realistic logits; use jax.nn.softmax "
                        "(or the online-softmax kernels in ops/)")


@register
class ControlSeamRule(Rule):
    """CT01 — raw ring/pool mutation in the control plane.

    The autoscaler's correctness argument (DESIGN.md §26) rests on
    every scale action being all-or-nothing THROUGH the serving seams:
    ``PrefixRouter.scale_up`` gates ring admission on the warmed flag,
    ``scale_down`` drains via the quarantine state machine and refuses
    to detach a replica with requests in flight, and the pool publishes
    whole rings atomically.  A control module that calls
    ``ring.add``/``ring.remove``, builds a ``HashRing`` itself, assigns
    ``router.ring``, or reaches into ``pool._replicas``/``pool._state``
    re-creates exactly the failure modes those seams were built to make
    unrepresentable: a cold replica on the ring (compile-storm TTFT), a
    half-drained replica, or a reader observing a mid-mutation ring.

    Fires, in modules under ``control/``, on: calls whose dotted target
    ends in ``.ring.add`` / ``.ring.remove`` / ``.ring.rebuild``; any
    ``HashRing(...)`` construction; assignment to an attribute ending
    ``.ring``; and any read/write of a ``._replicas`` / ``._state``
    attribute reached through another object (pool internals).

    Blind spots: mutation behind an alias (``r = router.ring`` then
    ``r.add(...)`` — only the aliasing assignment's reads escape), and
    helpers outside ``control/`` that mutate on control's behalf (the
    review catches those; keep actuators in serving).  Silence a
    deliberate hit with ``# graftlint: disable=CT01`` plus the reason.
    """

    id = "CT01"
    title = "control plane bypasses the ReplicaPool/PrefixRouter seams"

    _RING_CALL_SUFFIXES = (".ring.add", ".ring.remove", ".ring.rebuild")
    _INTERNAL_ATTRS = {"_replicas", "_state"}

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if "/control/" not in path and not path.startswith("control/"):
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                canon = module.canonical(node.func) \
                    or dotted_name(node.func) or ""
                if any(canon.endswith(s) for s in self._RING_CALL_SUFFIXES):
                    yield self.finding(
                        module, node,
                        f"`{canon}` mutates a hash ring directly from the "
                        "control plane — scale through "
                        "`PrefixRouter.scale_up`/`scale_down` (warmed "
                        "gate + quarantine drain + atomic ring swap)")
                elif last_segment(canon) == "HashRing":
                    yield self.finding(
                        module, node,
                        "control code builds a `HashRing` itself — ring "
                        "construction belongs to the router's atomic "
                        "swap; act through `PrefixRouter.scale_up`/"
                        "`scale_down` instead")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    name = dotted_name(t) or ""
                    if name.endswith(".ring") and "." in name:
                        yield self.finding(
                            module, t,
                            f"assignment to `{name}` swaps a router's "
                            "ring from the control plane — only the "
                            "router publishes rings (atomically, under "
                            "its own lock)")
            elif isinstance(node, ast.Attribute):
                if node.attr in self._INTERNAL_ATTRS \
                        and isinstance(node.value, ast.Attribute):
                    owner = dotted_name(node.value) or "<pool>"
                    yield self.finding(
                        module, node,
                        f"`{owner}.{node.attr}` reaches into pool "
                        "internals from the control plane — use the "
                        "pool's public membership seams "
                        "(`add_replica`/`drain_replica`/"
                        "`remove_replica`/`inflight`)")


#: page-accounting calls that move refcounts or hand pages across tiers —
#: inside serving/disagg/ these belong to the KVMigrator seams only
_DG_ACCOUNTING = {
    "alloc", "incref", "decref", "decref_quarantine", "lookup_prefix",
    "insert_prefix", "clear_prefix", "requeue", "queue_wipe",
    "admit_from_pages",
}


@register
class DisaggSeamRule(Rule):
    """DG01: page accounting or block-table writes in ``serving/disagg/``
    outside the ``KVMigrator`` export/import seams.

    The disagg tier's whole correctness story is one invariant: a
    migrated request's page refcounts hand off ATOMICALLY — the decode
    pool's claims plus fresh allocations transfer to the engine in the
    same step that queues the request, and every abort path releases
    exactly what it acquired (the chaos legs assert refcounts balance to
    zero leaked pages).  That invariant is auditable only because every
    pool acquire/release and block-table mutation in the package funnels
    through the :class:`~..serving.disagg.migrate.KVMigrator`'s seams —
    PG01's acquire/release discipline extended across the process
    boundary.  A scheduler (or future tier code) that increfs a page or
    pokes a block table itself reintroduces the scattered-refcount bug
    class the seam exists to kill.

    Fires, in modules whose path contains ``serving/disagg``, on (a) any
    call whose attribute is a page-accounting method (``alloc``,
    ``incref``, ``decref``, ``decref_quarantine``, ``lookup_prefix``,
    ``insert_prefix``, ``clear_prefix``, ``requeue``, ``queue_wipe``,
    ``admit_from_pages``) and (b) any assignment whose target mentions a
    block table (``bt`` / ``block_table``) — when the enclosing class is
    not ``KVMigrator``.

    Blind spots: accounting reached through a helper defined outside the
    package (the helper's own module gets PG01 instead), and a pool
    aliased into a collection.  Silence a deliberate hit with
    ``# graftlint: disable=DG01`` plus the reason.
    """

    id = "DG01"
    title = "disagg page accounting outside the KVMigrator seams"

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        path = module.path.replace("\\", "/")
        if "serving/disagg" not in path:
            return
        # map every node to its enclosing class, one walk
        owner: dict[int, str] = {}

        def _mark(node: ast.AST, cls: str | None) -> None:
            if isinstance(node, ast.ClassDef):
                cls = node.name
            for child in ast.iter_child_nodes(node):
                if cls is not None:
                    owner[id(child)] = cls
                _mark(child, cls)

        _mark(module.tree, None)
        for node in ast.walk(module.tree):
            if owner.get(id(node)) == "KVMigrator":
                continue
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _DG_ACCOUNTING:
                recv = dotted_name(node.func.value) or "<expr>"
                yield self.finding(
                    module, node,
                    f"`{recv}.{node.func.attr}` moves page references "
                    "outside the KVMigrator export/import seams — route "
                    "it through the migrator so the refcount handoff "
                    "stays atomic and auditable (DESIGN.md §27)")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    name = (dotted_name(t) or "").lower()
                    seg = name.rsplit(".", 1)[-1]
                    if seg == "bt" or "block_table" in name:
                        yield self.finding(
                            module, t,
                            f"assignment to `{dotted_name(t)}` writes a "
                            "block table outside the KVMigrator seams — "
                            "block-table rows are installed only by the "
                            "engine's admit path on the migrator's "
                            "behalf (DESIGN.md §27)")
