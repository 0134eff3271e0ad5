"""Kernel candidate registry + the evidence-gated auto-pick.

One registration API for every accelerated op in the tree (the flash
attention kernel predates this package and registers through the same
surface — no parallel mechanisms).  A candidate bundles the kernel entry
point, its pure-jnp reference, the block configs the TUNE battery should
sweep, and the documented correctness tolerances the adoption gate
enforces.

``autopick`` is the decision procedure bench.py's pickers share: a
candidate replaces the incumbent only when

1. a TUNE battery row proves it *correct* (its ``check`` dict passes the
   candidate's tolerances — ``max_err``-style upper bounds and/or
   ``min``-keyed lower bounds such as int8's top-1 agreement), and
2. its best measured metric beats the incumbent's best by the >2% margin
   (one noisy row must not flip a production config), where a 0.0 row is
   EVIDENCE of a broken config, not missing data, and no incumbent
   evidence means no adoption (never adopt by void).

Losers stay registered but unpicked; every dropped candidate lands in
``Pick.dropped`` with the reason, so the bench artifact's pick table has
no silent caps.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Iterable, Mapping

#: kernel modules pulled in lazily so importing the registry never drags
#: jax.experimental.pallas in; a module that fails to import raises at
#: the first lookup — on the installed stack that is a bug, not a
#: degraded wheel
_KERNEL_MODULES = (
    "deeplearning4j_tpu.ops.pallas.attention",
    "deeplearning4j_tpu.ops.pallas.layernorm",
    "deeplearning4j_tpu.ops.pallas.xent",
    "deeplearning4j_tpu.ops.pallas.matmul_int8",
    "deeplearning4j_tpu.ops.pallas.paged_attention",
    "deeplearning4j_tpu.ops.flash_attention",
)


@dataclasses.dataclass(frozen=True)
class KernelCandidate:
    """One selectable implementation of a kernel kind."""

    kind: str                     # "attention" | "layernorm_residual" | ...
    name: str                     # registry key within the kind
    fn: Callable                  # kernel entry point (jnp-compatible API)
    reference: Callable | None = None   # pure-jnp ground truth
    blocks: tuple = ()            # block configs the TUNE battery sweeps
    tolerances: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    source: str = "pallas"        # "pallas" kernel or "xla" incumbent


@dataclasses.dataclass
class Pick:
    """One auto-pick decision, artifact-ready via :meth:`as_dict`."""

    kind: str
    choice: str
    reason: str
    dropped: list            # [{"candidate": name, "reason": why}, ...]
    considered: int          # TUNE rows consulted for this kind

    def as_dict(self) -> dict:
        return {"choice": self.choice, "reason": self.reason,
                "dropped": self.dropped, "rows_considered": self.considered}


_REGISTRY: dict[tuple[str, str], KernelCandidate] = {}
_SCOPED: dict[tuple[str, str], KernelCandidate] = {}    # what get() returns
_LOADED = False


def resolve_interpret(interpret: bool | None) -> bool:
    """The one rule behind every kernel's ``interpret`` flag.  An explicit
    bool is obeyed.  ``None`` compiles on a TPU backend, interprets on the
    CPU backend (the test suite), and raises on any other backend — so a
    process on the chip either compiles a kernel or fails, and never runs
    it interpreted without the caller having asked for that."""
    if interpret is not None:
        return interpret
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; backend "
        f"{backend!r} is neither — pass interpret= explicitly")


def register(candidate: KernelCandidate) -> KernelCandidate:
    """Register a candidate; re-registration with identical identity is a
    no-op (kernels register at module import, which can run twice under
    importlib reload), a *different* candidate under a taken key is a
    programming error."""
    key = (candidate.kind, candidate.name)
    prev = _REGISTRY.get(key)
    fn = getattr(candidate.fn, "unscoped", candidate.fn)   # from get()
    if prev is not None and prev.fn is not fn:
        raise ValueError(f"kernel candidate {key} already registered")
    _REGISTRY[key] = dataclasses.replace(candidate, fn=fn)
    _SCOPED.pop(key, None)
    return candidate


def _scoped(cand: KernelCandidate) -> KernelCandidate:
    """``cand`` with its entry point under ``jax.named_scope("<kind>.<name>")``,
    so a profiler trace names the kernel's device time by its registered
    name (metadata only: the compiled program does not change)."""
    scope = f"{cand.kind}.{cand.name}"

    @functools.wraps(cand.fn)
    def fn(*args, **kwargs):
        import jax

        with jax.named_scope(scope):
            return cand.fn(*args, **kwargs)

    fn.unscoped = cand.fn
    return dataclasses.replace(cand, fn=fn)


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    for mod in _KERNEL_MODULES:
        importlib.import_module(mod)
    _LOADED = True


def kinds() -> list[str]:
    _ensure_loaded()
    return sorted({k for k, _ in _REGISTRY})


def candidates(kind: str) -> list[KernelCandidate]:
    _ensure_loaded()
    return [c for (k, _), c in sorted(_REGISTRY.items()) if k == kind]


def get(kind: str, name: str) -> KernelCandidate:
    """The candidate production code runs: its ``fn`` executes under a named
    scope of its registered name (``candidates`` lists the bare entries)."""
    _ensure_loaded()
    key = (kind, name)
    if key not in _SCOPED:
        if key not in _REGISTRY:
            avail = [c.name for c in candidates(kind)]
            raise KeyError(
                f"no kernel candidate {name!r} of kind {kind!r} "
                f"(registered: {avail})")
        _SCOPED[key] = _scoped(_REGISTRY[key])
    return _SCOPED[key]


# --------------------------------------------------------------- adoption gate

def check_passes(cand: KernelCandidate, check: Mapping) -> tuple[bool, str]:
    """Apply ``cand.tolerances`` to one TUNE ``check`` row.

    Plain keys in ``tolerances`` (e.g. ``max_err``) upper-bound every
    numeric value in the check row; the nested ``min`` mapping
    lower-bounds named keys (e.g. ``{"min": {"top1_agree": 0.999}}``).
    """
    if not isinstance(check, Mapping) or not check:
        return False, "empty correctness row"
    mins = cand.tolerances.get("min", {})
    max_err = cand.tolerances.get("max_err")
    for key, val in check.items():
        if not isinstance(val, (int, float)) or isinstance(val, bool):
            return False, f"non-numeric check value {key}={val!r}"
        if key in mins:
            if val < mins[key]:
                return False, f"{key}={val} below required {mins[key]}"
        elif max_err is not None and val >= max_err:
            return False, f"{key}={val} exceeds tolerance {max_err}"
    return True, "check passed"


def _best_metric(rows: Iterable[Mapping], name: str, metric: str):
    vals = [r[metric] for r in rows
            if r.get("candidate") == name
            and isinstance(r.get(metric), (int, float))
            and not isinstance(r.get(metric), bool)]
    return max(vals) if vals else None


def autopick(kind: str, rows: Iterable[Mapping], *, incumbent: str,
             metric: str = "tokens_per_sec", margin: float = 1.02) -> Pick:
    """Pick the production implementation for ``kind`` from TUNE rows.

    ``rows`` are battery JSONL dicts; this consumes the generic schema
    ``{"kernel": kind, "candidate": name, <metric>: float}`` for
    measurements and ``{"kernel": kind, "candidate": name, "check":
    {...}}`` for correctness evidence (bench.py adapts its legacy
    per-kind row shapes into this).
    """
    _ensure_loaded()
    rows = [r for r in rows if isinstance(r, Mapping)
            and r.get("kernel") == kind]
    inc_best = _best_metric(rows, incumbent, metric)
    dropped: list[dict] = []
    eligible: list[tuple[float, KernelCandidate]] = []
    for cand in candidates(kind):
        if cand.name == incumbent:
            continue
        best = _best_metric(rows, cand.name, metric)
        if best is None:
            dropped.append({"candidate": cand.name,
                            "reason": f"no TUNE {metric} rows"})
            continue
        checks = [r["check"] for r in rows
                  if r.get("candidate") == cand.name
                  and isinstance(r.get("check"), Mapping)]
        if not checks:
            dropped.append({"candidate": cand.name,
                            "reason": "no correctness evidence"})
            continue
        verdicts = [check_passes(cand, c) for c in checks]
        if not any(ok for ok, _ in verdicts):
            dropped.append({"candidate": cand.name,
                            "reason": f"correctness gate: {verdicts[0][1]}"})
            continue
        if inc_best is None:
            dropped.append({"candidate": cand.name,
                            "reason": f"no incumbent ({incumbent}) evidence "
                                      "— never adopt by void"})
            continue
        if best <= inc_best * margin:
            dropped.append({"candidate": cand.name,
                            "reason": f"{metric} {best:.4g} within {margin:g}x"
                                      f" of {incumbent} {inc_best:.4g} "
                                      "(no >2% margin)"})
            continue
        eligible.append((best, cand))

    if eligible:
        eligible.sort(key=lambda bc: bc[0], reverse=True)
        best, winner = eligible[0]
        for lost, cand in eligible[1:]:
            dropped.append({"candidate": cand.name,
                            "reason": f"passed the gate but lost to "
                                      f"{winner.name} ({lost:.4g} vs "
                                      f"{best:.4g} {metric})"})
        pick = Pick(kind, winner.name,
                    f"TUNE: {winner.name} {best:.4g} > {incumbent} "
                    f"{inc_best:.4g} {metric} (>2% margin), check passed",
                    dropped, len(rows))
    else:
        pick = Pick(kind, incumbent,
                    f"default ({incumbent}: no TUNE evidence that a "
                    "candidate wins by >2%)", dropped, len(rows))

    try:  # observability is core, but the pick must survive without it
        from ...observability.kernels import publish_autopick
        publish_autopick(pick)
    except Exception:
        pass
    return pick
