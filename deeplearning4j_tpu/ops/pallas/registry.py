"""Kernel candidate registry.

One registration API for every accelerated op in the tree.  A candidate
bundles the kernel entry point, its pure-jnp reference and the correctness
tolerances it declares; ``tests/test_pallas_kernels.py`` holds every Pallas
candidate to those tolerances against its reference on every PR.

How a candidate becomes a default: the code's own predicate (for attention,
``ops.pallas.attention.attention_candidate``) chooses it from the platform
and the shape, and the benchmark's ledger judges the cells that run it.  A
kernel that has been measured is the default where that predicate applies,
or it is deleted; one that has not stays registered and default off.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Mapping

#: kernel modules pulled in lazily so importing the registry never drags
#: jax.experimental.pallas in; a module that fails to import raises at
#: the first lookup — on the installed stack that is a bug, not a
#: degraded wheel
_KERNEL_MODULES = (
    "deeplearning4j_tpu.ops.pallas.attention",
    "deeplearning4j_tpu.ops.pallas.sparse_attention",
    "deeplearning4j_tpu.ops.pallas.layernorm",
    "deeplearning4j_tpu.ops.pallas.xent",
    "deeplearning4j_tpu.ops.pallas.matmul_int8",
    "deeplearning4j_tpu.ops.pallas.paged_attention",
)


@dataclasses.dataclass(frozen=True)
class KernelCandidate:
    """One selectable implementation of a kernel kind."""

    kind: str                     # "attention" | "layernorm_residual" | ...
    name: str                     # registry key within the kind
    fn: Callable                  # kernel entry point (jnp-compatible API)
    reference: Callable | None = None   # pure-jnp ground truth
    tolerances: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    source: str = "pallas"        # "pallas" kernel or "xla" incumbent


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
