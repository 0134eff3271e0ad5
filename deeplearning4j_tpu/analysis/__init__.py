"""graftlint — static JAX/TPU hazard analysis + runtime transfer guards.

Two halves of one contract (DESIGN.md §11):

- **static**: an AST rule engine (``engine.Analyzer``) with six rules for
  the hazards PR 2 removed by hand — host syncs in hot paths (HS01),
  recompile storms (RC01), PRNG key reuse (RNG01), use-after-donate
  (DON01), traced-value branching (TB01), and uninstrumented hot loops
  (HOT02) — plus per-line suppressions and a committed baseline so
  ``python -m tools.graftlint --check`` can gate every PR on *new*
  violations only.
- **runtime**: ``runtime.hot_loop_guard()`` wraps the trainer's hot
  loops in ``jax.transfer_guard("disallow")`` so implicit transfers fail
  loudly at the call site (opt out: ``DL4J_TPU_TRANSFER_GUARD=0``),
  ``lockguard.LOCKGUARD`` instruments ``threading`` locks to detect
  lock-order inversions and Eraser-style unguarded shared writes at
  test time (``@pytest.mark.lockguard`` / ``DL4J_TPU_LOCKGUARD=1``), and
  ``shardguard.SHARDGUARD`` diffs the shardings crossing wrapped step
  dispatches against the placed ``NamedSharding``s to catch implicit
  resharding (``@pytest.mark.shardguard`` / ``DL4J_TPU_SHARDGUARD=1``).

The static sharding tier (SH01-SH04, NM01) resolves mesh-axis bindings
interprocedurally in ``sharding.ShardingInfo``; its canonical axis
registry is parsed from ``parallel/mesh.py``.

Results flow through the PR 1 observability layer as
``graftlint.violations.<RULE>`` and ``shardguard.violations.<kind>``
gauges (``report.emit_metrics`` / ``ShardGuard.emit_metrics``).
"""

from .baseline import Baseline
from .core import ACTIVE, BASELINED, SUPPRESSED, Finding, Rule, all_rules
from .engine import Analyzer, active
from .jitinfo import JitInfo, ModuleInfo
from .lockguard import (ENV_LOCKGUARD, LOCKGUARD, LockGuard, Violation,
                        enabled_from_env, lockguard_active)
from .report import emit_metrics, summarize, to_json, to_text
from .runtime import ENV_FLAG, allow_transfers, guard_mode, hot_loop_guard
from .sharding import ShardingInfo, axis_registry, sharding_info
from .shardguard import (ENV_SHARDGUARD, SHARDGUARD, ShardGuard,
                         shardguard_active)

__all__ = [
    "ACTIVE", "Analyzer", "BASELINED", "Baseline", "ENV_FLAG",
    "ENV_LOCKGUARD", "ENV_SHARDGUARD", "Finding", "JitInfo", "LOCKGUARD",
    "LockGuard", "ModuleInfo", "Rule", "SHARDGUARD", "SUPPRESSED",
    "ShardGuard", "ShardingInfo", "Violation", "active", "all_rules",
    "allow_transfers", "axis_registry", "emit_metrics", "enabled_from_env",
    "guard_mode", "hot_loop_guard", "lockguard_active", "sharding_info",
    "shardguard_active", "summarize", "to_json", "to_text",
]
