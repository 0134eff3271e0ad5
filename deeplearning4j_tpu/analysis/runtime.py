"""Runtime enforcement: jax.transfer_guard scopes for hot loops.

The static rules catch the *patterns*; this module catches the *behavior*:
hot loops (trainer steady state, perf smoke) run under
``jax.transfer_guard("disallow")``, so any IMPLICIT host<->device transfer
— a numpy batch leaking into a jitted call, a Python scalar materialized
per step, a stray ``float(loss)`` on a real accelerator — raises at the
exact call site instead of silently serializing the dispatch queue.

Explicit transfers (``jax.device_put`` / ``jax.device_get``) stay allowed:
the contract is not "no transfers", it is "every transfer is spelled out"
(DESIGN.md §10's synchronization-points-are-explicit rule, now enforced).

Opt out with ``DL4J_TPU_TRANSFER_GUARD=0`` (or ``off``/``allow``), or set
it to ``log`` to trace offenders without failing.  Known backend quirk:
on the CPU backend device->host reads are free (host-addressable memory,
no transfer happens), so only host->device hazards trip the guard there —
the full contract is enforced on real devices.
"""

from __future__ import annotations

import contextlib
import os

ENV_FLAG = "DL4J_TPU_TRANSFER_GUARD"

_OFF_VALUES = {"0", "off", "false", "allow", "no", "disabled"}
_MODES = {"disallow", "log", "disallow_explicit", "log_explicit"}


def guard_mode() -> str | None:
    """The transfer-guard level for hot loops, or None when opted out."""
    raw = os.environ.get(ENV_FLAG, "").strip().lower()
    if raw in _OFF_VALUES:
        return None
    if raw in _MODES:
        return raw
    return "disallow"


@contextlib.contextmanager
def hot_loop_guard():
    """Run a hot loop under the configured transfer guard.

    No-op (and no jax import) when opted out, so host-only tooling can
    wrap loops unconditionally.
    """
    mode = guard_mode()
    if mode is None:
        yield None
        return
    import jax

    with jax.transfer_guard(mode):
        yield mode


@contextlib.contextmanager
def allow_transfers():
    """Explicit sync point inside a guarded region (checkpoint fences,
    end-of-run parameter pulls): re-allows implicit transfers for the
    scope, making 'this code is ALLOWED to sync' a visible annotation."""
    import jax

    with jax.transfer_guard("allow"):
        yield
