"""Pallas kernel tier: registered candidates behind the bench auto-pick.

This package is the TPU-native half of the framework's premise — custom
kernels where XLA's generic lowering leaves the chip idle — organized so
no kernel is ever adopted on faith:

- every kernel lives here as a *registered candidate* (``registry.py``)
  next to a pure-jnp reference implementation;
- every kernel threads an ``interpret`` flag through
  ``registry.resolve_interpret`` (explicit value obeyed; ``None`` compiles
  on a TPU backend, interprets on the CPU backend, raises elsewhere) so
  tier-1 CPU tests execute the real kernel body, not a stand-in, and a
  process on the chip never runs a kernel interpreted by accident;
- production adoption happens only through ``registry.autopick`` fed by
  TUNE battery rows: a correctness gate at documented tolerances plus a
  >2% throughput margin over the incumbent, with every dropped candidate
  logged (DESIGN.md §14).

Kinds currently registered:

- ``attention``           — ring (XLA incumbent) / flash / fused
- ``layernorm_residual``  — unfused (XLA incumbent) / fused
- ``xent``                — scan (XLA incumbent) / blocked
- ``int8_matmul``         — f32 (XLA incumbent) / pallas_int8
"""

from . import registry  # noqa: F401  (re-export the registration surface)
from .registry import (  # noqa: F401
    KernelCandidate,
    Pick,
    autopick,
    candidates,
    get,
    kinds,
    register,
    resolve_interpret,
)
