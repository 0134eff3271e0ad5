"""Pallas kernel tier: registered candidates beside their references.

This package is the TPU-native half of the framework's premise — custom
kernels where XLA's generic lowering leaves the chip idle — organized so
no kernel is ever adopted on faith:

- every kernel lives here as a *registered candidate* (``registry.py``)
  next to a pure-jnp reference implementation and the tolerances it
  declares against it, which a tier-1 test enforces;
- every kernel threads an ``interpret`` flag through
  ``registry.resolve_interpret`` (explicit value obeyed; ``None`` compiles
  on a TPU backend, interprets on the CPU backend, raises elsewhere) so
  tier-1 CPU tests execute the real kernel body, not a stand-in, and a
  process on the chip never runs a kernel interpreted by accident;
- a kernel the benchmark has measured is the default where the code's own
  predicate says it applies (``attention.attention_candidate``), or it is
  deleted; the others are default off until their one paired chip
  measurement (DESIGN.md §14, ROADMAP D1b).

Kinds currently registered:

- ``attention``             — ring (XLA) / fused (the default on a TPU)
- ``sparse_attention``      — selected (attention over a selection of keys;
  the default on a TPU for ``models/hybrid``'s sparse mixer, whose own XLA
  path is the incumbent)
- ``layernorm_residual``    — unfused (XLA) / fused
- ``xent``                  — scan (XLA) / blocked
- ``int8_matmul``           — f32 (XLA) / pallas_int8
- ``paged_attention``       — gather (XLA) / pallas
- ``paged_attention_int8``  — gather_int8 (XLA) / pallas_int8
"""

from . import registry  # noqa: F401  (re-export the registration surface)
from .registry import (  # noqa: F401
    KernelCandidate,
    candidates,
    get,
    kinds,
    register,
    resolve_interpret,
)
