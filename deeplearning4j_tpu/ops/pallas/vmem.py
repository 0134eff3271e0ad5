"""The block spec every kernel of this tier stages its operands through.

Kept apart from ``registry.py``, which stays importable without
``jax.experimental.pallas``."""

import functools

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

vmem_spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
