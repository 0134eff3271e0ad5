"""Analytic utilization accounting from XLA ``cost_analysis()``.

``COSTS.capture("train_step.b128", step_fn, *args)`` lowers+compiles the
jitted fn for the concrete arg shapes, pulls XLA's cost analysis (FLOPs and
bytes accessed), and caches the result per (key, arg-signature) — so the
second trace is paid once per compiled signature, amortized by the
persistent XLA compile cache.  Combined with a measured wall time, the
result publishes live utilization gauges:

    ``{prefix}.mfu``  = flops / (seconds * peak_flops)
    ``{prefix}.mbu``  = bytes_accessed / (seconds * peak_bytes_per_s)

Peaks come from :data:`PEAKS`, the repo's one table, keyed by the
exact ``device_kind``; an unknown kind publishes no utilization gauge.
Caveats (see DESIGN.md §18): some backends return no ``cost_analysis`` or
report ``flops <= 0`` ("unknown"); ``capture`` then falls back to a
caller-supplied analytic FLOPs estimate, or returns None — callers must
treat None as "no utilization numbers", never an error.

Capturing is safe before a donating call: ``fn.lower(*args)`` reads only
shapes/dtypes and does not consume donated buffers.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Any

from . import core
from .metrics import METRICS


@dataclass(frozen=True)
class DevicePeak:
    """Published per-chip peaks of one accelerator."""
    flops: float          # dense bf16 FLOP/s
    bytes_per_s: float    # HBM bandwidth
    hbm_bytes: float      # HBM capacity
    source: str


#: The repo's ONE peak table, keyed by the exact ``device_kind`` JAX
#: reports (``jax.devices()[0].device_kind``).  A kind that is not here has
#: no utilization: the library publishes no ``*.mfu``/``*.mbu`` gauge for
#: it, and ``chip_smoke.py`` treats it as an error.
PEAKS: dict[str, DevicePeak] = {
    "TPU v5 lite": DevicePeak(
        flops=197e12, bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def device_peak() -> DevicePeak | None:
    """Peak row of the default device, or None for an unknown kind."""
    import jax
    return PEAKS.get(jax.devices()[0].device_kind)


@dataclass(frozen=True)
class CostInfo:
    """Per-execution cost of one compiled fn (whole program, all devices)."""
    flops: float
    bytes_accessed: float
    source: str  # "xla" | "analytic"


def _signature(args: tuple) -> tuple:
    """Hashable (shape, dtype) signature of a concrete arg tree."""
    import jax
    sig = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None:
            sig.append((tuple(shape), str(dtype)))
        else:
            sig.append((type(leaf).__name__, repr(leaf)[:32]))
    return tuple(sig)


def _extract(analysis: Any) -> tuple[float, float]:
    """Pull (flops, bytes_accessed) out of ``cost_analysis()``'s return,
    which is a dict on some backends and a list of per-program dicts on
    others.  Missing/garbage values come back as 0.0."""
    if analysis is None:
        return 0.0, 0.0
    entries = analysis if isinstance(analysis, (list, tuple)) else [analysis]
    flops = 0.0
    nbytes = 0.0
    for entry in entries:
        if not isinstance(entry, dict):
            continue
        try:
            f = float(entry.get("flops", 0.0))
            if math.isfinite(f) and f > 0:
                flops += f
        except (TypeError, ValueError):
            pass
        try:
            b = float(entry.get("bytes accessed", 0.0))
            if math.isfinite(b) and b > 0:
                nbytes += b
        except (TypeError, ValueError):
            pass
    return flops, nbytes


class CostModel:
    """Caches per-compiled-signature cost; publishes utilization gauges."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cache: dict[tuple, CostInfo | None] = {}
        self._by_key: dict[str, CostInfo] = {}

    # ------------------------------------------------------------- capture
    def capture(self, key: str, fn, *args,
                analytic_flops: float | None = None) -> CostInfo | None:
        """Cost of ``fn(*args)`` for these concrete arg shapes, from XLA's
        cost analysis (cached per signature).  ``fn`` must be jitted (have
        ``.lower``).  Never raises; returns None when no cost is knowable
        and no ``analytic_flops`` fallback was given."""
        if not core.enabled():
            return None
        try:
            sig = (key,) + _signature(args)
        except Exception:
            return None
        with self._lock:
            if sig in self._cache:
                info = self._cache[sig]
                if info is not None:
                    self._by_key[key] = info
                return info
        info = None
        try:
            compiled = fn.lower(*args).compile()
            flops, nbytes = _extract(compiled.cost_analysis())
            if flops > 0:
                info = CostInfo(flops, nbytes, "xla")
        except Exception:
            info = None
        if info is None and analytic_flops is not None and analytic_flops > 0:
            info = CostInfo(float(analytic_flops), 0.0, "analytic")
        with self._lock:
            self._cache[sig] = info
            if info is not None:
                self._by_key[key] = info
        return info

    def put(self, key: str, info: CostInfo) -> None:
        """Install an externally computed cost under ``key``."""
        with self._lock:
            self._by_key[key] = info

    def get(self, key: str) -> CostInfo | None:
        """Most recently captured cost for ``key`` (any signature)."""
        with self._lock:
            return self._by_key.get(key)

    # ------------------------------------------------------------- publish
    def publish_utilization(self, info: CostInfo | None, seconds: float,
                            mfu_gauge: str, mbu_gauge: str | None = None,
                            registry=None) -> float | None:
        """Gauge ``mfu_gauge`` (and ``mbu_gauge`` when bytes are known)
        from one execution's cost and measured wall seconds.  Returns the
        MFU value, or None when nothing could be published — which
        includes every device whose kind is not in :data:`PEAKS`."""
        if info is None or not (seconds > 0) or not core.enabled():
            return None
        peak = device_peak()
        if peak is None:
            return None
        reg = registry if registry is not None else METRICS
        mfu = None
        if info.flops > 0:
            mfu = info.flops / (seconds * peak.flops)
            if math.isfinite(mfu):
                reg.gauge(mfu_gauge, mfu)
            else:
                mfu = None
        if mbu_gauge and info.bytes_accessed > 0:
            mbu = info.bytes_accessed / (seconds * peak.bytes_per_s)
            if math.isfinite(mbu):
                reg.gauge(mbu_gauge, mbu)
        return mfu

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()
            self._by_key.clear()


COSTS = CostModel()
