"""Kernel-tier observability: per-kernel timing histograms.

``record_kernel_time`` is one wall-clock observation per kernel call
(``kernel.<kind>.<name>`` histogram) plus an optional bytes-moved gauge,
fed by ``tools/kernel_smoke.py`` and any harness that times a dispatched
kernel, so the kernel tier (``ops/pallas``) never imports histogram
internals.
"""

from __future__ import annotations

from .metrics import METRICS

# kernel calls run µs-to-ms: the default request-latency buckets would
# dump everything in the first bin
KERNEL_TIME_BUCKETS = (
    1e-6, 5e-6, 1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2, 1e-1,
)


def record_kernel_time(kind: str, name: str, seconds: float,
                       bytes_moved: int | None = None) -> None:
    """One timing observation for a ``(kind, name)`` kernel dispatch."""
    metric = f"kernel.{kind}.{name}"
    METRICS.observe_time(metric, seconds, buckets=KERNEL_TIME_BUCKETS)
    if bytes_moved is not None:
        METRICS.gauge(f"{metric}.bytes_per_call", bytes_moved)
        if seconds > 0:
            METRICS.gauge(f"{metric}.gbps", bytes_moved / seconds / 1e9)
