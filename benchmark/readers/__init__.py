"""Per-layer metric readers, found by the ``reader`` name in a layer_metrics file."""
