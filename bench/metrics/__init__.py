"""Per-layer metric readers, one module per metric name up to its first
dot.  Each has ``read(ctx, metric) -> float | None``: None where the run
gave it nothing to read, and the harness then leaves the metric out."""
