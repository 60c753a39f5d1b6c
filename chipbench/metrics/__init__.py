"""Per-layer metric readers: one module per metric,
``chipbench/metrics/<name>.py`` (dots in the name become ``__``), each
with ``read(ctx)`` returning a number, or ``None`` where the run holds
nothing to read; the harness then leaves the metric out of the line."""
