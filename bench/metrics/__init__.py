"""Per-layer metric readers: ``<base>.py`` serves every metric named
``<base>`` or ``<base>.<suffix>``. Each has ``read(ctx) -> float | None``;
None means the run had nothing to read, and the metric is left out."""
