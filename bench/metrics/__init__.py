"""Per-layer metrics, one reader a file: ``read(ctx) -> float | None``.

``ctx`` is ``harness.Context``. A reader that finds nothing to read returns
None, and the harness leaves the metric out of the result line.
"""
