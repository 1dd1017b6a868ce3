"""Metric readers, one module per metric named in ``BENCHMARK.json``.

Each has ``read(run) -> float | None`` over a ``bench.harness.Run``; ``None``
means the run holds nothing to read, and the metric is left out of the line.
"""
