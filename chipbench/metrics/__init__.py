"""One reader per metric, found by the metric's name (for a name split by
cells, such as ``mfu.telemetry``, by the part before the first dot).

Each module has ``read(ctx) -> float | None``; ``ctx`` holds the cell's
configuration and traffic, the rounds completed in the window, its
host-clock length, the set-up time and the chips used, and in a traced run
the reduced trace of the window (``trace.reduce``) and the chip's peaks.
A reader that finds nothing to read returns None, and the metric is left
out of the result.
"""
