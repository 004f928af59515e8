"""Median ``pipeline.decode`` span inside the window: one batch read and
decoded by one producer thread (host clock)."""

import statistics

from reduce import spans


def read(ctx):
    whole = spans.whole_inside(ctx["spans"], "pipeline.decode",
                               ctx["window_ns"])
    return statistics.median(whole) / 1e6 if whole else None
