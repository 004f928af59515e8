"""Median ``placement.h2d`` span inside the window: the host's time to slice
one batch per device and dispatch its transfers. The dispatch is asynchronous,
so this is dispatch time, not transfer time."""

import statistics

from reduce import spans


def read(ctx):
    whole = spans.whole_inside(ctx["spans"], "placement.h2d",
                               ctx["window_ns"])
    return statistics.median(whole) / 1e6 if whole else None
