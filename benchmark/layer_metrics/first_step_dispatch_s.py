"""Seconds of the first ``train.step`` phase: the step traced, lowered and
compiled or loaded from the cache, and its first dispatch."""

from reduce import startup


def read(ctx):
    step = startup.first_step(ctx["spans"])
    return None if step is None else (step["end_ns"] - step["start_ns"]) / 1e9
