"""What the loop's small programs cost the host a step where nothing blocks:
``loop.rng_split`` (the split and its unpacking) + ``loop.loss_sum`` +
``loop.stats_add`` (one add for each ``*_total`` the task returns) of one
step, median over the window's steps that began with at most one step in
flight. Each is an eager program of its own on the loop thread; folded into
the step they would cost the host nothing (``reduce/loop_calls.py``)."""

import statistics

from reduce import loop_calls


def read(ctx):
    steps = loop_calls.aux_ns_per_step(ctx["spans"], ctx["window_ns"])
    return statistics.median(steps) / 1e6 if steps else None
