"""Seconds from the end of the first ``train.step`` phase to the window's
first edge: the first step on the device, the later shapes' programs, and the
steps up to the log point at which the window opens."""

from reduce import startup


def read(ctx):
    step = startup.first_step(ctx["spans"])
    return None if step is None \
        else (ctx["window_ns"][0] - step["end_ns"]) / 1e9
