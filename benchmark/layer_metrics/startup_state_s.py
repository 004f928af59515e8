"""Seconds of ``startup.state`` (the init program, traced, lowered and
compiled or loaded, and the step's builders) and, where the run has a
checkpoint directory, ``startup.restore``."""

from reduce import startup


def read(ctx):
    return startup.phases_s(ctx["spans"],
                            ("startup.state", "startup.restore"))
