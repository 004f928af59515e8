"""Device time of one train step (XLA module ``jit_step``) in the trace, on
the first chip: the median of each compiled shape, weighted by how often it
ran (the ragged cell steps in two shapes, 58 and 93 ms on the v5e)."""

from reduce import xplane


def read(ctx):
    return xplane.module_ms(ctx["trace"], "jit_step")
