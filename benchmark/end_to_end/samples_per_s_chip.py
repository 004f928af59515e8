"""Samples trained on inside the window / window seconds / chips. A sample is
what the configuration's file says (``sample_unit``): an image, or a real,
non-padding token; the traffic's plan counts them from the seed."""


def read(ctx):
    return ctx["samples"] / ctx["window_s"] / ctx["chips"]
