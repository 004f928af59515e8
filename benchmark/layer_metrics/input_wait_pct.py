"""Share of the window the loop thread spent inside ``next(loader)``
(``train.loader`` spans): waiting for input, as the host sees it. Not the
device's idle share, which the trace gives."""

from reduce import spans


def read(ctx):
    inside = spans.inside(ctx["spans"], "train.loader", ctx["window_ns"])
    if not inside:
        return None
    return 100.0 * sum(inside) / (ctx["window_s"] * 1e9)
