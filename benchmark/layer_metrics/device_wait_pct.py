"""Share of the window the loop thread spent in ``train.drain``: the
``float(loss)`` that bounds the dispatch queue at ``sync_every`` and at log
points, which is the loop thread waiting for the device. High in a
device-bound cell, low where the host holds the chips back."""

from reduce import spans


def read(ctx):
    inside = spans.inside(ctx["spans"], "train.drain", ctx["window_ns"])
    if not inside:
        return None
    return 100.0 * sum(inside) / (ctx["window_s"] * 1e9)
