"""Share of the window the placement thread spent in
``placement.wait_input``: pulling the next host batch from upstream, so
starved by read and decode. Its other two phases are ``placement.h2d``
(dispatch) and ``placement.wait_ring`` (the ring is full: the input plane is
ahead of the trainer); the three tile the thread (``data/placement.py``)."""

from reduce import spans


def read(ctx):
    inside = spans.inside(ctx["spans"], "placement.wait_input",
                          ctx["window_ns"])
    if not inside:
        return None
    return 100.0 * sum(inside) / (ctx["window_s"] * 1e9)
