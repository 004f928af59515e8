"""Host cores' worth of decoding: the time inside ``pipeline.decode`` spans,
summed over the producer threads and cut to the window, over the window. The
native decoder runs its own threads below a span, so this counts producers
kept busy, not CPU seconds."""

from reduce import spans


def read(ctx):
    inside = spans.inside(ctx["spans"], "pipeline.decode", ctx["window_ns"])
    if not inside:
        return None
    return sum(inside) / (ctx["window_s"] * 1e9)
