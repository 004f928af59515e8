"""Share of the token grid the device worked on that was padding, over the
window: 100 * (1 - pack_payload_tokens_total / pack_grid_tokens_total), both
the program's exact counters. They run ahead of the loop by the prefetch
depth, so the window's edges are a few batches early on both sides."""


def read(ctx):
    grid = ctx["counters"].get("pack_grid_tokens_total", 0)
    if not grid:
        return None
    return 100.0 * (1 - ctx["counters"]["pack_payload_tokens_total"] / grid)
