"""Device time of one step in the routed part of the expert layers behind the
router, as one rank of an expert-parallel sixteen runs it with 10 experts a
token, forward and backward (and the backward pass's recomputation of it):
the sort of the 81,920 assignments a row that puts those on the 32 held
experts first and the gather (``moe.dispatch``), the held experts' grouped
products (``moe.experts`` and the kernel XLA makes of ``ragged_dot``, found
by its name) and the weighted sum back over 10 slots (``moe.combine``). The
router is ``qwen_router_ms``; the gated shared expert (``moe.shared``) is in
neither."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.dispatch", "moe.experts",
                                    "moe.combine",
                                    also=named_scopes.GROUPED_PRODUCTS)
