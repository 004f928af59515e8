"""Device time of one step in the routed part of the expert layers behind the
router, as one rank of an expert-parallel four runs it with 6 experts a
token, forward and backward (and the backward pass's recomputation of it):
the sort of the 98,304 assignments a row that puts those on the 16 held
experts first and the gather (``moe.dispatch``), the held experts' grouped
ReGLU products (``moe.experts`` and the kernel XLA makes of ``ragged_dot``,
found by its name) and the weighted sum back over 6 slots (``moe.combine``).
The router is ``st_router_ms``."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.dispatch", "moe.experts",
                                    "moe.combine",
                                    also=named_scopes.GROUPED_PRODUCTS)
