"""Device time of one step in the routed part of the expert layers behind the
router, as one rank of an expert-parallel pair runs it with one expert a
token, forward and backward (and the backward pass's recomputation of it): the
sort of the assignments that puts those on held experts first and the gather
(``moe.dispatch``), the held experts' grouped products (``moe.experts`` and
the kernel XLA makes of ``ragged_dot``, found by its name) and the weighted
sum back (``moe.combine``). The router is ``zaya_router_ms``."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.dispatch", "moe.experts",
                                    "moe.combine",
                                    also=named_scopes.GROUPED_PRODUCTS)
