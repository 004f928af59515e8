"""Device time of one step in the routed part of Laguna's four expert layers
behind the router, as rank 0 of an expert-parallel 32 runs it with 10
experts a token, forward and backward (and the backward pass's recomputation
of it): the sort of the 81,920 assignments a row that puts those on the 8
held experts first and the gather (``moe.dispatch``), the held experts'
grouped SwiGLU products over a list of 5,120 built rows (``moe.experts`` and
the kernel XLA makes of ``ragged_dot``, found by its name) and the weighted
sum back (``moe.combine``). The router is under ``moe.router`` and the
shared expert under ``moe.shared``; neither is here."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.dispatch", "moe.experts",
                                    "moe.combine",
                                    also=named_scopes.GROUPED_PRODUCTS)
