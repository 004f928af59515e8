"""Device time of one step spent moving tokens to and from their experts,
forward and backward: the router with its softmax and top-k
(``moe.router``), the sort of the assignments and the gather of the tokens
(``moe.dispatch``) and the weighted sum back (``moe.combine``); what is left
of ``step_moe_ms`` is the grouped products. ROADMAP S8 was about this share."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.router", "moe.dispatch",
                                    "moe.combine")
