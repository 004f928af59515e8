"""Device time of one step inside the expert layer, forward and backward:
operations of ``jit_step`` under the model's scopes ``moe.router``,
``moe.dispatch``, ``moe.experts`` and ``moe.combine`` (``models/moe.py``,
``DroplessMoE``), and the grouped products themselves, which XLA's rewrite
of ``ragged_dot`` leaves without a scope and which are found by the
kernel's name. Read from the trace by ``reduce/named_scopes.py``."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.router", "moe.dispatch",
                                    "moe.experts", "moe.combine",
                                    also=named_scopes.GROUPED_PRODUCTS)
