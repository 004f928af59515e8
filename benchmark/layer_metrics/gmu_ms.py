"""Device time of one step in the gated memory units, forward and backward:
operations of ``jit_step`` under the model's ``gmu`` scope
(``models/transformer.py`` ``GatedMemoryUnit``: two products and a gate over
the scan output that the M* layer handed on)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "gmu")
