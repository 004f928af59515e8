"""Device time of one step in the attention sub-layer, forward and backward:
operations of ``jit_step`` under the model's ``attention`` scope (the four
projections, the norms on queries and keys, the rotary turn, scores, softmax
and context, or the kernel where the cell runs it)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attention")
