"""Device time of one step in granite-4.0-h-micro's feed-forward sub-layers,
forward and backward, all ten layers: operations of ``jit_step`` under the
model's ``mlp.dense`` scope (``models/transformer.py`` ``DecoderBlock``: a
dense SwiGLU of 8,192, three products of [8,192, 2,048] by [2,048, 8,192];
63% of the step's counted multiply-adds)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "mlp.dense")
